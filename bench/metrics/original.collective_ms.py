"""Original program: device milliseconds a step in collective ops, in the
traced original blocks, mean over devices (``bench/collectives.py``)."""
from bench.collectives import collective_s


def read(rec):
    s = None if rec.trace is None else collective_s(rec.trace, "original")
    return None if s is None else s * 1e3
