"""Mesh replay: device milliseconds a proxy sweep in collective ops, in the
traced proxy blocks, mean over devices (``bench/collectives.py``)."""
from bench.collectives import collective_s


def read(rec):
    s = None if rec.trace is None else collective_s(rec.trace, "proxy.sweep")
    return None if s is None else s * 1e3
