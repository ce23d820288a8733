"""Replay basis blocks: the sweep's least HBM bytes (its fitted blocks'
turns, ``bench/hbm.py``) over its time, as a share of the chips' HBM peak,
in percent."""
from bench.flops import peaks


def read(rec):
    t = rec.per_unit("proxy.sweep")
    b = rec.counters.get("bytes_per_sweep")
    if not t or not b:
        return None
    return 100.0 * b / t / (rec.chips * peaks(rec.device_kind)["hbm_bytes_per_s"])
