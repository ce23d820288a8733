"""Original program: the step's least HBM bytes (counted from shapes by its
program kind, ``count_bytes``) over its time, as a share of the chips' HBM
peak, in percent."""
from bench.flops import peaks


def read(rec):
    t = rec.per_unit("original")
    b = rec.counters.get("bytes_per_step")
    if not t or not b:
        return None
    return 100.0 * b / t / (rec.chips * peaks(rec.device_kind)["hbm_bytes_per_s"])
