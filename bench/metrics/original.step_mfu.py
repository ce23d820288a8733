"""Original program: the step's dense-matmul flops (counted from shapes by
``bench/flops.py``) times steps per second, as a share of the chips' bf16
peak, in percent."""


def read(rec):
    t = rec.per_unit("original")
    f = rec.counters.get("flops_per_step")
    if not t or not f:
        return None
    return 100.0 * f / t / (rec.chips * rec.peak_flops())
