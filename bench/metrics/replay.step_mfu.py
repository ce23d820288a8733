"""Replay basis blocks: the proxy's MXU flops per sweep, counted from its
fitted block counts and each block's operand shapes (``bench/flops.py``),
times sweeps per second, as a share of the chips' bf16 peak, in percent."""


def read(rec):
    t = rec.per_unit("proxy.sweep")
    f = rec.counters.get("flops_per_sweep")
    if not t or not f:
        return None
    return 100.0 * f / t / (rec.chips * rec.peak_flops())
