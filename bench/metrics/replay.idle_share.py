"""Device: share of the traced proxy blocks in which no op ran on the
device, 1 - union of op intervals / block time, mean over devices, in
percent."""
from bench import trace as T


def read(rec):
    if rec.trace is None:
        return None
    s = T.idle_share(rec.trace, "proxy.sweep")
    return None if s is None else 100.0 * s
