"""Replay: milliseconds per proxy sweep over every proxy block of the timed
window (host spans around the benchmark's own blocks)."""


def read(rec):
    t = rec.per_unit("proxy.sweep")
    return None if t is None else t * 1e3
