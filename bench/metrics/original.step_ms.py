"""Original program: milliseconds per step over every original block of
the timed window (host spans around the benchmark's own blocks)."""


def read(rec):
    t = rec.per_unit("original")
    return None if t is None else t * 1e3
