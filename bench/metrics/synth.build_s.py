"""Tracer, front half, fit and codegen: seconds per program spent in
``synthesize()`` in the timed window (host spans)."""


def read(rec):
    return rec.per_unit("synthesize")
