"""Proxy compile: seconds per program spent in the proxy's first
``run_all()`` (compile and first run) in the timed window (host spans)."""


def read(rec):
    return rec.per_unit("proxy.compile")
