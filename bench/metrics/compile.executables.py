"""Proxy compile: executables a program that XLA compiled in the proxy's
first ``run_all()`` (counter ``jax.compiles`` under ``proxy.run_all``), in
the timed window (``bench/program_spans.py``)."""
from bench import program_spans


def read(rec):
    return program_spans.read_part(rec, "executables")
