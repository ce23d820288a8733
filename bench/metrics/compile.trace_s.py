"""Proxy compile: seconds a program that the proxy's first ``run_all()``
spent in JAX's trace to a jaxpr (``jax.trace`` spans), in the timed window
(program spans, ``bench/program_spans.py``)."""
from bench import program_spans


def read(rec):
    return program_spans.read_part(rec, "trace")
