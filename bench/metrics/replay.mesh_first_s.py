"""Mesh replay: seconds of the set-up's first mesh run spent in each placed
group's first dispatch, its trace, lowering and compile (program spans
``proxy.mesh_first``, ``bench/mesh_spans.py``)."""
from bench import mesh_spans


def read(rec):
    return mesh_spans.first_run_s(rec)
