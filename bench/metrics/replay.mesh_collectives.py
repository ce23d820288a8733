"""Mesh replay: collectives one proxy sweep runs on the mesh, counted at
trace time as each is lowered into an executable (program counter
``replay.collectives``, ``bench/mesh_spans.py``)."""
from bench import mesh_spans


def read(rec):
    return mesh_spans.collectives(rec)
