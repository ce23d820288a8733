"""Wrappers for the proxy-block kernels.  ``interpret=True`` runs the
kernel body in the Pallas interpreter (CPU tests); the default compiles it
for the TPU."""
from __future__ import annotations

from repro.kernels.proxy_blocks.kernel import mxu_pallas, stream_pallas


def mxu_block(a, b, reps: int, interpret: bool = False):
    return mxu_pallas(a, b, reps, interpret=interpret)


def stream_block(v, reps: int, interpret: bool = False):
    return stream_pallas(v, reps, interpret=interpret)
