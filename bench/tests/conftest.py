"""Shared helpers of the benchmark's own tests: small sizes, and a run of
one cell on the CPU at those sizes with the chip check skipped."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

#: mamba2 at a size a test run can hold; the fit still places MXU blocks
MAMBA_SMALL = {"name": "mamba2-small", "n_layers": 4, "d_model": 256,
               "vocab": 2048, "ssm_state": 32, "ssm_head_dim": 32,
               "ssm_groups": 1, "ssm_expand": 2, "ssm_chunk": 8,
               "conv_width": 4, "dtype": "bfloat16"}

#: a large seed, past 32 signed bits, as the driver's are
BIG_SEED = 2**31 + 12345


def run_small(cell: str, sizes=None, seconds: float = 1.0, trace=False,
              seed: int = BIG_SEED, devices=None, sink=None):
    import jax
    from bench import harness
    spec = harness.load_spec()
    return harness.run_cell(spec, cell, seed, seconds, trace,
                            time.perf_counter(), sizes=sizes,
                            devices=devices or jax.devices()[:1],
                            log=lambda m: None, sink=sink)


@pytest.fixture
def cpu_peaks(monkeypatch):
    """Peaks for the CPU device kind, so traced runs can be reduced here."""
    from bench import flops
    real = flops.peaks
    monkeypatch.setattr(flops, "peaks", lambda kind: real("TPU v5 lite")
                        if kind == "cpu" else real(kind))
