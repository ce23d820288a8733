"""The synthesis cell's run, with the timed path broken underneath, must
come out not correct: once for each fault the cell can have."""
from __future__ import annotations

import jax
import pytest

from bench.tests.conftest import MAMBA_SMALL, run_small

CELL = "mamba2-2.7b.decode-buckets.synth"


def _plant(kind, monkeypatch):
    from repro.core import synthesize as S
    from repro.core.replay import ProxyProgram
    if kind == "half_work":              # the fit asks for half the work
        real = S.synthesize
        monkeypatch.setattr(S, "synthesize", lambda *a, **kw: real(
            *a, **dict(kw, count_scale=0.5)))
    elif kind == "state_unchanged":      # the proxy runs no block at all
        monkeypatch.setattr(ProxyProgram, "_fn_for_rank",
                            lambda self, rank, comm: jax.jit(lambda st: st))
    elif kind == "altered_count":        # the tracer counts 1.5x the work
        real = S.trace_fn_store

        def miscount(*a, **kw):
            store = real(*a, **kw)
            store.metrics = store.metrics * 1.5
            return store
        monkeypatch.setattr(S, "trace_fn_store", miscount)
    else:
        raise ValueError(kind)


@pytest.mark.parametrize("kind,caught_by", [
    ("half_work", "proxy_fit_gap"), ("state_unchanged", "proxy_exec_gap"),
    ("altered_count", "trace_mxu_gap")])
def test_synth_fault_is_not_correct(kind, caught_by, monkeypatch):
    _plant(kind, monkeypatch)
    out = run_small(CELL, MAMBA_SMALL, seconds=0.1)
    assert not out["correct"], (kind, out["checks"])
    c = out["checks"][caught_by]
    assert c["value"] > c["limit"], (kind, out["checks"])
