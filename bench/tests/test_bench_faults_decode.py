"""The decode replay cell's run, with the timed path broken underneath,
must come out not correct: once for each fault the cell can have."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from bench.tests.conftest import MAMBA_SMALL, run_small

CELL = "mamba2-2.7b.decode-b8.replay"


def _broken(kind):
    from repro.models import model as M
    real = M.build_forward

    def build_forward(cfg, k):
        step = real(cfg, k)
        if k != "decode":
            return step

        def broken(params, cache, batch, pos, cfg):
            if kind == "state_unchanged":
                logits, _ = step(params, cache, batch, pos, cfg)
                return logits, cache
            if kind == "half_batch":
                b = batch["tokens"].shape[0]
                half = jax.tree.map(lambda a: a[:, :b // 2], cache)
                logits, new = step(params, half, {"tokens":
                                                  batch["tokens"][:b // 2]},
                                   pos, cfg)
                logits = jnp.concatenate([logits, jnp.zeros_like(logits)], 0)
                new = jax.tree.map(lambda n, a: jnp.concatenate(
                    [n, a[:, b // 2:]], axis=1), new, cache)
                return logits, new
            if kind == "altered_token":
                logits, new = step(params, cache, batch, pos, cfg)
                top = jnp.max(logits[0]) + 1.0
                return logits.at[0, 1].set(top), new
            raise ValueError(kind)
        return broken
    return build_forward


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "altered_token"])
def test_decode_fault_is_not_correct(kind, monkeypatch):
    from repro.models import model as M
    monkeypatch.setattr(M, "build_forward", _broken(kind))
    out = run_small(CELL, MAMBA_SMALL, seconds=0.5)
    assert not out["correct"], (kind, out["checks"])
    assert out["checks"]["decode_gap"]["value"] > \
        out["checks"]["decode_gap"]["limit"]


def test_proxy_returning_its_state_is_not_correct(monkeypatch):
    """A proxy sweep whose executables return their state unchanged."""
    from repro.core.replay import ProxyProgram
    monkeypatch.setattr(ProxyProgram, "_fn_for_rank",
                        lambda self, rank, comm: jax.jit(lambda st: st))
    out = run_small(CELL, MAMBA_SMALL, seconds=0.5)
    assert not out["correct"]
    assert out["checks"]["proxy_exec_gap"]["value"] == 1.0
