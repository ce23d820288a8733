"""Training step builder + fault-tolerant trainer.

``make_train_step`` builds the jitted step for any ArchConfig on any mesh:
microbatched gradient accumulation (lax.scan), AdamW, donated buffers.
``make_manual_dp_train_step`` is the explicit shard_map DP variant whose
gradient all-reduce goes through int8 error-feedback compression
(4× collective-byte reduction, visible in the lowered HLO).

:class:`Trainer` provides the 1000-node operational envelope on one host:
checkpoint/restart (async saves, atomic commits), deterministic data resume,
failure injection + automatic restore, and elastic re-shard onto a new mesh.
Straggler mitigation for bulk-synchronous SPMD lives in (a) the data
prefetcher (host jitter never stalls the step) and (b) checkpoint cadence
(bounded recompute after eviction); both are exercised in tests.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from repro.configs.base import ArchConfig
from repro.configs.registry import rules_for
from repro.models.model import build_forward, init_params, logical_axes_tree
from repro.sharding.partition import sharding_for_shape
from repro.train import checkpoint as ckpt_lib
from repro.train.compression import compressed_psum, init_error_state
from repro.train.data import Prefetcher, TokenDataset
from repro.train.optimizer import AdamWConfig, adamw_init, adamw_update


@dataclasses.dataclass(frozen=True)
class TrainOptions:
    num_microbatches: int = 1
    donate: bool = True
    grad_compression: str = "none"     # none | int8 (manual-DP step only)


def _microbatched_grads(loss_fn, params, batch, n_mb: int):
    if n_mb <= 1:
        return jax.value_and_grad(loss_fn)(params, batch)

    def reshape(x):
        b = x.shape[0]
        return x.reshape((n_mb, b // n_mb) + x.shape[1:])

    mbatch = jax.tree.map(reshape, batch)
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def body(acc, mb):
        loss_acc, g_acc = acc
        loss, g = jax.value_and_grad(loss_fn)(params, mb)
        g_acc = jax.tree.map(lambda a, x: a + x.astype(jnp.float32), g_acc, g)
        return (loss_acc + loss, g_acc), None

    (loss, grads), _ = jax.lax.scan(body, (jnp.float32(0.0), zeros), mbatch)
    scale = 1.0 / n_mb
    return loss * scale, jax.tree.map(lambda g: g * scale, grads)


def make_train_step(cfg: ArchConfig, mesh=None, opt_cfg: AdamWConfig | None = None,
                    options: TrainOptions | None = None) -> Callable:
    """jit(train_step)(params, opt_state, batch) → (params, opt_state, metrics)."""
    opt_cfg = opt_cfg or AdamWConfig()
    options = options or TrainOptions()
    loss_fn_raw = build_forward(cfg, "loss")

    def loss_fn(p, b):
        return loss_fn_raw(p, b, cfg, mesh)

    def step(params, opt_state, batch):
        loss, grads = _microbatched_grads(loss_fn, params, batch,
                                          options.num_microbatches)
        params, opt_state, metrics = adamw_update(grads, params, opt_state,
                                                  opt_cfg)
        metrics["loss"] = loss
        return params, opt_state, metrics

    donate = (0, 1) if options.donate else ()
    return jax.jit(step, donate_argnums=donate)


def make_manual_dp_train_step(cfg: ArchConfig, mesh,
                              opt_cfg: AdamWConfig | None = None,
                              data_axis: str = "data") -> Callable:
    """Explicit-DP step: per-device grads → int8 error-feedback psum.

    Params replicated over ``data_axis``; batch sharded on it.  State gains
    an ``err`` tree (the feedback accumulator).  The gradient all-reduce
    moves int8 (int32-accumulated) payloads — 4× fewer wire bytes than f32.
    """
    from jax.sharding import PartitionSpec as P
    opt_cfg = opt_cfg or AdamWConfig()
    loss_fn_raw = build_forward(cfg, "loss")

    def local_step(params, opt_state, err, batch):
        loss, grads = jax.value_and_grad(
            lambda p, b: loss_fn_raw(p, b, cfg, None))(params, batch)
        flat_g, treedef = jax.tree.flatten(grads)
        flat_e = treedef.flatten_up_to(err)
        red, new_e = [], []
        for g, e in zip(flat_g, flat_e):
            r, ne = compressed_psum(g, data_axis, e)
            red.append(r)
            new_e.append(ne)
        grads = jax.tree.unflatten(treedef, red)
        err = jax.tree.unflatten(treedef, new_e)
        loss = jax.lax.pmean(loss, data_axis)
        params, opt_state, metrics = adamw_update(grads, params, opt_state,
                                                  opt_cfg)
        metrics["loss"] = loss
        return params, opt_state, err, metrics

    pspec = P()

    def batch_spec(x):
        return P(data_axis)

    step = shard_map(
        local_step, mesh=mesh,
        in_specs=(pspec, pspec, pspec, P(data_axis)),
        out_specs=(pspec, pspec, pspec, pspec),
        check_vma=False)
    return jax.jit(step)


# ---------------------------------------------------------------------------
# fault-tolerant trainer
# ---------------------------------------------------------------------------


class Trainer:
    """Single-controller trainer with the production operational envelope."""

    def __init__(self, cfg: ArchConfig, mesh=None, *, global_batch: int = 8,
                 seq_len: int = 32, ckpt_dir: str = "/tmp/repro_ckpt",
                 opt_cfg: AdamWConfig | None = None,
                 options: TrainOptions | None = None, seed: int = 0):
        self.cfg = cfg
        self.mesh = mesh
        self.opt_cfg = opt_cfg or AdamWConfig()
        self.options = options or TrainOptions()
        self.rules = rules_for(cfg)
        self.dataset = TokenDataset(cfg.vocab, seq_len, global_batch, seed)
        self.ckpt = ckpt_lib.CheckpointManager(ckpt_dir)
        self.step_fn = make_train_step(cfg, mesh, self.opt_cfg, self.options)
        self._init_state(seed)
        self.step = 0
        self.metrics_log: list[dict] = []

    def _init_state(self, seed: int):
        params = init_params(self.cfg, seed)
        if self.mesh is not None:
            axes = logical_axes_tree(self.cfg)
            params = jax.tree.map(
                lambda a, ax: jax.device_put(
                    a, sharding_for_shape(a.shape, ax, self.mesh, self.rules)),
                params, axes,
                is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, tuple))
        self.params = params
        self.opt_state = adamw_init(params)

    def _place_batch(self, batch: dict) -> dict:
        if self.mesh is None:
            return {k: jnp.asarray(v) for k, v in batch.items()}
        out = {}
        for k, v in batch.items():
            axes = ("batch",) + (None,) * (v.ndim - 1)
            out[k] = jax.device_put(
                v, sharding_for_shape(v.shape, axes, self.mesh, self.rules))
        return out

    # -- checkpoint/restart ---------------------------------------------------

    def save(self, async_: bool = True):
        state = {"params": self.params, "opt": self.opt_state}
        extra = {"step": self.step}
        if async_:
            self.ckpt.save_async(self.step, state, extra)
        else:
            self.ckpt.save(self.step, state, extra)

    def restore(self, step: int | None = None) -> bool:
        if self.ckpt.latest_step() is None:
            return False
        template = {"params": self.params, "opt": self.opt_state}
        got_step, state, extra = self.ckpt.restore(template, step)
        self.params = state["params"]
        self.opt_state = state["opt"]
        self.step = extra.get("step", got_step)
        return True

    def reshard(self, new_mesh):
        """Elastic re-scale: persist, rebuild on the new mesh, restore."""
        self.ckpt.wait()
        self.save(async_=False)
        self.mesh = new_mesh
        self.step_fn = make_train_step(self.cfg, new_mesh, self.opt_cfg,
                                       self.options)
        self._init_state(seed=0)
        self.restore()
        if new_mesh is not None:
            axes = logical_axes_tree(self.cfg)
            self.params = jax.tree.map(
                lambda a, ax: jax.device_put(
                    a, sharding_for_shape(a.shape, ax, new_mesh, self.rules)),
                self.params, axes,
                is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, tuple))

    # -- run loop ---------------------------------------------------------------

    def run(self, n_steps: int, ckpt_every: int = 0,
            failure_injector: Callable[[int], None] | None = None,
            max_restarts: int = 3) -> list[dict]:
        restarts = 0
        target = self.step + n_steps
        extras = self.dataset.extras(self.cfg)
        while self.step < target:
            pf = Prefetcher(self.dataset, start_step=self.step, extras=extras)
            try:
                while self.step < target:
                    got_step, batch = next(pf)
                    assert got_step == self.step, (got_step, self.step)
                    if failure_injector is not None:
                        failure_injector(self.step)
                    t0 = time.perf_counter()
                    batch = self._place_batch(batch)
                    self.params, self.opt_state, metrics = self.step_fn(
                        self.params, self.opt_state, batch)
                    loss = float(metrics["loss"])
                    self.metrics_log.append({
                        "step": self.step, "loss": loss,
                        "sec": time.perf_counter() - t0,
                    })
                    self.step += 1
                    if ckpt_every and self.step % ckpt_every == 0:
                        self.save(async_=True)
            except _InjectedFailure:
                restarts += 1
                if restarts > max_restarts:
                    raise
                self.ckpt.wait()
                self._init_state(seed=0)       # fresh process semantics
                if not self.restore():
                    self.step = 0
            finally:
                pf.close()
        self.ckpt.wait()
        return self.metrics_log


class _InjectedFailure(RuntimeError):
    """Raised by tests' failure injectors to simulate a node loss."""
