"""Batched serving engine: prefill → KV caches → greedy decode loop.

Iteration-level batching "lite": a fixed pool of batch slots decodes in
lockstep; finished sequences are masked (kept numerically live so the
compiled step shape never changes) and harvested at the end.  On a mesh the
caches follow the "kv_seq → model" sharding rule, which is what lets a 32k
context × 128-slot pool fit per chip.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models.model import build_forward, init_cache
from repro.obs import StageTimers


@dataclasses.dataclass
class GenResult:
    tokens: np.ndarray          # (b, n_new)
    prefill_sec: float
    decode_sec: float
    tokens_per_sec: float


class ServeEngine:
    def __init__(self, cfg: ArchConfig, params, mesh=None, *,
                 max_len: int = 128, eos_id: int = -1):
        self.cfg = cfg
        self.params = params
        self.mesh = mesh
        self.max_len = max_len
        self.eos_id = eos_id
        self.timers = StageTimers("serve", "prefill", "decode")
        self._prefill = jax.jit(
            lambda p, b: build_forward(cfg, "prefill")(p, b, cfg, mesh))
        self._decode = jax.jit(
            lambda p, c, b, pos: build_forward(cfg, "decode")(p, c, b, pos,
                                                              cfg, mesh))

    def _extras(self, batch_size: int) -> dict:
        out = {}
        if self.cfg.n_vision_tokens:
            out["vision_embeds"] = jnp.zeros(
                (batch_size, self.cfg.n_vision_tokens, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype))
        if self.cfg.n_audio_frames:
            out["audio_frames"] = jnp.zeros(
                (batch_size, self.cfg.n_audio_frames, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype))
        return out

    def generate(self, prompts: np.ndarray, n_new: int) -> GenResult:
        """prompts: (b, prompt_len) int32 (already padded to a bucket)."""
        b, plen = prompts.shape
        assert plen + n_new <= self.max_len, "exceeds engine max_len"
        batch = {"tokens": jnp.asarray(prompts, jnp.int32), **self._extras(b)}

        with self.timers.time("prefill") as pre:
            logits, pre_cache = self._prefill(self.params, batch)
            jax.block_until_ready(logits)

        with self.timers.time("decode") as dec:
            # re-home the prefill cache into full-length decode buffers
            full = init_cache(self.cfg, b, self.max_len,
                              self.cfg.n_audio_frames or 0)
            cache = jax.tree.map(self._embed_cache, full, pre_cache)

            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out = [np.asarray(tok)]
            done = np.zeros((b,), bool)
            for i in range(n_new - 1):
                dbatch = {"tokens": tok[:, None]}
                logits, cache = self._decode(self.params, cache, dbatch,
                                             jnp.int32(plen + i))
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                t_np = np.asarray(tok)
                if self.eos_id >= 0:
                    done |= t_np == self.eos_id
                    t_np = np.where(done, self.eos_id, t_np)
                out.append(t_np)
                if done.all():
                    break
            jax.block_until_ready(tok)
        gen = np.stack(out, axis=1)
        n_tok = gen.size
        prefill_sec, decode_sec = pre.ns * 1e-9, dec.ns * 1e-9
        return GenResult(tokens=gen, prefill_sec=prefill_sec,
                         decode_sec=decode_sec,
                         tokens_per_sec=n_tok / max(decode_sec, 1e-9))

    @staticmethod
    def _embed_cache(full_leaf, pre_leaf):
        """Place a prefill cache leaf into the front of the full-length buffer
        (matching trailing dims; seq axis is wherever shapes differ)."""
        if full_leaf.shape == pre_leaf.shape:
            return pre_leaf.astype(full_leaf.dtype)
        # find the (single) mismatching axis = the cache sequence axis
        axis = next(i for i, (a, b) in enumerate(zip(full_leaf.shape,
                                                     pre_leaf.shape)) if a != b)
        idx = (0,) * full_leaf.ndim
        return jax.lax.dynamic_update_slice(
            full_leaf, pre_leaf.astype(full_leaf.dtype), idx)
