"""Program kind ``mamba2_decode``: the repository's bf16 Mamba-2 decode step
(``build_forward(cfg, "decode")``) at a fixed batch.

As the original of a replay cell it is driven as a greedy decode loop:
each step feeds the previous step's argmax tokens and carries the decode
state.  The configuration module (``cfg``) gives the architecture, the
weights and state from the seed, and the plain reference.

The mix's program entry: ``{"kind": "mamba2_decode", "batch": <b>}``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import padded_vocab


def count_flops(cfg, s: dict, program: dict) -> float:
    """Dense-matmul flops of one decode step at the program's batch: per
    layer the projections (in_proj, out_proj), the causal conv and the state
    readout (C . state), plus the LM head over the padded vocabulary."""
    batch = int(program["batch"])
    d = s["d_model"]
    d_in = s["ssm_expand"] * d
    h = d_in // s["ssm_head_dim"]
    gn = s["ssm_groups"] * s["ssm_state"]
    layer = 2.0 * batch * (d * (2 * d_in + 2 * gn + h)
                           + s["conv_width"] * (d_in + 2 * gn) + d_in * d
                           + h * s["ssm_head_dim"] * s["ssm_state"])
    head = 2.0 * batch * d * padded_vocab(s["vocab"])
    return s["n_layers"] * layer + head


def trace_spec(cfg, s: dict, program: dict):
    """(step, abstract args, axis sizes) of one decode step at the
    program's batch: what synthesis traces."""
    from repro.models.model import abstract_cache, build_forward, init_abstract
    arch = cfg.arch(s)
    decode = build_forward(arch, "decode")
    b = int(program["batch"])

    def step(params, cache, batch, pos):
        return decode(params, cache, batch, pos, arch)

    args = (init_abstract(arch), abstract_cache(arch, b, 1),
            {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)},
            jax.ShapeDtypeStruct((), jnp.int32))
    return step, args, {}


class Decode:
    """Greedy decode at a fixed batch: the original program of a cell."""

    def __init__(self, cfg, s: dict, program: dict, seed: int):
        from repro.models.model import build_forward
        self.cfg, self.s, self.seed = cfg, s, seed
        self.program = dict(program)
        self.batch = int(program["batch"])
        self.flops_per_step = count_flops(cfg, s, program)
        arch = cfg.arch(s)
        decode = build_forward(arch, "decode")

        def step(params, cache, tokens):
            logits, cache = decode(params, cache, {"tokens": tokens},
                                   jnp.int32(0), arch)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            return nxt, cache

        self._step = jax.jit(step, donate_argnums=(1,))
        self.params = cfg.make_weights(s, seed)
        self.cache, self.tokens = cfg.make_state(s, self.batch, seed)
        self.first = self.tokens
        self.served: list = []

    def trace_spec(self):
        return trace_spec(self.cfg, self.s, self.program)

    def warm(self):
        self.run(2)

    def run(self, n: int):
        for _ in range(n):
            self.tokens, self.cache = self._step(self.params, self.cache,
                                                 self.tokens)
            jax.block_until_ready((self.tokens, self.cache))
            self.served.append(self.tokens)

    def release(self):
        self.cache = None
        self._step = None

    def _gaps(self, control):
        served = np.concatenate([np.asarray(t) for t in self.served], axis=1)
        cache, _ = self.cfg.make_state(self.s, self.batch, self.seed)
        return self.cfg.reference_gaps(self.s, self.params, cache,
                                       np.asarray(self.first), served, control)

    def check(self) -> dict:
        """The served tokens' widest gap below the reference's best."""
        gaps = self._gaps(None)
        self.params = None
        return {"decode_gap": float(gaps.max())}

    def control(self) -> dict:
        """The same reading with an fp8 reference in the program's place."""
        return {"decode_gap": float(self._gaps("fp8").max())}


def build(cfg, s: dict, program: dict, seed: int, devices):
    with jax.default_device(devices[0]):
        return Decode(cfg, s, program, seed)
