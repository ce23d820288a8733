"""Shared model layers: norms, RoPE, MLP, embeddings.

Params are plain nested dicts; every leaf has a parallel *logical axes*
annotation consumed by :mod:`repro.sharding.partition`.  A ``Param`` carries
(shape, logical axes, init scale); :func:`materialize`/:func:`abstractify`
turn a Param tree into concrete arrays or ShapeDtypeStructs.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.sharding.partition import constraint


@dataclasses.dataclass(frozen=True)
class Param:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    scale: float = 1.0          # fan-in style init scale
    dtype: str = "bfloat16"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_param(x) -> bool:
    return isinstance(x, Param)


def abstractify(tree):
    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.dtype(p.dtype)),
        tree, is_leaf=is_param)


def logical_axes(tree):
    return jax.tree.map(lambda p: p.axes, tree, is_leaf=is_param)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _init_leaf(key, shape: tuple[int, ...], dtype: str, scale: float):
    dt = jnp.dtype(dtype)
    if scale == 0.0:
        return jnp.zeros(shape, dt)
    if len(shape) <= 1:
        return jnp.full(shape, scale, dt)
    std = scale / math.sqrt(max(shape[-2], 1))

    def draw(k, shp):
        return (jax.random.normal(k, shp, jnp.float32) * std).astype(dt)

    if len(shape) == 2:
        return draw(key, shape)
    # stacked leaves (layers, ...): one slice at a time, so the random bits
    # of a full-width leaf never sit on the device at once
    return jax.lax.map(lambda k: draw(k, shape[1:]),
                       jax.random.split(key, shape[0]))


def materialize(tree, seed: int = 0):
    """Concrete init on the default device, drawn from ``seed`` leaf by leaf
    in each leaf's dtype: zeros for ``scale == 0``, ``scale`` for vectors,
    ``N(0, scale/√fan_in)`` otherwise.  Nothing passes through host memory,
    so full-width configs materialize on one chip too."""
    leaves, treedef = jax.tree.flatten(tree, is_leaf=is_param)
    keys = jax.random.split(jax.random.PRNGKey(seed), max(len(leaves), 1))
    out = [_init_leaf(k, tuple(p.shape), p.dtype, float(p.scale))
           for p, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------


def rms_norm(x, w, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def rope(x, positions, theta: float = 10000.0):
    """Rotary embedding over the last dim; x: (..., seq, heads, head_dim)."""
    hd = x.shape[-1]
    half = hd // 2
    freq = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq          # (..., seq, half)
    cos = jnp.cos(ang)[..., None, :]                                # bcast heads
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    dt = x.dtype
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([x1f * cos - x2f * sin,
                            x2f * cos + x1f * sin], axis=-1).astype(dt)


def swiglu(x, wi, wg, wo, act=jax.nn.silu):
    h = act(x @ wg) * (x @ wi)
    return h @ wo


def mlp_params(d: int, ff: int, dtype: str) -> dict:
    return {
        "wi": Param((d, ff), ("embed", "ffn"), dtype=dtype),
        "wg": Param((d, ff), ("embed", "ffn"), dtype=dtype),
        "wo": Param((ff, d), ("ffn", "embed"), dtype=dtype),
    }


def mlp_apply(p, x, mesh=None):
    h = jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])
    h = constraint(h, ("batch", "seq", "ffn"), mesh)
    return h @ p["wo"]


def embed_params(vocab: int, d: int, dtype: str) -> Param:
    return Param((vocab, d), ("vocab", "embed"), dtype=dtype)


def embed_lookup(table, tokens, mesh=None):
    x = jnp.take(table, tokens, axis=0)
    return constraint(x, ("batch", "seq", "embed"), mesh)


def unembed(x, table, mesh=None):
    logits = x @ table.T.astype(x.dtype)
    return constraint(logits, ("batch", "seq", "vocab"), mesh)


def softmax_xent(logits, labels, vocab: int):
    """Stable CE in f32; logits (..., V), labels int (...)."""
    logits = logits.astype(jnp.float32)
    m = jnp.max(logits, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - gold


def chunked_loss(x, table, labels, chunk: int, mesh=None):
    """LM head + CE scanned over sequence chunks: peak logits memory drops
    from O(S·V) to O(chunk·V) per device (framework-level memory opt)."""
    b, s, d = x.shape
    if chunk <= 0 or s % chunk != 0 or s == chunk:
        logits = unembed(x, table, mesh)
        return jnp.mean(softmax_xent(logits, labels, table.shape[0]))
    n = s // chunk
    xc = x.reshape(b, n, chunk, d).swapaxes(0, 1)          # (n, b, chunk, d)
    lc = labels.reshape(b, n, chunk).swapaxes(0, 1)

    def body(acc, xl):
        xi, li = xl
        logits = unembed(xi, table, mesh)
        return acc + jnp.sum(softmax_xent(logits, li, table.shape[0])), None

    tot, _ = jax.lax.scan(body, jnp.float32(0.0), (xc, lc))
    return tot / (b * s)
