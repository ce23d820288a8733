"""Pallas TPU flash-attention forward kernel.

Grid: (batch·heads, q blocks).  Per program: one (cq, d) query tile in VMEM;
K/V live as full (t, d) VMEM refs and are walked in ck-sized blocks with an
in-kernel ``fori_loop`` carrying the online-softmax state (m, l, acc) in
registers/VMEM.  Causal blocks *behind* the query tile are skipped by
bounding the loop trip count with the block-diagonal index — the block-skip
the pure-XLA path cannot express (it must mask), worth ~2× on causal
sequences (see DESIGN.md §kernels).

Block shapes are MXU-aligned: cq and ck are multiples of 128 (the systolic
array edge), d is the lane width.  VMEM budget per program =
cq·d (q) + t·d·2 (k,v) + cq·ck (scores) floats — for t ≤ 8k, d = 128 this
is ≤ 6 MiB, inside the ~16 MiB VMEM envelope.  Longer contexts tile K/V
over a third grid axis with a VMEM accumulator (same math; the dry-run
cells use the XLA path, which is the oracle for this kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float,
                      causal: bool, window: int | None, ck: int, t: int):
    cq = q_ref.shape[0]
    d = q_ref.shape[1]
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32)          # (cq, d)
    qpos = qi * cq + jax.lax.broadcasted_iota(jnp.int32, (cq, 1), 0)[:, 0]

    nk_total = t // ck
    if causal:
        # block-skip: only kv blocks that intersect [q_start - window, q_end]
        hi = jnp.minimum((qi * cq + cq + ck - 1) // ck, nk_total)
        lo = jnp.maximum((qi * cq - (window or t)) // ck, 0) if window else 0
    else:
        lo, hi = 0, nk_total

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(j * ck, ck), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * ck, ck), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = j * ck + jax.lax.broadcasted_iota(jnp.int32, (1, ck), 1)[0]
        if causal:
            mask = kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask = mask & (kpos[None, :] > qpos[:, None] - window)
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((cq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((cq,), jnp.float32)
    a0 = jnp.zeros((cq, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_fwd_pallas(q, k, v, *, causal: bool = True,
                     window: int | None = None, cq: int = 128, ck: int = 128,
                     interpret: bool = False):
    """q: (bh, s, d); k/v: (bh, t, d) — KV already expanded to q heads.

    Returns (bh, s, d).  ``interpret=True`` runs the kernel body in Python
    on CPU (the validation mode for this container); on TPU pass False.
    """
    bh, s, d = q.shape
    t = k.shape[1]
    assert s % cq == 0 and t % ck == 0, (s, cq, t, ck)
    nq = s // cq
    scale = 1.0 / float(d) ** 0.5
    kern = functools.partial(_flash_fwd_kernel, scale=scale, causal=causal,
                             window=window, ck=ck, t=t)
    return pl.pallas_call(
        kern,
        grid=(bh, nq),
        in_specs=[
            pl.BlockSpec((None, cq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, t, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, t, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, cq, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        interpret=interpret,
    )(q, k, v)
