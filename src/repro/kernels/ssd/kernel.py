"""Pallas TPU kernel for the SSD (Mamba2) intra-chunk diagonal block.

Computes, for one (batch, chunk, group) program:

    scores  = C Bᵀ                      (q×k MXU matmul, n-contraction)
    L[i,j]  = exp(cum_i − cum_j)·1[i≥j]  per head      (VPU)
    Y_diag  = (scores ∘ L) (dt·X)        (one q×k×p MXU matmul per head)

This is the quadratic-in-chunk hot spot of the SSD dual form — the analog
of flash attention's score block, with the decay mask in place of softmax.
Every in-kernel array is 2-D with a 128-lane-friendly minor dim: heads are
a leading block dim, and the wrapper hands ``cum``/``dt`` to the kernel
both head-major (rows, ``(r, q)``) and time-major (columns, ``(q, r)``) so
the decay matrix is a broadcast difference with no in-kernel reshape.
VMEM per program: q·n (B,C) + 2·q·r (cum, dt) + 2·r·q·p (X, Y) + a few
q·q (scores, one head's mask) floats; q=128..256, r≤8-per-slab keeps it in
budget — ops.py slabs the head dim when r is large.  Chunk q and state n
are 128-multiples (MXU-aligned); the inter-chunk recurrence stays in XLA
(it is linear-time and bandwidth-bound, not MXU work).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_diag_kernel(x_ref, dtc_ref, cumr_ref, cumc_ref, b_ref, c_ref,
                     y_ref):
    # blocks: x/y (r, q, p)  dtc/cumc (q, r)  cumr (r, q)  b/c (q, n)
    r, q, _ = x_ref.shape
    scores = jax.lax.dot_general(
        c_ref[...].astype(jnp.float32), b_ref[...].astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)  # (q, k)
    iq = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    ik = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    causal = iq >= ik
    cumr = cumr_ref[...].astype(jnp.float32)
    cumc = cumc_ref[...].astype(jnp.float32)
    dtc = dtc_ref[...].astype(jnp.float32)
    for j in range(r):
        dec = cumc[:, j:j + 1] - cumr[j:j + 1, :]                  # (q, k)
        m = scores * jnp.where(causal, jnp.exp(dec), 0.0)
        dx = dtc[:, j:j + 1] * x_ref[j].astype(jnp.float32)         # (k, p)
        y = jax.lax.dot_general(m, dx, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        y_ref[j] = y.astype(y_ref.dtype)


def ssd_diag_pallas(x, dt, cum, b, c, *, interpret: bool = False):
    """x: (nb, nc, q, g, r, p); dt/cum: (nb, nc, q, g, r); b/c: (nb, nc, q, g, n).

    Returns y_diag: (nb, nc, q, g, r, p).  Grid: (nb, nc, g).
    """
    nb, nc, q, g, r, p = x.shape
    n = b.shape[-1]
    def cols(a):                                       # (nb, nc, g, q, *)
        return a.transpose(0, 1, 3, 2, 4)

    xh = x.transpose(0, 1, 3, 4, 2, 5)                 # (nb, nc, g, r, q, p)
    cumr = cum.transpose(0, 1, 3, 4, 2)                # (nb, nc, g, r, q)
    per_head = pl.BlockSpec((None, None, None, r, q, p),
                            lambda i, j, k: (i, j, k, 0, 0, 0))
    y = pl.pallas_call(
        _ssd_diag_kernel,
        grid=(nb, nc, g),
        in_specs=[
            per_head,
            pl.BlockSpec((None, None, None, q, r),
                         lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((None, None, None, r, q),
                         lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((None, None, None, q, r),
                         lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((None, None, None, q, n),
                         lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((None, None, None, q, n),
                         lambda i, j, k: (i, j, k, 0, 0)),
        ],
        out_specs=per_head,
        out_shape=jax.ShapeDtypeStruct(xh.shape, x.dtype),
        interpret=interpret,
    )(xh, cols(dt), cumr, cols(cum), cols(b), cols(c))
    return y.transpose(0, 1, 4, 2, 3, 5)
