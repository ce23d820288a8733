"""Pallas TPU kernels for the two hottest Siesta proxy blocks.

The paper's replay spends its cycles in the basic blocks (Fig. 3); on TPU
the two that dominate replay wall-time are the MXU block (repeated 128³
matmul) and the HBM stream block.  Both are written as explicit-iteration
kernels so one ``pallas_call`` replays ``reps`` applications without
re-entering XLA per application — the kernel-level analog of the paper's
block-11 loop.

* ``mxu_iter_kernel``: a: (128,128) bf16 resident in VMEM; ``reps``
  fori_loop turns of a ← (a·b)/128 on the MXU.  One grid program, zero HBM
  traffic between turns — this is the block's designed behavior (high AI).
* ``stream_iter_kernel``: grid over 8·128-aligned vector tiles; each
  program streams its tile through VMEM ``reps`` times (v ← v·c + d).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

MM = 128
TILE = 8 * 128


def _mxu_iter_kernel(a_ref, b_ref, o_ref, *, reps: int):
    b = b_ref[...]

    def body(i, a):
        return (jax.lax.dot(a, b, preferred_element_type=jnp.float32)
                * (1.0 / MM)).astype(a.dtype)

    o_ref[...] = jax.lax.fori_loop(0, reps, body, a_ref[...])


def mxu_pallas(a, b, reps: int, *, interpret: bool = False):
    """a, b: (128, 128) bf16; returns a after ``reps`` MXU turns."""
    kern = functools.partial(_mxu_iter_kernel, reps=reps)
    return pl.pallas_call(
        kern,
        in_specs=[pl.BlockSpec((MM, MM), lambda: (0, 0)),
                  pl.BlockSpec((MM, MM), lambda: (0, 0))],
        out_specs=pl.BlockSpec((MM, MM), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((MM, MM), a.dtype),
        interpret=interpret,
    )(a, b)


def _stream_iter_kernel(v_ref, o_ref, *, reps: int):
    def body(i, v):
        return v * 0.999999 + 1e-6

    o_ref[...] = jax.lax.fori_loop(0, reps, body, v_ref[...])


def stream_pallas(v, reps: int, *, interpret: bool = False):
    """v: (n,) f32 with n a multiple of 1024; tiled streaming update."""
    n = v.shape[0]
    assert n % TILE == 0, n
    kern = functools.partial(_stream_iter_kernel, reps=reps)
    return pl.pallas_call(
        kern,
        grid=(n // TILE,),
        in_specs=[pl.BlockSpec((TILE,), lambda i: (i,))],
        out_specs=pl.BlockSpec((TILE,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), v.dtype),
        interpret=interpret,
    )(v)
