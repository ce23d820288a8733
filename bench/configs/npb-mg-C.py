"""npb-mg-C: NPB MG's right-hand side from the seed, and its plain reference.

Written from ``MG/mg.f`` (NPB 3.x) and the sizes file alone; it imports
nothing of the program.  The grid, the iterations, the levels and every
operator's coefficients are read from the sizes file: ``a`` for ``resid``,
``c`` for ``psinv`` (the class's smoother), ``p`` for ``rprj3`` and ``q``
for ``interp``, each indexed by how many of a neighbour's three offsets
are nonzero.  The reference runs mg.f's solve on global float32 arrays,
one device, with ``jnp.roll`` for the periodic neighbours: no
``shard_map``, no halos.  ``resid``, ``psinv`` and ``rprj3`` take mg.f's
partial sums (the four neighbours one step off in y or z, the four off in
both, then along x); ``rprj3`` keeps fine point 2c + 1 (0-based) as coarse
point c; ``interp`` puts coarse c on fine 2c + 1 and, on fine 2c + 2, the
mean of the coarse points it lies between, as mg.f's eight cases do.

Arrays are (z, y, x): x, mg.f's first index, is the last axis.  Departures
from mg.f, listed under ``assumed`` in the sizes file:

- v is ten +1 and ten -1 charges at twenty distinct grid points drawn from
  the seed (:func:`charges`), in place of ``zran3``'s field;
- float32 in place of float64;
- no untimed first iteration: the solve starts from u = 0;
- a(1) and c(3), zero in every class, are left out as mg.f leaves them.
"""
from __future__ import annotations

import functools
import itertools
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def charges(s: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """The seed's charge points (z, y, x), int32 (k, 3), and their values:
    +1 on the first ``plus`` points, -1 on the next ``minus``."""
    n, q = s["n"], s["rhs_charges"]
    k = q["plus"] + q["minus"]
    flat = np.random.default_rng(seed).choice(n ** 3, size=k, replace=False)
    pts = np.stack(np.unravel_index(flat, (n, n, n)), axis=1)
    vals = np.concatenate([np.ones(q["plus"]), -np.ones(q["minus"])])
    return pts.astype(np.int32), vals


@functools.lru_cache(maxsize=None)
def _rhs_fn(n: int, dtype):
    def make(pts, vals):
        v = jnp.zeros((n, n, n), dtype)
        return v.at[pts[:, 0], pts[:, 1], pts[:, 2]].set(vals.astype(dtype))

    return jax.jit(make)


def rhs(s: dict, seed: int, device, dtype=jnp.float32):
    """v of the seed's charges, made on ``device``."""
    pts, vals = charges(s, seed)
    pts, vals = jax.device_put((pts, vals), device)
    return _rhs_fn(s["n"], jnp.dtype(dtype))(pts, vals)


# -- mg.f's operators on periodic global arrays --------------------------------


def _at(x, dz=0, dy=0, dx=0):
    """x at (z + dz, y + dy, x + dx), periodic."""
    return jnp.roll(x, (-dz, -dy, -dx), axis=(0, 1, 2))


def _weigh(x, w):
    """The 27-point sum at every point, each neighbour weighed by ``w`` of
    its count of nonzero offsets, in mg.f's partial sums: x1 (the four
    neighbours one step off in y or z) and x2 (the four off in both), then
    along x.  A zero weight's terms are left out, as mg.f leaves out a(1)
    and c(3)."""
    x1 = _at(x, dy=-1) + _at(x, dy=1) + _at(x, dz=-1) + _at(x, dz=1)
    x2 = (_at(x, dz=-1, dy=-1) + _at(x, dz=-1, dy=1)
          + _at(x, dz=1, dy=-1) + _at(x, dz=1, dy=1))
    terms = [x, _at(x, dx=-1) + _at(x, dx=1) + x1,
             x2 + _at(x1, dx=-1) + _at(x1, dx=1), _at(x2, dx=-1) + _at(x2, dx=1)]
    out = 0.0
    for wk, t in zip(w, terms):
        if wk:
            out = out + wk * t
    return out


def resid(u, v, a):
    """r = v - A u (mg.f ``resid``)."""
    return v - _weigh(u, a)


def psinv(r, u, c):
    """u + S r (mg.f ``psinv``)."""
    return u + _weigh(r, c)


def rprj3(r, p):
    """The coarse grid's P r (mg.f ``rprj3``): coarse c weighs the 27 fine
    neighbours of fine 2c + 1 by ``p``."""
    return _weigh(r, p)[1::2, 1::2, 1::2]


def _interleave(even, odd, ax):
    """Fine 2c from ``even[c]`` and fine 2c + 1 from ``odd[c]`` along
    axis ``ax``."""
    shape = list(even.shape)
    shape[ax] *= 2
    return jnp.stack([even, odd], axis=ax + 1).reshape(shape)


def interp(z, q):
    """Q z on the fine grid, twice the points an axis (mg.f ``interp``):
    fine 2c + 1 + h (h 0 or 1 on each axis) is ``q`` of h's count times
    the sum of coarse c + e over e <= h."""
    parts = {}
    for h in itertools.product((0, 1), repeat=3):
        acc = 0.0
        for e in itertools.product(*[range(k + 1) for k in h]):
            acc = acc + _at(z, *e)
        # fine 2c + 2 is fine 2c' with c' = c + 1: one roll on each h = 1 axis
        parts[h] = q[sum(h)] * jnp.roll(acc, h, axis=(0, 1, 2))
    # fine 2c is a halfway point (h 1), fine 2c + 1 a coarse point (h 0)
    xs = {hzy: _interleave(parts[(*hzy, 1)], parts[(*hzy, 0)], 2)
          for hzy in itertools.product((0, 1), repeat=2)}
    ys = {hz: _interleave(xs[(hz, 1)], xs[(hz, 0)], 1) for hz in (0, 1)}
    return _interleave(ys[1], ys[0], 0)


# -- the solve -----------------------------------------------------------------


def reference(s: dict, seed: int, device, dtype=jnp.float32):
    """(u, rnm2, rnmu) of the sizes' solve of the seed's v on ``device`` in
    ``dtype``: u after ``nit`` iterations from u = 0, the final residual's
    L2 norm sqrt(sum r² / n³) and its largest magnitude (mg.f
    ``norm2u3``)."""
    v = rhs(s, seed, device, dtype)
    key = json.dumps({k: s[k] for k in ("n", "nit", "lt", "lb", "a", "c",
                                        "p", "q")}, sort_keys=True)
    with jax.default_matmul_precision("highest"):
        return _solve_fn(key, jnp.dtype(dtype))(v)


@functools.lru_cache(maxsize=None)
def _solve_fn(key: str, dtype):
    s = json.loads(key)
    nit, lt, lb, a, c, p, q = (s[k] for k in ("nit", "lt", "lb", "a", "c",
                                              "p", "q"))
    if s["n"] != 2 ** lt:
        raise ValueError(f"n = {s['n']} is not 2**lt = {2 ** lt}")

    def mg3p(u, v, r):
        rs = {lt: r}
        for k in range(lt, lb, -1):
            rs[k - 1] = rprj3(rs[k], p)
        z = psinv(rs[lb], jnp.zeros_like(rs[lb]), c)
        for k in range(lb + 1, lt):
            uk = interp(z, q)
            z = psinv(resid(uk, rs[k], a), uk, c)
        u = u + interp(z, q)
        return psinv(resid(u, v, a), u, c)

    def solve(v):
        u = jnp.zeros_like(v)

        def iteration(_, ur):
            u = mg3p(ur[0], v, ur[1])
            return u, resid(u, v, a)

        u, r = lax.fori_loop(0, nit, iteration, (u, resid(u, v, a)))
        r = r.astype(jnp.float32)
        return (u, jnp.sqrt(jnp.sum(r * r) / float(s["n"]) ** 3),
                jnp.max(jnp.abs(r)))

    return jax.jit(solve)
