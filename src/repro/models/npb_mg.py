"""NPB MG: the multigrid kernel of the NAS Parallel Benchmarks (NPB 3.x
``MG/mg.f``; D. Bailey et al., "The NAS Parallel Benchmarks", NASA
RNR-94-007), as one ``shard_map``'d step, and its plain reference.

One step is one whole solve of the discrete Poisson problem A u = v on a
periodic n³ grid.  From u = 0 it runs ``nit`` iterations, each a V-cycle
(``mg3P``) and then the residual r = v - A u (``resid``); at the end it
reads r's L2 norm as ``norm2u3`` does, sqrt(sum r² / n³), and its largest
magnitude.  The V-cycle restricts the residual from the finest level ``lt``
(2**lt = n points an axis) down to ``lb`` = 1 (``rprj3``), smooths there
(``psinv``), and on each level on the way up interpolates the coarser
correction (``interp``), takes the residual and smooths again.  Every
operator is a 27-point stencil whose weight depends on how many of a
neighbour's three offsets are nonzero (centre, face, edge, corner):

- A (``resid``): a = (-8/3, 0, 1/6, 1/12);
- S (``psinv``): c = (-3/8, 1/32, -1/64, 0) for classes S, W and A, and
  (-3/17, 1/33, -1/61, 0) for B and C;
- P (``rprj3``): 1/2, 1/4, 1/8, 1/16 around fine point 2c + 1 of coarse c;
- Q (``interp``): trilinear, fine 2c + 1 on coarse c and fine 2c + 2
  halfway between c and c + 1.

Arrays are (z, y, x) with x, NPB's first Fortran index, last.  ``build``
splits array axes over the mesh axes ``axes`` names (None: the axis is
kept whole), NPB's rank grid.  After ``resid``, ``psinv`` and ``rprj3``
``comm3`` fills a ghost shell of width 1, axis by axis from x to z as
mg.f does, so edges and corners fill too: on a split axis by two
``ppermute``s, one a direction, on a whole axis by a local periodic wrap.
These operators read the ghost shell of their stencil input and write
interiors.  ``interp``, as in mg.f, writes the fine grid's ghost shell
too, from the coarse grid's, and is followed by no ``comm3``: 26
exchanges an iteration, 104 ``ppermute``s a rank.

:func:`reference` runs the same solve on global arrays, with ``jnp.roll``
for the periodic neighbours, no ``shard_map`` and no ghost shells.

Departures from mg.f, in the program and the reference alike:

- v is ten +1 and ten -1 charges at twenty distinct grid points drawn from
  a seed (:func:`charges`), where NPB's ``zran3`` puts them at the largest
  and smallest values of its pseudo-random field;
- float32, where NPB computes in float64;
- the a(1) and c(3) terms, zero in every class, are left out, as mg.f
  leaves them out;
- no untimed first iteration: mg.f runs one ``mg3P`` and resets u and v
  before its timed ``nit`` iterations, which this solve starts from.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

#: NPB's classes: grid points an axis and iterations
CLASSES = {
    "S": {"n": 32, "nit": 4},
    "W": {"n": 128, "nit": 4},
    "A": {"n": 256, "nit": 4},
    "B": {"n": 256, "nit": 20},
    "C": {"n": 512, "nit": 20},
}
LB = 1                                  # coarsest level: 2 points an axis
A = (-8.0 / 3.0, 0.0, 1.0 / 6.0, 1.0 / 12.0)
SMOOTHER = {"S": (-3.0 / 8.0, 1.0 / 32.0, -1.0 / 64.0, 0.0),
            "B": (-3.0 / 17.0, 1.0 / 33.0, -1.0 / 61.0, 0.0)}
RESTRICT = (0.5, 0.25, 0.125, 0.0625)   # rprj3's weights
N_CHARGES = 10                          # of each sign
DTYPE = jnp.float32


def levels(cls: str) -> int:
    """``lt``: the finest level, 2**lt = n."""
    return int(math.log2(CLASSES[cls]["n"]))


def smoother(cls: str) -> tuple:
    return SMOOTHER["S" if cls in ("S", "W", "A") else "B"]


def charges(n: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Twenty distinct grid points (z, y, x) drawn from ``seed``, and their
    charges: +1 on the first ten, -1 on the rest."""
    flat = np.random.default_rng(seed).choice(n ** 3, size=2 * N_CHARGES,
                                              replace=False)
    pts = np.stack(np.unravel_index(flat, (n, n, n)), axis=1)
    vals = np.repeat(np.array([1.0, -1.0]), N_CHARGES)
    return pts, vals


@functools.lru_cache(maxsize=None)
def _rhs_fn(n: int, dtype, sharding):
    def make(pts, vals):
        v = jnp.zeros((n, n, n), dtype)
        return v.at[pts[:, 0], pts[:, 1], pts[:, 2]].set(vals.astype(dtype))

    return jax.jit(make, out_shardings=sharding)


def rhs(n: int, seed: int, sharding, dtype=None):
    """v of the seed's charges, made on the device(s) of ``sharding``."""
    pts, vals = charges(n, seed)
    dt = jnp.dtype(DTYPE if dtype is None else dtype)
    return _rhs_fn(n, dt, sharding)(pts.astype(np.int32), vals)


# -- the sharded program ------------------------------------------------------


def _stencil_sums(p, start, m, step):
    """Sums of a padded array's 27 windows by how many offsets are nonzero:
    [centre, faces, edges, corners].  On each axis window o (0, 1, 2; 1 is
    the centre) starts at ``start + o`` and takes ``m`` points ``step``
    apart.  Axis by axis, as mg.f's partial sums do: each partial sum
    splits into its centre and its two neighbours' sum along the next
    axis, and the eight products sort by their count of neighbour axes."""
    parts = [(p, 0)]
    for ax in (2, 1, 0):
        def win(x, o, ax=ax):
            lo = start[ax] + o
            return lax.slice_in_dim(x, lo, lo + step * (m[ax] - 1) + 1, step,
                                    axis=ax)

        parts = [q for x, k in parts
                 for q in ((win(x, 1), k), (win(x, 0) + win(x, 2), k + 1))]
    sums = [None] * 4
    for x, k in parts:
        sums[k] = x if sums[k] is None else sums[k] + x
    return sums


def _interior(p):
    return p[1:-1, 1:-1, 1:-1]


def resid(u, v):
    """v - A u: ``u`` padded, ``v`` an interior."""
    s = _stencil_sums(u, (0, 0, 0), v.shape, 1)
    return v - (A[0] * s[0] + A[2] * s[2] + A[3] * s[3])


def psinv(r, u, c):
    """u + S r: ``r`` padded, ``u`` an interior."""
    s = _stencil_sums(r, (0, 0, 0), u.shape, 1)
    return u + (c[0] * s[0] + c[1] * s[1] + c[2] * s[2])


def rprj3(r):
    """P r: padded fine ``r`` to the coarse interior, half as many points
    an axis."""
    m = tuple((k - 2) // 2 for k in r.shape)
    s = _stencil_sums(r, (1, 1, 1), m, 2)
    return sum(w * x for w, x in zip(RESTRICT, s))


def _prolong_axis(z, dim):
    """Q along one axis: padded coarse (mc + 2) to padded fine (2 mc + 2),
    for j = 0 .. mc fine 2j - 1 at coarse j - 1 and fine 2j at the mean of
    coarse j - 1 and j.  Fine -1 and 2 mc, the ghosts, come from the
    coarse ghosts as a neighbour computes them."""
    mc = z.shape[dim] - 2
    lo = lax.slice_in_dim(z, 0, mc + 1, axis=dim)
    hi = lax.slice_in_dim(z, 1, mc + 2, axis=dim)
    out = jnp.stack([lo, 0.5 * (lo + hi)], axis=dim + 1)
    shape = list(z.shape)
    shape[dim] = 2 * mc + 2
    return out.reshape(shape)


def interp(z, u=None):
    """u + Q z: padded coarse ``z`` onto the padded fine ``u`` (zero where
    ``u`` is None, as after mg.f's ``zero3``), ghost shell included, so no
    ``comm3`` follows, as in mg.f."""
    for dim in (2, 1, 0):
        z = _prolong_axis(z, dim)
    return z if u is None else u + z


def _ghosts(x, dim, name, size):
    """``x`` with one ghost plane each side of ``dim``: the neighbours'
    faces by ``ppermute`` over mesh axis ``name``, or the periodic wrap of
    its own where the axis is whole (``name`` None)."""
    k = x.shape[dim]
    lo = lax.slice_in_dim(x, 0, 1, axis=dim)
    hi = lax.slice_in_dim(x, k - 1, k, axis=dim)
    if name is None:
        below, above = hi, lo
    else:
        below = lax.ppermute(hi, name, [(i, (i + 1) % size)
                                        for i in range(size)])
        above = lax.ppermute(lo, name, [(i, (i - 1) % size)
                                        for i in range(size)])
    return jnp.concatenate([below, x, above], axis=dim)


def comm3(x, axes, sizes):
    """``x`` with its ghost shell, axis by axis from x (the last) to z:
    array axis ``d`` is split over mesh axis ``axes[d]`` in ``sizes[d]``
    ranks, or whole where ``axes[d]`` is None."""
    for dim in (2, 1, 0):
        x = _ghosts(x, dim, axes[dim], sizes[dim])
    return x


def build(cls: str, mesh, axes=("z", "y", None)):
    """The jitted, ``shard_map``'d solve of class ``cls``: v, an n³ array
    split over ``mesh`` as ``axes`` says, to (u, rnm2, rnmu).  u is the
    solution's interior, split as v; rnm2 and rnmu are the final
    residual's L2 norm and largest magnitude."""
    n, nit = CLASSES[cls]["n"], CLASSES[cls]["nit"]
    lt = levels(cls)
    c = smoother(cls)
    sizes = [mesh.shape[a] if a else 1 for a in axes]
    split = tuple(a for a in axes if a)

    def halo(x):
        return comm3(x, axes, sizes)

    def zeros(k, dt):
        return jnp.zeros([(1 << k) // s for s in sizes], dt)

    def mg3p(u, v, r):
        rs = {lt: r}
        for k in range(lt, LB, -1):
            rs[k - 1] = halo(rprj3(rs[k]))
        z = halo(psinv(rs[LB], zeros(LB, v.dtype), c))
        for k in range(LB + 1, lt):
            uk = interp(z)
            rk = halo(resid(uk, _interior(rs[k])))
            z = halo(psinv(rk, _interior(uk), c))
        u = interp(z, u)
        r = halo(resid(u, v))
        return halo(psinv(r, _interior(u), c))

    def solve(v):
        u = jnp.pad(jnp.zeros_like(v), 1)
        r = halo(resid(u, v))

        def iteration(carry, _):
            u, r = carry
            u = mg3p(u, v, r)
            return (u, halo(resid(u, v))), None

        (u, r), _ = lax.scan(iteration, (u, r), None, length=nit)
        r = _interior(r).astype(jnp.float32)
        rnm2 = jnp.sqrt(lax.psum(jnp.sum(r * r), split) / float(n) ** 3)
        rnmu = lax.pmax(jnp.max(jnp.abs(r)), split)
        return _interior(u), rnm2, rnmu

    spec = P(*axes)
    return jax.jit(jax.shard_map(solve, mesh=mesh, in_specs=spec,
                                 out_specs=(spec, P(), P())))


def sharding(mesh, axes=("z", "y", None)):
    return NamedSharding(mesh, P(*axes))


# -- the plain reference ------------------------------------------------------


def _ref_sums(x):
    """[centre, faces, edges, corners] of every point's 27 periodic
    neighbours, by ``jnp.roll``."""
    def nb(y, ax):
        return jnp.roll(y, 1, ax) + jnp.roll(y, -1, ax)

    n2, n1 = nb(x, 2), nb(x, 1)
    n12 = nb(n2, 1)
    return [x, nb(x, 0) + n1 + n2, nb(n1 + n2, 0) + n12, nb(n12, 0)]


def _ref_resid(u, v):
    s = _ref_sums(u)
    return v - (A[0] * s[0] + A[2] * s[2] + A[3] * s[3])


def _ref_psinv(r, u, c):
    s = _ref_sums(r)
    return u + (c[0] * s[0] + c[1] * s[1] + c[2] * s[2])


def _ref_rprj3(r):
    s = _ref_sums(r)
    full = sum(w * x for w, x in zip(RESTRICT, s))
    return full[1::2, 1::2, 1::2]


def _ref_interp(z):
    for ax in range(3):
        odd = z
        even = 0.5 * (jnp.roll(z, 1, ax) + z)
        shape = list(z.shape)
        shape[ax] *= 2
        z = jnp.stack([even, odd], axis=ax + 1).reshape(shape)
    return z


def reference(cls: str, v, dtype=jnp.float32):
    """(u, rnm2, rnmu) of the class's solve of ``v`` on global arrays in
    ``dtype``, at ``highest`` matmul precision."""
    with jax.default_matmul_precision("highest"):
        return _reference_fn(cls, jnp.dtype(dtype))(v)


@functools.lru_cache(maxsize=None)
def _reference_fn(cls: str, dtype):
    nit = CLASSES[cls]["nit"]
    lt = levels(cls)
    c = smoother(cls)

    def mg3p(u, v, r):
        rs = {lt: r}
        for k in range(lt, LB, -1):
            rs[k - 1] = _ref_rprj3(rs[k])
        z = _ref_psinv(rs[LB], jnp.zeros_like(rs[LB]), c)
        for k in range(LB + 1, lt):
            uk = _ref_interp(z)
            z = _ref_psinv(_ref_resid(uk, rs[k]), uk, c)
        u = u + _ref_interp(z)
        return _ref_psinv(_ref_resid(u, v), u, c)

    def solve(v):
        v = v.astype(dtype)
        u = jnp.zeros_like(v)

        def iteration(_, ur):
            u = mg3p(ur[0], v, ur[1])
            return u, _ref_resid(u, v)

        u, r = lax.fori_loop(0, nit, iteration, (u, _ref_resid(u, v)))
        r = r.astype(jnp.float32)
        return (u, jnp.sqrt(jnp.sum(r * r) / float(v.size)),
                jnp.max(jnp.abs(r)))

    return jax.jit(solve)
