"""Program kind ``npb_mg``: NPB MG's solve (``repro.models.npb_mg``) as one
``shard_map``'d step over the cell's chips, NPB's rank grid.

A step is one whole solve from u = 0 of the seed's right-hand side: the
class's ``nit`` V-cycles and residuals, and the final residual's norm.  The
configuration's sizes give the class and the rank grid; the configuration
module (``cfg``) holds the plain reference that decides the check, written
from mg.f and the sizes alone.  A checkout whose ``src/`` has no
``repro.models.npb_mg`` runs the program from ``bench/npb_mg_program.py``,
a copy of that module, which the check never uses.

The mix's program entry: ``{"kind": "npb_mg"}``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.lru_cache(maxsize=1)
def _mg():
    """The program's module: the repository's, else the benchmark's copy."""
    try:
        from repro.models import npb_mg
    except ImportError:
        from bench.harness import BENCH, load_file_module
        return load_file_module(BENCH / "npb_mg_program.py",
                                "bench_npb_mg_program")
    return npb_mg


def _axes(s: dict) -> tuple:
    """Array axes (z, y, x) to mesh axis names, None for a whole axis."""
    return tuple(a if s["rank_grid"][a] > 1 else None for a in ("z", "y", "x"))


def make_mesh(s: dict, devices):
    """NPB's rank grid over the first of ``devices``: one mesh axis a split
    array axis."""
    from repro.compat import make_mesh
    names = [a for a in _axes(s) if a]
    ranks = int(np.prod(list(s["rank_grid"].values())))
    return make_mesh([s["rank_grid"][a] for a in names], names,
                     devices=devices[:ranks])


def count_flops(cfg, s: dict, program: dict) -> float:
    """Dense-matmul flops of one solve: none, every operator is a stencil."""
    return 0.0


def count_bytes(cfg, s: dict, program: dict) -> float:
    """The least HBM bytes one solve moves, all ranks together: each array
    of each operator read once and written once, at each level, and no
    ghost shell (NPB's ``comm3`` moves faces only).  With N_k = 8**k points
    at level k: ``resid`` and ``psinv`` read two arrays and write one,
    ``rprj3`` reads N_k and writes N_k / 8, ``interp`` reads the coarse
    N_k / 8, and at the top level u, and writes N_k; the bottom ``psinv``
    reads r and writes u; the norm reads r once."""
    lt, lb, nit = s["lt"], s["lb"], s["nit"]
    word = jnp.dtype(s["dtype"]).itemsize

    def n(k):
        return 8.0 ** k

    down = sum(n(k) + n(k) / 8 for k in range(lb + 1, lt + 1))
    up = sum(n(k) / 8 + n(k) + 6 * n(k) for k in range(lb + 1, lt))
    top = n(lt) / 8 + 2 * n(lt) + 6 * n(lt)
    cycle = down + 2 * n(lb) + up + top + 3 * n(lt)
    return word * (3 * n(lt) + nit * cycle + n(lt))


def trace_spec(cfg, s: dict, program: dict, mesh=None):
    """(step, abstract args, axis sizes) of one solve on ``mesh`` (default:
    the first chips of this host): what synthesis traces."""
    mg = _mg()
    mesh = make_mesh(s, jax.devices()) if mesh is None else mesh
    n = s["n"]
    step = mg.build(s["class"], mesh, _axes(s))
    return (step, (jax.ShapeDtypeStruct((n, n, n), jnp.dtype(s["dtype"])),),
            {a: int(mesh.shape[a]) for a in mesh.axis_names})


class Solve:
    """The class's solve of the seed's charges, over the cell's chips: the
    original program of a cell."""

    def __init__(self, cfg, s: dict, program: dict, seed: int, devices):
        self.cfg, self.s, self.program = cfg, s, dict(program)
        mg = _mg()
        if (mg.CLASSES[s["class"]]["n"], mg.CLASSES[s["class"]]["nit"]) != \
                (s["n"], s["nit"]):
            raise ValueError(f"sizes {s} are not NPB MG class {s['class']}")
        self.mesh = make_mesh(s, devices)
        self.bytes_per_step = count_bytes(cfg, s, program)
        self._step = mg.build(s["class"], self.mesh, _axes(s))
        self.reset(seed)

    def reset(self, seed: int):
        """The right-hand side of ``seed``; the next solve is the first."""
        mg = _mg()
        self.seed = seed
        self.v = mg.rhs(self.s["n"], seed,
                        mg.sharding(self.mesh, _axes(self.s)))
        self.first = None

    def trace_spec(self):
        return trace_spec(self.cfg, self.s, self.program, self.mesh)

    def warm(self):
        self.run(1)

    def run(self, n: int):
        for _ in range(n):
            out = self._step(self.v)
            jax.block_until_ready(out)
            if self.first is None:
                self.first = out

    def release(self):
        self._step = None

    def _gaps(self, dtype) -> dict:
        """The first solve's u and residual norm against the reference's,
        run on the first chip in ``dtype`` from the seed alone."""
        dev = self.mesh.devices.flat[0]
        u_ref, rnm2_ref, _ = self.cfg.reference(self.s, self.seed, dev, dtype)
        u, rnm2, _ = self.first
        u = jax.device_put(u, dev)
        if dtype == jnp.float32:
            u_gap = gap(u, u_ref)
            norm = float(rnm2)
        else:       # the control: the lower precision in the program's place
            u_f32, rnm2_f32, _ = self.cfg.reference(self.s, self.seed, dev)
            u_gap = gap(u_ref, u_f32)
            norm, rnm2_ref = float(rnm2_ref), float(rnm2_f32)
        return {"mg_u_gap": float(u_gap),
                "mg_norm_gap": abs(norm - float(rnm2_ref)) / float(rnm2_ref)}

    def check(self) -> dict:
        """The first solve's u and norm against the float32 reference."""
        return self._gaps(jnp.float32)

    def control(self) -> dict:
        """The same readings with a bfloat16 reference in the program's
        place."""
        return self._gaps(jnp.bfloat16)


@jax.jit
def gap(a, b):
    """max |a - b| / max |b|, in float32."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))


def build(cfg, s: dict, program: dict, seed: int, devices):
    return Solve(cfg, s, program, seed, devices)
