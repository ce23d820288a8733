"""Collective wrappers + replay comm backends (DESIGN.md §2).

Two roles:

1. **Instrumented wrappers** (`psum`, `all_gather`, ...): thin wrappers over
   ``jax.lax`` collectives that additionally record a :class:`CommEvent` into
   the active :class:`~repro.core.tracer.TraceSession` — the literal PMPI
   interposition analog for host-level drivers (pipeline schedules, serving
   engines).  Inside ``jit`` they are recorded once at trace time, which is
   exactly the event the compiled program will execute.

2. **Replay comm backends** for generated proxy-apps:
   * :class:`LocalSim` — executes a cheap local op honoring the payload
     shape; used for single-host replay where only the compute stream is
     measured (comm fidelity is validated via the lowered HLO instead).
   * :class:`DeviceComm` — executes the *real* collective over mesh axes
     (must run inside ``shard_map``); payload shape, dtype, axes and permute
     offsets reproduce the traced event exactly, so the proxy's compiled
     collective schedule matches the original's (losslessness, paper §1).

Every backend folds the collective result back into the fixed-shape pool
buffer (mean/slice), so proxy state is a stable pytree through ``fori_loop``.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro import obs
from repro.core.events import CommEvent, decode_relative_perm
from repro.core import tracer as _tracer

_RUNS = threading.local()


def traced_runs() -> int:
    """How often the code being traced now runs in one call of its
    executable: the product of the trip counts :func:`runs` declared
    around it (1 outside them)."""
    return getattr(_RUNS, "n", 1)


@contextlib.contextmanager
def runs(n: int):
    """Trace the enclosed code, which the executable runs ``n`` times for
    each run of the code around it: a rolled loop's body, a switch branch
    taken ``n`` times."""
    outer = traced_runs()
    _RUNS.n = outer * int(n)
    try:
        yield
    finally:
        _RUNS.n = outer


# ---------------------------------------------------------------------------
# instrumented wrappers (use these in framework code instead of raw lax.*)
# ---------------------------------------------------------------------------


def _record(kind: str, x, axes, detail: tuple = ()):
    s = _tracer.active_session()
    if s is not None:
        axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
        shape = tuple(getattr(x, "shape", ()))
        dtype = str(getattr(x, "dtype", "float32"))
        s.emit(None, CommEvent(kind=kind, shape=shape, dtype=dtype,
                               axes=tuple(str(a) for a in axes_t),
                               detail=detail))


def psum(x, axes):
    _record("psum", x, axes)
    return lax.psum(x, axes)


def pmax(x, axes):
    _record("pmax", x, axes)
    return lax.pmax(x, axes)


def all_gather(x, axis, *, gather_dim: int = 0, tiled: bool = False):
    _record("all_gather", x, axis, (gather_dim,))
    return lax.all_gather(x, axis, axis=gather_dim, tiled=tiled)


def psum_scatter(x, axis, *, scatter_dim: int = 0, tiled: bool = True):
    _record("reduce_scatter", x, axis, (scatter_dim,))
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_dim, tiled=tiled)


def all_to_all(x, axis, split_axis: int, concat_axis: int, *, tiled: bool = True):
    _record("all_to_all", x, axis, (split_axis, concat_axis))
    return lax.all_to_all(x, axis, split_axis, concat_axis, tiled=tiled)


def ppermute(x, axis, perm: Sequence[tuple[int, int]]):
    _record("ppermute", x, axis, ("rawperm", tuple(tuple(p) for p in perm)))
    return lax.ppermute(x, axis, perm=perm)


# ---------------------------------------------------------------------------
# replay comm backends
# ---------------------------------------------------------------------------


class LocalSim:
    """Single-host replay: a shape-honoring local op per collective.

    The op creates a true data dependency on the pool buffer (a sequence
    point, like an MPI call is), with negligible compute — the paper replays
    communication on the real network; on this CPU container the network
    fidelity is asserted on the lowered HLO of the DeviceComm path instead.

    Batched rank axis: the sequence point is shape-agnostic, so the same
    backend serves the per-rank path and the ``vmap``-ed signature-group
    path of :meth:`repro.core.replay.ProxyProgram.run_all`, where every
    pool buffer carries a leading rank dimension (JAX batches
    ``optimization_barrier`` as the identity on batch dims).

    ``trace_events`` counts ``do`` calls *at trace time* (one per comm call
    site per program trace — loop bodies count once, like the grammar's
    run-length symbols).  Caveat: plain ``LocalSim`` instances are
    interchangeable to the replay compile cache (keyed by class, so fresh
    instances reuse warm executables and trigger **no** new traces); to
    count exactly, pass an identity-keyed *subclass* instance to a fresh
    ``ProxyProgram`` — see ``CountingSim`` in tests/test_replay_batched.py,
    where equal per-signature counts between the batched and per-rank paths
    serve as a cheap losslessness probe.
    """

    def __init__(self):
        self.trace_events = 0

    def do(self, st: dict, buf: str, *, kind: str, axes, detail, shape, dtype):
        self.trace_events += 1
        st = dict(st)
        # a pure sequence point: orders the replay like the MPI call does,
        # contributes zero compute metrics (it is not the comm being modeled)
        st[buf] = jax.lax.optimization_barrier(st[buf])
        return st


class DeviceComm:
    """Mesh replay inside shard_map: executes the recorded collective exactly.

    ``axis_sizes`` must match the mesh the proxy runs under.  The payload
    tensor fed to the collective has exactly the traced shape/dtype; the
    result is folded back (mean over gathered dim / broadcast) so the pool
    buffer shape is stable — shape *and* dtype of ``st[buf]`` are invariant
    through ``do`` for every collective kind, which is what keeps the proxy
    state a fixed pytree under ``fori_loop`` and ``vmap`` alike.

    Batched rank axis: ``do`` is ``vmap``-compatible over a leading rank
    dimension, mirroring :class:`LocalSim`.  Inside ``shard_map``, the mesh
    replay engine stacks a whole signature group's states and ``vmap``-s
    ``run_rank`` over them; JAX's collective batching rules fold the rank
    axis through the *real* collectives (one batched all-reduce instead of
    n sequential ones), so an entire group replays in a single dispatch.
    Every branch below — including the non-divisible ``reduce_scatter`` /
    ``all_to_all`` fallbacks and all :func:`_detail_to_perm` decode paths —
    is audited for this (see :func:`repro.compat.collective_batching_audit`
    and tests/test_replay_mesh.py: batched-vs-sequential replay is
    bit-identical for every kind).

    Each collective ``do`` lowers adds, at trace time, how often the
    executable runs it (:func:`traced_runs`) to the counter
    ``replay.collectives``.
    """

    def __init__(self, axis_sizes: dict[str, int]):
        self.axis_sizes = dict(axis_sizes)

    def do(self, st: dict, buf: str, *, kind: str, axes, detail, shape, dtype):
        obs.count("replay.collectives", traced_runs())
        st = dict(st)
        x = st[buf].astype(dtype).reshape(shape)
        ax = axes if len(axes) > 1 else axes[0]
        if kind in ("psum", "pmax", "pmin"):
            op = {"psum": lax.psum, "pmax": lax.pmax, "pmin": lax.pmin}[kind]
            y = op(x, ax)
            if kind == "psum":
                n = 1
                for a in axes:
                    n *= self.axis_sizes[a]
                y = y / max(n, 1)
        elif kind == "all_gather":
            dim = int(detail[0]) if detail else 0
            g = lax.all_gather(x, ax, axis=0)
            y = jnp.mean(g.astype(jnp.float32), axis=0).astype(dtype)
            del dim
        elif kind == "reduce_scatter":
            dim = int(detail[0]) if detail else 0
            size = self.axis_sizes[axes[0]]
            if shape[dim] % size == 0:
                y = lax.psum_scatter(x, ax, scatter_dimension=dim, tiled=True)
                reps = [1] * y.ndim
                reps[dim] = size
                y = jnp.tile(y, reps) / size
            else:
                y = lax.psum(x, ax) / size
        elif kind == "all_to_all":
            split, concat = (int(detail[0]), int(detail[1])) if len(detail) >= 2 else (0, 0)
            size = self.axis_sizes[axes[0]]
            if x.shape[split] % size == 0:
                y = lax.all_to_all(x, ax, split, concat, tiled=True)
                y = _reshape_back(y, shape)
            else:
                y = lax.ppermute(x, ax, [(i, (i + 1) % size) for i in range(size)])
        elif kind == "ppermute":
            size = self.axis_sizes[axes[0]]
            perm = _detail_to_perm(detail, size)
            y = lax.ppermute(x, ax, perm)
        elif kind == "broadcast":
            y = lax.all_gather(x, ax, axis=0)[0]
        else:
            raise ValueError(f"unknown collective kind {kind!r}")
        st[buf] = y.reshape(st[buf].shape).astype(st[buf].dtype)
        return st


def _reshape_back(y, shape):
    n = 1
    for s in shape:
        n *= s
    return y.reshape(shape) if y.size == n else y


def _detail_to_perm(detail: tuple, size: int) -> list[tuple[int, int]]:
    if detail and detail[0] in ("shift", "perm", "empty"):
        return decode_relative_perm(detail, size)
    if detail and detail[0] == "rawperm":
        return [tuple(p) for p in detail[1]]
    return [(i, (i + 1) % size) for i in range(size)]
