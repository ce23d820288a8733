"""Event tracing front-ends (paper §2.2–2.3, DESIGN.md §2).

The paper intercepts MPI calls with PMPI and reads PAPI counters around them.
Our programs are staged JAX, so tracing needs no runtime interposition at all:

* :func:`trace_fn` walks the jaxpr of a step function.  Collective primitives
  (``psum``/``all_gather``/``reduce_scatter``/``all_to_all``/``ppermute`` …,
  visible inside ``shard_map`` bodies) become :class:`CommEvent`s; every
  equation between two collectives accumulates into the pending 6-metric
  vector of a :class:`ComputeEvent` (the virtual ``MPI_Compute`` call).

* :class:`TraceSession` is the host-level recorder for multi-step drivers
  (pipeline schedules, serving engines) whose per-rank behaviour differs in
  Python, not in the jaxpr.  The collective wrappers in
  :mod:`repro.sharding.collectives` record into the active session — the
  literal PMPI-interposition analog.

``lax.scan`` bodies that contain collectives are walked once per iteration so
the event sequence is exact; Sequitur's run-length constraint collapses the
repetition back to O(1) grammar space.  Collective-free bodies are costed
``length`` times in O(1) and charged ``length`` scan steps (the serialization
hazard metric).

Handle canonicalization (paper: MPI_Request/MPI_Comm pools): distinct
``axis_index_groups`` values are renumbered in first-use order, so traces stay
low-entropy and compressible.
"""
from __future__ import annotations

import dataclasses
import math
import threading
from typing import Any, Callable, Iterable, Sequence

import jax
import numpy as np
from jax.extend.core import Literal as _Literal

from repro.core.events import (
    CommEvent, ComputeEvent, Event, N_METRICS, encode_relative_perm, is_comm,
)
from repro.core.metrics import (
    CALL_PRIMS, COLLECTIVE_PRIMS, CUSTOM_DIFF_PRIMS, HIGHER_ORDER_PRIMS,
    I_SCAN, collective_event_info, eqn_cost,
)

_FOLD_SIZE_CAP = 1 << 16   # skip folding on large operands (opcode arrays ok)


@dataclasses.dataclass
class Trace:
    """A template trace: one SPMD event stream plus mesh-axis metadata.

    ``ppermute`` events carry their raw permutation; :func:`per_rank_traces`
    specializes them into per-rank relative-encoded events.
    """
    events: list[Event]
    axis_sizes: dict[str, int]

    def comm_events(self) -> list[CommEvent]:
        return [e for e in self.events if is_comm(e)]

    def compute_events(self) -> list[ComputeEvent]:
        return [e for e in self.events if not is_comm(e)]

    def total_compute(self) -> np.ndarray:
        vec = np.zeros(N_METRICS)
        for e in self.compute_events():
            vec += e.vector
        return vec

    def total_comm_bytes(self) -> int:
        return sum(e.payload_bytes for e in self.comm_events())

    def compute_metrics_array(self) -> np.ndarray:
        """``(n_compute_events, 6)`` float64 metric rows in stream order —
        the per-event variance the noise calibrator consumes (the columnar
        twin of ``TraceStore.metrics`` for a single template trace)."""
        rows = [e.metrics for e in self.compute_events()]
        if not rows:
            return np.zeros((0, N_METRICS))
        return np.asarray(rows, dtype=np.float64)


class JaxprWalker:
    """Recursive jaxpr walk producing the template event stream.

    ``exact_cond=True`` switches on constant-propagated control-flow
    resolution: jaxpr constants (and scan-carried constants / per-iteration
    xs slices) flow through an environment, ``cond`` equations with a
    resolved scalar index walk **only the selected branch**, a
    ``fori_loop`` whose bound folds walks its body that many times, and
    equations whose inputs are fully constant fold to zero cost (they are
    program-counter bookkeeping — e.g. the ``clamp`` a ``lax.switch``
    inserts — not workload).  This is how grammar-compiled proxy modules
    (scan-over-opcodes + switch dispatch, :mod:`repro.core.progtable`) measure
    bit-identically to the unrolled reference.  Default off: original-
    program traces (which may use data-dependent ``lax.cond``) keep the
    legacy branch-0 / max-cost semantics, so fidelity baselines are
    untouched.
    """

    def __init__(self, axis_sizes: dict[str, int] | None = None,
                 exact_cond: bool = False):
        self.events: list[Event] = []
        self.pending = np.zeros(N_METRICS, dtype=np.float64)
        self.axis_sizes: dict[str, int] = dict(axis_sizes or {})
        self.exact_cond = bool(exact_cond)
        self._group_pool: dict[tuple, int] = {}   # handle canonicalization

    # -- event emission -------------------------------------------------------

    def flush(self) -> None:
        if self.pending.any():
            self.events.append(ComputeEvent(tuple(self.pending)))
            self.pending = np.zeros(N_METRICS, dtype=np.float64)

    def _emit_comm(self, eqn) -> None:
        self.flush()
        info = collective_event_info(eqn)
        # canonicalize axis_index_groups handles through a first-use pool
        detail = info["detail"]
        if detail and detail[0] == "groups" or (len(detail) > 2 and "groups" in detail):
            detail = self._canon_groups(detail)
        elif "groups" in detail:
            detail = self._canon_groups(detail)
        info["detail"] = detail
        self.events.append(CommEvent(**info))

    def _canon_groups(self, detail: tuple) -> tuple:
        out = []
        i = 0
        while i < len(detail):
            if detail[i] == "groups" and i + 1 < len(detail):
                gid = self._group_pool.setdefault(detail[i + 1],
                                                  len(self._group_pool))
                out.extend(["groups", gid])
                i += 2
            else:
                out.append(detail[i])
                i += 1
        return tuple(out)

    # -- recursion ------------------------------------------------------------

    def walk(self, jaxpr, env: dict | None = None) -> None:
        """Walk a (possibly Closed) jaxpr, emitting events in program order.

        ``env`` (exact mode only) maps jaxpr Vars to known host values;
        the closed jaxpr's own constants are merged in."""
        inner = getattr(jaxpr, "jaxpr", jaxpr)
        if self.exact_cond:
            env = dict(env or {})
            for var, val in zip(inner.constvars, getattr(jaxpr, "consts", ())):
                env.setdefault(var, np.asarray(val))
        else:
            env = None
        for eqn in inner.eqns:
            self._walk_eqn(eqn, env)

    # -- constant environment (exact mode) --------------------------------------

    @staticmethod
    def _val(v, env):
        """Known host value of an atom, or None."""
        if isinstance(v, _Literal):
            return np.asarray(v.val)
        return None if env is None else env.get(v)

    def _walk_sub(self, closed, invars, env) -> None:
        """Walk a sub-jaxpr, mapping resolved outer invars onto its invars."""
        if not self.exact_cond:
            self.walk(closed)
            return
        inner = getattr(closed, "jaxpr", closed)
        sub: dict = {}
        if invars is not None:
            for ivar, outer in zip(inner.invars, invars):
                val = self._val(outer, env)
                if val is not None:
                    sub[ivar] = val
        self.walk(closed, sub)

    def _try_fold(self, eqn, env) -> bool:
        """Eagerly evaluate a fully-constant equation; record its outputs in
        ``env`` and treat it as free.  Constant equations in generated
        modules are dispatch bookkeeping (switch index clamps, opcode
        casts), not replayed workload — costing them would break δ̄ parity
        with the unrolled reference, which has no dispatch machinery."""
        name = eqn.primitive.name
        if name in HIGHER_ORDER_PRIMS or name in COLLECTIVE_PRIMS \
                or "callback" in name:
            return False
        for v in eqn.params.values():
            if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                return False
        vals = []
        for v in eqn.invars:
            val = self._val(v, env)
            if val is None or np.size(val) > _FOLD_SIZE_CAP:
                return False
            vals.append(val)
        try:
            out = eqn.primitive.bind(*[np.asarray(v) for v in vals],
                                     **eqn.params)
        except Exception:
            return False
        outs = out if eqn.primitive.multiple_results else [out]
        for var, val in zip(eqn.outvars, outs):
            env[var] = np.asarray(val)
        return True

    def _walk_eqn(self, eqn, env: dict | None = None) -> None:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            self._emit_comm(eqn)
            return
        if name in CALL_PRIMS:
            self._walk_sub(eqn.params["jaxpr"], eqn.invars, env)
            return
        if name in CUSTOM_DIFF_PRIMS:
            self.walk(eqn.params["call_jaxpr"])
            return
        if name == "shard_map":
            mesh = eqn.params.get("mesh")
            if mesh is not None:
                for ax, sz in zip(mesh.axis_names, mesh.shape.values()
                                  if hasattr(mesh.shape, "values") else mesh.shape):
                    self.axis_sizes[str(ax)] = int(sz)
            self._walk_sub(eqn.params["jaxpr"], eqn.invars, env)
            return
        if name == "scan":
            self._walk_scan(eqn, env)
            return
        if name == "while":
            self._walk_while(eqn, env)
            return
        if name == "cond":
            self._walk_cond(eqn, env)
            return
        if env is not None and self._try_fold(eqn, env):
            return
        self.pending += eqn_cost(eqn)

    # -- higher-order handling --------------------------------------------------

    def _scan_layout(self, eqn):
        nc = int(eqn.params.get("num_consts", 0))
        ncar = int(eqn.params.get("num_carry", 0))
        return nc, ncar

    def _scan_iter_env(self, body, invals, t: int) -> dict | None:
        """Body-invar environment for scan iteration ``t``: scan constants
        pass through whole, xs operands are sliced per iteration, carries
        stay unknown."""
        if invals is None:
            return None
        nc, ncar, vals = invals
        inner = getattr(body, "jaxpr", body)
        bvars = inner.invars
        env: dict = {}
        for var, val in zip(bvars[:nc], vals[:nc]):
            if val is not None:
                env[var] = val
        for var, val in zip(bvars[nc + ncar:], vals[nc + ncar:]):
            if val is not None:
                env[var] = np.asarray(val)[t]
        return env

    def _walk_scan(self, eqn, env: dict | None = None) -> None:
        body = eqn.params["jaxpr"]
        length = int(eqn.params["length"])
        invals = None
        if self.exact_cond:
            nc, ncar = self._scan_layout(eqn)
            invals = (nc, ncar, [self._val(v, env) for v in eqn.invars])
        has_cond = self.exact_cond and _contains_cond(body)
        xs_known = (invals is not None
                    and len(invals[2]) > invals[0] + invals[1]
                    and all(v is not None
                            for v in invals[2][invals[0] + invals[1]:]))
        if _contains_collective(body) or (has_cond and xs_known):
            # exact event sequence; Sequitur's RLE makes this O(1) in grammar.
            # cond-bearing bodies with known xs (switch dispatch over a
            # constant opcode array) also walk per-iteration: each step
            # resolves to exactly the branch the reference emitted inline,
            # and no scan-step serialization is charged — the reference's
            # straight-line statements charge none either.
            for t in range(length):
                self.walk(body, self._scan_iter_env(body, invals, t))
            return
        if has_cond:
            # rolled rule body (cond nested below an exponent scan): cost one
            # exact iteration with the loop-invariant constants, like the
            # reference's rep()-scan of the same body
            self.pending += self._exact_body_cost(body, invals) * length
            self.pending[I_SCAN] += length
            return
        cost = _subtree_cost(body)
        self.pending += cost * length
        self.pending[I_SCAN] += length

    def _exact_body_cost(self, body, invals) -> np.ndarray:
        """One-iteration 6-metric cost of a comm-free scan body, walked in
        exact mode with the scan constants bound (xs/carries unknown)."""
        w = JaxprWalker(self.axis_sizes, exact_cond=True)
        env = self._scan_iter_env(body, invals, 0)
        if env is not None and invals is not None:
            nc, ncar, _ = invals
            inner = getattr(body, "jaxpr", body)
            # xs slices are iteration-dependent: drop them from the cost env
            for var in inner.invars[nc + ncar:]:
                env.pop(var, None)
        w.walk(body, env)
        w.flush()
        vec = np.zeros(N_METRICS)
        for e in w.events:
            vec += e.vector
        return vec

    def _walk_while(self, eqn, env: dict | None = None) -> None:
        body = eqn.params["body_jaxpr"]
        cond = eqn.params["cond_jaxpr"]
        trips = self._fori_trips(eqn, env) if self.exact_cond else None
        if trips is not None:
            # a fori_loop whose bound folds from constants (a program
            # table's row loop) dispatches ``trips`` runs of its body, as a
            # switch step dispatches its branch: the counter and its test
            # are the table's bookkeeping and free, the body is costed whole
            nc = int(eqn.params["cond_nconsts"])
            nb = int(eqn.params["body_nconsts"])
            sub = list(eqn.invars[nc:nc + nb + 1]) + [None] * (
                len(eqn.invars) - nc - nb - 1)
            if _contains_collective(body):
                for _ in range(trips):
                    self._walk_sub(body, sub, env)
                return
            w = JaxprWalker(self.axis_sizes, exact_cond=True)
            w._walk_sub(body, sub, env)
            w.flush()
            for e in w.events:
                self.pending += e.vector * trips
            return
        # trip count is dynamic; cost one iteration and flag serialization.
        if _contains_collective(body):
            self.walk(cond)
            self.walk(body)
        else:
            self.pending += _subtree_cost(cond) + _subtree_cost(body)
            self.pending[I_SCAN] += 1

    def _fori_trips(self, eqn, env) -> int | None:
        """Trip count of a ``fori_loop`` lowered to a ``while`` (carries
        ``i, upper, ...``; cond ``i < upper``; body ``i + 1``, ``upper``
        unchanged, ``i`` read by nothing else) whose bounds are known in
        ``env``; None for any other loop."""
        nc = int(eqn.params["cond_nconsts"])
        nb = int(eqn.params["body_nconsts"])
        cond = eqn.params["cond_jaxpr"].jaxpr
        body = eqn.params["body_jaxpr"].jaxpr
        if len(cond.eqns) != 1 or cond.eqns[0].primitive.name != "lt":
            return None
        cvars = cond.invars[nc:]
        if list(cond.eqns[0].invars) != cvars[:2] \
                or cond.outvars[0] is not cond.eqns[0].outvars[0]:
            return None
        i_in, up_in = body.invars[nb], body.invars[nb + 1]
        i_out, up_out = body.outvars[0], body.outvars[1]
        step = [q for q in body.eqns if q.outvars[0] is i_out]
        uses = [q for q in body.eqns if any(v is i_in for v in q.invars)]
        if up_out is not up_in or len(step) != 1 or uses != step \
                or step[0].primitive.name != "add" \
                or step[0].invars[0] is not i_in \
                or not isinstance(step[0].invars[1], _Literal) \
                or int(step[0].invars[1].val) != 1:
            return None
        lo = self._val(eqn.invars[nc + nb], env)
        hi = self._val(eqn.invars[nc + nb + 1], env)
        if lo is None or hi is None or np.ndim(lo) or np.ndim(hi):
            return None
        return max(int(hi) - int(lo), 0)

    def _walk_cond(self, eqn, env: dict | None = None) -> None:
        branches = eqn.params["branches"]
        if self.exact_cond:
            idx = self._val(eqn.invars[0], env)
            if idx is not None and np.ndim(idx) == 0:
                b = branches[min(max(int(idx), 0), len(branches) - 1)]
                self._walk_sub(b, eqn.invars[1:], env)
                return
        if any(_contains_collective(b) for b in branches):
            # SPMD safety requires identical collective skeletons; walk branch 0
            self.walk(branches[0])
            return
        costs = [_subtree_cost(b) for b in branches]
        self.pending += np.max(np.stack(costs), axis=0)


def _contains_collective(jaxpr) -> bool:
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            return True
        for v in eqn.params.values():
            if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                if _contains_collective(v):
                    return True
            elif isinstance(v, (tuple, list)):
                for b in v:
                    if (hasattr(b, "eqns") or hasattr(b, "jaxpr")) and _contains_collective(b):
                        return True
    return False


def _contains_cond(jaxpr) -> bool:
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            return True
        for v in eqn.params.values():
            if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                if _contains_cond(v):
                    return True
            elif isinstance(v, (tuple, list)):
                for b in v:
                    if (hasattr(b, "eqns") or hasattr(b, "jaxpr")) and _contains_cond(b):
                        return True
    return False


def _subtree_cost(jaxpr) -> np.ndarray:
    """Total 6-metric cost of a collective-free jaxpr subtree."""
    w = JaxprWalker()
    w.walk(jaxpr)
    w.flush()
    vec = np.zeros(N_METRICS)
    for e in w.events:
        vec += e.vector
    return vec


# ---------------------------------------------------------------------------
# public front-end: trace a function
# ---------------------------------------------------------------------------


def trace_fn(fn: Callable, *args, axis_sizes: dict[str, int] | None = None,
             exact_cond: bool = False, **kwargs) -> Trace:
    """Trace ``fn(*args, **kwargs)`` into a template event stream.

    Works on any JAX-traceable callable; args may be ShapeDtypeStructs
    (no allocation — the "binary only" analog is "staged artifact only").

    ``exact_cond=True`` enables the walker's constant-propagated control-
    flow resolution (see :class:`JaxprWalker`) — used when measuring
    generated proxy modules, whose switch dispatch is driven entirely by
    constant opcode arrays.
    """
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    w = JaxprWalker(axis_sizes, exact_cond=exact_cond)
    w.walk(jaxpr)
    w.flush()
    return Trace(w.events, w.axis_sizes)


def trace_fn_store(fn: Callable, *args,
                   axis_sizes: dict[str, int] | None = None, **kwargs):
    """Trace ``fn`` straight into a columnar :class:`~repro.core.trace_ir.
    TraceStore`: the template is walked once and specialized per rank in
    array form (no per-rank Event lists) — the fast path ``synthesize``
    uses.  Equivalent to ``TraceStore.from_rank_traces(per_rank_traces(
    trace_fn(...)))``."""
    from repro.core.trace_ir import TraceStore
    template = trace_fn(fn, *args, axis_sizes=axis_sizes, **kwargs)
    sizes = dict(template.axis_sizes if axis_sizes is None else axis_sizes)
    return TraceStore.from_template(template, sizes)


def compute_cost(fn: Callable, *args, **kwargs) -> np.ndarray:
    """Total 6-metric cost of a collective-free callable (block calibration)."""
    t = trace_fn(fn, *args, **kwargs)
    return t.total_compute()


# ---------------------------------------------------------------------------
# per-rank specialization (paper §2.2 relative ranks, §2.6 SPMD merging input)
# ---------------------------------------------------------------------------


def per_rank_traces(trace: Trace, axis_sizes: dict[str, int] | None = None,
                    ) -> list[list[Event]]:
    """Specialize the SPMD template to one event list per rank.

    Ranks are the row-major flattening of the mesh axes in ``axis_sizes``
    order.  ``ppermute`` events become relative-encoded events present only on
    participating ranks (paper Fig. 2: a shift permutation collapses to one
    shared terminal; boundary ranks of a non-periodic halo drop out, which is
    exactly what drives rank-set branches in the merged main rule).
    """
    axis_sizes = dict(axis_sizes or trace.axis_sizes)
    axes = list(axis_sizes)
    sizes = [axis_sizes[a] for a in axes]
    n_ranks = int(np.prod(sizes)) if sizes else 1

    def coords(rank: int) -> dict[str, int]:
        out = {}
        rem = rank
        for a, s in zip(reversed(axes), reversed(sizes)):
            out[a] = rem % s
            rem //= s
        return out

    traces: list[list[Event]] = []
    for rank in range(n_ranks):
        c = coords(rank)
        evs: list[Event] = []
        for ev in trace.events:
            if is_comm(ev) and ev.kind == "ppermute":
                ev2 = _specialize_ppermute(ev, c, axis_sizes)
                if ev2 is not None:
                    evs.append(ev2)
            else:
                evs.append(ev)
        traces.append(evs)
    return traces


def _specialize_ppermute(ev: CommEvent, coords: dict[str, int],
                         axis_sizes: dict[str, int]) -> CommEvent | None:
    if not ev.detail or ev.detail[0] != "rawperm":
        return ev
    perm = ev.detail[1]
    axis = ev.axes[0] if ev.axes else None
    size = axis_sizes.get(axis, max((max(s, d) for s, d in perm), default=0) + 1)
    me = coords.get(axis, 0)
    srcs = {s for s, _ in perm}
    dsts = {d for _, d in perm}
    if me not in srcs and me not in dsts:
        return None  # this rank does not participate
    rel = encode_relative_perm([tuple(p) for p in perm], size)
    return dataclasses.replace(ev, detail=rel)


# ---------------------------------------------------------------------------
# host-level interposition recorder (PMPI analog for multi-step drivers)
# ---------------------------------------------------------------------------

_TLS = threading.local()


class TraceSession:
    """Record events emitted by instrumented wrappers in host-driver code.

    ``rank_streams[r]`` is rank r's event list.  Wrappers use
    :func:`record_event`; compute segments are costed with
    :func:`record_compute`.  Nested sessions are not supported.
    """

    def __init__(self, n_ranks: int, axis_sizes: dict[str, int] | None = None):
        self.n_ranks = n_ranks
        self.axis_sizes = dict(axis_sizes or {})
        self.rank_streams: list[list[Event]] = [[] for _ in range(n_ranks)]

    def __enter__(self):
        if getattr(_TLS, "session", None) is not None:
            raise RuntimeError("TraceSession already active")
        _TLS.session = self
        return self

    def __exit__(self, *exc):
        _TLS.session = None
        return False

    def emit(self, ranks: Iterable[int] | None, ev: Event) -> None:
        ranks = range(self.n_ranks) if ranks is None else ranks
        for r in ranks:
            self.rank_streams[r].append(ev)

    def to_store(self):
        """Freeze the recorded streams into a columnar
        :class:`~repro.core.trace_ir.TraceStore`."""
        from repro.core.trace_ir import TraceStore
        return TraceStore.from_rank_traces(self.rank_streams, self.axis_sizes)


def active_session() -> TraceSession | None:
    return getattr(_TLS, "session", None)


def record_event(ev: Event, ranks: Iterable[int] | None = None) -> None:
    s = active_session()
    if s is not None:
        s.emit(ranks, ev)


def record_compute(fn: Callable, *args, ranks: Iterable[int] | None = None,
                   **kwargs) -> None:
    """Cost ``fn`` with the jaxpr walker and record one ComputeEvent."""
    s = active_session()
    if s is None:
        return
    vec = compute_cost(fn, *args, **kwargs)
    s.emit(ranks, ComputeEvent(tuple(vec)))
