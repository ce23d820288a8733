"""Proxy replay engine + fidelity measurement (paper §3.3).

``rep`` is the run-length replay primitive used by generated code: small
exponents unroll (cheap trace), large exponents become ``lax.fori_loop`` so
a loop that executed 10^6 times costs O(1) code and O(1) trace — mirroring
the grammar's a^i symbols.

:class:`ProxyProgram` wraps a generated module:

  * ``run_local(rank)`` executes ranks one at a time on this host (LocalSim
    comm), jit-compiling once per distinct control-flow signature;
  * ``run_all(ranks)`` is the **batched multi-rank engine**: ranks are
    grouped by control-flow signature (the generated module precomputes
    ``SIGNATURE_GROUPS``), per-rank states are stacked along a leading rank
    axis, and one ``vmap``-ed compiled executable replays a whole group at
    once — one trace + one dispatch per group instead of per rank;
  * ``run_all(ranks, mesh=...)`` is the **mesh-sharded sweep**: signature
    groups are placed on disjoint device subsets of a mesh
    (:func:`plan_mesh_sweep`, driven by the per-group device hints the
    generated module carries), each group replays its real collectives via
    ``DeviceComm`` inside a single ``shard_map`` dispatch with the rank axis
    ``vmap``-folded through them, and groups are dispatched asynchronously;
  * ``rank_metrics(rank)`` re-traces the generated code with the *same*
    jaxpr cost walker used on the original program — the measurement behind
    the paper's Table 3 relative-error columns.  Results are cached per
    (signature, state shapes): ranks in a group are byte-identical programs,
    so one walker trace covers them all;
  * ``fidelity(original)`` computes δ̄ = mean_{m,p} |A-B|/A (paper eq. 8),
    vectorized across all ranks in one pass.

Compile caching: every compiled executable (per-rank and batched) is keyed
by (signature, comm backend, batch size, state shapes) and kept on the
instance, so repeated ``run_all`` / ``fidelity`` / ``rank_metrics`` calls
never re-trace.  ``cache_stats()`` exposes trace/hit counters for tests and
benchmarks.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import math
import sys
import tempfile
import time
from pathlib import Path
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec

from repro import compat, obs
from repro.core import blocks
from repro.core import noise as noise_mod
from repro.core import proxy_search
from repro.core.events import Event, METRIC_NAMES, N_METRICS, is_comm
from repro.core.noise import (FidelityDistribution, NoiseConfig,  # noqa: F401
                              parse_fidelity_csv)
from repro.core.tracer import trace_fn
from repro.sharding.collectives import DeviceComm, LocalSim, runs

#: Exponents up to this unroll at trace time; above it ``rep`` emits a
#: rolled ``fori_loop`` (one body trace regardless of n).  Shared with the
#: program-table lowering in :mod:`repro.core.progtable`, so compiled and
#: unrolled modules make identical unroll-vs-loop decisions.
REP_UNROLL_THRESHOLD = 4


def rep(fn, n: int, st: dict, comm) -> dict:
    """Repeat ``fn`` n times: unrolled when small, ``fori_loop`` otherwise."""
    if n <= REP_UNROLL_THRESHOLD:
        for _ in range(n):
            st = fn(st, comm)
        return st
    with runs(n):
        return lax.fori_loop(0, n, lambda i, s: fn(s, comm), st)


def load_saved_module(path, name: str | None = None):
    """Re-import a previously generated proxy module from disk.

    Generated proxies are plain Python files (``module.__proxy_path__``);
    together with ``TraceStore.save``/``load`` this makes the pipeline
    fully offline: trace → store ``.npz`` → synthesize → proxy ``.py`` →
    reload and replay anywhere, no re-synthesis required."""
    path = Path(path)
    name = name or path.stem
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    mod.__proxy_path__ = str(path)
    return mod


def load_module(source: str, name: str = "generated_proxy",
                out_dir: str | Path | None = None):
    """Write generated source to a file and import it as a module."""
    out_dir = Path(out_dir) if out_dir else Path(tempfile.mkdtemp(prefix="proxy_"))
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"{name}.py"
    path.write_text(source)
    return load_saved_module(path, name)


def init_replay_state(module, seed: int = 0) -> dict:
    """Block state, with the ``move_shift`` row buffers the module's
    ``ROW_UNROLLS`` ask for, + the generated module's comm buffer pool."""
    st = blocks.init_state(seed, module.ROW_UNROLLS)
    for bname, (shape, dtype) in module.COMM_BUFFERS.items():
        st[bname] = jnp.full(shape, 0.5, dtype=dtype)
    return st


# ---------------------------------------------------------------------------
# mesh sweep scheduling (device-parallel signature-group replay)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GroupPlacement:
    """One signature group pinned to a mesh device subset.

    ``device_ids`` are flat indices into ``mesh.devices``; ``axis_sizes`` is
    the group's sub-mesh geometry (same axis names as the traced program,
    sizes shrunk to the subset).  Hashable: used as a compile-cache key
    component so executables are cached *per placement*."""
    sig: tuple
    ranks: tuple[int, ...]
    device_ids: tuple[int, ...]
    axis_sizes: tuple[tuple[str, int], ...]

    @property
    def n_devices(self) -> int:
        return len(self.device_ids)

    def key(self) -> tuple:
        return (self.device_ids, self.axis_sizes)


def submesh_axis_sizes(n_devices: int, axis_sizes: dict[str, int],
                       ) -> dict[str, int]:
    """Shrink a traced mesh geometry onto ``n_devices``.

    Keeps the axis names and order; each axis gets ``gcd(traced_size,
    devices_still_unassigned)`` so the product always divides ``n_devices``
    exactly and every collective still spans a nonempty axis.  A comm-free
    program (no traced axes) gets a single unit axis so ``shard_map`` has a
    mesh to run under.
    """
    out: dict[str, int] = {}
    rem = max(int(n_devices), 1)
    for a, s in axis_sizes.items():
        g = math.gcd(max(int(s), 1), rem)
        out[a] = g
        rem //= g
    if not out:
        out = {"x": 1}
    return out


def _proportional_alloc(want: Sequence[int], n_devices: int,
                        axis_sizes: dict[str, int],
                        ) -> tuple[list[int], list[int]]:
    """Hint-proportional contiguous device shares (requires
    ``len(want) <= n_devices``); returns (alloc, starts)."""
    total = sum(want)
    alloc = [min(w, max(1, (n_devices * w) // total)) for w in want]
    # bumping zero-share groups to 1 device can oversubscribe the mesh
    # (e.g. hints [100,1,1,1,1,1,1] on 8 devices); shave the largest
    # shares back until the plan fits (every group keeps >= 1)
    while sum(alloc) > n_devices:
        i = alloc.index(max(alloc))
        alloc[i] -= 1
    # hand leftovers to the groups furthest below their hint
    while sum(alloc) < n_devices:
        gaps = [w - a for w, a in zip(want, alloc)]
        if max(gaps) <= 0:
            break
        i = gaps.index(max(gaps))
        alloc[i] += 1
    # shrink each share to the largest realizable sub-mesh size (a
    # 7-device share of a 16-wide axis would otherwise collapse to 1)
    alloc = [_realizable(a, axis_sizes) for a in alloc]
    starts = []
    cur = 0
    for a in alloc:
        starts.append(cur)
        cur += a
    return alloc, starts


def plan_mesh_sweep(groups: Sequence[tuple[tuple, Sequence[int]]],
                    hints: dict[tuple, int],
                    axis_sizes: dict[str, int],
                    n_devices: int,
                    share_unit_groups: bool = False) -> list[GroupPlacement]:
    """Partition ``n_devices`` mesh devices among signature groups.

    Pure function of its inputs (deterministic; no jax state touched):

    * every group gets at least one device and never more than its hint —
      extra devices beyond the traced collective span would sit idle;
    * shares are proportional to the per-group device hints, leftovers go
      to the groups furthest below their hint;
    * device subsets are contiguous and disjoint while supply lasts; with
      more groups than devices, groups wrap round-robin onto single devices
      (dispatches then serialize per device, which is still correct);
    * each subset is trimmed to the realizable sub-mesh size
      (:func:`submesh_axis_sizes`), so the placement's geometry always
      multiplies out to exactly ``len(device_ids)``;
    * with ``share_unit_groups=True``, two or more unit-hint groups (the
      ``count_scale``-dilated tiny groups whose scaled hints collapsed to
      1) are packed onto **one shared device** instead of claiming one
      each — their dispatches serialize there while the freed devices go
      to groups still below their hint.
    """
    n_devices = max(int(n_devices), 1)
    groups = [(sig, list(rs)) for sig, rs in groups]
    if not groups:
        return []
    want = [max(int(hints.get(sig, 1)), 1) for sig, _ in groups]
    n = len(groups)
    if n >= n_devices:
        alloc = [1] * n
        starts = [i % n_devices for i in range(n)]
    else:
        unit = [i for i, w in enumerate(want) if w == 1]
        big = [i for i, w in enumerate(want) if w > 1]
        # pack only under device scarcity (demand above supply): with spare
        # devices, unit groups keep one each and run in parallel — packing
        # would serialize them for no one's benefit
        if share_unit_groups and len(unit) >= 2 and big \
                and n_devices >= 2 and sum(want) > n_devices:
            big_alloc, big_starts = _proportional_alloc(
                [want[i] for i in big], n_devices - 1, axis_sizes)
            alloc = [1] * n
            starts = [n_devices - 1] * n     # unit groups share the last dev
            for i, a, s0 in zip(big, big_alloc, big_starts):
                alloc[i] = a
                starts[i] = s0
        else:
            alloc, starts = _proportional_alloc(want, n_devices, axis_sizes)
    out = []
    for (sig, rs), a, s0 in zip(groups, alloc, starts):
        out.append(GroupPlacement(
            sig=sig, ranks=tuple(rs),
            device_ids=tuple(range(s0, s0 + a)),
            axis_sizes=tuple(submesh_axis_sizes(a, axis_sizes).items())))
    return out


def _realizable(n_devices: int, axis_sizes: dict[str, int]) -> int:
    """Largest ``v <= n_devices`` whose sub-mesh geometry multiplies out to
    exactly ``v`` (1 always qualifies)."""
    for v in range(max(int(n_devices), 1), 0, -1):
        p = 1
        for s in submesh_axis_sizes(v, axis_sizes).values():
            p *= s
        if p == v:
            return v
    return 1


@dataclasses.dataclass
class FidelityReport:
    """Per-(metric, rank) relative errors (paper Table 3 / Fig. 4)."""
    delta: np.ndarray          # (n_metrics, n_ranks)
    comm_lossless: bool        # event-id sequences reproduced exactly
    mean: float                # δ̄, paper eq. 8
    mesh_checked: bool = False  # a mesh-sharded sweep executed finitely
    seed: int = 0              # replay seed provenance (deterministic: 0)
    n_replicas: int = 1        # deterministic replay is one replica

    def heatmap_csv(self) -> str:
        lines = ["metric," + ",".join(f"rank{p}" for p in range(self.delta.shape[1]))]
        for m, name in enumerate(METRIC_NAMES):
            lines.append(name + "," + ",".join(f"{v:.4f}" for v in self.delta[m]))
        return "\n".join(lines)

    def to_csv(self) -> str:
        """Heatmap CSV with seed/replica provenance headers — the same
        parseable shape as :meth:`FidelityDistribution.to_csv`, so
        downstream consumers never have to guess which replay produced a
        bare float matrix (see :func:`repro.core.noise.parse_fidelity_csv`)."""
        return (f"# seed={self.seed}\n# n_replicas={self.n_replicas}\n"
                + self.heatmap_csv())


class ProxyProgram:
    """A synthesized proxy-app: source + module + replay/fidelity methods."""

    def __init__(self, source: str, module, merged, combos,
                 axis_sizes: dict[str, int] | None = None):
        self.source = source
        self.module = module
        self.merged = merged
        self.combos = combos
        self.axis_sizes = dict(axis_sizes or {})
        self._compiled: dict = {}          # (sig, comm, shapes) -> per-rank fn
        self._compiled_batched: dict = {}  # (sig, comm, n, shapes) -> vmapped fn
        self._metrics_cache: dict = {}     # (sig, shapes) -> np.ndarray
        self._mesh_comms: dict = {}        # placement key -> DeviceComm
        self._mesh_unrun: set = set()      # mesh executables not yet dispatched
        self._submeshes: dict = {}         # (mesh id, placement key) -> Mesh
        self._sig_by_rank: dict | None = None
        self._shapes_key_cache = None      # filled by _shapes_key()
        self._counters = {"jit_traces": 0, "metric_traces": 0,
                          "batch_cache_hits": 0, "batch_cache_misses": 0}
        #: root span id of the ``synthesize.program`` span that made this
        #: proxy (0: none), the root of its ``proxy.run_all`` spans
        self.root = 0

    # -- signature grouping ----------------------------------------------------

    def signature_of(self, rank: int):
        """Control-flow signature of ``rank`` (hashable jit/cache key)."""
        if self._sig_by_rank is None:
            groups = getattr(self.module, "SIGNATURE_GROUPS", None) or ()
            # entries are (sig, ranks) or (sig, ranks, device_hint)
            self._sig_by_rank = {r: g[0] for g in groups for r in g[1]}
        sig = self._sig_by_rank.get(rank)
        if sig is None:
            sig = self.module.program_signature(rank)
            self._sig_by_rank[rank] = sig
        return sig

    def _validate_ranks(self, ranks: Sequence[int]) -> None:
        bad = [r for r in ranks if not 0 <= r < self.merged.n_ranks]
        if bad:
            raise ValueError(f"ranks out of range: {bad} "
                             f"(proxy has {self.merged.n_ranks} ranks)")

    def signature_groups(self, ranks: Sequence[int] | None = None,
                         ) -> list[tuple[tuple, list[int]]]:
        """(signature, ranks) pairs covering ``ranks`` (default: all).

        Uses the generation-time ``SIGNATURE_GROUPS`` constant when the
        module has one (entries may be ``(sig, ranks)`` or
        ``(sig, ranks, device_hint)``); falls back to probing
        ``program_signature`` so pre-metadata modules keep working.
        """
        groups = getattr(self.module, "SIGNATURE_GROUPS", None)
        if groups is None:
            by_sig: dict[tuple, list[int]] = {}
            all_ranks = range(self.merged.n_ranks) if ranks is None else ranks
            for r in all_ranks:
                by_sig.setdefault(self.module.program_signature(r), []).append(r)
            return list(by_sig.items())
        if ranks is None:
            return [(g[0], list(g[1])) for g in groups]
        want = set(ranks)
        out = [(g[0], [r for r in g[1] if r in want]) for g in groups]
        out = [(sig, rs) for sig, rs in out if rs]
        missing = want - {r for _, rs in out for r in rs}
        if missing:
            raise ValueError(
                f"ranks not in any signature group: {sorted(missing)} "
                f"(proxy has {self.merged.n_ranks} ranks)")
        return out

    def _shapes_key(self) -> tuple:
        """State-shape fingerprint: part of every compile-cache key.

        Constant for this instance today (block geometry and COMM_BUFFERS
        are module-level), but kept in the key as the contract guard for
        the §3.3 cache spec — (signature, block shapes) — so a future
        configurable block geometry invalidates instead of aliasing."""
        if self._shapes_key_cache is None:
            st = jax.eval_shape(lambda: init_replay_state(self.module))
            self._shapes_key_cache = tuple(
                sorted((k, tuple(v.shape), str(v.dtype)) for k, v in st.items()))
        return self._shapes_key_cache

    # -- execution -------------------------------------------------------------

    @staticmethod
    def _comm_key(comm):
        """Compile-cache component for the comm backend.  A plain LocalSim
        is stateless at execution time, so all instances share compiled
        programs — the fresh ``LocalSim()`` each ``run_local``/``fidelity``
        call constructs must not force a re-trace.  Anything else (DeviceComm,
        counting subclasses) is keyed by identity."""
        return LocalSim if type(comm) is LocalSim else id(comm)

    def _fn_for_rank(self, rank: int, comm):
        sig = self.signature_of(rank)
        key = (sig, self._comm_key(comm), self._shapes_key())
        if key not in self._compiled:
            mod = self.module
            counters = self._counters

            def traced(st):
                counters["jit_traces"] += 1   # trace-time side effect
                return mod.run_rank(st, comm, rank)

            self._compiled[key] = jax.jit(traced)
        return self._compiled[key]

    def _fn_for_group(self, sig, rep_rank: int, n: int, comm,
                      tag: str | None = None):
        """Compiled executable replaying ``n`` stacked states of one group.

        ``tag`` disambiguates batched entries whose stacked state carries a
        different pytree structure at the same ``n`` (the noisy-replica
        states add the noise leaves) so the cache counters stay honest."""
        key = (sig, self._comm_key(comm), n, tag, self._shapes_key())
        fn = self._compiled_batched.get(key)
        if fn is None:
            self._counters["batch_cache_misses"] += 1
            mod = self.module
            counters = self._counters

            def traced(stacked):
                counters["jit_traces"] += 1   # trace-time side effect
                return jax.vmap(lambda st: mod.run_rank(st, comm, rep_rank))(stacked)

            fn = jax.jit(traced)
            self._compiled_batched[key] = fn
        else:
            self._counters["batch_cache_hits"] += 1
        return fn

    # -- mesh-sharded sweep (device-parallel signature groups) -----------------

    def group_device_hints(self) -> dict[tuple, int]:
        """Per-signature device-count hints from the generated module.

        Modules generated before the hint metadata (2-tuple groups) fall
        back to the full traced mesh size — the span every collective would
        need in the worst case."""
        default = 1
        for s in self.axis_sizes.values():
            default *= max(int(s), 1)
        out: dict[tuple, int] = {}
        for g in getattr(self.module, "SIGNATURE_GROUPS", None) or ():
            out[g[0]] = int(g[2]) if len(g) > 2 else default
        return out

    def mesh_sweep_plan(self, mesh, ranks: Sequence[int] | None = None,
                        share_unit_groups: bool = True,
                        ) -> list[GroupPlacement]:
        """Deterministic placement of signature groups onto ``mesh``'s
        devices (see :func:`plan_mesh_sweep`).  Unit-hint groups —
        typically ``count_scale``-dilated tiny groups — share one device
        by default instead of idling devices each."""
        return plan_mesh_sweep(self.signature_groups(ranks),
                               self.group_device_hints(), self.axis_sizes,
                               int(np.asarray(mesh.devices).size),
                               share_unit_groups=share_unit_groups)

    def mesh_comm_events(self, mesh, ranks: Sequence[int] | None = None,
                         ) -> dict[int, list]:
        """``{rank: [CommEvent, ...]}``: the collectives of the exact
        executable the mesh sweep dispatches for each rank's group, read
        by the jaxpr walker (one walk per placed group; exact-cond mode
        resolves the program tables' switch dispatch)."""
        st = jax.eval_shape(lambda: init_replay_state(self.module))
        out: dict[int, list] = {}
        for pl in self.mesh_sweep_plan(mesh, ranks):
            fn = self._fn_for_group_mesh(pl.sig, pl.ranks[0], None, pl, mesh)
            events = trace_fn(fn, st, exact_cond=True).comm_events()
            for r in pl.ranks:
                out[r] = events
        return out

    def _submesh_for(self, mesh, placement: GroupPlacement):
        devs = list(np.asarray(mesh.devices).flat)
        # keyed by the actual devices, not id(mesh): two Mesh objects over
        # the same device set share sub-meshes, and a recycled object id
        # can never resurrect a stale placement
        key = (tuple(d.id for d in devs), placement.key())
        sub = self._submeshes.get(key)
        if sub is None:
            sizes = dict(placement.axis_sizes)
            sub = compat.make_mesh(
                tuple(sizes.values()), tuple(sizes),
                devices=[devs[i] for i in placement.device_ids])
            self._submeshes[key] = sub
        return sub

    def _mesh_comm(self, placement: GroupPlacement) -> DeviceComm:
        """One DeviceComm per placement: its ``axis_sizes`` are the sub-mesh
        geometry, and reusing the instance keeps the identity-keyed compile
        cache warm across sweeps."""
        comm = self._mesh_comms.get(placement.key())
        if comm is None:
            comm = DeviceComm(dict(placement.axis_sizes))
            self._mesh_comms[placement.key()] = comm
        return comm

    def _fn_for_group_mesh(self, sig, rep_rank: int, n: int | None,
                           placement: GroupPlacement, mesh,
                           noise: bool = False):
        """Compiled ``shard_map`` executable for one placed group.

        ``n`` is the stacked rank count (``None`` = unbatched: one rank's
        state, the sequential-mesh baseline).  Cached per (signature, mesh
        devices, placement, n, state shapes) — a group moved to a different
        mesh, device subset, or sub-mesh geometry compiles afresh instead
        of aliasing.  ``noise=True`` stacks ``n`` seeded replicas instead
        of ranks: the shard_map in/out specs must then cover the extra
        noise leaves, so the entry is keyed (and traced) separately.
        """
        mesh_ids = tuple(d.id for d in np.asarray(mesh.devices).flat)
        key = (sig, "mesh", n, noise, mesh_ids, placement.key(),
               self._shapes_key())
        fn = self._compiled_batched.get(key)
        if fn is None:
            self._counters["batch_cache_misses"] += 1
            mod = self.module
            counters = self._counters
            comm = self._mesh_comm(placement)
            submesh = self._submesh_for(mesh, placement)

            def state_proto():
                st = init_replay_state(mod)
                if noise:   # spec must mirror the noise-attached pytree
                    st = noise_mod.attach(st, jax.random.PRNGKey(0))
                return st

            spec = jax.tree.map(lambda _: PartitionSpec(),
                                jax.eval_shape(state_proto))

            def traced(st):
                counters["jit_traces"] += 1   # trace-time side effect
                if n is None:
                    return mod.run_rank(st, comm, rep_rank)
                return jax.vmap(lambda s: mod.run_rank(s, comm, rep_rank))(st)

            fn = jax.jit(jax.shard_map(
                traced, mesh=submesh, in_specs=(spec,), out_specs=spec,
                check_vma=False))
            self._compiled_batched[key] = fn
            self._mesh_unrun.add(fn)
        else:
            self._counters["batch_cache_hits"] += 1
        return fn

    def _noise_group_state(self, rep_rank: int, cfg: "NoiseConfig",
                           seed: int = 0) -> dict:
        """``n_replicas`` noise-attached copies of one group's initial state,
        stacked on a leading replica axis.  Replica keys derive only from
        ``(cfg.seed, group representative, replica index)`` — never from
        placement — so LocalSim and mesh replay draw identical streams."""
        base = init_replay_state(self.module, seed)
        sts = [noise_mod.attach(base,
                                noise_mod.replica_key(cfg.seed, rep_rank, j))
               for j in range(cfg.n_replicas)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *sts)

    def _group_work_mesh(self, ranks, seed: int, per_rank_seeds: bool,
                         mesh, batched: bool = True,
                         noise: "NoiseConfig | None" = None) -> list[tuple]:
        """``(fn, input_state, group_ranks, stacked)`` units for a mesh sweep.

        ``batched=True`` emits exactly one unit — one ``shard_map``
        dispatch — per signature group: the group's ranks are stacked on a
        leading axis and ``vmap``-ed through the real collectives (or, with
        a shared seed, the byte-identical program runs once and the result
        is shared).  ``batched=False`` is the sequential mesh baseline: one
        dispatch per rank on the *same* placement, so results are
        comparable bit-for-bit.  ``noise=`` stacks seeded replicas instead
        of ranks (one unit per group; ranks of a group share the replica
        results, the run-level-platform-state reading of the noise model)."""
        work = []
        for pl in self.mesh_sweep_plan(mesh, ranks):
            grp = list(pl.ranks)
            if noise is not None:
                fn = self._fn_for_group_mesh(pl.sig, grp[0], noise.n_replicas,
                                             pl, mesh, noise=True)
                work.append((fn, self._noise_group_state(grp[0], noise, seed),
                             grp, False))
            elif batched and per_rank_seeds:
                stacked = jax.tree.map(
                    lambda *xs: jnp.stack(xs),
                    *[init_replay_state(self.module, seed + r) for r in grp])
                work.append((self._fn_for_group_mesh(pl.sig, grp[0], len(grp),
                                                     pl, mesh),
                             stacked, grp, True))
            elif batched:
                work.append((self._fn_for_group_mesh(pl.sig, grp[0], None,
                                                     pl, mesh),
                             init_replay_state(self.module, seed), grp, False))
            else:
                fn = self._fn_for_group_mesh(pl.sig, grp[0], None, pl, mesh)
                for r in grp:
                    st = init_replay_state(
                        self.module, seed + r if per_rank_seeds else seed)
                    work.append((fn, st, [r], False))
        return work

    def run_local(self, ranks: Sequence[int] | None = None, seed: int = 0,
                  comm=None) -> dict:
        """Execute ranks sequentially on this host; returns final state of
        the last rank (values are meaningless — this is a performance proxy)."""
        comm = comm or LocalSim()
        if ranks is None:
            ranks = range(self.merged.n_ranks)
        else:
            self._validate_ranks(ranks)
        st = init_replay_state(self.module, seed)
        out = st
        for r in ranks:
            out = self._fn_for_rank(r, comm)(st)
        jax.block_until_ready(out)
        return out

    def run_all(self, ranks: Sequence[int] | None = None, seed: int = 0,
                comm=None, batched: bool = True,
                per_rank_seeds: bool = False, mesh=None,
                noise: "NoiseConfig | None" = None) -> dict[int, dict]:
        """Replay every rank; returns ``{rank: final state}``.

        ``batched=True`` (default) replays one signature group per compiled
        call instead of one rank at a time:

        * with the default shared seed, every rank of a group is a
          byte-identical execution (same program, same initial state — the
          SPMD redundancy that made the grammars mergeable in the first
          place), so the group's program runs **once** and the result is
          shared by all its ranks.  Each rank gets its own result *dict*,
          but the leaf arrays of a group deliberately alias (one buffer, n
          references): ``jax.Array`` leaves are immutable — rebinding one
          rank's entry never touches its siblings, and ``np.asarray`` views
          of them are read-only — so the sharing is observable only as
          reduced memory, not as cross-rank mutation;
        * with ``per_rank_seeds=True`` each rank gets a distinct initial
          state (``seed + rank``); states are stacked on a leading rank
          axis and the group program is ``vmap``-ed over it — still one
          trace + one dispatch per group.

        ``batched=False`` is the per-rank baseline path (identical results;
        benchmarked against in benchmarks/replay_time.py).

        ``mesh=`` switches to the **mesh-sharded sweep**: signature groups
        are placed on disjoint device subsets of ``mesh`` (see
        :meth:`mesh_sweep_plan`), each group executes its real collectives
        via :class:`DeviceComm` inside one ``shard_map`` dispatch, and all
        groups are dispatched asynchronously before any result is gathered.
        ``comm`` is ignored in mesh mode (the backend is derived from the
        placement); ``batched=False`` gives the sequential mesh baseline
        (one dispatch per rank on the same placement).

        ``noise=NoiseConfig(...)`` replays ``n_replicas`` seeded noisy
        replicas per signature group as ONE extra vmapped axis (the
        default ``noise=None`` path is byte-identical to a build without
        the noise layer).  Every leaf of a rank's result then carries a
        leading replica axis; ranks of a group share the replica results
        (the noise models run-level platform state, not per-rank jitter),
        and the :data:`~repro.core.noise.NOISE_COMPUTE` /
        :data:`~repro.core.noise.NOISE_COMM` leaves hold the perturbed
        cost accumulators :meth:`fidelity` summarizes.

        Each call is the span ``proxy.run_all`` (:mod:`repro.obs`), under
        the root id of the synthesis that made the proxy (:attr:`root`); a
        first call's JAX trace, lowering and compile land in it as
        ``jax.trace``, ``jax.lower`` and ``jax.compile``.
        """
        if ranks is not None:
            self._validate_ranks(ranks)
        if noise is not None and per_rank_seeds:
            raise ValueError("noise= and per_rank_seeds are mutually "
                             "exclusive (both own the stacked batch axis)")
        if noise is not None and not batched:
            raise ValueError("noise= requires the batched replay path "
                             "(replicas ride the vmapped group axis)")
        with obs.span("proxy.run_all", root=self.root or None):
            if mesh is not None:
                return self._run_all_mesh(ranks, seed, batched, per_rank_seeds,
                                          mesh, noise)
            comm = comm or LocalSim()
            if noise is not None:
                out = {}
                for fn, arg, grp in self._group_work(ranks, seed, comm,
                                                     False, noise=noise):
                    res = fn(arg)
                    for r in grp:   # replicas are group-level, shared by ranks
                        out[r] = dict(res)
                for v in out.values():
                    jax.block_until_ready(v)
                return out
            out = {}
            if not batched:
                st = (None if per_rank_seeds
                      else init_replay_state(self.module, seed))
                for r in (range(self.merged.n_ranks) if ranks is None
                          else ranks):
                    out[r] = self._fn_for_rank(r, comm)(
                        init_replay_state(self.module, seed + r)
                        if per_rank_seeds else st)
                for v in out.values():
                    jax.block_until_ready(v)
                return out
            for fn, arg, grp in self._group_work(ranks, seed, comm,
                                                 per_rank_seeds):
                res = fn(arg)
                if per_rank_seeds:
                    for i, r in enumerate(grp):
                        out[r] = jax.tree.map(lambda a, i=i: a[i], res)
                else:
                    # identical input + program -> identical output: a fresh
                    # dict per rank; leaves alias on purpose (immutable)
                    for r in grp:
                        out[r] = dict(res)
            for v in out.values():
                jax.block_until_ready(v)
            return out

    def _run_all_mesh(self, ranks, seed: int, batched: bool,
                      per_rank_seeds: bool, mesh,
                      noise: "NoiseConfig | None" = None) -> dict[int, dict]:
        """Mesh-sharded sweep body: dispatch every placed group first (jax
        dispatch is asynchronous — groups on disjoint device subsets overlap),
        gather/unstack after, block once at the end.

        A group's first dispatch, which traces, lowers and compiles its
        executable, is the span ``proxy.mesh_first``; the collectives
        lowered into it count under it (``replay.collectives``)."""
        pending = []
        for fn, arg, grp, stacked in self._group_work_mesh(
                ranks, seed, per_rank_seeds, mesh, batched, noise):
            if fn in self._mesh_unrun:
                self._mesh_unrun.discard(fn)
                with obs.span("proxy.mesh_first", root=self.root or None):
                    res = fn(arg)
            else:
                res = fn(arg)
            pending.append((res, grp, stacked))
        out: dict[int, dict] = {}
        for res, grp, stacked in pending:
            if stacked:
                for i, r in enumerate(grp):
                    out[r] = jax.tree.map(lambda a, i=i: a[i], res)
            else:
                for r in grp:
                    out[r] = dict(res)
        jax.block_until_ready(out)
        return out

    def _group_work(self, ranks, seed: int, comm, per_rank_seeds: bool,
                    noise: "NoiseConfig | None" = None) -> list[tuple]:
        """One ``(compiled_fn, input_state, group_ranks)`` unit per signature
        group — the shared work plan of :meth:`run_all` and :meth:`time_all`.

        With ``noise=``, each unit stacks ``n_replicas`` seeded noisy
        replicas of the group's (shared) initial state on a leading axis —
        the same one-vmapped-axis shape as ``per_rank_seeds``, so the
        sweep scheduler and compile caches are reused as-is."""
        if noise is not None:
            work = []
            for sig, grp in self.signature_groups(ranks):
                fn = self._fn_for_group(sig, grp[0], noise.n_replicas, comm,
                                        tag="noise")
                work.append((fn, self._noise_group_state(grp[0], noise, seed),
                             grp))
            return work
        st = None if per_rank_seeds else init_replay_state(self.module, seed)
        work = []
        for sig, grp in self.signature_groups(ranks):
            if per_rank_seeds:
                stacked = jax.tree.map(
                    lambda *xs: jnp.stack(xs),
                    *[init_replay_state(self.module, seed + r) for r in grp])
                work.append((self._fn_for_group(sig, grp[0], len(grp), comm),
                             stacked, grp))
            else:
                work.append((self._fn_for_rank(grp[0], comm), st, grp))
        return work

    def time_local(self, rank: int = 0, iters: int = 1, seed: int = 0) -> float:
        """Wall-clock seconds of one rank's replay (compiled, warm)."""
        self._validate_ranks([rank])
        comm = LocalSim()
        fn = self._fn_for_rank(rank, comm)
        st = init_replay_state(self.module, seed)
        jax.block_until_ready(fn(st))  # warmup/compile
        t0 = time.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(fn(st))
        return (time.perf_counter() - t0) / iters

    def time_all(self, ranks: Sequence[int] | None = None, iters: int = 1,
                 seed: int = 0, batched: bool = True,
                 per_rank_seeds: bool = False, mesh=None,
                 noise: "NoiseConfig | None" = None) -> float:
        """Warm wall-clock seconds of one full multi-rank replay sweep.

        Mirrors :meth:`run_all`'s modes: per-rank baseline
        (``batched=False``), group-deduplicated (default), group-vmapped
        (``per_rank_seeds=True``), noisy-replica (``noise=NoiseConfig``,
        one vmapped replica axis per group), and — with ``mesh=`` — the
        mesh-sharded sweep (real collectives, one dispatch per placed
        group; the ``batched=False`` variant times the sequential mesh
        baseline).
        """
        ranks = list(range(self.merged.n_ranks) if ranks is None else ranks)
        self._validate_ranks(ranks)
        if noise is not None and (per_rank_seeds or not batched):
            raise ValueError("noise= requires the batched path and is "
                             "mutually exclusive with per_rank_seeds")
        comm = LocalSim()
        if mesh is not None:
            work = [(fn, arg) for fn, arg, _, _ in self._group_work_mesh(
                ranks, seed, per_rank_seeds, mesh, batched, noise)]
        elif batched:
            work = [(fn, arg) for fn, arg, _ in
                    self._group_work(ranks, seed, comm, per_rank_seeds,
                                     noise=noise)]
        else:
            st = None if per_rank_seeds else init_replay_state(self.module, seed)
            work = [(self._fn_for_rank(r, comm),
                     init_replay_state(self.module, seed + r)
                     if per_rank_seeds else st) for r in ranks]

        def sweep():
            out = None
            for fn, arg in work:
                out = fn(arg)
            jax.block_until_ready(out)

        sweep()  # warmup/compile
        t0 = time.perf_counter()
        for _ in range(iters):
            sweep()
        return (time.perf_counter() - t0) / iters

    def cache_stats(self) -> dict[str, int]:
        """Trace/cache counters (jit_traces counts actual re-traces)."""
        return dict(self._counters,
                    compiled_per_rank=len(self._compiled),
                    compiled_batched=len(self._compiled_batched),
                    cached_metric_groups=len(self._metrics_cache))

    # -- measurement -------------------------------------------------------------

    def rank_metrics(self, rank: int, use_cache: bool = True) -> np.ndarray:
        """Walker-measured 6-metric total of this rank's generated program.

        Cached per (signature, state shapes): ranks sharing a control-flow
        signature run byte-identical programs, so repeated ``fidelity`` /
        ``rank_metrics`` calls never re-trace a group already measured.
        """
        key = (self.signature_of(rank), self._shapes_key())
        if use_cache and key in self._metrics_cache:
            return self._metrics_cache[key]
        st = jax.eval_shape(lambda: init_replay_state(self.module))
        comm = LocalSim()
        self._counters["metric_traces"] += 1
        # exact_cond: generated modules' control flow is driven entirely by
        # constant opcode tables, so the walker resolves every switch to the
        # branch actually replayed — grammar-compiled and unrolled modules
        # measure bit-identically (the codegen_reference parity bar)
        tr = trace_fn(lambda s: self.module.run_rank(s, comm, rank), st,
                      exact_cond=True)
        out = tr.total_compute()
        self._metrics_cache[key] = out
        return out

    def group_eqn_counts(self, comm=None) -> dict[tuple, int]:
        """Traced-executable size per signature group: total jaxpr equation
        count of one representative rank's ``run_rank``.  For grammar-
        compiled modules this is O(grammar); for the unrolled reference it
        grows with the trace — the size bar the CI guard pins."""
        from repro.core.progtable import jaxpr_eqn_count
        comm = comm or LocalSim()
        st = jax.eval_shape(lambda: init_replay_state(self.module))
        out: dict[tuple, int] = {}
        for sig, grp in self.signature_groups():
            jaxpr = jax.make_jaxpr(
                lambda s, _r=grp[0]: self.module.run_rank(s, comm, _r))(st)
            out[sig] = jaxpr_eqn_count(jaxpr)
        return out

    def expand_rank_ids(self, rank: int) -> list[int]:
        return self.merged.expand_rank(rank)

    def _noise_totals(self, ranks: Sequence[int], cfg: "NoiseConfig",
                      mesh=None) -> tuple[dict, dict]:
        """Executed perturbed cost totals per rank.

        Returns ``(compute, comm_bytes)`` dicts: ``compute[r]`` is the
        ``(n_replicas, 6)`` float64 noise-accumulator matrix, ``comm[r]``
        the ``(n_replicas,)`` perturbed collective-byte totals.  δ̄ is
        normally measured by the static jaxpr walker, which runtime
        randomness cannot reach — the noisy path instead *executes* the
        replicas (LocalSim or mesh) and reads the accumulators the
        perturb wrappers summed during replay."""
        if mesh is not None:
            units = [(fn, arg, grp) for fn, arg, grp, _ in
                     self._group_work_mesh(ranks, 0, False, mesh, True,
                                           noise=cfg)]
        else:
            units = self._group_work(ranks, 0, LocalSim(), False, noise=cfg)
        pending = [(fn(arg), grp) for fn, arg, grp in units]
        compute: dict[int, np.ndarray] = {}
        comm_bytes: dict[int, np.ndarray] = {}
        for res, grp in pending:
            acc = np.asarray(jax.device_get(res[noise_mod.NOISE_COMPUTE]),
                             dtype=np.float64)
            cb = np.asarray(jax.device_get(res[noise_mod.NOISE_COMM]),
                            dtype=np.float64)
            for r in grp:       # replicas are group-level; ranks share them
                compute[r] = acc
                comm_bytes[r] = cb
        return compute, comm_bytes

    def fidelity(self, original_rank_traces: Sequence[Sequence[Event]],
                 original_rank_keys: Sequence[Sequence[str]] | None = None,
                 sample_ranks: int | None = None,
                 batched: bool = True, mesh=None,
                 noise: "NoiseConfig | None" = None,
                 ) -> "FidelityReport | FidelityDistribution":
        """Compare proxy vs original per rank (paper §3.3.1).

        ``original_rank_traces`` is either per-rank Event lists or a
        columnar :class:`~repro.core.trace_ir.TraceStore` (preferred: the
        original totals then come from one vectorized pass with no Event
        materialization).  Compute metrics: walker totals of generated
        code vs the original trace's compute totals, assembled for all
        sampled ranks in one vectorized pass (proxy totals come from the
        per-signature metrics cache — one walker trace per group, not per
        rank).  Communication:
        the merged grammar must expand to the original event *key* sequence
        exactly (losslessness; keys, not local ids — heterogeneous ranks
        intern in different orders).  ``batched=False`` forces the original
        per-rank/per-trace path (the parity baseline in tests).

        ``mesh=`` additionally executes one mesh-sharded sweep (real
        collectives via :class:`DeviceComm`, reusing the placement-keyed
        compile cache) and records whether every pool buffer came back
        finite in ``report.mesh_checked``.  δ̄ itself is placement-invariant
        by construction — walker metrics are keyed by (signature, state
        shapes) only — so mesh and local reports carry bit-identical deltas.

        ``noise=NoiseConfig(...)`` returns a
        :class:`~repro.core.noise.FidelityDistribution` instead: the proxy
        side becomes the *executed* perturbed-cost accumulators over
        ``n_replicas`` seeded replicas (one vmapped axis per group,
        LocalSim by default, ``mesh=`` for the sharded sweep), each
        replica's δ matrix computed against the same original totals.
        Fixed ``(seed, n_replicas)`` is reproducible bit-for-bit and
        identical between LocalSim and mesh (replica keys are
        placement-invariant and the accumulator math never reads buffer
        values).  Note the σ→0 limit of the executed totals tracks — but
        is not bit-equal to — the float64 walker totals (float32
        execution; rolled-loop scan-step accounting), so the bit-parity
        contract binds only the untouched ``noise=None`` walker path.
        """
        if hasattr(original_rank_traces, "compute_totals"):
            # columnar TraceStore: per-rank totals in one vectorized pass,
            # bit-identical to the per-event accumulation (np.add.at sums
            # in stream order) — no Event materialization
            totals = original_rank_traces.compute_totals()
            n_ranks = original_rank_traces.n_ranks
        else:
            totals = None
            n_ranks = len(original_rank_traces)
        ranks = list(range(n_ranks))
        if sample_ranks and n_ranks > sample_ranks:
            step = max(n_ranks // sample_ranks, 1)
            ranks = ranks[::step][:sample_ranks]
        lossless = True
        if original_rank_keys is not None:
            for r in range(n_ranks):
                got = [self.merged.table[i].key()
                       for i in self.expand_rank_ids(r)]
                if list(original_rank_keys[r]) != got:
                    lossless = False
                    break
        if totals is not None:
            a = totals[ranks].T
        else:
            a = np.zeros((N_METRICS, len(ranks)))
            for col, r in enumerate(ranks):
                for ev in original_rank_traces[r]:
                    if not is_comm(ev):
                        a[:, col] += ev.vector
        if noise is not None:
            compute, comm_b = self._noise_totals(ranks, noise, mesh)
            bn = np.stack([compute[r] for r in ranks], axis=2)
            replica_delta = np.stack(
                [proxy_search.rel_error_matrix(a, bn[j])
                 for j in range(noise.n_replicas)])
            cb = np.stack([comm_b[r] for r in ranks], axis=1)
            mesh_checked = mesh is not None and \
                bool(np.isfinite(bn).all() and np.isfinite(cb).all())
            return FidelityDistribution(
                replica_delta=replica_delta, comm_bytes=cb,
                ranks=tuple(ranks), seed=noise.seed,
                n_replicas=noise.n_replicas, comm_lossless=lossless,
                mesh_checked=mesh_checked)
        b = np.stack([self.rank_metrics(r, use_cache=batched) for r in ranks],
                     axis=1)
        delta = proxy_search.rel_error_matrix(a, b)
        mesh_checked = False
        if mesh is not None:
            states = self._run_all_mesh(ranks, 0, True, False, mesh)
            mesh_checked = all(
                bool(np.isfinite(np.asarray(leaf, np.float32)).all())
                for st in states.values() for leaf in jax.tree.leaves(st))
        return FidelityReport(delta=delta, comm_lossless=lossless,
                              mean=float(delta.mean()),
                              mesh_checked=mesh_checked)
