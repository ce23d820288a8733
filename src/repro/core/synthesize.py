"""Top-level proxy-app synthesis pipeline (paper Fig. 1).

    trace → columnar TraceStore → joint compute-event clustering →
    per-rank Sequitur grammars (signature-deduped) → inter-process merge →
    QP block-combination search → code generation

One call::

    result = synthesize(step_fn, *specs, axis_sizes={"data": 16})
    result.proxy.run_local()
    print(result.stats["compression_ratio"], result.fidelity.mean)

The front half runs on the columnar trace IR (:mod:`repro.core.trace_ir`):
compute metrics live in one ``(n_events, 6)`` array, comm events are
interned ids, and clustering/interning are vectorized — bit-identical to
the per-event reference (:mod:`repro.core.frontend_reference`) and
measured in ``benchmarks/synthesize_time.py``.

:func:`synthesize_corpus` lifts the pipeline to a *corpus* of scenarios
(the model-zoo workloads registered in :mod:`repro.configs.registry`):
compute events cluster jointly across scenarios, the per-scenario merged
tables union into one corpus terminal table, and every block-combination
fit solves in a single batched-PGD device call — one solve for the whole
zoo instead of one per scenario.
"""
from __future__ import annotations

import dataclasses
import hashlib
from pathlib import Path
from typing import Callable, Sequence

import numpy as np

from repro import obs
from repro.core import noise as noise_mod
from repro.core import proxy_search
from repro.core.events import Event, cluster_corpus, is_comm
from repro.core.grammar import Grammar, TerminalTable
from repro.core.interproc import (
    MergedProgram, corpus_terminal_table, table_fingerprint,
)
from repro.core.codegen import generate_source
from repro.core.replay import FidelityReport, ProxyProgram, load_module
from repro.core.trace_ir import TraceStore, compress_store
from repro.core.tracer import trace_fn_store


@dataclasses.dataclass
class SynthesisResult:
    proxy: ProxyProgram
    merged: MergedProgram
    grammars: list[Grammar]
    store: TraceStore
    rank_ids: list[list[int]]
    fits: dict[int, proxy_search.FitResult]
    stats: dict

    @property
    def source(self) -> str:
        return self.proxy.source

    @property
    def rank_traces(self) -> list[list[Event]]:
        """Materialized per-rank event lists (lazy: the pipeline itself
        never needs them; tests and benchmarks do)."""
        cached = getattr(self, "_rank_traces_cache", None)
        if cached is None:
            cached = self.store.to_rank_traces()
            self._rank_traces_cache = cached
        return cached

    def fidelity(self, sample_ranks: int | None = 16,
                 batched: bool = True, mesh=None, noise=None):
        """δ̄ report; ``batched`` uses the vectorized per-signature-group
        path (identical numbers, one walker trace per group).  The
        original side reads straight from the columnar store — no Event
        materialization.  ``noise=NoiseConfig(...)`` returns the seeded
        :class:`~repro.core.noise.FidelityDistribution` instead (see
        :meth:`repro.core.replay.ProxyProgram.fidelity`)."""
        keys = [[g.table[i].key() for i in ids]
                for g, ids in zip(self.grammars, self.rank_ids)]
        return self.proxy.fidelity(self.store, keys,
                                   sample_ranks=sample_ranks, batched=batched,
                                   mesh=mesh, noise=noise)


def compress_rank_traces(rank_traces: Sequence[Sequence[Event]],
                         rel_tol: float = 0.05,
                         threshold: float = 0.5,
                         ) -> tuple[list[Grammar], MergedProgram,
                                    list[list[int]], dict[int, np.ndarray]]:
    """Cluster compute events jointly, build per-rank grammars, merge.

    Joint clustering across ranks is the paper's "inter-process merging of
    computing terminals has been completed in the process of processing
    computing events" (§2.6.1).  Thin wrapper: ingests the event lists
    into a :class:`TraceStore` and runs the columnar front half.
    """
    store = TraceStore.from_rank_traces(rank_traces)
    return compress_store(store, rel_tol, threshold)


def _fit_terminals(table: TerminalTable, reps: dict[int, np.ndarray],
                   solver: str, count_scale: float,
                   ) -> tuple[dict[int, proxy_search.FitResult],
                              dict[int, tuple], str]:
    """QP block-combination search, one fit per unique compute terminal.

    ``solver="pgd"`` solves every target in one batched device call;
    ``"nnls"`` runs the exact active-set solver per target."""
    targets, gids = [], []
    for gid, ev in enumerate(table.events):
        if not is_comm(ev):
            t = np.asarray(reps[ev.cluster_id] if ev.cluster_id >= 0
                           else ev.vector) * count_scale
            targets.append(t)
            gids.append(gid)
    solver = proxy_search.choose_solver(len(targets), solver)
    fits: dict[int, proxy_search.FitResult] = {}
    combos: dict[int, tuple] = {}
    if solver == "pgd" and targets:
        for gid, fr in zip(gids, proxy_search.fit_batch(np.stack(targets))):
            fits[gid] = fr
            combos[gid] = (tuple(int(v) for v in fr.x), fr.unroll)
    else:
        for gid, t in zip(gids, targets):
            fr = proxy_search.fit_combination(t)
            fits[gid] = fr
            combos[gid] = (tuple(int(v) for v in fr.x), fr.unroll)
    return fits, combos, solver


def _assemble_result(store: TraceStore, grammars, merged, rank_ids, fits,
                     combos, solver: str, name: str,
                     axis_sizes: dict[str, int], count_scale: float,
                     out_dir, codegen: str = "table",
                     noise_model: "noise_mod.NoiseModel | None" = None,
                     ) -> SynthesisResult:
    """Codegen + module load + stats: the shared back half of
    :func:`synthesize` and :func:`synthesize_corpus`.

    ``codegen`` picks the emitter: ``"table"`` (default) is the grammar-
    compiled program-table flavor (executables sized O(grammar));
    ``"unrolled"`` is the per-symbol reference oracle
    (:mod:`repro.core.codegen_reference`) — same δ̄ and comm sequences,
    trace-sized executables.

    ``noise_model`` is the calibrated :class:`~repro.core.noise.NoiseModel`
    whose per-terminal ``(σ, shift)`` pairs land in the emitted module's
    ``NOISE_MODELS`` table (both flavors; ``None`` emits unit factors)."""
    if codegen == "table":
        emit = generate_source
    elif codegen == "unrolled":
        from repro.core.codegen_reference import generate_source as emit
    else:
        raise ValueError(f"unknown codegen flavor: {codegen!r} "
                         "(expected 'table' or 'unrolled')")
    noise_models = (noise_model.terminal_params(merged.table.events)
                    if noise_model is not None else None)
    with obs.span("synthesize.codegen"):
        source = emit(merged, combos, name, axis_sizes,
                      count_scale=count_scale, noise_models=noise_models)
    with obs.span("synthesize.load"):
        module = load_module(source, name=f"{name}_mod", out_dir=out_dir)
    proxy = ProxyProgram(source, module, merged, combos, axis_sizes)

    trace_bytes = store.raw_trace_bytes()
    grammar_bytes = merged.encoded_size_bytes()
    fit_errs = [float(np.mean(f.per_metric_rel_err[f.target > 0]))
                for f in fits.values() if np.any(f.target > 0)]
    stats = {
        "n_ranks": store.n_ranks,
        "n_events": store.n_events,
        "n_signature_groups": len(module.SIGNATURE_GROUPS),
        "n_unique_terminals": len(merged.table),
        "n_rules": len(merged.rules),
        "trace_bytes": trace_bytes,
        "grammar_bytes": grammar_bytes,
        "compression_ratio": trace_bytes / max(grammar_bytes, 1),
        "source_lines": source.count("\n") + 1,
        "codegen": codegen,
        "solver": solver,
        "mean_fit_rel_err": float(np.mean(fit_errs)) if fit_errs else 0.0,
        "max_fit_rel_err": float(np.max(fit_errs)) if fit_errs else 0.0,
    }
    return SynthesisResult(proxy=proxy, merged=merged, grammars=grammars,
                           store=store, rank_ids=rank_ids, fits=fits,
                           stats=stats)


def synthesize(fn: Callable | None = None, *args,
               rank_traces: Sequence[Sequence[Event]] | None = None,
               store: TraceStore | None = None,
               axis_sizes: dict[str, int] | None = None,
               name: str = "proxy",
               rel_tol: float = 0.05,
               threshold: float = 0.5,
               solver: str = "auto",
               count_scale: float = 1.0,
               out_dir=None,
               codegen: str = "table") -> SynthesisResult:
    """Synthesize a proxy-app from a step function, pre-recorded traces,
    or a saved columnar :class:`TraceStore` (``TraceStore.load(path)`` —
    traces are offline artifacts).

    ``solver="auto"`` (default) picks the block-combination solver by
    terminal count: exact NNLS for small traces, the batched-PGD device
    solver above :data:`repro.core.proxy_search.PGD_TERMINAL_THRESHOLD`
    distinct compute terminals (``"nnls"``/``"pgd"`` force either); the
    resolved name lands in ``stats["solver"]``.

    ``count_scale`` < 1 shrinks the fitted block counts (and hence replay
    time) proportionally — the proxy then represents a 1/count_scale
    time-dilated execution; useful to keep CPU-host replay benchmarks
    fast.  The generated module's per-group device hints scale with it, so
    the mesh sweep scheduler packs time-dilated groups onto fewer devices.

    ``codegen="table"`` (default) emits the grammar-compiled program-table
    module; ``"unrolled"`` emits the per-symbol reference oracle — both
    replay the same program with bit-identical δ̄ and comm sequences.

    The call is the root span ``synthesize.program`` of one program (see
    :mod:`repro.obs`), with the children ``synthesize.trace``,
    ``compress`` (:func:`compress_store`), ``synthesize.fit``, ``.noise``,
    ``.codegen`` and ``.load``; ``stats["stage_ms"]`` is its split by the
    innermost span, ``rest`` included, and ``stats["counts"]`` the
    counters under it.  The proxy keeps the root's id (``proxy.root``) for
    the spans of its runs.
    """
    if store is None and rank_traces is None and fn is None:
        raise ValueError("need fn, rank_traces, or store")
    with obs.span("synthesize.program") as top:
        if store is None:
            with obs.span("synthesize.trace"):
                if rank_traces is not None:
                    store = TraceStore.from_rank_traces(rank_traces,
                                                        axis_sizes)
                else:
                    store = trace_fn_store(fn, *args, axis_sizes=axis_sizes)
        axis_sizes = dict(store.axis_sizes if axis_sizes is None
                          else axis_sizes)

        grammars, merged, rank_ids, reps = compress_store(store, rel_tol,
                                                          threshold)
        with obs.span("synthesize.fit"):
            fits, combos, solver = _fit_terminals(merged.table, reps, solver,
                                                  count_scale)
        # same rel_tol → same cluster assignment as compress_store, so the
        # calibrated σ keys line up with the merged table's cluster ids
        with obs.span("synthesize.noise"):
            noise_model = noise_mod.calibrate(store, rel_tol=rel_tol)
        res = _assemble_result(store, grammars, merged, rank_ids, fits,
                               combos, solver, name, axis_sizes, count_scale,
                               out_dir, codegen=codegen,
                               noise_model=noise_model)
    under = obs.descendants(top)
    res.stats["stage_ms"] = obs.stage_ms(top, under)
    res.stats["counts"] = obs.counts_of([top, *under])
    res.proxy.root = top.root
    return res


# ---------------------------------------------------------------------------
# corpus-level synthesis across the scenario zoo
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CorpusResult:
    """Per-scenario synthesis results plus the corpus-level shared state."""
    results: dict[str, SynthesisResult]
    table: TerminalTable               # corpus terminal table (shared)
    reps: dict[int, np.ndarray]        # joint cluster representatives
    stats: dict
    #: corpus-gid-keyed block-combination fits (one per compute terminal
    #: of ``table``) — the serve tier featurizes scenarios over these
    #: coefficients without touching per-scenario modules
    fits: dict[int, proxy_search.FitResult] = dataclasses.field(
        default_factory=dict)

    def report(self, sample_ranks: int | None = None) -> dict:
        """Aggregate fidelity/compression report: per-scenario δ̄ and
        compression ratio plus corpus totals (runs the walker-metric
        fidelity measurement per scenario)."""
        rows = {}
        for sname, res in self.results.items():
            fid = res.fidelity(sample_ranks=sample_ranks)
            rows[sname] = {
                "mean_delta": float(fid.mean),
                "comm_lossless": bool(fid.comm_lossless),
                "compression_ratio": float(res.stats["compression_ratio"]),
                "n_events": int(res.stats["n_events"]),
                "n_ranks": int(res.stats["n_ranks"]),
            }
        deltas = [r["mean_delta"] for r in rows.values()]
        return dict(self.stats, scenarios=rows,
                    mean_delta=float(np.mean(deltas)) if deltas else 0.0,
                    all_comm_lossless=all(r["comm_lossless"]
                                          for r in rows.values()))


def _corpus_scenario_results(stores: dict[str, TraceStore],
                             names: Sequence[str], per: dict[str, tuple],
                             corpus_fits: dict[int, proxy_search.FitResult],
                             gid_maps: Sequence[dict[int, int]],
                             count_scale: float, out_dir,
                             memo: dict | None = None,
                             id_of: dict[str, tuple] | None = None,
                             noise_models: dict | None = None,
                             ) -> tuple[dict[str, SynthesisResult], int]:
    """Back half shared by batch and incremental corpus synthesis: map
    corpus-level fits onto each scenario's merged table and assemble its
    proxy module.

    With ``memo``/``id_of`` (the incremental path), assembly itself is
    content-addressed: a scenario whose identity (content hash, cluster
    assignments, threshold) *and* fit inputs (per-terminal target/x/
    unroll) are unchanged reuses its previous :class:`SynthesisResult`
    wholesale — no re-codegen, no module reload.  Returns ``(results,
    n_reused)``.  ``noise_models[sname]`` is the scenario's calibrated
    :class:`~repro.core.noise.NoiseModel` (a pure function of the
    scenario content + joint cluster assignment, both already part of
    the memo identity, so memo hits stay valid).
    """
    results: dict[str, SynthesisResult] = {}
    n_reused = 0
    for i, sname in enumerate(names):
        grammars, merged, rank_ids = per[sname]
        gmap = gid_maps[i]
        fits, combos = {}, {}
        for gid, ev in enumerate(merged.table.events):
            if is_comm(ev):
                continue
            fr = corpus_fits[gmap[gid]]
            fits[gid] = fr
            combos[gid] = (tuple(int(v) for v in fr.x), fr.unroll)
        rkey = None
        if memo is not None:
            fit_id = tuple(
                (gid, fr.unroll, fr.x.tobytes(), fr.target.tobytes())
                for gid, fr in sorted(fits.items()))
            # sname is part of the key: assembly bakes the scenario name
            # into the module and the out_dir layout, so duplicate-content
            # scenarios must still assemble separately
            rkey = ("result", sname, id_of[sname], count_scale,
                    repr(out_dir), fit_id)
            hit = memo.get(rkey)
            if hit is not None:
                results[sname] = hit
                n_reused += 1
                continue
        sdir = Path(out_dir) / sname if out_dir else None
        results[sname] = _assemble_result(
            stores[sname], grammars, merged, rank_ids, fits, combos, "pgd",
            sname.replace("-", "_"), stores[sname].axis_sizes, count_scale,
            sdir, noise_model=(noise_models or {}).get(sname))
        if rkey is not None:
            memo[rkey] = results[sname]
    return results, n_reused


def _corpus_stats(names: Sequence[str], table: TerminalTable,
                  corpus_fits: dict, gid_maps: Sequence[dict[int, int]],
                  results: dict[str, SynthesisResult]) -> dict:
    from collections import Counter
    use = Counter()
    for m in gid_maps:
        use.update(set(m.values()))
    stats = {
        "n_scenarios": len(names),
        "n_corpus_terminals": len(table),
        "n_compute_terminals": len(corpus_fits),
        "n_shared_terminals": sum(1 for v in use.values() if v > 1),
        "n_solver_calls": 1 if corpus_fits else 0,
        "total_trace_bytes": sum(r.stats["trace_bytes"]
                                 for r in results.values()),
        "total_grammar_bytes": sum(r.stats["grammar_bytes"]
                                   for r in results.values()),
    }
    stats["corpus_compression_ratio"] = (
        stats["total_trace_bytes"] / max(stats["total_grammar_bytes"], 1))
    return stats


def synthesize_corpus(scenarios=None, *,
                      store=None,
                      rel_tol: float = 0.05,
                      threshold: float = 0.5,
                      count_scale: float = 1.0,
                      out_dir=None,
                      **scenario_kwargs) -> CorpusResult:
    """Synthesize proxies for a whole corpus of scenarios at once.

    ``scenarios`` entries are registry names (``repro.configs.registry.
    SCENARIOS``; ``None`` = the full zoo) or ``(name, TraceStore)`` pairs
    for pre-built/loaded traces.  Extra ``scenario_kwargs`` (``n_ranks``,
    ``steps``) forward to the registry builders.

    ``store=`` accepts a :class:`repro.core.corpus_store.CorpusStore`
    instead: synthesis then runs **incrementally** over everything the
    store holds, in canonical manifest order (shard-major, content-hash
    sorted — a pure function of the scenario set) — cluster assignments
    come from the store's persisted :class:`~repro.core.corpus_store.
    ClusterIndex`, unchanged scenarios reuse their memoized grammar front
    half, and only compute terminals without a content-addressed cached
    fit re-solve (still in one ``fit_batch`` dispatch).  Per-scenario δ̄
    is bit-identical to a from-scratch call on the same scenario set in
    the same order — the load-bearing invariant of the streaming corpus
    (pinned by tests/test_corpus_store.py and the CI incremental job).

    Versus a per-scenario :func:`synthesize` loop:

    * compute events cluster **jointly** across scenarios
      (:func:`cluster_corpus`: one pass-1 bucket table per scenario,
      partial sums folded in list order — the same semantics the
      streaming store derives incrementally), so a compute behaviour
      shared by two workloads is one terminal, not two;
    * the per-scenario merged tables union into one corpus terminal table
      (:func:`corpus_terminal_table`), and every block-combination fit
      solves in **one** batched-PGD device call;
    * each scenario still gets its own merged grammar, generated module,
      and :class:`SynthesisResult` (δ̄ measurable per scenario).
    """
    if store is not None:
        if scenarios is not None or scenario_kwargs:
            raise ValueError(
                "store= synthesizes everything the CorpusStore holds; "
                "pass scenarios/builder kwargs at add_scenario time")
        if rel_tol != store.rel_tol:
            raise ValueError(
                f"corpus store was clustered at rel_tol={store.rel_tol}; "
                f"got rel_tol={rel_tol}")
        return _synthesize_corpus_incremental(store, threshold, count_scale,
                                              out_dir)

    from repro.configs import registry   # lazy: configs pulls in models

    if scenarios is None:
        scenarios = list(registry.SCENARIOS)
    stores: dict[str, TraceStore] = {}
    for sc in scenarios:
        if isinstance(sc, str):
            stores[sc] = registry.build_scenario(sc, **scenario_kwargs)
        else:
            sname, st = sc
            stores[sname] = st
    names = list(stores)

    # joint clustering across every scenario's compute events: the
    # per-scenario partial-sums fold (one pass-1 bucket table per
    # scenario, folded in list order) — the same semantics the streaming
    # CorpusStore's ClusterIndex derives incrementally, which is what
    # keeps batch and incremental synthesis bit-identical
    cids_list, reps = cluster_corpus([stores[n].metrics for n in names],
                                     rel_tol)

    per: dict[str, tuple] = {}
    mergeds: list[MergedProgram] = []
    noise_models: dict[str, noise_mod.NoiseModel] = {}
    for i, sname in enumerate(names):
        cids = cids_list[i]
        grammars, merged, rank_ids, _ = compress_store(
            stores[sname], rel_tol, threshold, cluster_ids=cids, reps=reps)
        per[sname] = (grammars, merged, rank_ids)
        mergeds.append(merged)
        # calibrated against the JOINT assignment slice, so σ keys match
        # the merged table's (joint) cluster ids — and so the incremental
        # path, which calibrates from the persisted ClusterIndex's
        # identical assignment, emits identical NOISE_MODELS tables
        noise_models[sname] = noise_mod.calibrate(stores[sname],
                                                  cluster_ids=cids,
                                                  rel_tol=rel_tol)

    # one corpus table, one batched-PGD solve for every compute terminal
    table, gid_maps = corpus_terminal_table(mergeds)
    corpus_fits, _, _ = _fit_terminals(table, reps, "pgd", count_scale)

    results, _ = _corpus_scenario_results(stores, names, per, corpus_fits,
                                          gid_maps, count_scale, out_dir,
                                          noise_models=noise_models)
    stats = _corpus_stats(names, table, corpus_fits, gid_maps, results)
    return CorpusResult(results=results, table=table, reps=reps, stats=stats,
                        fits=corpus_fits)


# ---------------------------------------------------------------------------
# incremental corpus synthesis over a CorpusStore
# ---------------------------------------------------------------------------

_FIT_KEY_VERSION = 1
_basis_fp: str | None = None


def _fit_cache_key(target: np.ndarray) -> str:
    """Content address of one block-combination fit: the exact scaled
    target vector + the calibration-basis fingerprint + a solver-grid
    version (bump :data:`_FIT_KEY_VERSION` when ``fit_batch`` semantics
    change).  A fit is a pure function of these, so a cache hit is valid
    across table re-unions and scenario re-ingests."""
    global _basis_fp
    if _basis_fp is None:
        from repro.core import blocks as B
        _basis_fp = hashlib.sha256(
            np.ascontiguousarray(B.calibration_matrix()).tobytes()
        ).hexdigest()
    h = hashlib.sha256(f"fit|{_FIT_KEY_VERSION}|{_basis_fp}|".encode())
    h.update(np.ascontiguousarray(target, dtype=np.float64).tobytes())
    return h.hexdigest()


def _synthesize_corpus_incremental(cstore, threshold: float,
                                   count_scale: float, out_dir,
                                   ) -> CorpusResult:
    """The ``synthesize_corpus(store=...)`` path: same outputs as the
    batch path over the store's scenarios in manifest order, touching only
    what changed since the last synthesis."""
    # a damaged store must fail loudly here, not emit a proxy silently
    # missing scenarios: repair()/quarantine is an operator decision
    damaged = getattr(cstore, "damaged", None)
    if damaged:
        raise next(iter(damaged.values()))
    shard_errors = getattr(cstore, "shard_errors", None)
    if shard_errors:
        raise next(iter(shard_errors.values()))
    names = cstore.names
    ids_by_name, reps = cstore.cluster_assignments()

    per: dict[str, tuple] = {}
    id_of: dict[str, tuple] = {}
    mergeds: list[MergedProgram] = []
    n_front_reused = 0
    g_hits0 = cstore.grammars.hits
    g_miss0 = cstore.grammars.misses
    with obs.span("corpus.front") as front:
        for sname in names:
            cids = ids_by_name[sname]
            ident = (cstore.content_hash(sname),
                     hashlib.sha256(cids.tobytes()).hexdigest(), threshold)
            id_of[sname] = ident
            key = ("front",) + ident
            hit = cstore.memo.get(key)
            if hit is None:
                # scenarios without an in-memory front-half memo (new
                # content, or a freshly opened store) still skip Sequitur
                # for every rank stream already in the persisted grammar
                # cache
                grammars, merged, rank_ids, _ = compress_store(
                    cstore.load_scenario(sname), cstore.rel_tol, threshold,
                    cluster_ids=cids, reps=reps,
                    grammar_cache=cstore.grammars)
                hit = (grammars, merged, rank_ids)
                cstore.memo[key] = hit
            else:
                n_front_reused += 1
            grammars, merged, rank_ids = hit
            # fresh per-rank id-list copies: memoized grammars/merged are
            # read-only downstream, but id lists are caller-mutable
            per[sname] = (grammars, merged, [list(ids) for ids in rank_ids])
            mergeds.append(merged)
    grammar_ms = sum(s.ms for s in obs.descendants(front)
                     if s.name == "compress.grammar")
    cstore.save_grammars()

    table, gid_maps = corpus_terminal_table(mergeds)
    table_fp = table_fingerprint(table)

    # content-addressed fits: only targets without a cached fit re-solve,
    # still in ONE fit_batch dispatch
    corpus_fits: dict[int, proxy_search.FitResult] = {}
    miss_gids: list[int] = []
    miss_keys: list[str] = []
    miss_targets: list[np.ndarray] = []
    for gid, ev in enumerate(table.events):
        if is_comm(ev):
            continue
        t = np.asarray(reps[ev.cluster_id] if ev.cluster_id >= 0
                       else ev.vector) * count_scale
        k = _fit_cache_key(t)
        cached = cstore.fits.get(k)
        if cached is None:
            miss_gids.append(gid)
            miss_keys.append(k)
            miss_targets.append(t)
        else:
            corpus_fits[gid] = cached
    if miss_targets:
        # pad the miss batch to a power-of-two bucket: per-row PGD results
        # are independent (the same invariance the fit cache itself relies
        # on), and bucketed shapes let successive appends reuse the jitted
        # PGD executable instead of recompiling per miss count
        batch = np.stack(miss_targets)
        n_miss = len(batch)
        padded = max(4, 1 << (n_miss - 1).bit_length())
        if padded > n_miss:
            batch = np.concatenate(
                [batch, np.repeat(batch[-1:], padded - n_miss, axis=0)])
        frs = proxy_search.fit_batch(batch)[:n_miss]
        for gid, k, fr in zip(miss_gids, miss_keys, frs):
            corpus_fits[gid] = fr
            cstore.fits.put(k, fr)
    if miss_targets or cstore.manifest.get("table_fingerprint") != table_fp:
        cstore.save_fits(table_fp)   # fully-cached runs stay read-only

    stores = {n: cstore.load_scenario(n) for n in names}
    # same joint cluster assignment (the persisted ClusterIndex is pinned
    # bit-identical to the batch path) + same metrics → identical noise
    # params, so batch and incremental emit identical NOISE_MODELS tables
    noise_models = {n: noise_mod.calibrate(stores[n],
                                           cluster_ids=ids_by_name[n],
                                           rel_tol=cstore.rel_tol)
                    for n in names}
    results, n_result_reused = _corpus_scenario_results(
        stores, names, per, corpus_fits, gid_maps, count_scale, out_dir,
        memo=cstore.memo, id_of=id_of, noise_models=noise_models)
    stats = _corpus_stats(names, table, corpus_fits, gid_maps, results)
    stats.update(
        incremental=True,
        table_fingerprint=table_fp,
        n_refit_terminals=len(miss_targets),
        n_cached_fits=len(corpus_fits) - len(miss_targets),
        n_front_reused=n_front_reused,
        n_result_reused=n_result_reused,
        n_solver_calls=1 if miss_targets else 0,
        n_grammar_cache_hits=cstore.grammars.hits - g_hits0,
        n_grammar_cache_misses=cstore.grammars.misses - g_miss0,
        grammar_ms=round(grammar_ms, 3),
    )
    return CorpusResult(results=results, table=table, reps=reps, stats=stats,
                        fits=corpus_fits)
