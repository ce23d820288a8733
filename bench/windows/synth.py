"""Synthesis window: what a new program costs, from its trace to its proxy's
first run.

The window synthesizes whole programs, one per entry of the mix's
``programs`` list in an order drawn from the seed, cycling until
``--seconds`` have passed and then finishing the cycle in progress, so
every seed does the same set of programs.  A program runs from the call
that traces it to the end of its proxy's first ``run_all()``; the
persistent compile cache is off in the window, so each program pays the
compile a user's new program pays.  ``synth_s`` is the window's wall time
over the programs completed.
"""
from __future__ import annotations

import time

import numpy as np

from bench.harness import CompileCounter, Context, Span, check, memory_peak, traced
from bench.proxy import Sweep, comm_signature, proxy_exec_gap, proxy_fit_gap


def synth_s(rec) -> float:
    """Wall time of the synthesis window, from the first program's trace to
    the last program's first run, over the programs completed."""
    sel = [s for s in rec.spans if s.name in ("synthesize", "proxy.compile")]
    n = rec.total("synthesize")[1]
    return (max(s.t1 for s in sel) - min(s.t0 for s in sel)) / n


def run(ctx: Context, log) -> None:
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from repro.core.events import is_comm
    from repro.core.synthesize import synthesize

    rec, tr = ctx.rec, ctx.traffic
    programs = tr["programs"]
    rng = np.random.default_rng(ctx.seed)
    order = [programs[i] for i in rng.permutation(len(programs))]

    def spec(prog):
        return ctx.program(prog).trace_spec(ctx.config, ctx.sizes, prog)

    def one(prog):
        fn, args, axes = spec(prog)
        with CompileCounter() as cc:
            t0 = time.perf_counter()
            res = synthesize(fn, *args, axis_sizes=axes)
            t1 = time.perf_counter()
            out = res.proxy.run_all()
            t2 = time.perf_counter()
        return res, out, (t0, t1, t2), cc.compiles

    # set-up: the pipeline's own helpers warm on the first mix entry
    one(programs[0])
    rec.e2e["setup_s"] = time.perf_counter() - ctx.t_start
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    done = []
    try:
        t_end = time.perf_counter() + ctx.seconds
        while True:
            for prog in order:
                res, out, (t0, t1, t2), compiles = one(prog)
                rec.spans.append(Span("synthesize", t0, t1, 1))
                rec.spans.append(Span("proxy.compile", t1, t2, 1))
                done.append((prog, res, out, compiles))
                log(f"[window] program={prog} synth_s={t1 - t0:.3f} "
                    f"first_run_s={t2 - t1:.3f} compiles={compiles} "
                    f"jit_traces={res.proxy.cache_stats()['jit_traces']}")
            if time.perf_counter() >= t_end:
                break
        if ctx.trace:
            fn, args, axes = spec(order[0])
            holder = {}
            rec.trace = traced([
                ("synthesize", lambda: holder.__setitem__(
                    "res", synthesize(fn, *args, axis_sizes=axes)), 0.0),
                ("proxy.compile", lambda: holder["res"].proxy.run_all(), 0.0),
            ], log)
            holder.clear()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    rec.e2e["synth_s"] = synth_s(rec)
    ctx.attempted = len(done)
    rec.counters["programs"] = len(done)
    rec.counters["memory_peak_bytes"] = memory_peak(ctx.devices)
    log(f"[window] programs={len(done)} "
        f"synth_s={rec.e2e['synth_s']:.4f} compiles_per_program="
        f"{[c for *_, c in done]}")

    lim = tr["limits"]
    t_check = time.perf_counter()
    # every program compiled its own executables in the window
    check(ctx, "programs_without_compile",
          sum(1 for *_, c in done if c < 1), 0)
    trace_gap, grammar_bad, nonfinite, exec_gap = 0.0, 0, 0, 0.0
    fit_gaps = []
    for prog, res, out, _ in done:
        want = ctx.program(prog).count_flops(ctx.config, ctx.sizes, prog)
        got = float(res.store.compute_totals()[:, 0].sum())
        trace_gap = max(trace_gap, abs(got - want) / want)
        for r, evs in enumerate(res.rank_traces):
            ids = res.proxy.expand_rank_ids(r)
            comm_want = comm_signature(e for e in evs if is_comm(e))
            comm_got = comm_signature(res.merged.table[i] for i in ids
                                      if is_comm(res.merged.table[i]))
            grammar_bad += (len(ids) != len(evs)) or comm_got != comm_want
        nonfinite += not all(bool(np.isfinite(np.asarray(x, np.float32)).all())
                             for st in out.values()
                             for x in jax.tree.leaves(st))
        # every executable of every program against the fitted blocks it
        # should run, and the fitted matmul work against the original's
        exec_gap = max(exec_gap, proxy_exec_gap(Sweep(res.proxy)))
        fit_gaps.append(proxy_fit_gap(res.proxy, want))
    check(ctx, "trace_mxu_gap", trace_gap, 0)
    check(ctx, "grammar_mismatch", grammar_bad, 0)
    check(ctx, "first_run_nonfinite", nonfinite, 0)
    check(ctx, "proxy_exec_gap", exec_gap, 0)
    check(ctx, "proxy_fit_gap", float(np.mean(fit_gaps)), lim["proxy_fit_gap"])
    log(f"[check] seconds={time.perf_counter() - t_check:.3f} "
        f"fit_gaps={[round(g, 6) for g in fit_gaps]}")
