"""Replay window: the original program against its proxy on the same chip.

Set-up builds the mix's program (``programs/<kind>.py``) from the seed,
synthesizes its proxy and compiles both.  The window alternates blocks of
about ``block_s`` seconds of original steps and proxy sweeps, each step and
sweep ending in ``block_until_ready`` on every output, until ``--seconds``
have passed.  ``fidelity_err`` is |t_proxy - t_orig| / t_orig over all the
blocks.
"""
from __future__ import annotations

import time

from bench.harness import (CompileCounter, Context, Span, check, memory_peak,
                           timed_block, traced)
from bench.proxy import (Sweep, proxy_comm_mismatches, proxy_exec_gap,
                         proxy_fit_gap)


def fidelity_err(rec) -> float:
    """|t_proxy - t_orig| / t_orig, each the total time of its blocks over
    their total steps or sweeps: a stall anywhere in a block counts."""
    t_orig = rec.per_unit("original")
    t_proxy = rec.per_unit("proxy.sweep")
    return abs(t_proxy - t_orig) / t_orig


def run(ctx: Context, log) -> None:
    from repro.core.synthesize import synthesize

    rec, tr = ctx.rec, ctx.traffic
    orig = ctx.program(tr["program"]).build(ctx.config, ctx.sizes,
                                            tr["program"], ctx.seed,
                                            ctx.devices)
    orig.warm()
    fn, args, axes = orig.trace_spec()
    t0 = time.perf_counter()
    res = synthesize(fn, *args, axis_sizes=axes)
    synth_s = time.perf_counter() - t0
    res.proxy.run_all()
    sweep = Sweep(res.proxy)
    for _ in range(2):
        sweep()
    log(f"[setup] synthesize_s={synth_s:.3f} stats={res.stats} "
        f"combos={res.proxy.combos}")

    block_s = float(tr["block_s"])
    rec.e2e["setup_s"] = time.perf_counter() - ctx.t_start
    traces0 = res.proxy.cache_stats()["jit_traces"]
    with CompileCounter() as cc:
        t_end = time.perf_counter() + ctx.seconds
        while True:
            rec.spans.append(Span("original", *timed_block(
                lambda: orig.run(1), block_s)))
            rec.spans.append(Span("proxy.sweep", *timed_block(sweep, block_s)))
            if time.perf_counter() >= t_end:
                break
    jit_traces = res.proxy.cache_stats()["jit_traces"] - traces0
    log(f"[window] compiles={cc.compiles} proxy_jit_traces={jit_traces} "
        f"blocks={len(rec.spans)}")
    rec.e2e["fidelity_err"] = fidelity_err(rec)
    steps = rec.total("original")[1]
    sweeps = rec.total("proxy.sweep")[1]
    ctx.attempted = steps + sweeps
    rec.counters.update(flops_per_step=orig.flops_per_step,
                        flops_per_sweep=sweep.mxu_flops(),
                        compiles_in_window=cc.compiles)
    log("[window] original_block_step_ms=" + " ".join(
        f"{(sp.t1 - sp.t0) / sp.count * 1e3:.3f}" for sp in rec.spans
        if sp.name == "original"))
    log(f"[window] original_step_ms={rec.per_unit('original') * 1e3:.4f} "
        f"steps={steps} proxy_sweep_ms={rec.per_unit('proxy.sweep') * 1e3:.4f}"
        f" sweeps={sweeps} fidelity_err={rec.e2e['fidelity_err']:.6f}")
    if ctx.trace:
        tb = float(tr["trace_block_s"])
        rec.trace = traced([("original", lambda: orig.run(1), tb),
                            ("proxy.sweep", sweep, tb)], log)
    rec.counters["memory_peak_bytes"] = memory_peak(ctx.devices)

    lim = tr["limits"]
    t_check = time.perf_counter()
    check(ctx, "compiles_in_window", cc.compiles + jit_traces, 0)
    check(ctx, "proxy_comm_mismatch", proxy_comm_mismatches(res), 0)
    check(ctx, "proxy_exec_gap", proxy_exec_gap(sweep), 0)
    check(ctx, "proxy_fit_gap", proxy_fit_gap(res.proxy, orig.flops_per_step),
          lim["proxy_fit_gap"])
    # for information only: a count of walker metrics, not of time
    log(f"[info] walker delta_bar={res.fidelity().mean:.6f}")
    del sweep, res
    orig.release()
    for name, value in orig.check().items():
        check(ctx, name, value, lim[name])
    log(f"[check] seconds={time.perf_counter() - t_check:.3f}")
