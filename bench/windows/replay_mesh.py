"""Mesh replay window: a program sharded over the cell's chips against its
proxy, replayed on the same chips with its real collectives.

Set-up builds the mix's program (``programs/<kind>.py``) from the seed on
its mesh and compiles it (host span ``setup.original``), synthesizes its
proxy (``setup.synthesize``), and runs the proxy once by
``run_all(mesh=)`` with a shared seed (``setup.mesh_first``): that run
compiles one executable for each placed signature group.  The timed sweep
dispatches those same executables (``_group_work_mesh(..., batched=True)``),
one dispatch a placed group, then blocks on every output; it compiles
nothing else.  The window alternates blocks of about ``block_s`` seconds of
original steps and proxy sweeps, as the replay window does, and
``fidelity_err`` is the same quantity.
"""
from __future__ import annotations

import math
import time

from bench import hbm
from bench.harness import (CompileCounter, Context, Span, check, load_window,
                           memory_peak, timed_block, traced)
from bench.proxy import comm_signature, proxy_comm_mismatches, rank_mxu_flops

fidelity_err = load_window("replay").fidelity_err


class MeshSweep:
    """One proxy sweep on the mesh as the benchmark times it: dispatch each
    placed group's executable, then block on every group's output."""

    def __init__(self, proxy, mesh):
        units = proxy._group_work_mesh(None, 0, False, mesh, True)
        self.proxy = proxy
        self.work = [(fn, arg) for fn, arg, _, _ in units]
        self.groups = [list(grp) for _, _, grp, _ in units]
        self.placement = proxy.mesh_sweep_plan(mesh)

    def __call__(self):
        import jax
        outs = [fn(arg) for fn, arg in self.work]
        jax.block_until_ready(outs)
        return outs

    def hbm_bytes(self) -> float:
        """Least HBM bytes of one sweep: each placed group's representative
        rank's fitted blocks, on every device of its placement."""
        total = 0.0
        for pl in self.placement:
            occ: dict[int, int] = {}
            for gid in self.proxy.expand_rank_ids(pl.ranks[0]):
                occ[gid] = occ.get(gid, 0) + 1
            total += pl.n_devices * hbm.proxy_bytes(self.proxy.combos, occ)
        return total


def walks(sweep: MeshSweep) -> list:
    """The cost walker's reading of each executable the sweep dispatches
    (``mesh_comm_events``' walk, once an executable): (ranks, trace)."""
    import jax
    from repro.core.replay import init_replay_state
    from repro.core.tracer import trace_fn
    st = jax.eval_shape(lambda: init_replay_state(sweep.proxy.module))
    return [(grp, trace_fn(fn, st, exact_cond=True))
            for (fn, _), grp in zip(sweep.work, sweep.groups)]


def mesh_comm_mismatches(res, walked) -> int:
    """Ranks whose collective sequence in the mesh executables differs from
    the trace's."""
    from repro.core.events import is_comm
    got = {r: tr.comm_events() for grp, tr in walked for r in grp}
    return sum(comm_signature(got.get(r, ()))
               != comm_signature(e for e in evs if is_comm(e))
               for r, evs in enumerate(res.rank_traces))


def fitted_cost(proxy, rank: int):
    """The walker's count of the fitted blocks ``rank``'s program runs
    (``blocks.combo_cost`` of each compute terminal, as often as it runs)."""
    import numpy as np
    from repro.core.blocks import combo_cost
    total = np.zeros_like(combo_cost([0] * 11))
    for gid in proxy.expand_rank_ids(rank):
        if gid in proxy.combos:
            x, unroll = proxy.combos[gid]
            total = total + combo_cost(x, unroll)
    return total


def proxy_byte_gap(res) -> float:
    """|ln(proxy bytes / the original's)| over all ranks, both as the cost
    walker counts them: the fitted blocks' (``blocks.combo_cost``) against
    the trace's.  The factor by which the fit misses the original's bytes."""
    from repro.core.metrics import I_BYTES
    proxy = res.proxy
    got = sum(fitted_cost(proxy, r)[I_BYTES]
              for r in range(proxy.merged.n_ranks))
    want = float(res.store.compute_totals()[:, I_BYTES].sum())
    return abs(math.log(max(got, 1.0) / want))


def proxy_exec_gaps(proxy, walked) -> tuple[float, float]:
    """Largest relative gaps, over the executables the sweep dispatches,
    between what the walker reads off the executable and what the fitted
    blocks of the group's first rank should run: MXU flops against the
    benchmark's count from shapes (``proxy_exec_gap``, exact), and HBM
    bytes against the walker's count of the fitted blocks
    (``proxy_exec_byte_gap``, for a program with no MXU work).  Both read
    0 when codegen and mesh replay run the blocks the fit asked for (the
    program table's own bookkeeping adds a few bytes), 1 for an executable
    without its blocks."""
    from repro.core.metrics import I_BYTES, I_MXU
    mxu = byte = 0.0
    for grp, tr in walked:
        got = tr.total_compute()
        want = rank_mxu_flops(proxy, grp[0])
        mxu = max(mxu, abs(float(got[I_MXU]) - want) / max(want, 1.0))
        want = fitted_cost(proxy, grp[0])[I_BYTES]
        byte = max(byte, abs(float(got[I_BYTES]) - want) / max(want, 1.0))
    return mxu, byte


def run(ctx: Context, log) -> None:
    from repro.core.synthesize import synthesize

    rec, tr = ctx.rec, ctx.traffic
    t0 = time.perf_counter()
    orig = ctx.program(tr["program"]).build(ctx.config, ctx.sizes,
                                            tr["program"], ctx.seed,
                                            ctx.devices)
    orig.warm()
    t1 = time.perf_counter()
    fn, args, axes = orig.trace_spec()
    res = synthesize(fn, *args, axis_sizes=axes)
    t2 = time.perf_counter()
    res.proxy.run_all(mesh=orig.mesh)
    t3 = time.perf_counter()
    sweep = MeshSweep(res.proxy, orig.mesh)
    for _ in range(2):
        sweep()
    for name, a, b in (("setup.original", t0, t1), ("setup.synthesize", t1, t2),
                       ("setup.mesh_first", t2, t3)):
        rec.spans.append(Span(name, a, b, 1))
    log(f"[setup] original_s={t1 - t0:.3f} synthesize_s={t2 - t1:.3f} "
        f"mesh_first_s={t3 - t2:.3f} stats={res.stats} "
        f"groups={[(pl.ranks, pl.device_ids) for pl in sweep.placement]}")

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
    rec.counters.update(bytes_per_step=orig.bytes_per_step,
                        bytes_per_sweep=sweep.hbm_bytes(),
                        compiles_in_window=cc.compiles)
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
    walked = walks(sweep)
    check(ctx, "mesh_comm_mismatch", mesh_comm_mismatches(res, walked), 0)
    mxu_gap, byte_gap = proxy_exec_gaps(res.proxy, walked)
    check(ctx, "proxy_exec_gap", mxu_gap, 0)
    check(ctx, "proxy_exec_byte_gap", byte_gap, lim["proxy_exec_byte_gap"])
    check(ctx, "proxy_fit_gap", proxy_byte_gap(res), lim["proxy_fit_gap"])
    del sweep, res, walked
    orig.release()
    for name, value in orig.check().items():
        check(ctx, name, value, lim[name])
    log(f"[check] seconds={time.perf_counter() - t_check:.3f}")
