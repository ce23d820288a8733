"""What every window measures a synthesized proxy with: the benchmark's own
sweep timer, and the proxy checks that count its work from shapes.

A proxy is replayed on LocalSim, one executable per signature group; a
sweep dispatches every group's executable and then blocks on every output.
"""
from __future__ import annotations

import math

from bench import flops as F


class Sweep:
    """One proxy sweep as the benchmark times it: dispatch every signature
    group's compiled executable, then block on every group's output."""

    def __init__(self, proxy):
        from repro.sharding.collectives import LocalSim
        self.proxy = proxy
        units = proxy._group_work(None, 0, LocalSim(), False)
        self.work = [(fn, arg) for fn, arg, _ in units]
        self.reps = [grp[0] for _, _, grp in units]

    def __call__(self):
        import jax
        outs = [fn(arg) for fn, arg in self.work]
        jax.block_until_ready(outs)
        return outs

    def mxu_flops(self) -> float:
        """Counted MXU flops of one sweep: each group's representative
        rank's count."""
        return sum(rank_mxu_flops(self.proxy, rep) for rep in self.reps)


def rank_mxu_flops(proxy, rank: int) -> float:
    """The benchmark's count of one rank's proxy MXU flops: each compute
    terminal's fitted block counts, unroll and per-application flops, times
    how often the rank's program runs the terminal."""
    occ: dict[int, int] = {}
    for gid in proxy.expand_rank_ids(rank):
        occ[gid] = occ.get(gid, 0) + 1
    return F.proxy_sweep_flops(proxy.combos, occ)


def proxy_fit_gap(proxy, want: float) -> float:
    """|ln(proxy MXU flops over all ranks / the original's)|: the factor by
    which the fitted proxy misses the original's matmul work.  A proxy with
    no MXU work reads as if it had one flop, so the number stays finite."""
    got = sum(rank_mxu_flops(proxy, r) for r in range(proxy.merged.n_ranks))
    return abs(math.log(max(got, 1.0) / want))


def comm_signature(events) -> list[tuple]:
    return [(e.kind, tuple(e.shape), str(e.dtype), tuple(e.axes))
            for e in events]


def proxy_comm_mismatches(res) -> int:
    """Ranks whose replayed collective sequence (the program tables'
    expansion, which LocalSim replays) differs from the trace's."""
    from repro.core.events import is_comm
    table = res.merged.table
    bad = 0
    for r, evs in enumerate(res.rank_traces):
        want = comm_signature(e for e in evs if is_comm(e))
        got = comm_signature(table[i] for i in res.proxy.expand_rank_ids(r)
                             if is_comm(table[i]))
        bad += got != want
    return bad


def proxy_exec_gap(sweep: Sweep) -> float:
    """Largest relative gap, over the executables the sweep dispatches,
    between the MXU flops the cost walker reads off the executable and the
    benchmark's count of the fitted blocks it should run: 0 when codegen and
    replay run exactly what the fit asked for."""
    import jax
    from repro.core.replay import init_replay_state
    from repro.core.tracer import trace_fn
    st = jax.eval_shape(lambda: init_replay_state(sweep.proxy.module))
    worst = 0.0
    for (fn, _), rep in zip(sweep.work, sweep.reps):
        want = rank_mxu_flops(sweep.proxy, rep)
        got = float(trace_fn(fn, st, exact_cond=True).total_compute()[0])
        worst = max(worst, abs(got - want) / max(want, 1.0))
    return worst
