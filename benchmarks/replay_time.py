"""Paper Fig. 7-8 analog: replay execution time + cumulative-progress curve.

On this CPU host the original program and the proxy both execute for real;
we compare wall times and the time-vs-events-executed staircase (sequence
similarity, Fig. 8).

Also benchmarks the multi-rank replay engine (§3.3) across its three tiers
on a 16-rank synthetic trace:

1. **per-rank** (``batched=False``): one jitted dispatch per rank — the
   original baseline.  Use it only as a parity/measurement reference.
2. **batched-local** (``run_all``/``time_all`` default): one compiled
   executable per control-flow signature group, the rank axis ``vmap``-ed
   through ``LocalSim`` sequence points.  The right tier when only the
   compute stream matters (single host, no real network): ~7× sweep
   throughput here.
3. **mesh-sharded** (``mesh=``): signature groups placed on disjoint device
   subsets, each group replaying its *real* collectives via ``DeviceComm``
   in a single ``shard_map`` dispatch (rank axis folded through the
   collectives), groups dispatched asynchronously.  The right tier when
   comm fidelity at the target's concurrency matters — it is the path
   whose lowered HLO reproduces the traced collective schedule.

Run under ``benchmarks.run`` (which forces an 8-device CPU host platform),
the mesh sweep replays all 16 per-rank-seeded ranks in one dispatch per
signature group.  ``mesh_state_delta_vs_seq`` is the max |final-state
difference| between that batched sweep and the sequential mesh path (one
dispatch per rank, same placement) — executed on the mesh, and must be
exactly 0.0 (bit-identical).  ``fid_delta_vs_local`` confirms δ̄ is
placement-invariant (walker metrics never depend on the replay backend).
Local-tier acceptance target stays ≥ 3× (``replay_speedup``)."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import PROGRAMS, ensure_devices

ensure_devices()

_BATCH_RANKS = 16


def _batched_replay_rows() -> list[dict]:
    import jax
    from repro.core.events import CommEvent, ComputeEvent
    from repro.core.replay import submesh_axis_sizes
    from repro.core.synthesize import synthesize
    from repro.launch.mesh import make_replay_mesh

    comm = CommEvent("psum", (16,), "float32", ("x",))
    perm = CommEvent("ppermute", (4, 4), "bfloat16", ("x",), ("shift", 1))
    comp = ComputeEvent((2.1e7, 3.3e5, 1.1e7, 8.2e3, 0., 0.))
    traces = []
    for r in range(_BATCH_RANKS):
        tr = [comp, comm, comp, perm] * 6
        if r == 0:
            tr = tr + [comm]     # heterogeneous rank → second signature group
        traces.append(tr)
    res = synthesize(rank_traces=traces, axis_sizes={"x": _BATCH_RANKS},
                     name="rt_batched")

    t_per_rank = res.proxy.time_all(iters=3, batched=False)
    t_batched = res.proxy.time_all(iters=3, batched=True)
    # distinct per-rank states: vmapped group sweep vs its own baseline
    t_vmapped = res.proxy.time_all(iters=3, batched=True, per_rank_seeds=True)
    t_seeded = res.proxy.time_all(iters=3, batched=False, per_rank_seeds=True)
    fid = res.fidelity(sample_ranks=None)
    fid_per_rank = res.proxy.fidelity(res.rank_traces, sample_ranks=None,
                                      batched=False)
    rows = [{
        "program": f"batched_replay_{_BATCH_RANKS}ranks",
        "n_signature_groups": res.stats["n_signature_groups"],
        "per_rank_sweep_ms": round(t_per_rank * 1e3, 3),
        "batched_sweep_ms": round(t_batched * 1e3, 3),
        "vmapped_sweep_ms": round(t_vmapped * 1e3, 3),
        "per_rank_seeded_sweep_ms": round(t_seeded * 1e3, 3),
        "replay_speedup": round(t_per_rank / max(t_batched, 1e-12), 2),
        "vmapped_speedup": round(t_seeded / max(t_vmapped, 1e-12), 2),
        "ranks_per_sec_batched": round(_BATCH_RANKS / max(t_batched, 1e-12), 1),
        "fidelity_delta_vs_per_rank": float(
            np.max(np.abs(fid.delta - fid_per_rank.delta))),
    }]

    # tier 3: mesh-sharded sweep — real collectives, one shard_map dispatch
    # per signature group, groups on disjoint device subsets
    n_dev = jax.device_count()
    mesh = make_replay_mesh(submesh_axis_sizes(n_dev, {"x": _BATCH_RANKS}))
    plan = res.proxy.mesh_sweep_plan(mesh)
    t_mesh_seq = res.proxy.time_all(iters=3, mesh=mesh, batched=False,
                                    per_rank_seeds=True)
    t_mesh = res.proxy.time_all(iters=3, mesh=mesh, per_rank_seeds=True)
    # executed-on-mesh bit-identity: batched group dispatch vs the
    # sequential per-rank dispatches on the same placement
    out_b = res.proxy.run_all(mesh=mesh, per_rank_seeds=True)
    out_s = res.proxy.run_all(mesh=mesh, per_rank_seeds=True, batched=False)
    state_delta = max(
        float(np.max(np.abs(np.asarray(out_b[r][k], np.float32)
                            - np.asarray(out_s[r][k], np.float32))))
        for r in out_b for k in out_b[r])
    fid_mesh = res.proxy.fidelity(res.rank_traces, sample_ranks=None,
                                  mesh=mesh)
    rows.append({
        "program": f"mesh_sharded_replay_{_BATCH_RANKS}ranks",
        "mesh_devices": n_dev,
        "mesh_groups": len(plan),
        "mesh_dispatches_per_sweep": len(plan),   # one shard_map per group
        "mesh_seq_sweep_ms": round(t_mesh_seq * 1e3, 3),
        "mesh_sweep_ms": round(t_mesh * 1e3, 3),
        "mesh_speedup": round(t_mesh_seq / max(t_mesh, 1e-12), 2),
        "mesh_state_delta_vs_seq": state_delta,
        "fid_delta_vs_local": float(
            np.max(np.abs(fid_mesh.delta - fid_per_rank.delta))),
        "mesh_checked": fid_mesh.mesh_checked,
    })
    rows.append(_codegen_row(res))
    return rows


def _codegen_row(res) -> dict:
    """Grammar-compiled vs unrolled-reference executables on the same
    grammar: per-signature-group traced eqn counts and cold compile cost.
    δ̄ bit-identity between the flavors is asserted here too — the
    benchmark must never report timings for diverging programs."""
    from benchmarks.common import exec_size_cols
    from repro.core.codegen_reference import generate_source as emit_unrolled
    from repro.core.replay import ProxyProgram, load_module

    src_u = emit_unrolled(res.merged, res.proxy.combos, name="rt_unrolled",
                          axis_sizes=res.proxy.axis_sizes)
    mod_u = load_module(src_u, "rt_unrolled")
    ref = ProxyProgram(src_u, mod_u, res.merged, res.proxy.combos,
                       res.proxy.axis_sizes)
    for r in (0, 1):
        assert np.array_equal(res.proxy.rank_metrics(r),
                              ref.rank_metrics(r)), f"δ̄ diverged, rank {r}"
    tab = exec_size_cols(res.proxy)
    unr = exec_size_cols(ref)
    return {
        "program": f"codegen_table_vs_unrolled_{_BATCH_RANKS}ranks",
        "table_jaxpr_eqns": tab["jaxpr_eqns"],
        "unrolled_jaxpr_eqns": unr["jaxpr_eqns"],
        "eqn_ratio": round(unr["jaxpr_eqns"] / max(tab["jaxpr_eqns"], 1), 2),
        "table_compile_ms": tab["compile_ms"],
        "unrolled_compile_ms": unr["compile_ms"],
        "group_eqns_table": {str(k): v
                             for k, v in res.proxy.group_eqn_counts().items()},
        "group_eqns_unrolled": {str(k): v
                                for k, v in ref.group_eqn_counts().items()},
    }


def run() -> list[dict]:
    import jax
    from repro.core.synthesize import synthesize
    rows = _batched_replay_rows()
    for name, builder in PROGRAMS.items():
        fn, args, axes = builder(8)
        jfn = jax.jit(fn)
        jax.block_until_ready(jfn(*args))     # compile
        t0 = time.perf_counter()
        for _ in range(3):
            jax.block_until_ready(jfn(*args))
        t_orig = (time.perf_counter() - t0) / 3

        res = synthesize(fn, *args, axis_sizes=axes, name=f"rt_{name}")
        t_proxy = res.proxy.time_local(0, iters=3)
        rows.append({
            "program": name,
            "orig_ms": round(t_orig * 1e3, 3),
            "proxy_ms": round(t_proxy * 1e3, 3),
            "time_err": round(abs(t_proxy - t_orig) / t_orig, 3),
        })

        # Fig. 8: cumulative roofline-seconds vs event index (shape match)
        from repro.core.metrics import roofline_seconds, comm_seconds
        from repro.core.events import is_comm
        from repro.core import blocks as B

        def curve(events, combos=None):
            out, t = [], 0.0
            ci = 0
            for e in events:
                if is_comm(e):
                    t += comm_seconds(e.payload_bytes, 8)
                else:
                    t += roofline_seconds(e.vector)
                out.append(t)
            return np.asarray(out)

        orig_curve = curve(res.rank_traces[0])
        proxy_events = [res.merged.table[i]
                        for i in res.merged.expand_rank(0)]
        proxy_curve = []
        t = 0.0
        for e in proxy_events:
            if is_comm(e):
                t += comm_seconds(e.payload_bytes, 8)
            else:
                x, u = res.proxy.combos[
                    res.merged.table.by_key[e.key()]]
                t += roofline_seconds(B.combo_cost(x, u))
            proxy_curve.append(t)
        proxy_curve = np.asarray(proxy_curve)
        m = min(len(orig_curve), len(proxy_curve))
        corr = float(np.corrcoef(orig_curve[:m], proxy_curve[:m])[0, 1])
        end_err = float(abs(proxy_curve[-1] - orig_curve[-1])
                        / orig_curve[-1])
        rows.append({
            "program": name + "_curve",
            "staircase_corr": round(corr, 5),
            "endpoint_err": round(end_err, 4),
        })
    return rows
