"""Mesh-sharded signature-group replay (the device-parallel sweep tier).

Three layers of coverage:

* pure planner tests (no devices touched): sub-mesh geometry, proportional
  device partitioning, hint capping, round-robin overflow;
* single-device in-process tests: the mesh path runs on whatever mesh the
  host has, and the placement-keyed compile cache hits on repeat sweeps;
* subprocess tests on a forced 8-device CPU host platform (the repo idiom
  for mesh execution, see test_device_comm.py): every ``DeviceComm``
  collective kind — including the non-divisible ``reduce_scatter`` /
  ``all_to_all`` fallbacks and all ``_detail_to_perm`` decode paths — with
  the rank axis ``vmap``-folded through the real collectives, asserting
  pool-buffer shape/dtype stability and batched-vs-sequential equality,
  plus the end-to-end 16-rank sweep: one ``shard_map`` dispatch per
  signature group, disjoint placements, placement-keyed caching, and δ̄
  bit-identical to the sequential mesh path.
"""
import subprocess
import sys
import textwrap

import numpy as np

from repro import compat
from repro.core.replay import plan_mesh_sweep, submesh_axis_sizes


def _run(prog: str, timeout: int = 420):
    proc = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                          text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


# ---------------------------------------------------------------------------
# planner (pure)
# ---------------------------------------------------------------------------


def test_collective_batching_audit_clean():
    """Every collective the replay can emit must be vmap-batchable — the
    soundness condition of folding the rank axis through DeviceComm."""
    assert compat.collective_batching_audit() == []


def test_submesh_axis_sizes():
    assert submesh_axis_sizes(8, {"x": 16}) == {"x": 8}
    assert submesh_axis_sizes(8, {"data": 4, "model": 4}) == \
        {"data": 4, "model": 2}
    assert submesh_axis_sizes(6, {"x": 4}) == {"x": 2}
    assert submesh_axis_sizes(5, {"x": 16}) == {"x": 1}   # coprime → unit
    assert submesh_axis_sizes(3, {}) == {"x": 1}          # comm-free proxy
    assert submesh_axis_sizes(1, {"x": 16}) == {"x": 1}


def test_plan_proportional_disjoint():
    groups = [(("a",), [0]), (("b",), list(range(1, 16)))]
    plan = plan_mesh_sweep(groups, {("a",): 16, ("b",): 16}, {"x": 16}, 8)
    assert [p.n_devices for p in plan] == [4, 4]
    assert plan[0].device_ids == (0, 1, 2, 3)
    assert plan[1].device_ids == (4, 5, 6, 7)
    assert dict(plan[0].axis_sizes) == {"x": 4}
    assert plan[0].ranks == (0,) and plan[1].ranks == tuple(range(1, 16))
    # placements are hashable cache-key components
    assert isinstance(hash(plan[0]), int) and plan[0].key() != plan[1].key()


def test_plan_caps_at_hint_and_realizable():
    """A comm-free group never gets more than 1 device, and the big group's
    share shrinks to a realizable sub-mesh size (7 → 4 on a 16-wide axis)
    instead of collapsing to a unit mesh."""
    groups = [(("free",), [0]), (("big",), list(range(1, 16)))]
    plan = plan_mesh_sweep(groups, {("free",): 1, ("big",): 16}, {"x": 16}, 8)
    assert plan[0].n_devices == 1
    assert dict(plan[0].axis_sizes) == {"x": 1}
    assert plan[1].n_devices == 4
    assert dict(plan[1].axis_sizes) == {"x": 4}
    assert set(plan[0].device_ids).isdisjoint(plan[1].device_ids)


def test_plan_never_oversubscribes():
    """One dominant hint + many unit groups: bumping every group to >= 1
    device must not push device ids past the mesh (regression: hints
    [100,1,1,1,1,1,1] on 8 devices used to plan ids 8 and 9)."""
    groups = [((i,), [i]) for i in range(7)]
    hints = {(0,): 100, **{(i,): 1 for i in range(1, 7)}}
    plan = plan_mesh_sweep(groups, hints, {"x": 100}, 8)
    ids = [i for p in plan for i in p.device_ids]
    assert max(ids) < 8
    assert len(ids) == len(set(ids))     # still disjoint
    assert all(p.n_devices >= 1 for p in plan)


def test_plan_wraps_when_groups_exceed_devices():
    groups = [((i,), [i]) for i in range(5)]
    plan = plan_mesh_sweep(groups, {}, {"x": 4}, 2)
    assert [p.device_ids for p in plan] == [(0,), (1,), (0,), (1,), (0,)]
    assert all(dict(p.axis_sizes) == {"x": 1} for p in plan)


def test_plan_empty_groups():
    assert plan_mesh_sweep([], {}, {"x": 4}, 8) == []


def test_count_scale_scaled_hints_and_unit_group_sharing():
    """ROADMAP item: ``count_scale`` scales the generated device hints, and
    the planner packs the resulting unit-hint groups onto one shared device
    instead of idling devices sized for the full traced span."""
    from repro.core.events import CommEvent, ComputeEvent
    from repro.core.synthesize import synthesize

    comp = ComputeEvent((2.1e6, 3.3e4, 1.1e6, 8.2e2, 0., 0.))
    comp2 = ComputeEvent((7.7e5, 1.1e4, 3.3e5, 0., 0., 1.0))
    big = CommEvent("psum", (16,), "float32", ("x", "y"))
    small = CommEvent("psum", (4,), "float32", ("y",))
    traces = [[comp, big] * 6 for _ in range(14)]
    traces.append([comp2, small] * 6)                 # own main cluster
    traces.append([comp2, small] * 6 + [small])       # … with a branch
    axis = {"x": 8, "y": 2}

    full = synthesize(rank_traces=traces, axis_sizes=axis, name="cs_full")
    scaled = synthesize(rank_traces=traces, axis_sizes=axis,
                        count_scale=0.5, name="cs_half")
    assert sorted(g[2] for g in full.proxy.module.SIGNATURE_GROUPS) == \
        [2, 2, 16]
    assert sorted(g[2] for g in scaled.proxy.module.SIGNATURE_GROUPS) == \
        [1, 1, 8]

    # scaled hints + sharing: the two unit groups land on ONE shared device
    groups = scaled.proxy.signature_groups()
    plan = plan_mesh_sweep(groups, scaled.proxy.group_device_hints(), axis,
                           8, share_unit_groups=True)
    units = [p for p in plan if len(p.ranks) == 1]
    bigp = next(p for p in plan if len(p.ranks) > 1)
    assert len(units) == 2
    assert units[0].device_ids == units[1].device_ids
    assert set(units[0].device_ids).isdisjoint(bigp.device_ids)
    assert bigp.n_devices == 4         # realizable share of the freed mesh

    # unscaled hints (no unit groups): placements stay disjoint
    plan2 = plan_mesh_sweep(full.proxy.signature_groups(),
                            full.proxy.group_device_hints(), axis, 8,
                            share_unit_groups=True)
    ids = [i for p in plan2 for i in p.device_ids]
    assert len(ids) == len(set(ids))

    # no scarcity (total demand fits the mesh): unit groups keep their own
    # devices and run in parallel — packing only kicks in when demand
    # exceeds supply
    plan3 = plan_mesh_sweep(
        [(("a",), [0]), (("b",), [1]), (("c",), [2])],
        {("a",): 4, ("b",): 1, ("c",): 1}, {"x": 8}, 8,
        share_unit_groups=True)
    ids3 = [i for p in plan3 for i in p.device_ids]
    assert len(ids3) == len(set(ids3))


# ---------------------------------------------------------------------------
# mesh execution on whatever the host has (single device in tier-1)
# ---------------------------------------------------------------------------


def _synth(n_ranks=8):
    from repro.core.events import CommEvent, ComputeEvent
    from repro.core.synthesize import synthesize

    comm = CommEvent("psum", (16,), "float32", ("x",))
    perm = CommEvent("ppermute", (4, 4), "bfloat16", ("x",), ("shift", 1))
    comp = ComputeEvent((2.1e6, 3.3e4, 1.1e6, 8.2e2, 0., 0.))
    traces = []
    for r in range(n_ranks):
        tr = [comp, comm, comp, perm] * 6
        if r == 0:
            tr = tr + [comm]        # rank-0 extra event → second signature
        traces.append(tr)
    return synthesize(rank_traces=traces, axis_sizes={"x": n_ranks},
                      name=f"mesh_{n_ranks}")


def test_mesh_run_all_and_placement_cache():
    """The mesh sweep runs on the host's own device set (a unit mesh on the
    tier-1 single-CPU run) and repeat sweeps hit the placement-keyed
    compile cache instead of re-tracing."""
    import jax
    from repro.launch.mesh import make_replay_mesh

    res = _synth()
    mesh = make_replay_mesh(
        submesh_axis_sizes(jax.device_count(), {"x": 8}))
    plan = res.proxy.mesh_sweep_plan(mesh)
    assert len(plan) == 2

    out = res.proxy.run_all(mesh=mesh, per_rank_seeds=True)
    assert sorted(out) == list(range(8))
    stats = res.proxy.cache_stats()
    assert stats["jit_traces"] == len(plan)   # one dispatchable per group
    for st in out.values():
        for leaf in jax.tree_util.tree_leaves(st):
            assert np.isfinite(np.asarray(leaf, np.float32)).all()

    res.proxy.run_all(mesh=mesh, per_rank_seeds=True)
    stats2 = res.proxy.cache_stats()
    assert stats2["jit_traces"] == stats["jit_traces"]      # no re-trace
    assert stats2["batch_cache_hits"] > stats["batch_cache_hits"]
    assert stats2["batch_cache_misses"] == stats["batch_cache_misses"]


def test_mesh_fidelity_matches_local():
    """δ̄ is placement-invariant: the mesh-mode report carries bit-identical
    deltas and records the on-mesh execution check."""
    import jax
    from repro.launch.mesh import make_replay_mesh

    res = _synth()
    mesh = make_replay_mesh(
        submesh_axis_sizes(jax.device_count(), {"x": 8}))
    fid_local = res.proxy.fidelity(res.rank_traces, sample_ranks=None,
                                   batched=False)
    fid_mesh = res.proxy.fidelity(res.rank_traces, sample_ranks=None,
                                  mesh=mesh)
    np.testing.assert_array_equal(fid_mesh.delta, fid_local.delta)
    assert fid_mesh.mesh_checked
    assert not fid_local.mesh_checked


# ---------------------------------------------------------------------------
# forced 8-device mesh (subprocess)
# ---------------------------------------------------------------------------


def test_device_comm_batched_rank_axis_all_kinds():
    """Every DeviceComm collective kind — fallback branches and all three
    _detail_to_perm decode paths included — replays a vmapped rank axis
    inside one shard_map dispatch, with pool-buffer shape/dtype stability
    and bit-equality against the sequential (per-rank dispatch) path."""
    out = _run(textwrap.dedent("""\
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.compat import make_mesh
        from repro.sharding.collectives import DeviceComm

        mesh = make_mesh((8,), ("x",))
        comm = DeviceComm({"x": 8})
        N = 4
        cases = [
            ("psum", (), (16, 8), "float32"),
            ("pmax", (), (16, 8), "float32"),
            ("pmin", (), (16, 8), "float32"),
            ("all_gather", (0,), (16, 8), "float32"),
            ("reduce_scatter", (0,), (16, 8), "float32"),   # divisible
            ("reduce_scatter", (0,), (15, 8), "float32"),   # fallback
            ("all_to_all", (0, 1), (16, 8), "float32"),     # divisible
            ("all_to_all", (0, 1), (15, 8), "float32"),     # fallback
            ("ppermute", ("shift", 1), (16, 8), "float32"),  # decode: shift
            ("ppermute", ("empty",), (16, 8), "float32"),    # decode: empty
            ("ppermute", ("rawperm", tuple((i, (i + 3) % 8)
                                           for i in range(8))),
             (16, 8), "float32"),                            # decode: rawperm
            ("ppermute", (), (16, 8), "float32"),            # decode: default
            ("broadcast", (), (16, 8), "float32"),
            ("psum", (), (4, 4), "bfloat16"),   # wire dtype != buffer dtype
        ]
        rng = np.random.RandomState(0)
        for kind, detail, shape, dtype in cases:
            buf = jnp.asarray(rng.rand(N, *shape), jnp.bfloat16
                              if dtype == "bfloat16" else jnp.float32)
            def one(s, kind=kind, detail=detail, shape=shape, dtype=dtype):
                return comm.do(s, "b0", kind=kind, axes=("x",), detail=detail,
                               shape=shape, dtype=dtype)
            seq_fn = jax.jit(shard_map(one, mesh=mesh, in_specs=({"b0": P()},),
                                       out_specs={"b0": P()}, check_vma=False))
            bat_fn = jax.jit(shard_map(lambda st: jax.vmap(one)(st), mesh=mesh,
                                       in_specs=({"b0": P()},),
                                       out_specs={"b0": P()}, check_vma=False))
            bat = bat_fn({"b0": buf})["b0"]
            # pool-buffer stability: shape and dtype survive the fold-back
            assert bat.shape == buf.shape, (kind, detail, bat.shape)
            assert bat.dtype == buf.dtype, (kind, detail, bat.dtype)
            bnp = np.asarray(bat, np.float32)
            assert np.isfinite(bnp).all(), (kind, detail)
            for i in range(N):
                s = np.asarray(seq_fn({"b0": buf[i]})["b0"], np.float32)
                assert (bnp[i] == s).all(), (kind, detail, i)
        print("OK", len(cases), "cases")
    """))
    assert "OK" in out


def test_mesh_sharded_sweep_end_to_end():
    """16 per-rank-seeded ranks on a forced 8-device mesh: one shard_map
    dispatch per signature group, disjoint device subsets, states equal to
    the sequential mesh baseline, δ̄ bit-identical, and the compile cache
    keyed by placement (same mesh hits; a different placement re-traces)."""
    out = _run(textwrap.dedent("""\
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np
        from repro.core.events import CommEvent, ComputeEvent
        from repro.core.replay import submesh_axis_sizes
        from repro.core.synthesize import synthesize
        from repro.launch.mesh import make_replay_mesh

        N = 16
        comm = CommEvent("psum", (16,), "float32", ("x",))
        perm = CommEvent("ppermute", (4, 4), "bfloat16", ("x",), ("shift", 1))
        comp = ComputeEvent((2.1e6, 3.3e4, 1.1e6, 8.2e2, 0., 0.))
        traces = []
        for r in range(N):
            tr = [comp, comm, comp, perm] * 6
            if r == 0:
                tr = tr + [comm]
            traces.append(tr)
        res = synthesize(rank_traces=traces, axis_sizes={"x": N},
                         name="mesh_e2e")
        groups = res.proxy.module.SIGNATURE_GROUPS
        assert all(len(g) == 3 and g[2] == N for g in groups), groups

        mesh = make_replay_mesh(submesh_axis_sizes(8, {"x": N}))
        plan = res.proxy.mesh_sweep_plan(mesh)
        assert len(plan) == 2
        ids = [set(p.device_ids) for p in plan]
        assert ids[0].isdisjoint(ids[1])
        assert (ids[0] | ids[1]) <= set(range(8))

        # batched: exactly one compiled dispatch per signature group
        out_b = res.proxy.run_all(mesh=mesh, per_rank_seeds=True)
        stats = res.proxy.cache_stats()
        assert stats["jit_traces"] == len(plan), stats
        out_s = res.proxy.run_all(mesh=mesh, per_rank_seeds=True,
                                  batched=False)
        assert sorted(out_b) == sorted(out_s) == list(range(N))
        for r in out_b:
            for k in out_b[r]:
                a = np.asarray(out_b[r][k], np.float32)
                b = np.asarray(out_s[r][k], np.float32)
                assert out_b[r][k].dtype == out_s[r][k].dtype, (r, k)
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6,
                                           err_msg=f"rank {r} leaf {k}")

        # placement-keyed cache: same mesh -> hits, no new traces
        before = res.proxy.cache_stats()
        res.proxy.run_all(mesh=mesh, per_rank_seeds=True)
        after = res.proxy.cache_stats()
        assert after["jit_traces"] == before["jit_traces"]
        assert after["batch_cache_misses"] == before["batch_cache_misses"]
        assert after["batch_cache_hits"] > before["batch_cache_hits"]

        # a different placement (4-device mesh) compiles afresh
        mesh4 = make_replay_mesh(submesh_axis_sizes(4, {"x": N}),
                                 devices=jax.devices()[:4])
        res.proxy.run_all(mesh=mesh4, per_rank_seeds=True)
        moved = res.proxy.cache_stats()
        assert moved["batch_cache_misses"] > after["batch_cache_misses"]

        # fidelity: δ̄ bit-identical to the sequential mesh path
        fid_seq = res.proxy.fidelity(res.rank_traces, sample_ranks=None,
                                     batched=False)
        fid_mesh = res.proxy.fidelity(res.rank_traces, sample_ranks=None,
                                      mesh=mesh)
        assert np.array_equal(fid_mesh.delta, fid_seq.delta)
        assert fid_mesh.mesh_checked
        print("OK")
    """))
    assert "OK" in out


def test_mesh_comm_events_match_each_rank_trace():
    """The collectives of the executables the mesh sweep dispatches, read
    back by the walker, are rank by rank the original trace's — also when
    ranks diverge into two signature groups on disjoint devices."""
    out = _run(textwrap.dedent("""\
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        from repro.core.events import CommEvent, ComputeEvent, is_comm
        from repro.core.synthesize import synthesize
        from repro.launch.mesh import make_replay_mesh

        N = 4
        comm = CommEvent("psum", (16,), "float32", ("x",))
        perm = CommEvent("ppermute", (4, 4), "bfloat16", ("x",), ("shift", 1))
        comp = ComputeEvent((2.1e6, 3.3e4, 1.1e6, 8.2e2, 0., 0.))
        traces = [[comp, comm, comp, perm] * 5 + ([comm] if r == 0 else [])
                  for r in range(N)]
        res = synthesize(rank_traces=traces, axis_sizes={"x": N})
        mesh = make_replay_mesh({"x": N})
        assert len(res.proxy.mesh_sweep_plan(mesh)) == 2
        sig = lambda evs: [(e.kind, tuple(e.shape), str(e.dtype),
                            tuple(e.axes)) for e in evs]
        got = res.proxy.mesh_comm_events(mesh)
        assert sorted(got) == list(range(N))
        for r, tr in enumerate(res.rank_traces):
            assert sig(got[r]) == sig(e for e in tr if is_comm(e)), r
        print("OK")
    """))
    assert "OK" in out


def test_mesh_compiled_vs_unrolled_parity_and_reload():
    """Grammar-compiled modules on a forced 8-device mesh: states match the
    unrolled codegen_reference oracle, δ̄ is bit-identical, and a compiled
    module reloaded via load_saved_module replays on the mesh with the same
    states and metadata (SIGNATURE_GROUPS round-trip)."""
    out = _run(textwrap.dedent("""\
        import os, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np
        from pathlib import Path
        from repro.core.events import CommEvent, ComputeEvent
        from repro.core.replay import (ProxyProgram, load_saved_module,
                                       submesh_axis_sizes)
        from repro.core.synthesize import synthesize
        from repro.launch.mesh import make_replay_mesh

        N = 8
        comm = CommEvent("psum", (16,), "float32", ("x",))
        perm = CommEvent("ppermute", (4, 4), "bfloat16", ("x",), ("shift", 1))
        comps = [ComputeEvent(tuple(
            np.array([2.1e6, 3.3e4, 1.1e6, 8.2e2, 0., 0.]) * 1.5 ** i))
            for i in range(5)]
        sched = [(7 * i * i + 3 * i) % 5 for i in range(24)]
        def traces():
            out = []
            for r in range(N):
                tr = []
                for s in sched:
                    tr.extend([comps[s], comm if s % 2 == 0 else perm])
                if r == 0:
                    tr = tr + [comm]
                out.append(tr)
            return out

        tmp = Path(tempfile.mkdtemp())
        res = synthesize(rank_traces=traces(), axis_sizes={"x": N},
                         name="mesh_tbl", out_dir=tmp / "t")
        ref = synthesize(rank_traces=traces(), axis_sizes={"x": N},
                         name="mesh_unr", codegen="unrolled")
        assert res.proxy.module.CODEGEN == "table"
        assert ref.proxy.module.CODEGEN == "unrolled"
        assert res.proxy.module.SIGNATURE_GROUPS == \\
            ref.proxy.module.SIGNATURE_GROUPS

        mesh = make_replay_mesh(submesh_axis_sizes(8, {"x": N}))
        out_t = res.proxy.run_all(mesh=mesh, per_rank_seeds=True)
        out_u = ref.proxy.run_all(mesh=mesh, per_rank_seeds=True)
        assert sorted(out_t) == sorted(out_u) == list(range(N))
        for r in out_t:
            for k in out_t[r]:
                np.testing.assert_allclose(
                    np.asarray(out_t[r][k], np.float32),
                    np.asarray(out_u[r][k], np.float32),
                    rtol=1e-4, atol=1e-5, err_msg=f"rank {r} leaf {k}")

        fid_t = res.proxy.fidelity(res.rank_traces, sample_ranks=None,
                                   mesh=mesh)
        fid_u = ref.proxy.fidelity(ref.rank_traces, sample_ranks=None,
                                   mesh=mesh)
        assert np.array_equal(fid_t.delta, fid_u.delta)
        assert fid_t.mesh_checked and fid_u.mesh_checked

        # reload the saved compiled module and replay it on the mesh
        mod = load_saved_module(res.proxy.module.__proxy_path__, "mesh_rt")
        assert mod.CODEGEN == "table"
        assert mod.SIGNATURE_GROUPS == res.proxy.module.SIGNATURE_GROUPS
        redo = ProxyProgram(res.source, mod, res.merged, res.proxy.combos,
                            res.proxy.axis_sizes)
        out_r = redo.run_all(mesh=mesh, per_rank_seeds=True)
        for r in out_t:
            for k in out_t[r]:
                np.testing.assert_allclose(
                    np.asarray(out_r[r][k], np.float32),
                    np.asarray(out_t[r][k], np.float32),
                    rtol=1e-5, atol=1e-6, err_msg=f"rank {r} leaf {k}")
        print("OK")
    """))
    assert "OK" in out
