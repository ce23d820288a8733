"""NPB MG (``repro.models.npb_mg``): the sharded class S solve against the
plain reference, ``comm3`` against ``jnp.roll``'s halos, each operator
against the reference's, and the solve through the normal path, trace →
``synthesize`` → ``run_all(mesh=)``, with its collectives replayed
losslessly and counted.

The mesh tests run in a subprocess with 4 forced host devices, NPB's
1 x 2 x 2 rank grid (the main pytest process keeps one CPU device)."""
import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import npb_mg as M

_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, time
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro import obs
    from repro.compat import make_mesh
    from repro.core.events import is_comm
    from repro.core.synthesize import synthesize
    from repro.models import npb_mg as M

    mesh = make_mesh((2, 2), ("z", "y"))
    axes = ("z", "y", None)
    out = {}

    # one comm3 on the 1 x 2 x 2 grid against the periodic wrap of the
    # whole array, block by block
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 8, 12), jnp.float32)
    halo = jax.jit(jax.shard_map(lambda b: M.comm3(b, axes, (2, 2, 1)),
                                 mesh=mesh, in_specs=P(*axes),
                                 out_specs=P(*axes)))
    got = np.asarray(halo(jax.device_put(x, M.sharding(mesh))))
    want = np.pad(np.asarray(x), 1, mode="wrap")
    blocks = [want[i * 4:i * 4 + 6, j * 4:j * 4 + 6]
              for i in range(2) for j in range(2)]
    out["comm3_equal"] = all(
        np.array_equal(got[i * 6:i * 6 + 6, j * 6:j * 6 + 6], blocks[2 * i + j])
        for i in range(2) for j in range(2))

    # the class S solve against the reference
    n = M.CLASSES["S"]["n"]
    v = M.rhs(n, 2**31 + 99, M.sharding(mesh))
    u, rnm2, rnmu = M.build("S", mesh, axes)(v)
    u_ref, rnm2_ref, rnmu_ref = M.reference("S", jax.device_put(v, jax.devices()[0]))
    u, u_ref = np.asarray(u), np.asarray(u_ref)
    out["u_gap"] = float(np.abs(u - u_ref).max() / np.abs(u_ref).max())
    out["norm_gap"] = abs(float(rnm2) - float(rnm2_ref)) / float(rnm2_ref)
    out["rnmu_gap"] = abs(float(rnmu) - float(rnmu_ref)) / float(rnmu_ref)

    # trace -> synthesize -> run_all(mesh=)
    res = synthesize(M.build("S", mesh, axes),
                     jax.ShapeDtypeStruct((n, n, n), jnp.float32))
    t0 = time.perf_counter_ns()
    states = res.proxy.run_all(mesh=mesh)
    first = [s for s in obs.spans(t0) if s.name == "proxy.mesh_first"]
    again = res.proxy.run_all(mesh=mesh)
    out["mesh_first_spans"] = len(first)
    out["mesh_first_after_second_run"] = len(
        [s for s in obs.spans(t0) if s.name == "proxy.mesh_first"])
    out["mesh_first_root"] = all(s.root == res.proxy.root for s in first)
    out["counted"] = sum(obs.counts_of([s, *obs.descendants(s)]).get(
        "replay.collectives", 0) for s in first)
    plan = res.proxy.mesh_sweep_plan(mesh)
    out["placed_groups"] = len(plan)
    out["trace_per_sweep"] = sum(
        sum(1 for e in res.rank_traces[pl.ranks[0]] if is_comm(e))
        for pl in plan)
    sig = lambda evs: [(e.kind, tuple(e.shape), str(e.dtype), tuple(e.axes))
                       for e in evs]
    table = res.merged.table
    out["proxy_comm_mismatch"] = sum(
        sig(table[i] for i in res.proxy.expand_rank_ids(r) if is_comm(table[i]))
        != sig(e for e in evs if is_comm(e))
        for r, evs in enumerate(res.rank_traces))
    got = res.proxy.mesh_comm_events(mesh)
    out["mesh_comm_mismatch"] = sum(
        sig(got[r]) != sig(e for e in evs if is_comm(e))
        for r, evs in enumerate(res.rank_traces))
    out["n_ranks"] = res.merged.n_ranks
    out["finite"] = all(bool(np.isfinite(np.asarray(l, np.float32)).all())
                        for st in again.values() for l in jax.tree.leaves(st))
    print("REPORT:" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def report():
    proc = subprocess.run([sys.executable, "-c", _PROG], capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("REPORT:")]
    assert line, proc.stdout
    return json.loads(line[0][len("REPORT:"):])


def _limits():
    from pathlib import Path
    mix = Path(__file__).resolve().parents[1] / "bench" / "traffic" / \
        "solve-nit20.replay-mesh.json"
    return json.loads(mix.read_text())["limits"]


def test_sharded_solve_matches_reference(report):
    """u and the residual norm within the float32 limits the benchmark's
    cell uses (``bench/traffic/solve-nit20.replay-mesh.json``)."""
    lim = _limits()
    assert report["u_gap"] <= lim["mg_u_gap"], report
    assert report["norm_gap"] <= lim["mg_norm_gap"], report
    assert report["rnmu_gap"] <= lim["mg_norm_gap"], report


def test_comm3_matches_roll_halos(report):
    """One ``comm3`` on a 1 x 2 x 2 mesh is the periodic wrap, bit for bit."""
    assert report["comm3_equal"]


def test_mesh_replay_is_lossless(report):
    assert report["n_ranks"] == 4
    assert report["proxy_comm_mismatch"] == 0
    assert report["mesh_comm_mismatch"] == 0
    assert report["finite"]


def test_collective_counter_is_the_trace_per_sweep(report):
    """``replay.collectives``, counted at trace time, equals the
    collectives a sweep runs: each placed group's representative rank's."""
    assert report["trace_per_sweep"] > 0
    assert report["counted"] == report["trace_per_sweep"]


def test_mesh_first_spans(report):
    """One span a placed group's first dispatch, none on a cached one."""
    assert report["mesh_first_spans"] == report["placed_groups"] >= 1
    assert report["mesh_first_after_second_run"] == report["mesh_first_spans"]
    assert report["mesh_first_root"]


# -- each operator against the reference's, on one device ----------------------


def _field(shape, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _wrap(x):
    return jnp.pad(x, 1, mode="wrap")


@pytest.mark.parametrize("op", ["resid", "psinv", "rprj3", "interp"])
def test_operator_matches_reference(op):
    """A, S, P and Q on a random periodic field, the program's (ghost shell
    from the periodic wrap) against the reference's (``jnp.roll``)."""
    u, v = _field((8, 8, 16), 1), _field((8, 8, 16), 2)
    c = M.smoother("C")
    if op == "resid":
        got, want = M.resid(_wrap(u), v), M._ref_resid(u, v)
    elif op == "psinv":
        got, want = M.psinv(_wrap(u), v, c), M._ref_psinv(u, v, c)
    elif op == "rprj3":
        got, want = M.rprj3(_wrap(u)), M._ref_rprj3(u)
    else:       # the fine ghost shell too: no comm3 follows interp
        got, want = M.interp(_wrap(u)), _wrap(M._ref_interp(u))
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_charges_are_distinct_and_balanced():
    pts, vals = M.charges(512, 2**31 + 12345)
    assert len({tuple(p) for p in pts}) == 2 * M.N_CHARGES
    assert (pts >= 0).all() and (pts < 512).all()
    assert vals.sum() == 0 and (vals == 1).sum() == M.N_CHARGES
    again, _ = M.charges(512, 2**31 + 12345)
    assert np.array_equal(pts, again)


def test_smoother_follows_the_class():
    assert M.smoother("S") == M.smoother("W") == M.smoother("A")
    assert M.smoother("B") == M.smoother("C") == \
        (-3.0 / 17.0, 1.0 / 33.0, -1.0 / 61.0, 0.0)
    assert {k: (v["n"], v["nit"]) for k, v in M.CLASSES.items()} == {
        "S": (32, 4), "W": (128, 4), "A": (256, 4), "B": (256, 20),
        "C": (512, 20)}
