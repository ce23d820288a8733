"""The NPB MG mesh cell's per-layer readers: collective time on hand-made
traces and on the small trace recorded on four chips
(``bench/testdata/trace_small_stencil.json``) against a brute-force count,
the HBM shares and their byte floors, and the program spans and counter
of the mesh replay's first run."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from bench import harness, hbm
from bench import trace as T
from bench.collectives import collective_s, is_collective
from bench.harness import Record, Span

DATA = Path(__file__).resolve().parents[1] / "testdata"
CELL = "npb-mg-C.solve-nit20.replay-mesh"


def _reader(name):
    return harness.metric_reader(name).read


@pytest.mark.parametrize("op,want", [
    ("%collective-permute-start", True), ("%collective-permute-done.1", True),
    ("%all-reduce.2", True), ("%all-gather-start.3", True),
    ("%psum_invariant.8", True), ("%fusion.30", False), ("%copy-start.1", False),
    ("%while.12", False), ("%add_reduce_fusion.2", False)])
def test_collective_op_names(op, want):
    assert is_collective(op) is want


def test_collective_time_on_a_hand_trace():
    host = [["original", 0, 100, 2], ["proxy.sweep", 100, 200, 4]]
    dev0 = [["%fusion.1", 0, 40], ["%collective-permute-start", 40, 50],
            ["%collective-permute-done", 45, 60], ["%all-reduce.2", 150, 170]]
    dev1 = [["%collective-permute-start.1", 90, 110], ["%fusion.3", 110, 200]]
    tr = {"host": host, "devices": {"/device:TPU:0": dev0,
                                    "/device:TPU:1": dev1}}
    # original: dev0 [40, 60) = 20, dev1 [90, 100) = 10; mean 15 over 2 steps
    assert collective_s(tr, "original") == pytest.approx(7.5e-9)
    # proxy: dev0 20, dev1 [100, 110) = 10; mean 15 over 4 sweeps
    assert collective_s(tr, "proxy.sweep") == pytest.approx(3.75e-9)
    rec = Record({"name": CELL}, 4, "TPU v5 lite", trace=tr)
    assert _reader("original.collective_ms")(rec) == pytest.approx(7.5e-6)
    assert _reader("replay.collective_ms")(rec) == pytest.approx(3.75e-6)
    assert collective_s({"host": host, "devices": {}}, "original") is None


def test_collective_time_on_the_recorded_four_chip_trace():
    tr = json.loads((DATA / "trace_small_stencil.json").read_text())
    for name in ("original", "proxy.sweep"):
        win = T.spans(tr, name)
        per_dev = []
        for ops in tr["devices"].values():
            busy = np.zeros(sum(w[2] - w[1] for w in win), bool)
            off = 0
            for w in win:
                for op, s, e in ops:
                    if is_collective(op):
                        s, e = max(s, w[1]) - w[1], min(e, w[2]) - w[1]
                        if e > s:
                            busy[off + s:off + e] = True
                off += w[2] - w[1]
            per_dev.append(busy.sum())
        want = np.mean(per_dev) * 1e-9 / sum(w[3] for w in win)
        assert collective_s(tr, name) == pytest.approx(want, rel=1e-12)
    assert collective_s(tr, "original") > 0
    assert collective_s(tr, "proxy.sweep") == 0.0      # no device ops there


def test_hbm_shares():
    rec = Record({"name": CELL}, 4, "TPU v5 lite",
                 spans=[Span("original", 0.0, 1.0, 10),
                        Span("proxy.sweep", 1.0, 2.0, 5)],
                 counters={"bytes_per_step": 4 * 819e9 * 0.05,
                           "bytes_per_sweep": 4 * 819e9 * 0.1})
    assert _reader("original.hbm_share")(rec) == pytest.approx(50.0)
    assert _reader("replay.hbm_share")(rec) == pytest.approx(50.0)
    rec.counters.clear()
    assert _reader("original.hbm_share")(rec) is None
    assert _reader("replay.hbm_share")(rec) is None


def test_block_byte_floors_lie_under_the_walker_count():
    """A turn's floor never exceeds what the cost walker counts for the
    turn's applications, unfused, at any unroll."""
    from repro.core.blocks import combo_cost
    from repro.core.metrics import I_BYTES
    for i in hbm.TURN_BYTES:
        for unroll in (1, 8, 512, 4096):
            x = [0] * 11
            x[i], x[10] = 1, 1
            assert hbm.combo_bytes(x, unroll) <= combo_cost(x, unroll)[I_BYTES]
    x = [0, 0, 3, 0, 0, 0, 1, 0, 5, 0, 9]
    assert hbm.combo_bytes(x, 512) == \
        3 * 256 * 1024 + 128 * 1024 + 5 * 512 * 256 * 1024
    assert hbm.proxy_bytes({7: (x, 512), 9: (x, 1)}, {7: 2}) == \
        2 * hbm.combo_bytes(x, 512)


def test_original_byte_floor_lies_under_the_walker_count():
    """The class S solve's floor, all ranks together, against the walker's
    count of the traced program on each of the four ranks (traced over an
    abstract 2 x 2 mesh)."""
    from jax.sharding import AbstractMesh, AxisType
    from repro.core.metrics import I_BYTES
    from repro.core.tracer import trace_fn
    prog = harness.load_program("npb_mg")
    sizes = dict(harness.load_config(harness.load_spec(), "npb-mg-C")[0],
                 **{"class": "S", "n": 32, "nit": 4, "lt": 5})
    floor = prog.count_bytes(None, sizes, {"kind": "npb_mg"})
    n = {k: 8.0 ** k for k in range(1, 6)}
    cycle = (sum(n[k] * 9 / 8 for k in range(2, 6)) + 2 * n[1]
             + sum(n[k] * (1 / 8 + 7) for k in range(2, 5))
             + n[5] * (1 / 8 + 8) + 3 * n[5])
    assert floor == 4 * (3 * n[5] + 4 * cycle + n[5])
    mesh = AbstractMesh((2, 2), ("z", "y"), axis_types=(AxisType.Auto,) * 2)
    step, args, axes = prog.trace_spec(None, sizes, {}, mesh)
    assert axes == {"z": 2, "y": 2}
    walker = 4 * trace_fn(step, *args).total_compute()[I_BYTES]
    assert 0 < floor < walker


def test_mesh_first_spans_and_counter():
    import time

    from repro import obs
    t0 = time.perf_counter()
    with obs.span("proxy.mesh_first"):
        obs.count("replay.collectives", 290)
        with obs.span("inner"):
            obs.count("replay.collectives", 4)
    with obs.span("proxy.mesh_first"):
        obs.count("replay.collectives", 6)
    t1 = time.perf_counter()
    with obs.span("proxy.mesh_first"):         # after the set-up's mesh run
        obs.count("replay.collectives", 1000)
    rec = Record({"name": CELL}, 4, "TPU v5 lite",
                 spans=[Span("setup.mesh_first", t0, t1, 1)])
    assert _reader("replay.mesh_collectives")(rec) == 300
    first = [s for s in obs.spans(round(t0 * 1e9))
             if s.name == "proxy.mesh_first"][:2]
    assert _reader("replay.mesh_first_s")(rec) == \
        pytest.approx(sum(s.ns for s in first) * 1e-9)
    # a program without the spans (an older tree) reads nothing
    empty = Record({"name": CELL}, 4, "TPU v5 lite",
                   spans=[Span("setup.mesh_first", t1, t1, 1)])
    assert _reader("replay.mesh_first_s")(empty) is None
    assert _reader("replay.mesh_collectives")(empty) is None
    assert _reader("replay.mesh_first_s")(
        Record({"name": CELL}, 4, "TPU v5 lite")) is None


def _mg_sizes(**kw):
    """The configuration's sizes at class S (32 points an axis, nit 4)."""
    s = dict(harness.load_config(harness.load_spec(), "npb-mg-C")[0],
             **{"class": "S", "n": 32, "nit": 4, "lt": 5,
                "c": [-3 / 8, 1 / 32, -1 / 64, 0.0]})
    s.update(kw)
    return s


def test_reference_makes_the_programs_right_hand_side():
    """The reference draws the seed's charges as the program does, on its
    own: the same v, bit for bit, from a seed past 32 bits."""
    import jax
    from jax.sharding import SingleDeviceSharding
    from repro.models import npb_mg as M
    cfg = harness.load_config(harness.load_spec(), "npb-mg-C")[1]
    dev = jax.devices()[0]
    seed = 2 ** 33 + 7
    got = np.asarray(cfg.rhs(_mg_sizes(), seed, dev))
    want = np.asarray(M.rhs(32, seed, SingleDeviceSharding(dev)))
    np.testing.assert_array_equal(got, want)
    assert (got == 1).sum() == 10 and (got == -1).sum() == 10


@pytest.mark.parametrize("op", ["resid", "psinv", "rprj3", "interp"])
def test_reference_operator_matches_the_programs_module(op):
    """Each of A, S, P and Q, written from mg.f with the sizes file's
    coefficients, against ``repro.models.npb_mg``'s reference operator
    (which ``tests/test_npb_mg.py`` holds the sharded program to)."""
    import jax
    import jax.numpy as jnp
    from repro.models import npb_mg as M
    cfg = harness.load_config(harness.load_spec(), "npb-mg-C")[1]
    s = _mg_sizes()
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(k1, (16, 16, 16), jnp.float32)
    y = jax.random.normal(k2, (16, 16, 16), jnp.float32)
    got, want = {
        "resid": lambda: (cfg.resid(x, y, s["a"]), M._ref_resid(x, y)),
        "psinv": lambda: (cfg.psinv(x, y, s["c"]),
                          M._ref_psinv(x, y, M.smoother("S"))),
        "rprj3": lambda: (cfg.rprj3(x, s["p"]), M._ref_rprj3(x)),
        "interp": lambda: (cfg.interp(x, s["q"]), M._ref_interp(x)),
    }[op]()
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_reference_reads_its_coefficients_from_the_sizes():
    """A coefficient changed in the sizes changes the reference's solve:
    nothing of it comes from the program."""
    import jax
    cfg = harness.load_config(harness.load_spec(), "npb-mg-C")[1]
    dev = jax.devices()[0]
    u0, n0, _ = cfg.reference(_mg_sizes(), 11, dev)
    a = list(_mg_sizes()["a"])
    a[3] *= 1.01
    u1, n1, _ = cfg.reference(_mg_sizes(a=a), 11, dev)
    gap = float(np.abs(np.asarray(u1 - u0)).max() / np.abs(np.asarray(u0)).max())
    assert gap > 1e-3 and float(n1) != float(n0)
