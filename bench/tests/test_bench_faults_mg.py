"""The NPB MG mesh cell at class S on 4 forced host devices: a sound run is
correct and reports its new metrics; with the timed path broken
underneath, once for each fault the cell can have, it is not correct.

Each run needs a mesh, so all of them run in one subprocess (the main
pytest process keeps one CPU device)."""
from __future__ import annotations

import json
import subprocess
import sys
import textwrap

import pytest

from bench.tests.conftest import ROOT

CELL = "npb-mg-C.solve-nit20.replay-mesh"

_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, sys, time
    sys.path[:0] = [%(root)r, %(root)r + "/src"]
    import jax, jax.numpy as jnp
    from jax import lax
    from bench import flops, harness
    from bench.tests.conftest import BIG_SEED
    from repro.core import blocks as B
    from repro.models import npb_mg as M
    from repro.sharding import collectives as C

    real_peaks = flops.peaks
    flops.peaks = lambda k: real_peaks("TPU v5 lite" if k == "cpu" else k)
    spec = harness.load_spec()
    sizes = dict(harness.load_config(spec, "npb-mg-C")[0],
                 **{"class": "S", "n": 32, "nit": 4, "lt": 5,
                    "c": [-3 / 8, 1 / 32, -1 / 64, 0.0]})

    def skip_smoother(real):
        # psinv leaves u as it is on level 3 (8 points an axis)
        def psinv(r, u, c):
            return u if u.shape == (4, 4, 8) else real(r, u, c)
        return psinv

    def wrong_way(real):
        # each split axis sends its faces to the wrong neighbour
        def ghosts(x, dim, name, size):
            if name is None:
                return real(x, dim, name, size)
            k = x.shape[dim]
            lo = lax.slice_in_dim(x, 0, 1, axis=dim)
            hi = lax.slice_in_dim(x, k - 1, k, axis=dim)
            shift = [(i, (i + 1) %% size) for i in range(size)]
            below = lax.ppermute(lo, name, shift)
            above = lax.ppermute(hi, name, shift)
            return jnp.concatenate([below, x, above], axis=dim)
        return ghosts

    def drop_collective(real):
        # the mesh executables lower every collective but the first
        seen = []
        def do(self, st, buf, **kw):
            seen.append(1)
            return st if len(seen) == 1 else real(self, st, buf, **kw)
        return do

    def blockless(real):
        # the proxy's executables run no basis block
        def run_combo(st, x, unroll=1):
            return st
        return run_combo

    def half_work(real):
        # each fitted block runs half its loop turns
        def run_combo(st, x, unroll=1):
            x = [int(v) for v in x]
            return real(st, [v // 2 for v in x[:9]] + x[9:], unroll)
        return run_combo

    patches = {
        "none": [],
        "skip_smoother": [(M, "psinv", skip_smoother)],
        "halo_wrong_way": [(M, "_ghosts", wrong_way)],
        "class_b_smoother": [(M, "smoother", lambda real: lambda cls: real("B"))],
        "proxy_drops_collective": [(C.DeviceComm, "do", drop_collective)],
        "proxy_blockless": [(B, "run_combo", blockless)],
        "proxy_half_work": [(B, "run_combo", half_work)],
        "bfloat16": [(M, "DTYPE", lambda real: jnp.bfloat16)],
    }
    out = {}
    for fault in sys.argv[1:]:
        saved = [(obj, name, getattr(obj, name)) for obj, name, _ in patches[fault]]
        for obj, name, make in patches[fault]:
            setattr(obj, name, make(getattr(obj, name)))
        try:
            sink = {}
            res = harness.run_cell(spec, %(cell)r, BIG_SEED, 0.5, fault == "none",
                                   time.perf_counter(), sizes=sizes,
                                   devices=jax.devices()[:4], log=lambda m: None,
                                   sink=sink)
        finally:
            for obj, name, val in saved:
                setattr(obj, name, val)
        rec = sink["record"]
        res["setup_spans"] = sorted({s.name for s in rec.spans
                                     if s.name.startswith("setup.")})
        out[fault] = res
    print("REPORT:" + json.dumps(out))
""")

FAULTS = ["skip_smoother", "halo_wrong_way", "class_b_smoother",
          "proxy_drops_collective", "proxy_blockless", "proxy_half_work",
          "bfloat16"]


@pytest.fixture(scope="module")
def runs():
    prog = _PROG % {"root": str(ROOT), "cell": CELL}
    proc = subprocess.run([sys.executable, "-c", prog, "none", *FAULTS],
                          capture_output=True, text=True, timeout=900,
                          cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("REPORT:")]
    assert line, proc.stdout
    return json.loads(line[0][len("REPORT:"):])


def test_sound_run_is_correct(runs):
    out = runs["none"]
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {
        "compiles_in_window", "proxy_comm_mismatch", "mesh_comm_mismatch",
        "proxy_exec_gap", "proxy_exec_byte_gap", "proxy_fit_gap", "mg_u_gap",
        "mg_norm_gap"}
    assert out["device"]["count"] == 4
    assert out["setup_spans"] == ["setup.mesh_first", "setup.original",
                                  "setup.synthesize"]


def test_sound_run_reports_the_new_metrics(runs):
    m = {k: v["value"] for k, v in runs["none"]["metrics"].items()}
    assert {"original.step_ms", "replay.sweep_ms", "replay.mesh_first_s",
            "replay.mesh_collectives", "original.hbm_share",
            "replay.hbm_share"} <= set(m)
    # class S: 4 iterations of 14 comm3s (4 rprj3, 5 psinv, 5 resid; none
    # after interp, as in mg.f), 4 ppermutes each; the first residual's
    # comm3; the norm's psum and pmax
    assert m["replay.mesh_collectives"] == 4 * 14 * 4 + 4 + 2
    assert m["replay.mesh_first_s"] > 0
    assert 0 < m["original.hbm_share"] and 0 < m["replay.hbm_share"]


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_not_correct(runs, fault):
    out = runs[fault]
    assert not out["correct"], (fault, out["checks"])


def test_each_fault_fails_the_check_that_sees_it(runs):
    def over(fault, name):
        c = runs[fault]["checks"][name]
        return c["value"] > c["limit"]
    assert over("skip_smoother", "mg_u_gap")
    assert over("halo_wrong_way", "mg_u_gap")
    assert over("class_b_smoother", "mg_u_gap")
    assert over("proxy_drops_collective", "mesh_comm_mismatch")
    # nothing left but the program table's bookkeeping
    assert runs["proxy_blockless"]["checks"]["proxy_exec_byte_gap"]["value"] \
        > 0.99
    assert over("proxy_half_work", "proxy_exec_byte_gap")
    assert over("bfloat16", "mg_u_gap") or over("bfloat16", "mg_norm_gap")
