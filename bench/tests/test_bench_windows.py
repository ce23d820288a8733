"""Each traffic driver at a small size on the CPU, called directly; and the
command itself refusing to run without a TPU."""
from __future__ import annotations

import os
import subprocess
import sys


from bench import harness
from bench.tests.conftest import MAMBA_SMALL, ROOT, run_small


def test_command_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "mamba2-2.7b.decode-b8.replay", "--seed", "0", "--seconds", "10",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert "no TPU found" in p.stderr
    assert p.stdout.strip() == ""


def test_decode_replay_window_small(cpu_peaks):
    sink = {}
    out = run_small("mamba2-2.7b.decode-b8.replay", MAMBA_SMALL, trace=True,
                    sink=sink)
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {"compiles_in_window", "proxy_comm_mismatch",
                                  "proxy_exec_gap", "proxy_fit_gap",
                                  "decode_gap"}
    assert list(out)[-1] == "checks"
    rec = sink["record"]
    assert rec.e2e["fidelity_err"] > 0 and rec.e2e["setup_s"] > 0
    assert {"original.step_ms", "replay.sweep_ms", "original.step_mfu",
            "replay.step_mfu"} <= set(out["metrics"])
    names = {s.name for s in rec.spans}
    assert names == {"original", "proxy.sweep"}


def test_synth_window_small():
    sink = {}
    out = run_small("mamba2-2.7b.decode-buckets.synth", MAMBA_SMALL,
                    seconds=0.5, sink=sink)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"synth_s", "setup_s"}
    rec = sink["record"]
    n = rec.counters["programs"]
    buckets = len(harness.load_traffic("decode-buckets.synth")["programs"])
    assert n % buckets == 0 and n >= buckets    # whole cycles of the buckets
    assert out["checks"]["programs_without_compile"]["value"] == 0
