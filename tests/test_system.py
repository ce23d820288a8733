"""End-to-end system tests: the full Siesta pipeline on real distributed
programs — trace → grammar → merge → QP → codegen → replay → fidelity.

Runs in a subprocess with 8 forced host devices so shard_map programs have a
real mesh (the main pytest process keeps the single CPU device)."""
import json
import subprocess
import sys
import textwrap

import pytest

_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.compat import make_mesh
    from repro.core.synthesize import synthesize

    mesh = make_mesh((8,), ("x",))

    def stencil_step(u, w):
        def scanbody(c, _):
            u, w = c
            left = jax.lax.ppermute(u[:, :1], "x",
                                    [(i, (i + 1) % 8) for i in range(8)])
            right = jax.lax.ppermute(u[:, -1:], "x",
                                     [(i, (i - 1) % 8) for i in range(8)])
            u = u + 0.1 * (left + right - 2.0 * u)
            for _ in range(3):
                u = jnp.tanh(u @ w)
            r = jax.lax.psum(jnp.sum(u), "x")
            return (u, w), r
        (u, _), rs = jax.lax.scan(scanbody, (u, w), None, length=12)
        return u, rs

    f = shard_map(stencil_step, mesh=mesh,
                  in_specs=(P(None, "x"), P()), out_specs=(P(None, "x"), P()))
    u = jnp.ones((256, 1024))
    w = jnp.ones((128, 128)) * 0.01
    res = synthesize(f, u, w, name="systest")
    fid = res.fidelity()
    out = res.proxy.run_local(ranks=[0, 3])
    report = {
        "comm_lossless": bool(fid.comm_lossless),
        "mean_delta": float(fid.mean),
        "compression_ratio": float(res.stats["compression_ratio"]),
        "n_events": int(res.stats["n_events"]),
        "n_rules": int(res.stats["n_rules"]),
        "mean_fit": float(res.stats["mean_fit_rel_err"]),
        "replay_time": float(res.proxy.time_local(0, iters=2)),
        "source_has_shift": "('shift', 1)" in res.source,
    }
    print("REPORT:" + json.dumps(report))
""")


@pytest.fixture(scope="module")
def e2e_report():
    proc = subprocess.run([sys.executable, "-c", _PROG], capture_output=True,
                          text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("REPORT:")]
    assert line, proc.stdout
    return json.loads(line[0][len("REPORT:"):])


def test_comm_lossless(e2e_report):
    """Paper §1: communication behaviour reproduced losslessly."""
    assert e2e_report["comm_lossless"]


def test_fidelity(e2e_report):
    """Mean per-(metric, rank) relative error in the paper's Table 3 range."""
    assert e2e_report["mean_delta"] < 0.10, e2e_report


def test_compression(e2e_report):
    """Grammar ≪ trace (paper Table 3 shows 10^2-10^4x on loops)."""
    assert e2e_report["compression_ratio"] > 30, e2e_report


def test_relative_rank_encoding_in_source(e2e_report):
    assert e2e_report["source_has_shift"]


def test_replay_executes(e2e_report):
    assert e2e_report["replay_time"] > 0
