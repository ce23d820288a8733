"""DeviceComm replay backend + manual-DP compressed train step — the
mesh-executing paths (subprocess: needs forced host devices)."""
import subprocess
import sys
import textwrap


def _run(prog: str, timeout: int = 420):
    proc = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                          text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_device_comm_all_kinds_execute():
    """Every collective kind replays under shard_map on a real mesh and the
    lowered HLO contains exactly the expected collective ops."""
    out = _run(textwrap.dedent("""\
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.compat import make_mesh
        from repro.sharding.collectives import DeviceComm
        from repro.launch.hlo_cost import analyze

        mesh = make_mesh((8,), ("x",))
        comm = DeviceComm({"x": 8})
        st = {"b0": jnp.full((16, 8), 0.5, jnp.float32)}

        def prog(st):
            st = comm.do(st, "b0", kind="psum", axes=("x",), detail=(),
                         shape=(16, 8), dtype="float32")
            st = comm.do(st, "b0", kind="all_gather", axes=("x",),
                         detail=(0,), shape=(16, 8), dtype="float32")
            st = comm.do(st, "b0", kind="reduce_scatter", axes=("x",),
                         detail=(0,), shape=(16, 8), dtype="float32")
            st = comm.do(st, "b0", kind="all_to_all", axes=("x",),
                         detail=(0, 1), shape=(16, 8), dtype="float32")
            st = comm.do(st, "b0", kind="ppermute", axes=("x",),
                         detail=("shift", 1), shape=(16, 8), dtype="float32")
            return st

        sm = shard_map(prog, mesh=mesh,
                       in_specs=(jax.tree.map(lambda _: P(), st),),
                       out_specs=jax.tree.map(lambda _: P(), st),
                       check_vma=False)
        compiled = jax.jit(sm).lower(st).compile()
        got = compiled({"b0": jnp.full((16, 8), 0.5, jnp.float32)})
        import numpy as np
        assert np.isfinite(np.asarray(got["b0"])).all()
        kinds = analyze(compiled.as_text()).collective_by_kind
        for want in ("all-reduce", "all-gather", "reduce-scatter",
                     "all-to-all", "collective-permute"):
            assert kinds.get(want, 0) > 0, (want, dict(kinds))
        print("OK", dict(kinds))
    """))
    assert "OK" in out


def test_manual_dp_compressed_step_wire_dtype():
    """The int8 error-feedback DP step trains (loss finite, params move)
    and its gradient all-reduce moves s32 payloads (4x fewer bf16-equiv
    bytes than f32)."""
    out = _run(textwrap.dedent("""\
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np, re
        from repro.configs import get, smoke
        from repro.launch.mesh import make_dp_mesh
        from repro.models.model import init_params
        from repro.train.compression import init_error_state
        from repro.train.loop import make_manual_dp_train_step
        from repro.train.optimizer import adamw_init

        cfg = smoke(get("llama3.2-3b"))
        mesh = make_dp_mesh(4)
        step = make_manual_dp_train_step(cfg, mesh)
        params = init_params(cfg)
        opt = adamw_init(params)
        err = init_error_state(params)
        batch = {"tokens": jnp.ones((8, 16), jnp.int32),
                 "labels": jnp.ones((8, 16), jnp.int32)}
        lowered = jax.jit(step).lower(params, opt, err, batch)
        txt = lowered.compile().as_text()
        # int8 quantize -> int32-accumulate all-reduce on the wire
        int_ars = re.findall(r"s32\\[[0-9,]*\\][^\\n]*all-reduce", txt) or \
                  re.findall(r"all-reduce[^\\n]*s32", txt)
        assert int_ars, "no integer all-reduce found"
        p2, o2, e2, m = jax.jit(step)(params, opt, err, batch)
        assert np.isfinite(float(m["loss"]))
        d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         params, p2)
        assert max(jax.tree.leaves(d)) > 0
        print("OK loss", float(m["loss"]))
    """))
    assert "OK" in out


def test_proxy_replay_on_mesh_runs():
    """A synthesized proxy executes under DeviceComm on the mesh end-to-end
    (not just lowering) and produces finite state."""
    out = _run(textwrap.dedent("""\
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.compat import make_mesh
        from repro.core.synthesize import synthesize
        from repro.core.replay import init_replay_state
        from repro.sharding.collectives import DeviceComm

        mesh = make_mesh((8,), ("x",))

        def f(u):
            left = jax.lax.ppermute(u, "x", [(i, (i+1) % 8) for i in range(8)])
            u = jnp.tanh((u + left) @ jnp.ones((128, 128)) * 0.01)
            return jax.lax.psum(u.sum(), "x")

        g = shard_map(f, mesh=mesh, in_specs=P(None, "x"), out_specs=P())
        res = synthesize(g, jnp.ones((64, 1024)), name="mesh_replay")
        comm = DeviceComm({"x": 8})
        mod = res.proxy.module
        st = init_replay_state(mod)
        sm = shard_map(lambda s: mod.run_rank(s, comm, 0), mesh=mesh,
                       in_specs=(jax.tree.map(lambda _: P(), st),),
                       out_specs=jax.tree.map(lambda _: P(), st),
                       check_vma=False)
        got = jax.jit(sm)(st)
        for leaf in jax.tree.leaves(got):
            assert np.isfinite(np.asarray(leaf, np.float32)).all()
        print("OK")
    """))
    assert "OK" in out
