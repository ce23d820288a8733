"""Losslessness under compilation: lower the generated proxy under
shard_map on a mesh and compare its collective schedule (op kinds + wire
bytes from the loop-aware HLO analysis) with the traced original's.

This is the strongest portability claim the CPU container can check: the
proxy's *compiled* communication equals the original's, byte for byte."""
from __future__ import annotations


def run() -> list[dict]:
    from benchmarks.common import PROGRAMS, ensure_devices
    ensure_devices()
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.compat import make_mesh
    from repro.core.synthesize import synthesize
    from repro.core.replay import init_replay_state
    from repro.launch.hlo_cost import analyze
    from repro.sharding.collectives import DeviceComm

    rows = []
    for name, builder in PROGRAMS.items():
        fn, args, axes = builder(8)
        res = synthesize(fn, *args, axis_sizes=axes, name=f"pd_{name}")
        n = list(axes.values())[0]
        axis = list(axes.keys())[0]
        mesh = make_mesh((n,), (axis,))
        comm = DeviceComm(axes)
        mod = res.proxy.module
        st = init_replay_state(mod)

        def proxy_rank(st):
            return mod.run_rank(st, comm, 0)

        sm = shard_map(proxy_rank, mesh=mesh,
                       in_specs=(jax.tree.map(lambda _: P(), st),),
                       out_specs=jax.tree.map(lambda _: P(), st),
                       check_vma=False)
        proxy_hlo = jax.jit(sm).lower(st).compile().as_text()
        orig_hlo = jax.jit(fn).lower(*args).compile().as_text()
        pc = analyze(proxy_hlo)
        oc = analyze(orig_hlo)
        rows.append({
            "program": name,
            "orig_coll_bytes": int(oc.collective_bytes),
            "proxy_coll_bytes": int(pc.collective_bytes),
            "orig_kinds": {k: int(v) for k, v in oc.collective_by_kind.items()},
            "proxy_kinds": {k: int(v) for k, v in pc.collective_by_kind.items()},
            "bytes_err": round(abs(pc.collective_bytes - oc.collective_bytes)
                               / max(oc.collective_bytes, 1), 4),
        })
    return rows
