"""Shared benchmark harness: the traced target programs (the paper's BT/CG/
MG/... analogs are our framework's own distributed step functions).

The CPU benchmarks force an 8-device host platform (:func:`ensure_devices`,
called by each benchmark module before JAX starts).  :func:`stencil_program`
builds its mesh from whatever devices exist, so ``chip_smoke.py --chips 4``
runs it on four real chips without any forcing."""
from __future__ import annotations

import os

_N_DEV = 8


def ensure_devices():
    os.environ.setdefault("XLA_FLAGS",
                          f"--xla_force_host_platform_device_count={_N_DEV}")


def stencil_program(n: int = 8, length: int = 12):
    """2D-stencil analog (paper Fig. 2 / NPB MG-flavored): halo ppermutes +
    compute + global psum inside a scan.  ``n`` must not exceed the
    device count."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.compat import make_mesh

    mesh = make_mesh((n,), ("x",))

    def step(u, w):
        def body(c, _):
            u, w = c
            left = jax.lax.ppermute(u[:, :1], "x",
                                    [(i, (i + 1) % n) for i in range(n)])
            right = jax.lax.ppermute(u[:, -1:], "x",
                                     [(i, (i - 1) % n) for i in range(n)])
            u = u + 0.1 * (left + right - 2.0 * u)
            for _ in range(3):
                u = jnp.tanh(u @ w)
            r = jax.lax.psum(jnp.sum(u), "x")
            return (u, w), r
        (u, _), rs = jax.lax.scan(body, (u, w), None, length=length)
        return u, rs

    f = shard_map(step, mesh=mesh, in_specs=(P(None, "x"), P()),
                  out_specs=(P(None, "x"), P()))
    args = (jnp.ones((256, 128 * n)), jnp.ones((128, 128)) * 0.01)
    return f, args, {"x": n}


def allreduce_train_program(n: int = 8, layers: int = 6):
    """Data-parallel training analog (NPB CG-flavored): per-layer compute +
    gradient psum, explicit shard_map DP."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.compat import make_mesh

    mesh = make_mesh((n,), ("x",))

    def step(x, ws):
        def body(c, w):
            h = jnp.tanh(c @ w)
            g = jax.lax.psum(h.sum(axis=0), "x")     # grad all-reduce analog
            return h + 1e-6 * g[None, :], None
        out, _ = jax.lax.scan(body, x, ws)
        return jax.lax.psum(out.sum(), "x")

    f = shard_map(step, mesh=mesh, in_specs=(P("x"), P()),
                  out_specs=P())
    args = (jnp.ones((16 * n, 512)), jnp.ones((layers, 512, 512)) * 0.01)
    return f, args, {"x": n}


def pipeline_traces(n_ranks: int = 8, microbatches: int = 12):
    """Pipeline-parallel schedule (heterogeneous per-rank mains — the case
    that exercises Algorithm 1's clustering).  Host-level TraceSession."""
    import jax.numpy as jnp
    from repro.core.events import CommEvent, ComputeEvent
    from repro.core.tracer import TraceSession, compute_cost

    fwd = compute_cost(lambda a, b: jnp.tanh(a @ b),
                       jnp.ones((64, 256)), jnp.ones((256, 256)))
    with TraceSession(n_ranks=n_ranks) as sess:
        for mb in range(microbatches):
            for r in range(n_ranks):
                sess.emit([r], ComputeEvent(tuple(fwd)))
                if r < n_ranks - 1:   # send activation to next stage
                    sess.emit([r, r + 1],
                              CommEvent("ppermute", (64, 256), "float32",
                                        ("stage",), ("shift", 1)))
        for r in range(n_ranks):
            sess.emit([r], CommEvent("psum", (256, 256), "float32",
                                     ("stage",)))
    return sess.rank_streams


PROGRAMS = {
    "stencil2d": stencil_program,
    "dp_train": allreduce_train_program,
}


def exec_size_cols(proxy) -> dict:
    """Executable-size columns shared by the benchmark tables: the largest
    signature group's traced jaxpr equation count (O(grammar) for compiled
    modules, O(trace) for the unrolled reference) plus the wall-clock cost
    of tracing+compiling that group's dispatchable from cold."""
    import time

    import jax

    from repro.core.replay import init_replay_state
    from repro.sharding.collectives import LocalSim

    counts = proxy.group_eqn_counts()
    sig = max(counts, key=counts.get)
    rank = next(grp[0] for s, grp in proxy.signature_groups() if s == sig)
    comm = LocalSim()
    fn = jax.jit(lambda s: proxy.module.run_rank(s, comm, rank))
    st = init_replay_state(proxy.module)
    t0 = time.perf_counter()
    jax.block_until_ready(fn(st))
    compile_ms = (time.perf_counter() - t0) * 1e3
    return {"jaxpr_eqns": max(counts.values()),
            "compile_ms": round(compile_ms, 1)}
