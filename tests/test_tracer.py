"""Jaxpr tracer tests: event extraction, scan handling, per-rank expansion."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.events import is_comm
from repro.core.tracer import (
    Trace, TraceSession, compute_cost, per_rank_traces, record_compute,
    record_event, trace_fn,
)
from repro.core.events import CommEvent, ComputeEvent


def test_compute_only():
    tr = trace_fn(lambda x: jnp.tanh(x @ x).sum(), jnp.ones((64, 64)))
    assert len(tr.comm_events()) == 0
    total = tr.total_compute()
    assert total[0] == 2 * 64 ** 3              # mxu flops
    assert total[3] == 64 * 64                  # tanh transcendentals


def test_scan_without_collectives_is_o1_events():
    def f(x):
        def body(c, _):
            return jnp.tanh(c @ c), None
        y, _ = jax.lax.scan(body, x, None, length=50)
        return y
    tr = trace_fn(f, jnp.ones((16, 16)))
    comps = tr.compute_events()
    assert len(comps) == 1                      # one aggregated event
    v = comps[0].vector
    assert v[0] == 50 * 2 * 16 ** 3             # cost multiplied by length
    assert v[5] >= 50                           # scan steps recorded


def test_dynamic_while_counts_one_iteration():
    def f(x):
        return jax.lax.while_loop(lambda c: c[0, 0] < 100.0,
                                  lambda c: jnp.tanh(c @ c), x)
    tr = trace_fn(f, jnp.ones((8, 8)))
    v = tr.total_compute()
    assert v[0] == 2 * 8 ** 3


def test_gather_metric():
    tab = jnp.ones((1024,))
    idx = jnp.zeros((128,), jnp.int32)
    tr = trace_fn(lambda t, i: t[i].sum(), tab, idx)
    assert tr.total_compute()[4] == 128


def _shard_map_prog():
    from jax import shard_map
    from repro.compat import make_mesh
    mesh = make_mesh((jax.device_count(),), ("x",))
    n = jax.device_count()
    from jax.sharding import PartitionSpec as P

    def f(u):
        left = jax.lax.ppermute(u, "x", [(i, (i + 1) % n) for i in range(n)])
        u = jnp.tanh(u + left)
        return jax.lax.psum(u.sum(), "x")

    return shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P()), n


def test_shard_map_collectives_and_axis_sizes():
    f, n = _shard_map_prog()
    tr = trace_fn(f, jnp.ones((8 * jax.device_count(),)))
    kinds = [e.kind for e in tr.comm_events()]
    assert kinds == ["ppermute", "psum"]
    assert tr.axis_sizes == {"x": n}


def test_per_rank_traces_shift_dedup():
    f, n = _shard_map_prog()
    tr = trace_fn(f, jnp.ones((8 * jax.device_count(),)))
    ranks = per_rank_traces(tr)
    assert len(ranks) == n
    keys = {tuple(e.key() for e in r) for r in ranks}
    assert len(keys) == 1                       # SPMD: identical after encoding


def test_scan_with_collectives_unrolls_events():
    from jax import shard_map
    from repro.compat import make_mesh
    mesh = make_mesh((jax.device_count(),), ("x",))
    from jax.sharding import PartitionSpec as P

    def f(u):
        def body(c, _):
            return jnp.tanh(c) + jax.lax.psum(c.sum(), "x"), None
        u, _ = jax.lax.scan(body, u, None, length=7)
        return u

    g = shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    tr = trace_fn(g, jnp.ones((8 * jax.device_count(),)))
    assert len(tr.comm_events()) == 7


def test_nested_jit_inside_shard_map_keeps_collectives_and_flops():
    """A nested ``jax.jit`` is a ``jit`` equation: the walker must enter it,
    so its collectives become events and its matmul is MXU work."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.compat import make_mesh
    n = jax.device_count()
    mesh = make_mesh((n,), ("x",))

    @jax.jit
    def inner(u, w):
        h = u @ w
        return h + jax.lax.psum(h.sum(), "x")

    def f(u, w):
        h = inner(u, w)
        return jax.lax.ppermute(h, "x", [(i, (i + 1) % n) for i in range(n)])

    g = shard_map(f, mesh=mesh, in_specs=(P("x"), P()), out_specs=P("x"))
    tr = trace_fn(g, jnp.ones((16 * n, 32)), jnp.ones((32, 32)))
    assert [e.kind for e in tr.comm_events()] == ["psum", "ppermute"]
    assert tr.total_compute()[0] == 2 * 16 * 32 * 32     # mxu flops


def test_unknown_sub_jaxpr_primitive_raises():
    """A primitive carrying a sub-jaxpr the walker does not know (here a
    Pallas kernel) raises instead of being costed as one elementwise op."""
    from repro.kernels.proxy_blocks.ops import mxu_block
    a = jnp.ones((128, 128), jnp.float32)
    with pytest.raises(NotImplementedError, match="pallas_call"):
        trace_fn(lambda x, y: mxu_block(x, y, 1, interpret=True), a, a)


def test_trace_session_interposition():
    with TraceSession(n_ranks=4) as sess:
        record_event(CommEvent("psum", (4,), "float32", ("x",)))
        record_compute(lambda x: x @ x, jnp.ones((8, 8)))
        record_event(CommEvent("ppermute", (2,), "float32", ("x",),
                               ("shift", 1)), ranks=[0, 1])
    assert len(sess.rank_streams[0]) == 3
    assert len(sess.rank_streams[2]) == 2


def test_instrumented_wrappers_record():
    from jax import shard_map
    from repro.compat import make_mesh
    from repro.sharding import collectives as C
    mesh = make_mesh((jax.device_count(),), ("x",))
    from jax.sharding import PartitionSpec as P

    def f(u):
        return C.psum(u.sum(), "x")

    g = shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P())
    with TraceSession(n_ranks=jax.device_count()) as sess:
        jax.jit(g)(jnp.ones((8 * jax.device_count(),)))
    assert any(is_comm(e) and e.kind == "psum" for e in sess.rank_streams[0])
