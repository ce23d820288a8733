"""Batched multi-rank replay engine tests (paper §3.3).

Parity: ``run_all`` (group-deduplicated and group-vmapped) and the
vectorized ``fidelity`` path must agree with the per-rank baseline.
Caching: repeated calls must hit the compile/metrics caches — asserted via
the trace counters, not timing.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.events import CommEvent, ComputeEvent
from repro.core.replay import ProxyProgram
from repro.core.synthesize import synthesize
from repro.sharding.collectives import LocalSim


def _mk_traces(n_ranks=8):
    comm = CommEvent("psum", (16,), "float32", ("x",))
    perm = CommEvent("ppermute", (4, 4), "bfloat16", ("x",), ("shift", 1))
    comp = ComputeEvent((2.1e6, 3.3e4, 1.1e6, 8.2e2, 0., 0.))
    traces = []
    for r in range(n_ranks):
        tr = [comp, comm, comp, perm] * 6
        if r == 0:
            tr = tr + [comm]        # rank-0 extra event → second signature
        traces.append(tr)
    return traces


def _synth(n_ranks=8, **kw):
    return synthesize(rank_traces=_mk_traces(n_ranks), axis_sizes={"x": n_ranks},
                      name=f"batched_{n_ranks}", **kw)


def _fresh_proxy(res):
    """Second ProxyProgram over the same module: empty caches."""
    return ProxyProgram(res.proxy.source, res.proxy.module, res.merged,
                        res.proxy.combos, res.proxy.axis_sizes)


class CountingSim(LocalSim):
    """Subclass => identity-keyed in the compile cache, so every group is
    traced afresh against this instance and ``trace_events`` is exact."""


def _assert_states_close(a: dict, b: dict, rtol=1e-5, atol=1e-6):
    assert a.keys() == b.keys()
    for r in a:
        for k in a[r]:
            x = np.asarray(a[r][k], np.float32)
            y = np.asarray(b[r][k], np.float32)
            np.testing.assert_allclose(x, y, rtol=rtol, atol=atol,
                                       err_msg=f"rank {r} leaf {k}")


def test_signature_groups_metadata():
    res = _synth()
    mod = res.proxy.module
    groups = mod.SIGNATURE_GROUPS
    seen = [r for _, ranks, _ in groups for r in ranks]
    assert sorted(seen) == list(range(8))            # exact cover
    for sig, ranks, hint in groups:
        for r in ranks:
            assert mod.program_signature(r) == sig
        # every group's program touches axis "x" (size 8) → hint 8
        assert hint == 8
    # rank 0 (extra event) is alone; everyone else shares one group
    sizes = sorted(len(rs) for _, rs, _ in groups)
    assert sizes == [1, 7]
    assert res.stats["n_signature_groups"] == 2
    assert res.proxy.group_device_hints() == {sig: 8 for sig, _, _ in groups}


def test_run_all_rejects_out_of_range_ranks():
    res = _synth()
    import pytest
    for kw in ({}, {"batched": False}):
        with pytest.raises(ValueError, match="out of range"):
            res.proxy.run_all(ranks=[99], **kw)
    with pytest.raises(ValueError, match="out of range"):
        res.proxy.time_all(ranks=[-1])


def test_run_all_matches_per_rank():
    res = _synth()
    batched = res.proxy.run_all()
    per_rank = res.proxy.run_all(batched=False)
    _assert_states_close(batched, per_rank)


def test_run_all_vmap_path_matches_per_rank():
    """Distinct per-rank states: the stacked/vmapped executable must agree
    with replaying each seeded rank individually."""
    res = _synth()
    batched = res.proxy.run_all(per_rank_seeds=True)
    per_rank = res.proxy.run_all(batched=False, per_rank_seeds=True)
    _assert_states_close(batched, per_rank, rtol=1e-4, atol=1e-5)


def test_vectorized_fidelity_matches_per_rank():
    res = _synth()
    fb = res.fidelity(sample_ranks=None)
    fp = res.proxy.fidelity(res.rank_traces, sample_ranks=None, batched=False)
    np.testing.assert_allclose(fb.delta, fp.delta, rtol=1e-6, atol=0)
    assert abs(fb.mean - fp.mean) <= 1e-6 * max(abs(fp.mean), 1e-30)
    assert fb.comm_lossless == fp.comm_lossless


def test_compile_cache_hit_on_second_call():
    res = _synth()
    proxy = _fresh_proxy(res)
    proxy.run_all()
    first = proxy.cache_stats()
    assert first["jit_traces"] > 0
    proxy.run_all()
    second = proxy.cache_stats()
    # second sweep must not re-trace anything
    assert second["jit_traces"] == first["jit_traces"]

    # vmapped group executables: explicit hit counters
    proxy.run_all(per_rank_seeds=True)
    miss = proxy.cache_stats()["batch_cache_misses"]
    proxy.run_all(per_rank_seeds=True)
    after = proxy.cache_stats()
    assert after["batch_cache_misses"] == miss
    assert after["batch_cache_hits"] > 0


def test_metrics_cache_one_trace_per_group():
    res = _synth()
    proxy = _fresh_proxy(res)
    keys = [[g.table[i].key() for i in ids]
            for g, ids in zip(res.grammars, res.rank_ids)]
    proxy.fidelity(res.rank_traces, keys, sample_ranks=None)
    stats = proxy.cache_stats()
    assert stats["metric_traces"] == stats["cached_metric_groups"] == 2
    proxy.fidelity(res.rank_traces, keys, sample_ranks=None)
    assert proxy.cache_stats()["metric_traces"] == 2   # no re-trace


def test_event_counts_per_rank_vs_batched():
    """The batched engine traces the same generated comm call sites as the
    per-rank path (trace-time event counts per signature group agree)."""
    res = _synth()
    for _, grp in res.proxy.signature_groups():
        c_single = CountingSim()
        _fresh_proxy(res).run_all(ranks=grp[:1], batched=False, comm=c_single)
        c_group = CountingSim()
        _fresh_proxy(res).run_all(ranks=grp, per_rank_seeds=True, comm=c_group)
        assert c_single.trace_events > 0
        assert c_group.trace_events == c_single.trace_events


def test_run_all_group_results_isolated_across_ranks():
    """Shared-seed groups share result *leaves* (immutable, documented) but
    never result *dicts*: rebinding one rank's buffer — the only mutation
    JAX permits — must leave its group siblings untouched."""
    res = _synth()
    out = res.proxy.run_all()
    grp = next(rs for _, rs in res.proxy.signature_groups() if len(rs) > 1)
    r0, r1 = grp[0], grp[1]
    key = sorted(out[r1])[0]
    before = np.asarray(out[r1][key], np.float32).copy()
    out[r0][key] = jnp.zeros_like(out[r0][key]) - 123.0
    np.testing.assert_array_equal(np.asarray(out[r1][key], np.float32), before)
    # leaf aliasing is safe: numpy views of jax buffers are read-only, so
    # in-place mutation cannot corrupt a sibling behind the dict's back
    view = np.asarray(out[r1][key])
    assert not view.flags.writeable


def test_localsim_accepts_batched_rank_axis():
    """LocalSim.do is vmappable over a leading rank axis (JAX batches
    its ``optimization_barrier`` as the identity on batch dims)."""
    comm = LocalSim()
    st = {"buf0": jnp.full((4, 16), 0.5)}

    def one_rank(st):
        return comm.do(st, "buf0", kind="psum", axes=("x",), detail=(),
                       shape=(16,), dtype="float32")

    out = jax.jit(jax.vmap(one_rank))(st)
    assert out["buf0"].shape == (4, 16)
    np.testing.assert_allclose(np.asarray(out["buf0"]), 0.5)
