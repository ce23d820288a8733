"""The in-program recorder (repro.obs): spans, counters, JAX's compile
stages, the partition by stage, and the clock of a profiler capture."""
from __future__ import annotations

import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core.synthesize import synthesize


def _since():
    return time.perf_counter_ns()


def _fresh_jit():
    """A function no test has compiled yet (a new closure each call)."""
    k = float(time.perf_counter_ns() % 1000)
    return jax.jit(lambda x: x * 3.0 + k)


def test_synthesize_and_first_run_share_one_root():
    t = _since()
    res = synthesize(lambda x, w: jnp.tanh(x @ w),
                     jax.ShapeDtypeStruct((16, 128), jnp.float32),
                     jax.ShapeDtypeStruct((128, 128), jnp.float32))
    res.proxy.run_all()
    got = obs.spans(t)
    top = next(s for s in got if s.name == "synthesize.program")
    assert top.parent == 0 and top.root == top.id == res.proxy.root
    kids = {s.name: s for s in got if s.parent == top.id}
    assert {"synthesize.trace", "compress", "synthesize.fit",
            "synthesize.noise", "synthesize.codegen",
            "synthesize.load"} <= set(kids)
    comp = kids["compress"]
    for name in ("compress.cluster", "compress.intern", "compress.grammar",
                 "compress.merge"):
        sp = next(s for s in got if s.name == name and s.root == top.id)
        assert sp.parent == comp.id
    assert comp.counts["compress.streams"] == 1
    assert res.stats["counts"]["compress.sequitur_runs"] == 1
    # the split of one program adds up to its root span
    assert sum(res.stats["stage_ms"].values()) == pytest.approx(top.ms,
                                                               abs=0.01)
    assert res.stats["stage_ms"]["synthesize.fit"] > 0

    run = next(s for s in got if s.name == "proxy.run_all")
    assert run.root == top.id and run.parent == 0
    under = obs.descendants(run)
    assert under and all(s.root == top.id for s in under)
    # the first run compiled its group executables in its own span
    assert run.counts.get("jax.compiles", 0) >= res.stats["n_signature_groups"]
    assert {"jax.trace", "jax.lower", "jax.compile"} <= {
        s.name for s in under if s.parent == run.id}


def test_stacks_are_per_thread():
    barrier = threading.Barrier(2, timeout=30)
    seen = {}

    def work(tag):
        with obs.span(f"thread.{tag}") as outer:
            barrier.wait()           # both outer spans are open now
            with obs.span("thread.child") as child:
                barrier.wait()
            seen[tag] = (outer, child)

    ts = [threading.Thread(target=work, args=(t,)) for t in ("a", "b")]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
        assert not t.is_alive()
    (oa, ca), (ob, cb) = seen["a"], seen["b"]
    assert ca.parent == oa.id and cb.parent == ob.id
    assert ca.root == oa.id and cb.root == ob.id
    assert oa.thread != ob.thread and ca.thread == oa.thread


def test_threads_lose_no_span_or_count():
    """Many threads, switching often, on one small ring: every span is
    either held or counted as dropped, and no count is lost."""
    rec = obs.Recorder(size=64)
    n_threads, n_spans = 16, 200

    def work():
        for _ in range(n_spans):
            with rec.span("stress"):
                rec.count("stress.n")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    held = rec.spans()
    assert len(held) == 64
    assert len(held) + rec.dropped() == n_threads * n_spans
    assert rec.totals()["stress.n"] == n_threads * n_spans
    assert sum(s.counts["stress.n"] for s in held) == 64
    assert all(a.t1 <= b.t1 for a, b in zip(held, held[1:]))


def test_ring_counts_what_it_drops():
    rec = obs.Recorder(size=4)
    for i in range(6):
        with rec.span(f"s{i}"):
            pass
    got = rec.spans()
    assert [s.name for s in got] == ["s2", "s3", "s4", "s5"]
    assert rec.dropped() == 2
    # nothing dropped after the newest dropped span ended
    assert rec.dropped(got[0].t0) == 0
    assert rec.dropped(got[0].t0 - 10**12) == 2


def test_jax_stages_land_under_the_innermost_open_span():
    f = _fresh_jit()
    x = jnp.ones(7)
    with obs.span("test.outer"):
        with obs.span("test.inner") as inner:
            f(x).block_until_ready()
    kids = [s for s in obs.descendants(inner)]
    assert {s.name for s in kids} >= {"jax.trace", "jax.lower", "jax.compile"}
    assert all(s.parent == inner.id and s.root == inner.root for s in kids)
    assert all(inner.t0 <= s.t0 <= s.t1 <= inner.t1 for s in kids)
    assert inner.counts["jax.compiles"] == sum(
        1 for s in kids if s.name == "jax.compile")
    # with no span open, JAX's reports are not recorded
    t = _since()
    before = obs.totals().get("jax.compiles", 0)
    _fresh_jit()(x).block_until_ready()
    assert not [s for s in obs.spans(t) if s.name.startswith("jax.")]
    assert obs.totals().get("jax.compiles", 0) == before


def _sp(name, t0, t1):
    return obs.Span(name, 0, 0, 0, 0, t0, t1)


def test_partition_adds_up_to_its_windows():
    a, b, c = _sp("a", 10, 60), _sp("b", 20, 30), _sp("c", 50, 80)
    far = _sp("d", 200, 300)
    got = obs.partition([a, b, c, far], [(0, 100)])
    # b nests in a; c starts later than a, so it takes [50, 60]
    assert got == pytest.approx({"a": 30e-9, "b": 10e-9, "c": 30e-9,
                                 "rest": 30e-9})
    wins = [(0, 100), _sp("w", 250, 400)]
    got = obs.partition([a, b, c, far], wins)
    assert sum(got.values()) == pytest.approx(250e-9, rel=1e-12)
    assert got["d"] == pytest.approx(50e-9)


def test_disabled_records_nothing():
    t = _since()
    before = obs.totals()
    obs.enable(False)
    try:
        with obs.span("test.off") as sp:
            obs.count("test.off.n")
            _fresh_jit()(jnp.ones(3)).block_until_ready()
    finally:
        obs.enable(True)
    assert sp.ns > 0                      # still timed, for its callers
    assert obs.spans(t) == []
    assert obs.totals() == before


def test_stage_timers_are_a_view_of_spans():
    t = _since()
    timers = obs.StageTimers("test.svc", "match", "profile")
    for _ in range(2):
        with timers.time("match"):
            time.sleep(0.002)
    got = [s for s in obs.spans(t) if s.name == "test.svc.match"]
    assert len(got) == 2
    snap = timers.snapshot_ms()
    assert snap["profile_ms"] == 0.0
    assert snap["match_ms"] == round(sum(s.ns for s in got) * 1e-6, 3)


def test_clock_offset_places_spans_on_their_twins(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        for i in range(5):
            with obs.span("test.clock", i=i):
                time.sleep(0.003)
            with obs.span("test.clock.short"):
                pass
    twins = obs.twins(str(tmp_path))
    assert len(twins) == 10
    off = obs.clock_offset_ns(str(tmp_path))
    for sp, start, end in twins:
        assert abs(sp.t0 + off - start) < 1_000_000
        assert abs(sp.t1 + off - end) < 1_000_000
