"""In-program spans and counters: one recorder for the whole program.

``span(name, **attrs)`` is a context manager that records the span's name,
start and end (``time.perf_counter_ns()``, the clock the benchmark's own
host spans use), the id of the span that was open when it started
(``parent``), the id of the top span of its program (``root``) and its
thread.  Spans of one program share ``root``: pass ``root=`` to tie a span
opened later, such as a proxy's first run, to the program that made it.
``count(name, n)`` adds to the innermost open span and to a process total.

Spans are kept in memory in a bounded ring, so a long-lived service cannot
grow without limit; ``dropped()`` counts what fell off its end.  They are
coarse, a few dozen for a program and none for each event or sweep, and
always on: ``enable(False)`` exists to measure what they cost.

Each span also enters a ``jax.profiler.TraceAnnotation`` of the same name,
carrying its id as ``span_id``, so a profiler capture shows it among the
device's ops.  ``clock_offset_ns(capture)`` measures, from such a capture,
the offset that puts the spans held in memory on the capture's clock.

JAX reports how long it took to trace a function to a jaxpr, to lower the
jaxpr to a module and to compile that module.  The recorder turns each
report into a child span of the innermost open span on that thread:
``jax.trace``, ``jax.lower`` and ``jax.compile``, placed at [now -
duration, now]; a compile also counts ``jax.compiles``.  With no span open
the reports are not recorded.

The module imports no JAX itself, so the NumPy-only ingest workers of
:mod:`repro.core.corpus_store` stay free of accelerator code: the first
span opened once JAX is loaded registers the listener and the annotation.
Before JAX is loaded no profiler can be running and nothing compiles.

``partition(spans, windows)`` reduces spans to seconds by stage inside
time windows.  Where stage spans nest, the innermost takes the time, so
the stages and ``rest`` add up exactly to the windows.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import glob
import itertools
import os
import sys
import threading
import time
from typing import Iterable

#: spans the ring holds before the oldest fall off
RING_SIZE = 1 << 16


@dataclasses.dataclass(eq=False, slots=True)
class Span:
    name: str
    id: int
    parent: int          # 0: opened with no span open on its thread
    root: int
    thread: int
    t0: int              # perf_counter_ns
    t1: int = 0
    attrs: dict = dataclasses.field(default_factory=dict)
    counts: dict = dataclasses.field(default_factory=dict)

    @property
    def ns(self) -> int:
        return self.t1 - self.t0

    @property
    def ms(self) -> float:
        return (self.t1 - self.t0) * 1e-6


class Recorder:
    """Spans and counters of one process (``RECORDER``), or of a test."""

    def __init__(self, size: int = RING_SIZE):
        self._ring: collections.deque[Span] = collections.deque(maxlen=size)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._ids = itertools.count(1)
        self._totals: dict[str, int] = {}
        self._dropped = 0
        self._dropped_end = 0    # newest end among the dropped spans
        self.enabled = True

    def _stack(self) -> list[Span]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _new(self, name: str, root: int | None, attrs: dict) -> Span:
        stack = self._stack()
        parent = stack[-1] if stack else None
        sid = next(self._ids)
        if root is None:
            root = parent.root if parent is not None else sid
        return Span(name, sid, parent.id if parent is not None else 0, root,
                    threading.get_ident(), 0, attrs=attrs)

    def _record(self, sp: Span, t1: int | None = None) -> None:
        """Close ``sp`` and append it.  Its end is read under the lock, so
        the ring is in order of end time across threads."""
        with self._lock:
            sp.t1 = time.perf_counter_ns() if t1 is None else t1
            if len(self._ring) == self._ring.maxlen:
                self._dropped += 1
                self._dropped_end = max(self._dropped_end, self._ring[0].t1)
            self._ring.append(sp)

    @contextlib.contextmanager
    def span(self, name: str, *, root: int | None = None, **attrs):
        """Record the enclosed block as a span; yields the :class:`Span`,
        whose ``t1`` is set on exit.  Disabled, the span is timed but not
        recorded and opens no annotation."""
        sp = self._new(name, root, attrs)
        if not self.enabled:
            sp.t0 = time.perf_counter_ns()
            try:
                yield sp
            finally:
                sp.t1 = time.perf_counter_ns()
            return
        stack = self._stack()
        hooks = _jax_hooks()
        with (hooks[0](name, span_id=sp.id) if hooks
              else contextlib.nullcontext()):
            sp.t0 = time.perf_counter_ns()
            stack.append(sp)
            try:
                yield sp
            finally:
                stack.pop()
                self._record(sp)

    def count(self, name: str, n: int = 1) -> None:
        """Add ``n`` to counter ``name`` of the innermost open span and to
        the process total."""
        if not self.enabled:
            return
        stack = self._stack()
        if stack:
            c = stack[-1].counts
            c[name] = c.get(name, 0) + n
        with self._lock:
            self._totals[name] = self._totals.get(name, 0) + n

    def on_jax_event(self, event: str, duration: float, **kw) -> None:
        """JAX's duration listener: a compile stage becomes a child span of
        the innermost open span."""
        name = _JAX[1].get(event) if _JAX else None
        if name is None or not self.enabled:
            return
        stack = self._stack()
        if not stack:
            return
        parent = stack[-1]
        sp = self._new(name, None, {"fun": kw.get("fun_name", "")})
        now = time.perf_counter_ns()
        sp.t0 = max(now - int(duration * 1e9), parent.t0)
        self._record(sp, now)
        if name == "jax.compile":
            self.count("jax.compiles")

    # -- reading ---------------------------------------------------------------

    def spans(self, since_ns: int = 0) -> list[Span]:
        """Recorded spans that ended at or after ``since_ns``, oldest first."""
        with self._lock:
            out = []
            for sp in reversed(self._ring):
                if sp.t1 < since_ns:
                    break
                out.append(sp)
        out.reverse()
        return out

    def descendants(self, top: Span) -> list[Span]:
        """The recorded spans under ``top`` (children, theirs, ...)."""
        cand = [s for s in self.spans(top.t0) if s.t0 >= top.t0]
        parent = {s.id: s.parent for s in cand}
        out = []
        for s in cand:
            p = s.parent
            while p in parent and p != top.id:
                p = parent[p]
            if p == top.id and s is not top:
                out.append(s)
        return out

    def dropped(self, since_ns: int = 0) -> int:
        """Spans that fell off the ring; with ``since_ns``, 0 unless one of
        them ended at or after it."""
        with self._lock:
            if since_ns and self._dropped_end < since_ns:
                return 0
            return self._dropped

    def totals(self) -> dict[str, int]:
        with self._lock:
            return dict(self._totals)


def partition(spans: Iterable[Span], windows) -> dict[str, float]:
    """Seconds by span name inside ``windows`` (spans, or ``(t0, t1)`` in
    perf_counter ns), and ``"rest"`` for the time no span covers.  Where
    spans overlap, the innermost (latest start, then earliest end) takes the
    time, so the values add up to the windows' total."""
    spans = list(spans)
    acc: dict[str, int] = {}
    for w in windows:
        lo, hi = (w.t0, w.t1) if isinstance(w, Span) else (int(w[0]), int(w[1]))
        inside = [(max(s.t0, lo), min(s.t1, hi), s) for s in spans
                  if s.t1 > lo and s.t0 < hi]
        edges = sorted({lo, hi, *(a for a, _, _ in inside),
                        *(b for _, b, _ in inside)})
        for a, b in zip(edges, edges[1:]):
            cover = [s for s0, s1, s in inside if s0 <= a and b <= s1]
            name = (max(cover, key=lambda s: (s.t0, -s.t1)).name
                    if cover else "rest")
            acc[name] = acc.get(name, 0) + (b - a)
    return {k: v * 1e-9 for k, v in acc.items()}


def stage_ms(top: Span, under: list[Span]) -> dict[str, float]:
    """Milliseconds of ``top`` by the innermost of the spans ``under`` it."""
    parts = partition(under, [top])
    return {k: round(v * 1e3, 3) for k, v in sorted(parts.items())}


def counts_of(spans: Iterable[Span]) -> dict[str, int]:
    """Each counter summed over ``spans``."""
    out: dict[str, int] = {}
    for s in spans:
        for k, v in s.counts.items():
            out[k] = out.get(k, 0) + v
    return out


def twins(capture: str) -> list[tuple[Span, int, int]]:
    """``(span, start_ns, end_ns)`` for each recorded span whose
    ``TraceAnnotation`` twin is in ``capture`` (an ``.xplane.pb`` or a
    directory holding one), the twin's times on the capture's clock."""
    from jax.profiler import ProfileData
    if os.path.isdir(capture):
        paths = glob.glob(os.path.join(capture, "**", "*.xplane.pb"),
                          recursive=True)
        capture = max(paths, key=os.path.getmtime)
    found: dict[int, tuple[str, int, int]] = {}
    for plane in ProfileData.from_file(capture).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                sid = dict(ev.stats).get("span_id")
                if sid is not None:
                    s = int(ev.start_ns)
                    found[int(sid)] = (ev.name, s, s + int(ev.duration_ns))
    out = []
    for sp in RECORDER.spans():
        hit = found.get(sp.id)
        if hit is not None and hit[0] == sp.name:
            out.append((sp, hit[1], hit[2]))
    return out


def clock_offset_ns(capture: str) -> int:
    """The offset from ``perf_counter_ns`` to the clock of ``capture``,
    measured there: the median, over the spans whose twins it holds, of the
    twin's start less the span's.  Raises ``LookupError`` if it holds none."""
    d = sorted(s - sp.t0 for sp, s, _ in twins(capture))
    if not d:
        raise LookupError(f"no recorded span has a twin in {capture}")
    return d[len(d) // 2]


RECORDER = Recorder()
span = RECORDER.span
count = RECORDER.count
spans = RECORDER.spans
descendants = RECORDER.descendants
dropped = RECORDER.dropped
totals = RECORDER.totals


def enable(on: bool = True) -> None:
    """Switch recording on or off (off only to measure its cost)."""
    RECORDER.enabled = bool(on)


#: (TraceAnnotation, {JAX stage event: span name}) once JAX is loaded
_JAX = None


def _jax_hooks():
    """The annotation class and JAX's stage events, or None while JAX is
    not loaded; the first call after it is registers the listener."""
    global _JAX
    if _JAX is None and "jax" in sys.modules:
        import jax
        from jax._src import dispatch
        _JAX = (jax.profiler.TraceAnnotation, {
            dispatch.JAXPR_TRACE_EVENT: "jax.trace",
            dispatch.JAXPR_TO_MLIR_MODULE_EVENT: "jax.lower",
            dispatch.BACKEND_COMPILE_EVENT: "jax.compile",
        })
        jax.monitoring.register_event_duration_secs_listener(
            RECORDER.on_jax_event)
    return _JAX


class StageTimers:
    """Milliseconds by stage of one owner (``ServeEngine``,
    ``ProxyService``), a view of its spans: ``time(stage)`` opens the span
    ``<prefix>.<stage>`` and adds its duration; :meth:`snapshot_ms` renders
    ``{stage}_ms`` keys for a stats dict or a benchmark row."""

    def __init__(self, prefix: str, *stages: str):
        self._prefix = prefix
        self._acc = {s: 0 for s in stages}

    @contextlib.contextmanager
    def time(self, stage: str):
        try:
            with span(f"{self._prefix}.{stage}") as sp:
                yield sp
        finally:
            self._acc[stage] += sp.ns

    def snapshot_ms(self) -> dict[str, float]:
        return {f"{s}_ms": round(v * 1e-6, 3) for s, v in self._acc.items()}
