"""Profiler trace capture and the reductions every per-layer reader shares.

A traced window is reduced to plain interval lists, so a reader never
touches the profiler's own format:

    {"host":    [[span name, start_ns, end_ns, count], ...],
     "devices": {device plane name: [[op name, start_ns, end_ns], ...]}}

``host`` holds the benchmark's own ``TraceAnnotation`` spans (one per
timed block, ``count`` = steps, sweeps or programs in it); ``devices`` the
ops of each device's "XLA Ops" line.
"""
from __future__ import annotations

import glob
import os
from typing import Iterable

#: the benchmark's own host spans (TraceAnnotation names)
SPAN_NAMES = ("original", "proxy.sweep", "synthesize", "proxy.compile")


def op_name(text: str) -> str:
    """The HLO op's own name ("%fusion.82") out of the line the profiler
    gives, which may carry the whole HLO instruction."""
    return text.split(" = ", 1)[0].strip()


def parse_xspace(path: str, counts: dict[tuple[str, int], int]) -> dict:
    """Reduce one ``.xplane.pb`` to the interval lists above.  ``counts``
    maps (span name, start-order index) to the work count of that span."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host, devices = [], {}
    seen: dict[str, int] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    ops.append([op_name(ev.name), int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns)])
            if ops:
                devices[plane.name] = sorted(ops, key=lambda o: o[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPAN_NAMES:
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns)])
    host.sort(key=lambda h: h[1])
    for h in host:
        i = seen.get(h[0], 0)
        seen[h[0]] = i + 1
        h.append(int(counts.get((h[0], i), 0)))
    return {"host": host, "devices": devices}


def newest_xspace(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


# -- interval arithmetic ------------------------------------------------------


def union(intervals: Iterable[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted((int(s), int(e)) for s, e in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def spans(tr: dict, name: str) -> list[list]:
    return [h for h in tr["host"] if h[0] == name]


def busy_ns(ops, windows) -> int:
    """Nanoseconds inside ``windows`` in which any op of ``ops`` ran."""
    merged = union((o[1], o[2]) for o in ops)
    return sum(e - s for w in windows for s, e in clip(merged, w[1], w[2]))


def idle_share(tr: dict, name: str) -> float | None:
    """1 - busy/window over the spans called ``name``, mean over devices."""
    win = spans(tr, name)
    total = sum(w[2] - w[1] for w in win)
    if not win or total <= 0 or not tr["devices"]:
        return None
    shares = [1.0 - busy_ns(ops, win) / total for ops in tr["devices"].values()]
    return sum(shares) / len(shares)


def device_busy_s(tr: dict) -> tuple[float, float]:
    """(busy seconds averaged over devices, traced window seconds), the
    window being the first to the last benchmark span."""
    if not tr["host"]:
        return 0.0, 0.0
    lo = min(h[1] for h in tr["host"])
    hi = max(h[2] for h in tr["host"])
    win = [["window", lo, hi, 0]]
    if not tr["devices"]:
        return 0.0, (hi - lo) * 1e-9
    busy = [busy_ns(ops, win) for ops in tr["devices"].values()]
    return sum(busy) / len(busy) * 1e-9, (hi - lo) * 1e-9


def top_ops(tr: dict, k: int = 10) -> list[list]:
    """[[op name, device seconds], ...]: the ops that took most time, summed
    within the benchmark's spans and averaged over devices."""
    if not tr["host"] or not tr["devices"]:
        return []
    lo = min(h[1] for h in tr["host"])
    hi = max(h[2] for h in tr["host"])
    tot: dict[str, float] = {}
    for ops in tr["devices"].values():
        for name, s, e in ops:
            if e > lo and s < hi:
                tot[name] = tot.get(name, 0.0) + (min(e, hi) - max(s, lo))
    n = len(tr["devices"])
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / n * 1e-9] for name, ns in best]


def idle_gaps(tr: dict, k: int = 10) -> list[list]:
    """[[what the host was doing, seconds], ...]: the longest gaps between
    device ops on the first device, named by the span that holds the gap's
    middle ("between spans" when none does)."""
    if not tr["host"] or not tr["devices"]:
        return []
    lo = min(h[1] for h in tr["host"])
    hi = max(h[2] for h in tr["host"])
    ops = tr["devices"][sorted(tr["devices"])[0]]
    busy = clip(union((o[1], o[2]) for o in ops), lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = (s + e) // 2
        held = [h[0] for h in tr["host"] if h[1] <= mid < h[2]]
        out.append([held[0] if held else "between spans", (e - s) * 1e-9])
    return out
