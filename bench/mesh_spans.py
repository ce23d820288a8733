"""The program's own spans (``repro.obs``) of the mesh replay's first run
in set-up: the ``proxy.mesh_first`` spans, each placed group's first
dispatch with its trace, lowering and compile, and the counter
``replay.collectives`` under them, which counts each collective lowered
into an executable as often as one dispatch runs it.  The window's host
span ``setup.mesh_first`` bounds them.  A program without them, or a ring
that dropped spans inside that window, reads as None.
"""
from __future__ import annotations


def first_runs(rec) -> list | None:
    """The ``proxy.mesh_first`` spans inside the set-up's mesh run."""
    try:
        from repro import obs
    except ImportError:
        return None
    win = [s for s in rec.spans if s.name == "setup.mesh_first"]
    if not win:
        return None
    lo, hi = round(win[0].t0 * 1e9), round(win[0].t1 * 1e9)
    if obs.dropped(lo):
        return None
    runs = [s for s in obs.spans(lo) if s.name == "proxy.mesh_first"
            and lo <= s.t0 and s.t1 <= hi]
    return runs or None


def first_run_s(rec) -> float | None:
    runs = first_runs(rec)
    return None if runs is None else sum(s.ns for s in runs) * 1e-9


def collectives(rec) -> int | None:
    """Collectives one sweep runs: the counter under every placed group's
    first dispatch (a sweep dispatches each group once)."""
    runs = first_runs(rec)
    if runs is None:
        return None
    from repro import obs
    under = [x for s in runs for x in (s, *obs.descendants(s))]
    return obs.counts_of(under).get("replay.collectives")
