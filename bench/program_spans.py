"""The program's own spans (``repro.obs``) inside the timed window's
``proxy.compile`` spans: a proxy's first ``run_all()`` split into JAX's
trace to a jaxpr (``jax.trace``), lowering (``jax.lower``), XLA compile
(``jax.compile``) and the rest (state init, dispatch, first execution,
wait), and the executables it compiled (counter ``jax.compiles``).

The split covers each window whole: where the stage spans nest, the
innermost takes the time, so the four parts add up to ``synth.compile_s``.
A program without the recorder, a window without its ``proxy.run_all``
span, or a ring that dropped spans inside the window reads as None.
"""
from __future__ import annotations

#: JAX's stage spans and the part of the split each is
STAGES = {"jax.trace": "trace", "jax.lower": "lower", "jax.compile": "xla"}


def first_run_split(rec) -> dict | None:
    """Per program: seconds by part ("trace", "lower", "xla", "rest") and
    "executables" compiled; None where the program recorded nothing."""
    try:
        from repro import obs
    except ImportError:
        return None
    wins = [(round(s.t0 * 1e9), round(s.t1 * 1e9))
            for s in rec.spans if s.name == "proxy.compile"]
    n = rec.total("proxy.compile")[1]
    if not wins or not n:
        return None
    lo = min(a for a, _ in wins)
    if obs.dropped(lo):
        return None
    runs = [s for s in obs.spans(lo) if s.name == "proxy.run_all"
            and any(a <= s.t0 and s.t1 <= b for a, b in wins)]
    if len(runs) != len(wins):
        return None
    stage_spans, compiles = [], 0
    for run in runs:
        under = obs.descendants(run)
        stage_spans += [s for s in under if s.name in STAGES]
        compiles += obs.counts_of([run, *under]).get("jax.compiles", 0)
    parts = obs.partition(stage_spans, wins)
    out = {part: parts.get(name, 0.0) / n for name, part in STAGES.items()}
    out["rest"] = parts.get("rest", 0.0) / n
    out["executables"] = compiles / n
    return out


def read_part(rec, part: str) -> float | None:
    split = first_run_split(rec)
    return None if split is None else split[part]
