"""Paper Figs. 5-6 analog: Siesta QP vs MINIME greedy.

Fig. 5: one aggregate computation event per program (sum of all compute).
Fig. 6: every inter-collective segment fitted separately, then summed —
the regime where greedy drift compounds.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import PROGRAMS, ensure_devices

ensure_devices()


def _collect(fn, args, axes):
    from repro.core.tracer import trace_fn
    tr = trace_fn(fn, *args, axis_sizes=axes)
    return [e.vector for e in tr.compute_events()]


def run() -> list[dict]:
    from repro.core.baselines import minime_fit
    from repro.core.proxy_search import fit_combination, rel_error
    rows = []
    for name, builder in PROGRAMS.items():
        fn, args, axes = builder(8)
        vecs = _collect(fn, args, axes)

        # Fig. 5: single aggregate event
        agg = np.sum(vecs, axis=0)
        q = fit_combination(agg)
        g = minime_fit(agg)
        rows.append({
            "program": name, "mode": "single_block",
            "siesta_err": round(float(np.mean(
                q.per_metric_rel_err[agg > 0])), 4),
            "minime_err": round(float(np.mean(
                g.per_metric_rel_err[agg > 0])), 4),
        })

        # Fig. 6: per-event fits, total proxy vs total target
        tq = np.zeros(6)
        tg = np.zeros(6)
        for v in vecs:
            tq += fit_combination(v).predicted
            tg += minime_fit(v).predicted
        rows.append({
            "program": name, "mode": "per_event_sum",
            "siesta_err": round(float(np.mean(
                rel_error(agg, tq)[agg > 0])), 4),
            "minime_err": round(float(np.mean(
                rel_error(agg, tg)[agg > 0])), 4),
        })
    return rows
