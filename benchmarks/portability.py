"""Paper Figs. 9-11 analog: robustness to platform / implementation change.

The paper ports proxies between clusters A/B/C and MPI implementations; our
analog has two halves:

* **Platform scaling** (`platform_rows`): scale the platform's compute rate
  (A → B: 2x slower chip) and compare predicted times — Siesta's block
  mixes re-execute and track the change, the ScalaBench-style sleep proxy
  cannot.
* **Cross-chip prediction** (`cross_chip_rows`): feed synthesized zoo
  proxies to :func:`repro.core.portability.predict_profile` and tabulate
  the predicted roofline step-time bound (with NOISE_MODELS error bars)
  on chips the scenarios were never traced on, cross-checked against the
  walker-measured metric totals on the reference chip.

``--smoke`` is the CI gate (one reduced scenario, hard asserts); the full
run snapshots ``artifacts/BENCH_7.json`` via ``benchmarks.run`` or direct
invocation.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import PROGRAMS, ensure_devices

ensure_devices()

#: reduced-zoo shape for the cross-chip rows (matches the fidelity tier)
CROSS_CHIP_KWARGS = {"n_ranks": 4, "steps": 2}


def platform_rows() -> list[dict]:
    from repro.core.baselines import (
        original_time, scalabench_compress, siesta_predicted_time,
    )
    from repro.core.events import is_comm
    from repro.core.proxy_search import fit_combination
    from repro.core.tracer import per_rank_traces, trace_fn
    rows = []
    for name, builder in PROGRAMS.items():
        fn, args, axes = builder(8)
        tr = trace_fn(fn, *args, axis_sizes=axes)
        trace = per_rank_traces(tr)[0]
        comm = [e for e in trace if is_comm(e)]
        fits = [fit_combination(e.vector) for e in trace if not is_comm(e)]
        combos = [(f.x, f.unroll) for f in fits]
        sb = scalabench_compress(trace)
        for scale, plat in ((1.0, "A"), (0.5, "B_2x_slower"),
                            (2.0, "C_2x_faster")):
            t_ref = original_time(trace, scale)
            t_si = siesta_predicted_time(combos, comm, scale)
            t_sb = sb.predicted_time(scale)
            rows.append({
                # program key is unique per (target, platform) so the
                # write_artifacts merge keeps the full trajectory
                "program": f"{name}@{plat}", "platform": plat,
                "orig_s": round(t_ref, 6),
                "siesta_err": round(abs(t_si - t_ref) / t_ref, 4),
                "scalabench_err": round(abs(t_sb - t_ref) / t_ref, 4),
            })
    return rows


def _walker_err(proxy, pred) -> float:
    """Max relative gap between the prediction's reference-chip compute /
    memory terms and the same terms rebuilt from the walker-measured
    metric totals — an independent consistency bar (the walker traces the
    executable; the predictor only reads the terminal table)."""
    from repro.core.portability import CHIPS, REFERENCE_CHIP
    from repro.launch.hlo_cost import HloCost
    chip = CHIPS[REFERENCE_CHIP]
    errs = [0.0]
    # every rank appears in exactly one signature group, so the predictor's
    # sorted rank order is simply 0..N_RANKS-1
    for i, r in enumerate(range(proxy.module.N_RANKS)):
        hc = HloCost.from_metric_vector(proxy.rank_metrics(r))
        for want, got in ((hc.flops / chip.peak_flops, pred.t_compute[i]),
                          (hc.bytes / chip.hbm_bw, pred.t_memory[i])):
            if want > 0:
                errs.append(abs(got - want) / want)
    return float(max(errs))


def cross_chip_rows(scenarios=None, **kwargs) -> list[dict]:
    """Predicted profiles for the (reduced) zoo on every known chip."""
    from repro.core.portability import REFERENCE_CHIP, predict_all
    from repro.core.synthesize import synthesize_corpus
    kwargs = {**CROSS_CHIP_KWARGS, **kwargs}
    corp = synthesize_corpus(scenarios, **kwargs)
    rows = []
    for sname, res in corp.results.items():
        preds = predict_all(res.proxy.module)
        werr = _walker_err(res.proxy, preds[REFERENCE_CHIP])
        for cname, pred in preds.items():
            row = {"program": f"{sname}@{cname}", **pred.as_dict()}
            if cname == REFERENCE_CHIP:
                row["walker_err"] = round(werr, 6)
            rows.append(row)
    return rows


def run() -> list[dict]:
    return platform_rows() + cross_chip_rows()


def smoke() -> None:
    """CI gate: one reduced scenario, every chip, hard asserts."""
    rows = cross_chip_rows(["transformer-dp"])
    by_chip = {r["chip"]: r for r in rows}
    ref = by_chip["v5e"]
    # predictor ≡ walker on the reference chip (both read the same fitted
    # costs; the walker via the traced executable, the predictor via the
    # terminal table)
    assert ref["walker_err"] < 1e-6, ref
    assert ref["speedup_vs_ref"] == 1.0, ref
    # the noise band must contain the point prediction
    for r in rows:
        assert r["band_lo_s"] <= r["step_time_s"] <= r["band_hi_s"], r
        assert r["band_hi_s"] > r["band_lo_s"], (
            "degenerate noise band — NOISE_MODELS calibration missing?", r)
    # a strictly faster chip must predict a strictly faster step
    assert by_chip["v5p"]["step_time_s"] < ref["step_time_s"], by_chip
    assert by_chip["v5p"]["speedup_vs_ref"] > 1.0, by_chip
    for r in rows:
        print(", ".join(f"{k}={v}" for k, v in r.items()))
    print("portability smoke OK")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: one reduced scenario, hard asserts")
    args = ap.parse_args()
    if args.smoke:
        smoke()
    else:
        from benchmarks.synthesize_time import write_artifacts

        rows = run()
        for r in rows:
            print(", ".join(f"{k}={v}" for k, v in r.items()))
        write_artifacts(rows, snapshot="BENCH_7.json", suite="portability")
