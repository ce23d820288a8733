"""Paper Table 3 analog: per program × rank count — #events, trace size,
compressed grammar size, synthesis overhead, relative error.

``jaxpr_eqns``/``compile_ms`` report the grammar-compiled executable's
traced size and cold compile cost for the largest signature group — the
O(grammar)-vs-O(trace) axis the replay tier pins (see
benchmarks/codegen_parity.py for the hard guard)."""
from __future__ import annotations

import time

from benchmarks.common import (
    PROGRAMS, ensure_devices, exec_size_cols, pipeline_traces,
)

ensure_devices()


def run() -> list[dict]:
    from repro.core.synthesize import synthesize
    rows = []
    for name, builder in PROGRAMS.items():
        for n in (4, 8):
            fn, args, axes = builder(n)
            t0 = time.perf_counter()
            res = synthesize(fn, *args, axis_sizes=axes,
                             name=f"{name}_{n}")
            dt = time.perf_counter() - t0
            fid = res.fidelity()
            rows.append({
                "program": name, "ranks": n,
                "events": res.stats["n_events"],
                "trace_bytes": res.stats["trace_bytes"],
                "grammar_bytes": res.stats["grammar_bytes"],
                "ratio": round(res.stats["compression_ratio"], 1),
                "synth_sec": round(dt, 2),
                "rel_err": round(fid.mean, 4),
                "lossless_comm": fid.comm_lossless,
                **exec_size_cols(res.proxy),
            })
    # pipeline (host-level traces, heterogeneous ranks)
    for n in (4, 8):
        traces = pipeline_traces(n)
        t0 = time.perf_counter()
        res = synthesize(rank_traces=traces, axis_sizes={"stage": n},
                         name=f"pipeline_{n}")
        dt = time.perf_counter() - t0
        fid = res.fidelity()
        rows.append({
            "program": "pipeline", "ranks": n,
            "events": res.stats["n_events"],
            "trace_bytes": res.stats["trace_bytes"],
            "grammar_bytes": res.stats["grammar_bytes"],
            "ratio": round(res.stats["compression_ratio"], 1),
            "synth_sec": round(dt, 2),
            "rel_err": round(fid.mean, 4),
            "lossless_comm": fid.comm_lossless,
            **exec_size_cols(res.proxy),
        })
    return rows
