"""Readings that set a cell's limits: the program's compared numbers over
many seeds, and the same numbers with the control in the program's place.

    python3 bench/control.py --workload <name> --seeds 1-12 \\
        --control-seeds 1-3 --steps <n>

Replay cells: each seed builds the original anew, runs ``--steps`` steps
(as many as one run of the cell compares), then reads the configuration's
``check()`` and, on the control seeds, its ``control()``; the proxy's
``proxy_fit_gap``, which depends on shapes alone, is read once for the
default fit and once at half the fitted work (``count_scale=0.5``).
Synthesis cells: ``proxy_fit_gap`` of each program of the mix and their
mean, fitted by the default solver, by the program's float32 PGD solver,
at half the fitted work, and the mean with one program at a time at half
its work; each program's signed ln(proxy / original) MXU flops beside it.
One JSON line per reading, all in one process.  Not run by the
benchmark's own runs.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def seed_list(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        if "-" in part[1:]:
            lo, hi = part.split("-")
            out.extend(range(int(lo), int(hi) + 1))
        elif part:
            out.append(int(part))
    return out


def replay_readings(cfg, sizes, traffic, seeds, control_seeds, steps, devices):
    from bench.harness import load_program
    from bench.proxy import proxy_fit_gap
    from repro.core.synthesize import synthesize
    prog = load_program(traffic["program"]["kind"])
    for i, seed in enumerate(seeds):
        orig = prog.build(cfg, sizes, traffic["program"], seed, devices)
        orig.warm()
        if i == 0:
            fn, args, axes = orig.trace_spec()
            row = {"proxy_fit_gap": {}}
            for label, kw in (("default", {}), ("half_work", {"count_scale": 0.5})):
                res = synthesize(fn, *args, axis_sizes=axes, **kw)
                row["proxy_fit_gap"][label] = proxy_fit_gap(res.proxy,
                                                            orig.flops_per_step)
            print(json.dumps(row), flush=True)
        t0 = time.perf_counter()
        orig.run(steps)
        run_s = time.perf_counter() - t0
        row = {"seed": seed, "steps": steps, "run_s": run_s}
        if seed in control_seeds:
            row["control"] = orig.control()
        orig.release()
        t0 = time.perf_counter()
        row["program"] = orig.check()
        row["check_s"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        del orig


def synth_readings(cfg, sizes, traffic):
    import math

    import numpy as np
    from bench.harness import load_program
    from bench.proxy import proxy_fit_gap, rank_mxu_flops
    from repro.core.synthesize import synthesize
    gaps: dict[str, list] = {"default": [], "pgd": [], "half_work": []}
    for p in traffic["programs"]:
        mod = load_program(p["kind"])
        fn, args, axes = mod.trace_spec(cfg, sizes, p)
        want = mod.count_flops(cfg, sizes, p)
        row = {"program": p}
        for label, kw in (("default", {}), ("pgd", {"solver": "pgd"}),
                          ("half_work", {"count_scale": 0.5})):
            res = synthesize(fn, *args, axis_sizes=axes, **kw)
            row[label] = proxy_fit_gap(res.proxy, want)
            got = sum(rank_mxu_flops(res.proxy, r)
                      for r in range(res.merged.n_ranks))
            row[label + "_signed"] = math.log(max(got, 1.0) / want)
            gaps[label].append(row[label])
        print(json.dumps(row), flush=True)
    one_half = [float(np.mean(gaps["default"][:i] + [h]
                              + gaps["default"][i + 1:]))
                for i, h in enumerate(gaps["half_work"])]
    print(json.dumps({"mean_proxy_fit_gap": {k: float(np.mean(v))
                                             for k, v in gaps.items()},
                      "mean_with_one_program_at_half_work": one_half}),
          flush=True)


def main(argv=None) -> int:
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", default="1-3")
    ap.add_argument("--steps", type=int, default=100)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.ROOT / "src"))
    spec = harness.load_spec()
    cell = harness.find_cell(spec, args.workload)
    try:
        devices = harness.require_tpu(cell["chips"])
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    sizes, cfg = harness.load_config(spec, cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    if traffic["window"] == "synth":
        synth_readings(cfg, sizes, traffic)
    else:
        replay_readings(cfg, sizes, traffic, seed_list(args.seeds),
                        set(seed_list(args.control_seeds)), args.steps,
                        devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
