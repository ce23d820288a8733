"""Readings that set the NPB MG mesh cell's limits, as ``bench/control.py``
does for the other cells: the program's compared numbers over many seeds,
and the same numbers with the control in the program's place.

    python3 bench/control_mesh.py --workload <name> --seeds 1-12 \\
        --control-seeds 1-3

One process on the cell's chips builds and compiles the program once.
Each seed makes its right-hand side, runs one solve and reads the
configuration's ``check()`` (u and the residual norm against the float32
reference); the control seeds also read ``control()``, a bfloat16
reference in the program's place.  ``proxy_fit_gap``, which depends on
shapes alone, is read once for the default fit and once at half the
fitted work (``count_scale=0.5``).  One JSON line per reading.  Not run by
the benchmark's own runs.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    from bench import harness
    from bench.control import seed_list
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", default="1-3")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.ROOT / "src"))
    spec = harness.load_spec()
    cell = harness.find_cell(spec, args.workload)
    try:
        devices = harness.require_tpu(cell["chips"])
    except harness.NoChip as e:
        print(f"control_mesh: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    readings(spec, cell, seed_list(args.seeds),
             set(seed_list(args.control_seeds)), devices)
    return 0


def readings(spec, cell, seeds, control_seeds, devices, sizes=None):
    from bench import harness
    from repro.core.synthesize import synthesize
    cfg_sizes, cfg = harness.load_config(spec, cell["config"])
    sizes = sizes or cfg_sizes
    traffic = harness.load_traffic(cell["traffic"])
    window = harness.load_window(traffic["window"])
    prog = harness.load_program(traffic["program"]["kind"])
    orig = prog.build(cfg, sizes, traffic["program"], seeds[0], devices)
    fn, args, axes = orig.trace_spec()
    row = {"proxy_fit_gap": {}}
    for label, kw in (("default", {}), ("half_work", {"count_scale": 0.5})):
        res = synthesize(fn, *args, axis_sizes=axes, **kw)
        row["proxy_fit_gap"][label] = window.proxy_byte_gap(res)
    print(json.dumps(row), flush=True)
    for seed in seeds:
        orig.reset(seed)
        t0 = time.perf_counter()
        orig.run(1)
        row = {"seed": seed, "solve_s": time.perf_counter() - t0}
        t0 = time.perf_counter()
        row["program"] = orig.check()
        row["check_s"] = time.perf_counter() - t0
        if seed in control_seeds:
            row["control"] = orig.control()
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    sys.exit(main())
