"""Record a small trace of one cell on the chip for the reduction tests.

    python3 bench/record_trace.py --workload <name> --seed <n> --out <path>

Runs the cell once with ``--trace 1`` at a short window, keeps the first
``--keep-ms`` milliseconds of each traced host span and the device ops
inside them, and writes that reduced trace as JSON.  Not run by the
benchmark.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def cut(tr: dict, keep_ns: int) -> dict:
    """The first ``keep_ns`` of every host span, and the device ops (clipped)
    that fall inside what is kept."""
    from bench import trace as T
    host = [[h[0], h[1], min(h[2], h[1] + keep_ns), h[3]] for h in tr["host"]]
    devices = {}
    for dev, ops in tr["devices"].items():
        kept = []
        for name, s, e in ops:
            for _, lo, hi, _ in host:
                for cs, ce in T.clip([(s, e)], lo, hi):
                    kept.append([name, cs, ce])
        devices[dev] = kept
    return {"host": host, "devices": devices}


def main(argv=None) -> int:
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--keep-ms", type=float, default=3.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.ROOT / "src"))
    spec = harness.load_spec()
    cell = harness.find_cell(spec, args.workload)
    try:
        devices = harness.require_tpu(cell["chips"])
    except harness.NoChip as e:
        print(f"record_trace: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    sink: dict = {}
    out = harness.run_cell(spec, args.workload, args.seed, args.seconds, True,
                           time.perf_counter(), devices=devices,
                           log=lambda m: print(m, file=sys.stderr), sink=sink)
    small = cut(sink["record"].trace, int(args.keep_ms * 1e6))
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(small))
    print(json.dumps({"out": args.out, "correct": out["correct"],
                      "ops": {k: len(v) for k, v in small["devices"].items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
