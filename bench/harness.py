"""One run of one cell: set-up, the timed window, the traced window, and
the check that decides ``correct``.

The harness is driven by data.  ``BENCHMARK.json`` names each cell's
configuration and traffic mix, and every piece is a file found by name:

- ``configs/<config>.json``: the configuration's sizes, and beside it
  ``configs/<config>.py``: its weights from the seed and plain reference;
- ``traffic/<mix>.json``: the mix's parameters; its ``"window"`` key names
  the window driver ``windows/<window>.py``, and each program in it names
  its kind, the module ``programs/<kind>.py`` that builds, traces and
  counts that program;
- ``metrics/<metric>.py``: one per-layer metric's reader.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float
    count: int


@dataclasses.dataclass
class Record:
    """Everything a run measured: host spans of the timed window, the
    counts the window and the configuration report, and the reduced trace
    of the traced window (``--trace 1``)."""
    cell: dict
    chips: int
    device_kind: str
    spans: list = dataclasses.field(default_factory=list)
    counters: dict = dataclasses.field(default_factory=dict)
    e2e: dict = dataclasses.field(default_factory=dict)
    trace: dict | None = None

    def total(self, name: str) -> tuple[float, int]:
        """(seconds, work count) summed over the spans called ``name``."""
        sel = [s for s in self.spans if s.name == name]
        return sum(s.t1 - s.t0 for s in sel), sum(s.count for s in sel)

    def per_unit(self, name: str) -> float | None:
        secs, n = self.total(name)
        return secs / n if n else None

    def peak_flops(self) -> float:
        from bench.flops import peaks
        return peaks(self.device_kind)["bf16_flops"]


# -- finding things by name ---------------------------------------------------


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(have {[w['name'] for w in spec['workloads']]})")


def load_file_module(path: Path, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _safe(name: str) -> str:
    return "".join(c if c.isalnum() else "_" for c in name)


def load_config(spec: dict, name: str, root: Path = ROOT):
    """(sizes dict, module) of configuration ``name``: the sizes file that
    BENCHMARK.json names, and the module beside it (same stem, ``.py``)."""
    entry = next(c for c in spec["configs"] if c["name"] == name)
    path = root / entry["file"]
    sizes = json.loads(path.read_text())
    mod = load_file_module(path.with_suffix(".py"), f"bench_config_{_safe(name)}")
    return sizes, mod


def load_traffic(name: str, bench: Path = BENCH) -> dict:
    return json.loads((bench / "traffic" / f"{name}.json").read_text())


def load_window(name: str, bench: Path = BENCH):
    """The window driver ``windows/<name>.py``: its ``run(ctx, log)``."""
    return load_file_module(bench / "windows" / f"{name}.py",
                            f"bench_window_{_safe(name)}")


def load_program(kind: str, bench: Path = BENCH):
    """The program kind ``programs/<kind>.py``: its ``trace_spec``,
    ``count_flops`` and, for replay, ``build``."""
    return load_file_module(bench / "programs" / f"{kind}.py",
                            f"bench_program_{_safe(kind)}")


def metric_reader(name: str, bench: Path = BENCH):
    return load_file_module(bench / "metrics" / f"{name}.py",
                            f"bench_metric_{_safe(name)}")


def metrics_for(metrics: list[dict], cell: dict, e2e_names: set[str]) -> list[dict]:
    """The entries of ``metrics`` (end-to-end or per-layer) this cell
    reports: those that list it, or that list no cells and move (or are)
    an end-to-end metric the cell reports."""
    out = []
    for m in metrics:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif m.get("moves", m["name"]) in e2e_names:
            out.append(m)
    return out


# -- device and compile cache -------------------------------------------------


def require_tpu(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU found: JAX platform is {devs[0].platform!r}; "
                     "this benchmark has no CPU fallback")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def use_compile_cache(root: Path = ROOT) -> Path:
    """JAX's persistent compilation cache at a fixed path in the checkout."""
    import jax
    path = root / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA backend compiles (including loads from the persistent
    cache) while active.  JAX's monitoring listeners are process-wide, so
    one listener, installed once, feeds every active counter."""
    _installed = False
    _active: list["CompileCounter"] = []

    def __init__(self):
        self.compiles = 0

    @classmethod
    def _install(cls):
        if cls._installed:
            return
        import jax
        from jax._src import dispatch

        def on_duration(event, duration, **kw):
            if event == dispatch.BACKEND_COMPILE_EVENT:
                for c in cls._active:
                    c.compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        cls._installed = True

    def __enter__(self):
        self._install()
        self._active.append(self)
        return self

    def __exit__(self, *exc):
        self._active.remove(self)


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


# -- one run ------------------------------------------------------------------


@dataclasses.dataclass
class Context:
    spec: dict
    bench: Path
    cell: dict
    sizes: dict
    config: object          # the configuration's module
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    devices: list
    rec: Record
    checks: list = dataclasses.field(default_factory=list)
    attempted: int = 0
    _programs: dict = dataclasses.field(default_factory=dict)

    def program(self, prog: dict):
        """The module of ``prog``'s kind, loaded once."""
        kind = prog["kind"]
        if kind not in self._programs:
            self._programs[kind] = load_program(kind, self.bench)
        return self._programs[kind]


def timed_block(fn, budget_s: float) -> tuple[float, float, int]:
    """Call ``fn`` until ``budget_s`` has passed; (t0, t1, calls)."""
    t0 = time.perf_counter()
    n = 0
    while True:
        fn()
        n += 1
        t1 = time.perf_counter()
        if t1 - t0 >= budget_s:
            return t0, t1, n


def traced(blocks, log) -> dict:
    """Run ``blocks`` ([(span name, fn, seconds)]) under the profiler, each
    block in a ``TraceAnnotation`` of its name, and return the reduced
    trace (``bench/trace.py``)."""
    import shutil
    import tempfile

    import jax
    from bench import trace as T
    counts: dict[tuple[str, int], int] = {}
    seen: dict[str, int] = {}
    log_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        with jax.profiler.trace(log_dir):
            for name, fn, secs in blocks:
                with jax.profiler.TraceAnnotation(name):
                    _, _, n = timed_block(fn, secs)
                i = seen.get(name, 0)
                seen[name] = i + 1
                counts[(name, i)] = n
        tr = T.parse_xspace(T.newest_xspace(log_dir), counts)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    log(f"[trace] host spans={len(tr['host'])} devices="
        f"{ {k: len(v) for k, v in tr['devices'].items()} }")
    return tr


def run_cell(spec: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, t_start: float, *, sizes: dict | None = None,
             devices=None, log=print, sink: dict | None = None,
             root: Path = ROOT) -> dict:
    """Run one cell once and return the result object.  ``sizes`` and
    ``devices`` override the configuration's sizes and the chip check;
    the tests use them to drive a run at a small size on the CPU."""
    bench = root / "bench"
    cell = find_cell(spec, cell_name)
    cfg_sizes, cfg_mod = load_config(spec, cell["config"], root)
    if devices is None:
        devices = require_tpu(cell["chips"])
    traffic = load_traffic(cell["traffic"], bench)
    kind = devices[0].device_kind
    ctx = Context(spec=spec, bench=bench, cell=cell, sizes=sizes or cfg_sizes,
                  config=cfg_mod, traffic=traffic, seed=seed,
                  seconds=seconds, trace=trace, t_start=t_start,
                  devices=devices, rec=Record(cell, cell["chips"], kind))
    load_window(traffic["window"], bench).run(ctx, log)
    rec = ctx.rec
    if sink is not None:
        sink["record"] = rec

    e2e = metrics_for(spec["end_to_end"], cell, set(rec.e2e))
    e2e_names = {m["name"] for m in e2e}
    metrics = {}
    if trace:
        for m in metrics_for(spec["per_layer"], cell, e2e_names):
            val = metric_reader(m["name"], bench).read(rec)
            if val is not None:
                metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    else:
        for m in e2e:
            metrics[m["name"]] = {"value": float(rec.e2e[m["name"]]),
                                  "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": int(rec.counters.get("memory_peak_bytes", 0))}
    out = {"correct": all(ok for _, _, _, ok in ctx.checks) and bool(ctx.checks),
           "attempted": int(ctx.attempted),
           "failed": sum(1 for c in ctx.checks if not c[3]),
           "metrics": metrics, "device": device}
    if trace and rec.trace is not None:
        from bench import trace as tr
        busy, win = tr.device_busy_s(rec.trace)
        device["busy_s"] = busy
        device["window_s"] = win
        out["breakdown"] = {"device_ops": tr.top_ops(rec.trace),
                            "idle_gaps": tr.idle_gaps(rec.trace)}
    out["checks"] = {name: {"value": float(v), "limit": float(lim)}
                     for name, v, lim, _ in ctx.checks}
    return out


def check(ctx: Context, name: str, value: float, limit: float) -> None:
    """Record one compared number; it passes when ``value <= limit``."""
    ctx.checks.append((name, float(value), float(limit),
                       bool(value == value and value <= limit)))


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    spec = load_spec()
    cell = find_cell(spec, args.workload)
    try:
        devices = require_tpu(cell["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    use_compile_cache()

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    out = run_cell(spec, args.workload, args.seed, args.seconds,
                   bool(args.trace), t_start, devices=devices, log=log)
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0
