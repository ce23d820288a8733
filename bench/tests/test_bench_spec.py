"""BENCHMARK.json against the benchmark's contract, and the harness finding
every configuration, traffic mix and metric by name."""
from __future__ import annotations

import json
import re
import shutil

import pytest

from bench import harness

ROOT = harness.ROOT
SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def test_top_level_keys_and_size():
    assert set(SPEC) == TOP_KEYS
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= SPEC["run_seconds"] <= 51


def test_paths_and_command():
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    cmd = SPEC["command"]
    assert 1 <= len(cmd) <= 32
    for word in cmd:
        assert 1 <= len(word) <= 200 and "\n" not in word and "\t" not in word
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in SPEC["paths"])


def test_files_under_paths_are_named_from_name_characters():
    for p in SPEC["paths"]:
        for f in (ROOT / p).rglob("*"):
            if "__pycache__" in f.parts:
                continue
            assert re.match(r"^[A-Za-z0-9_./-]+$",
                            str(f.relative_to(ROOT))), f


def _entries():
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in SPEC[key]:
            yield key, e


@pytest.mark.parametrize("key,entry", list(_entries()),
                         ids=lambda x: x if isinstance(x, str) else x["name"])
def test_names_units_and_keys(key, entry):
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source",
                       "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"},
    }[key]
    assert set(entry) <= allowed
    assert NAME.match(entry["name"])
    texts = [entry[k] for k in ("why", "layer") if k in entry]
    if key == "configs":
        texts.append(entry["source"])
    for text in texts:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
    if key == "workloads":
        assert NAME.match(entry["config"]) and NAME.match(entry["traffic"])
        assert entry["chips"] in (1, 4)
    if key == "configs":
        assert len(entry["reduced"]) <= 16
        assert all(NAME.match(k) for k in entry["reduced"])


def test_names_unique():
    for key in ("configs", "workloads"):
        names = [e["name"] for e in SPEC[key]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_end_to_end_bounds():
    names = [m["name"] for m in SPEC["end_to_end"]]
    assert names == ["fidelity_err", "synth_s", "setup_s"]
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_four_chip_cells_at_most_half():
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_files_found_by_name(cell):
    w = harness.find_cell(SPEC, cell)
    sizes, mod = harness.load_config(SPEC, w["config"])
    assert sizes["name"] == w["config"]
    traffic = harness.load_traffic(w["traffic"])
    assert callable(harness.load_window(traffic["window"]).run)
    needs = ("trace_spec", "count_flops") + (
        ("build",) if traffic["window"] == "replay" else ())
    for prog in traffic.get("programs", [traffic.get("program")]):
        kind = harness.load_program(prog["kind"])
        for fn in needs:
            assert callable(getattr(kind, fn))
    e2e = harness.metrics_for(SPEC["end_to_end"], w, {"fidelity_err",
                                                      "synth_s", "setup_s"})
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    layer = harness.metrics_for(SPEC["per_layer"], w, names)
    assert layer, "every cell reports a per-layer metric"
    for m in layer:
        assert m["moves"] in names
        assert callable(harness.metric_reader(m["name"]).read)


def test_config_files_distinct_and_under_paths():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for f in files:
        assert any(f.startswith(p + "/") for p in SPEC["paths"])
        assert json.loads((ROOT / f).read_text())["reduced"] == \
            next(c["reduced"] for c in SPEC["configs"] if c["file"] == f)


def test_peaks_table_refuses_unknown_device():
    from bench.flops import peaks
    assert peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("cpu")


#: a program kind that exists only in the test's copy of the benchmark
TOY_PROGRAM = """
import jax
import jax.numpy as jnp


def count_flops(cfg, s, program):
    n = int(program["n"])
    return 2.0 * n * n * n


def trace_spec(cfg, s, program):
    n = int(program["n"])
    a = jax.ShapeDtypeStruct((n, n), jnp.float32)
    return (lambda x, w: jnp.tanh(x @ w)), (a, a), {}
"""

#: a window driver that exists only in the test's copy: it traces each
#: program and counts it, timing nothing but the trace
TOY_WINDOW = """
import time

from bench.harness import Span, check


def run(ctx, log):
    rec = ctx.rec
    rec.e2e["setup_s"] = time.perf_counter() - ctx.t_start
    for prog in ctx.traffic["programs"]:
        mod = ctx.program(prog)
        t0 = time.perf_counter()
        mod.trace_spec(ctx.config, ctx.sizes, prog)
        rec.spans.append(Span("synthesize", t0, time.perf_counter(), 1))
        check(ctx, "no_flops", float(mod.count_flops(ctx.config, ctx.sizes,
                                                     prog) <= 0), 0)
    rec.e2e["synth_s"] = rec.per_unit("synthesize")
    ctx.attempted = len(ctx.traffic["programs"])
"""


def _copy_with_new_files(tmp_path):
    """A copy of the benchmark with a new mix, metric, program kind and
    window, and cells naming them, added as files and entries alone."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    b = tmp_path / "bench"
    mix = dict(harness.load_traffic("decode-b8.replay"),
               program={"kind": "mamba2_decode", "batch": 4})
    (b / "traffic" / "decode-b4.replay.json").write_text(json.dumps(mix))
    (b / "metrics" / "replay.sweeps.py").write_text(
        "def read(rec):\n    return rec.total('proxy.sweep')[1]\n")
    (b / "programs" / "toy_matmul.py").write_text(TOY_PROGRAM)
    (b / "windows" / "trace_only.py").write_text(TOY_WINDOW)
    toy = [{"kind": "toy_matmul", "n": 128}]
    (b / "traffic" / "toy.synth.json").write_text(json.dumps(
        {"window": "synth", "programs": toy, "limits": {"proxy_fit_gap": 0.32}}))
    (b / "traffic" / "toy.trace.json").write_text(json.dumps(
        {"window": "trace_only", "programs": toy * 3}))
    cells = [{"name": n, "config": "mamba2-2.7b", "traffic": t, "chips": 1,
              "why": "test"}
             for n, t in (("mamba2-2.7b.decode-b4.replay", "decode-b4.replay"),
                          ("toy.synth", "toy.synth"),
                          ("toy.trace", "toy.trace"))]
    spec["workloads"] += cells
    for m in spec["end_to_end"]:
        if m["name"] == "synth_s":
            m["workloads"] += ["toy.synth", "toy.trace"]
    spec["per_layer"].append({"name": "replay.sweeps", "unit": "sweeps",
                              "better": "higher", "source": "host_clock",
                              "layer": "replay", "moves": "fidelity_err",
                              "workloads": [cells[0]["name"]]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return harness.load_spec(tmp_path)


def test_new_mix_and_metric_are_picked_up_from_files(tmp_path):
    """A later PR adds a cell, a mix and a metric as files and entries
    alone: copy the benchmark, add them, and the harness finds them."""
    spec2 = _copy_with_new_files(tmp_path)
    w = harness.find_cell(spec2, "mamba2-2.7b.decode-b4.replay")
    assert harness.load_traffic(w["traffic"], tmp_path / "bench")[
        "program"]["batch"] == 4
    sizes, _ = harness.load_config(spec2, w["config"], tmp_path)
    assert sizes["d_model"] == 2560
    layer = harness.metrics_for(spec2["per_layer"], w, {"fidelity_err",
                                                        "setup_s"})
    assert [m["name"] for m in layer] == ["replay.sweeps"]
    reader = harness.metric_reader("replay.sweeps", tmp_path / "bench")
    rec = harness.Record(w, 1, "TPU v5 lite",
                         spans=[harness.Span("proxy.sweep", 0.0, 1.0, 7)])
    assert reader.read(rec) == 7


@pytest.mark.parametrize("cell", ["toy.synth", "toy.trace"])
def test_new_program_kind_and_window_run_from_files(tmp_path, cell):
    """A new program kind under an existing window, and a new window
    driver, added as files alone, run a cell on the CPU."""
    import time

    import jax
    spec2 = _copy_with_new_files(tmp_path)
    out = harness.run_cell(spec2, cell, 7, 0.01, False, time.perf_counter(),
                           devices=jax.devices()[:1], log=lambda m: None,
                           root=tmp_path)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"synth_s", "setup_s"}
    assert out["attempted"] >= 1
