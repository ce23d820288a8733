"""The synthesis cell's split of the proxy's first run, read from the
program's own spans: reported, adding up to ``synth.compile_s``, moved by a
slow lowering only where the lowering is, and absent where the program
recorded nothing."""
from __future__ import annotations

import re
import time

import pytest

from bench import harness
from bench.tests.conftest import BIG_SEED, MAMBA_SMALL

CELL = "mamba2-2.7b.decode-buckets.synth"
PARTS = ("compile.trace_s", "compile.lower_s", "compile.xla_s",
         "compile.rest_s")
NEW = PARTS + ("compile.executables",)


def _run():
    import jax
    lines = []
    out = harness.run_cell(harness.load_spec(), CELL, BIG_SEED, 0.5, True,
                           time.perf_counter(), sizes=MAMBA_SMALL,
                           devices=jax.devices()[:1], log=lines.append)
    per = next(m for m in lines if "compiles_per_program=" in m)
    compiles = [int(c) for c in re.findall(r"\d+", per.split("=")[-1])]
    return out, {k: v["value"] for k, v in out["metrics"].items()}, compiles


def test_split_is_reported_and_adds_up_to_the_first_run():
    out, m, compiles = _run()
    assert out["correct"], out["checks"]
    assert set(NEW) <= set(m)
    assert sum(m[k] for k in PARTS) == pytest.approx(m["synth.compile_s"],
                                                     rel=0.05)
    assert m["compile.xla_s"] > 0 and m["compile.lower_s"] > 0
    assert 1 <= m["compile.executables"] <= sum(compiles) / len(compiles)


def test_slow_lowering_moves_only_the_lowering(monkeypatch):
    from jax._src.interpreters import mlir
    _, base, _ = _run()
    real = mlir.lower_jaxpr_to_module

    def slow(*a, **kw):
        time.sleep(0.2)
        return real(*a, **kw)

    monkeypatch.setattr(mlir, "lower_jaxpr_to_module", slow)
    out, slow_m, _ = _run()
    assert out["correct"], out["checks"]
    d_lower = slow_m["compile.lower_s"] - base["compile.lower_s"]
    assert d_lower >= 0.15 * slow_m["compile.executables"]
    assert abs(slow_m["compile.xla_s"] - base["compile.xla_s"]) < 0.5 * d_lower
    assert abs(slow_m["compile.rest_s"] - base["compile.rest_s"]) < 0.5 * d_lower
    assert sum(slow_m[k] for k in PARTS) == pytest.approx(
        slow_m["synth.compile_s"], rel=0.05)


@pytest.mark.parametrize("name", NEW)
def test_reads_nothing_where_the_program_recorded_nothing(name):
    """A window with no ``proxy.run_all`` span under it, as a program
    without the recorder leaves, reads as None and does not raise."""
    rec = harness.Record({"name": CELL}, 1, "cpu")
    rec.spans = [harness.Span("proxy.compile", 1.0, 2.0, 1)]  # before boot
    assert harness.metric_reader(name).read(rec) is None


def test_program_spans_are_not_named_as_the_benchmarks():
    """The program's spans open ``TraceAnnotation``s too.  None may take the
    name of a benchmark span, which ``bench/trace.py`` tells apart by name
    and order of start."""
    import pathlib

    import jax
    import jax.numpy as jnp
    from bench import trace
    from repro import obs
    from repro.core.synthesize import synthesize

    src = pathlib.Path(__file__).resolve().parents[2] / "src" / "repro"
    named = {m for p in src.rglob("*.py")
             for m in re.findall(r'span\(\s*"([^"]+)"', p.read_text())}
    t = time.perf_counter_ns()
    res = synthesize(lambda x, w: jnp.tanh(x @ w),
                     jax.ShapeDtypeStruct((8, 128), jnp.float32),
                     jax.ShapeDtypeStruct((128, 128), jnp.float32))
    res.proxy.run_all()
    recorded = {s.name for s in obs.spans(t)}
    assert {"synthesize.program", "compress", "proxy.run_all",
            "jax.compile"} <= recorded
    assert "synthesize.program" in named
    assert not (named | recorded) & set(trace.SPAN_NAMES)
