"""The reductions from a trace to per-layer metrics, on hand-made traces
with known answers and on small traces recorded on the chip
(``bench/testdata``), against a brute-force count at nanosecond
resolution."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from bench import harness
from bench import trace as T
from bench.harness import Record, Span

DATA = Path(__file__).resolve().parents[1] / "testdata"
RECORDED = sorted(DATA.glob("trace_small_*.json"))


def _hand_trace():
    # two devices; spans: original [0, 100), proxy.sweep [100, 200) x2 sweeps
    host = [["original", 0, 100, 4], ["proxy.sweep", 100, 200, 2]]
    dev0 = [["%fusion.1", 0, 50], ["%all-reduce.2", 110, 130],
            ["%fusion.3", 120, 160], ["%collective-permute-start.1", 170, 180]]
    dev1 = [["%fusion.1", 0, 100], ["%fusion.3", 100, 150]]
    return {"host": host, "devices": {"/device:TPU:0": dev0,
                                      "/device:TPU:1": dev1}}


def test_hand_trace_known_numbers():
    tr = _hand_trace()
    # dev0 busy in proxy: [110,160) + [170,180) = 60 of 100; dev1: 50 of 100
    assert T.idle_share(tr, "proxy.sweep") == pytest.approx((0.4 + 0.5) / 2)
    busy, win = T.device_busy_s(tr)
    assert win == pytest.approx(200e-9)
    assert busy == pytest.approx((50 + 60 + 150) / 2 * 1e-9)
    top = dict(T.top_ops(tr))
    assert top["%fusion.1"] == pytest.approx(75e-9)
    assert top["%fusion.3"] == pytest.approx(45e-9)
    gaps = T.idle_gaps(tr)
    assert gaps[0] == ["original", pytest.approx(60e-9)]


def test_empty_trace_reads_nothing():
    tr = {"host": [["proxy.sweep", 0, 10, 1]], "devices": {}}
    assert T.idle_share(tr, "proxy.sweep") is None
    assert T.top_ops(tr) == [] and T.idle_gaps(tr) == []


def test_op_name_drops_the_instruction():
    assert T.op_name("%while.2 = (s32[]) while(...)") == "%while.2"
    assert T.op_name("%fusion.82") == "%fusion.82"


def _brute(tr, name):
    """Idle share inside the spans ``name``, counted nanosecond by
    nanosecond, mean over devices."""
    win = [h for h in tr["host"] if h[0] == name]
    idle = []
    for ops in tr["devices"].values():
        busy_ns = total = 0
        for w in win:
            lo, hi = w[1], w[2]
            busy = np.zeros(hi - lo, bool)
            for o in ops:
                s, e = max(o[1], lo) - lo, min(o[2], hi) - lo
                if e > s:
                    busy[s:e] = True
            busy_ns += busy.sum()
            total += hi - lo
        idle.append(1 - busy_ns / total)
    return float(np.mean(idle))


@pytest.mark.skipif(not RECORDED, reason="no recorded trace in bench/testdata")
@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.stem)
def test_recorded_trace_against_brute_force(path):
    tr = json.loads(path.read_text())
    assert tr["devices"], "a recorded trace holds device ops"
    for name in {h[0] for h in tr["host"]}:
        assert T.idle_share(tr, name) == pytest.approx(_brute(tr, name),
                                                       abs=1e-9)
    top = T.top_ops(tr)
    assert 0 < len(top) <= 10
    assert [t[1] for t in top] == sorted((t[1] for t in top), reverse=True)
    busy, win = T.device_busy_s(tr)
    assert 0 < busy <= win


def _rec(spans):
    return Record({"name": "x"}, 1, "TPU v5 lite",
                  spans=[Span(*s) for s in spans])


def test_fidelity_err_moves_with_a_stall():
    window = harness.load_window("replay")
    base = [("original", 0.0, 1.0, 50), ("proxy.sweep", 1.0, 2.0, 250),
            ("original", 2.0, 3.0, 50), ("proxy.sweep", 3.0, 4.0, 250)]
    before = window.fidelity_err(_rec(base))
    assert before == pytest.approx(abs(1 / 250 - 1 / 50) / (1 / 50))
    stalled = list(base)
    stalled[3] = ("proxy.sweep", 3.0, 4.5, 250)      # one block stalls
    assert window.fidelity_err(_rec(stalled)) < before
    stalled = list(base)
    stalled[2] = ("original", 2.0, 3.5, 50)
    assert window.fidelity_err(_rec(stalled)) > before


def test_synth_s_moves_with_a_stall():
    window = harness.load_window("synth")
    base = [("synthesize", 0.0, 0.5, 1), ("proxy.compile", 0.5, 4.0, 1),
            ("synthesize", 4.0, 4.4, 1), ("proxy.compile", 4.4, 8.0, 1)]
    assert window.synth_s(_rec(base)) == pytest.approx(4.0)
    stalled = base[:3] + [("proxy.compile", 4.4, 10.0, 1)]
    assert window.synth_s(_rec(stalled)) == pytest.approx(5.0)


#: numbers each reduction gives on the recorded traces, read once and
#: checked against the brute-force count above
KNOWN = {
    "trace_small_decode": {
        "idle_share": {"proxy.sweep": 0.11848599999999998,
                       "original": 0.004214000000000051},
        "top_op": ["%while.2", 0.004345334],
    },
    "trace_small_stencil": {
        "idle_share": {"proxy.sweep": 1.0, "original": 0.9143215},
        "top_op": ["%while.12", 0.00024772475000000004],
    },
}


@pytest.mark.parametrize("stem", sorted(KNOWN))
def test_recorded_trace_known_numbers(stem):
    tr = json.loads((DATA / f"{stem}.json").read_text())
    want = KNOWN[stem]
    for name, share in want["idle_share"].items():
        assert T.idle_share(tr, name) == pytest.approx(share, rel=1e-12)
    top = T.top_ops(tr)[0]
    assert top[0] == want["top_op"][0]
    assert top[1] == pytest.approx(want["top_op"][1], rel=1e-12)
