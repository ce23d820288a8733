"""Operation counts the benchmark computes from shapes alone.

The yardstick's own arithmetic: the peaks table, and the proxy's counts
from its fitted block counts and each basis block's operand shapes, never
from the program's cost walker.  Each program kind counts its original's
operations from the configuration's sizes in its own file
(``programs/<kind>.py``).
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; unknown kinds fail."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {PEAKS_FILE.name}"
                       f" (known: {sorted(table)})")
    return table[device_kind]


def padded_vocab(vocab: int) -> int:
    return (vocab + 255) // 256 * 256


# proxy basis blocks: the MXU products one application performs, from the
# block geometry (core/blocks.py: mxu_vmem is a 128x128 @ 128x128 bf16
# product, mxu_small an 8x128 @ 128x128 f32 product); other blocks run no
# matmul.  Index = position in the fitted count vector.
BLOCK_MXU_FLOPS = {0: 2.0 * 128 * 128 * 128, 1: 2.0 * 8 * 128 * 128}


def proxy_sweep_flops(combos: dict, occurrences: dict) -> float:
    """MXU flops of one proxy sweep: each compute terminal's fitted block
    counts x unroll x per-application flops, times how often the sweep runs
    the terminal (``occurrences[gid]``, summed over replayed ranks)."""
    total = 0.0
    for gid, (x, unroll) in combos.items():
        per = sum(x[i] * unroll * f for i, f in BLOCK_MXU_FLOPS.items())
        total += per * occurrences.get(gid, 0)
    return total
