"""HBM bytes the benchmark counts from shapes alone: the least a proxy's
fitted blocks move, as a floor for a share of the HBM peak.

A loop turn of a basis block runs ``unroll`` applications that XLA fuses
into one pass over the block's operands (``core/blocks.py``), so a turn
reads each operand once and writes each result once, whatever the unroll;
``move_shift`` alone runs its applications over ``unroll`` distinct 128 KiB
rows, each read and written once a turn.  Never the cost walker's count,
which charges every application's operands and results unfused.
"""
from __future__ import annotations

KIB = 1024

#: bytes one loop turn of each block moves at least, by position in the
#: fitted count vector (block geometry of core/blocks.py); ``move_shift``
#: (8) is per row, times the unroll; the loop blocks (9, 10) move nothing
TURN_BYTES = {
    0: 3 * 32 * KIB,            # mxu_vmem: a, b read; a written (bf16 128x128)
    1: 96 * KIB,                # mxu_small: t, w read; t written (f32)
    2: 2 * 128 * KIB,           # hbm_stream: v read and written
    3: 2 * 4 * KIB,             # vpu_chain: t8 read and written
    4: 2 * 16 * KIB,            # trans_chain: t read and written
    5: 80 * KIB,                # gather_rand: tab and idx read
    6: 128 * KIB,               # reduce_long: v read
    7: 0,                       # scan_seq: one scalar
    8: 2 * 128 * KIB,           # move_shift: each row read and written
}
MOVE_SHIFT = 8


def combo_bytes(x, unroll: int) -> float:
    """Least HBM bytes of one fitted combination ``x`` at ``unroll``."""
    total = 0.0
    for i, per_turn in TURN_BYTES.items():
        rows = int(unroll) if i == MOVE_SHIFT else 1
        total += float(x[i]) * per_turn * rows
    return total


def proxy_bytes(combos: dict, occurrences: dict) -> float:
    """Least HBM bytes of a proxy program that runs compute terminal ``gid``
    ``occurrences[gid]`` times."""
    return sum(combo_bytes(x, unroll) * occurrences.get(gid, 0)
               for gid, (x, unroll) in combos.items())
