"""TPU proxy basic blocks (paper §2.4, Fig. 3 — DESIGN.md §2 re-founding).

The paper's 11 C blocks each excite ~1 hardware counter (IPC, LST/INS,
L1_DCM, BR_CN/MSP).  Our 11 JAX blocks each excite ~1 TPU metric axis:

  id  name          excites               paper analog
  --  ------------  --------------------  -------------------------------
   1  mxu_vmem      mxu_flops (high AI)   block1 simple add (high IPC)
   2  mxu_small     mxu_flops (low AI)    block2 add, low LST/INS
   3  hbm_stream    hbm_bytes (f32)       block7 cache-miss walk
   4  vpu_chain     vpu_elems (int8:      block1/2 ALU pressure
                    lowest bytes/elem)
   5  trans_chain   transcendentals       block3/4 div (low IPC slow path)
   6  gather_rand   gather_elems          block7-9 cache misses (irregular)
   7  reduce_long   vpu w/ bytes ratio 4  block8 cache miss + high ipc
   8  scan_seq      scan_steps + vpu      block5/6 msp loops (serialization)
   9  move_shift    hbm_bytes, zero vpu   block7 cache walk (pure movement)
  10  empty_loop    scan_steps only       block10 empty cycle for branch
  11  loop_turn     scan_steps (the       block11 loop achieving linear
                    combo-loop overhead)  combination of other blocks

Each block's loop runs in ``jax.named_scope("block.<name>")`` (the
combo-loop padding in ``block.loop_turn``, block10 in ``block.empty_loop``),
so the block's name reaches its ops' metadata and a profile can sum device
time by block; a scope adds no jaxpr equation.

Replay structure (faithful to the paper's "blocks 1-9 live inside block-11's
loop, x11 >= sum(x_1..9)"): each block i runs in its own ``fori_loop`` of
``x_i`` turns, followed by one padding loop of ``x11 - sum(x_i)`` empty turns.
Total loop turns = x11.  Hence one application of block i physically costs
(col_i + col_11), which is exactly the variable substitution that turns the
paper's coupled QP (eq. 6-7 + x11 constraint) into a plain NNLS — see
:mod:`repro.core.proxy_search`.

``move_shift`` rotates the 128 KiB rows of a ``(rows, 32768)`` f32
buffer, and one loop turn runs its ``unroll`` applications as one op over
``unroll`` distinct rows: chained on one vector, XLA composes an even
number of half-swaps into the identity and deletes the block.  The state
holds one buffer per row count its program's ``move_shift`` turns use
(:func:`row_count`), made on the device from the seed; an unroll past
``_ROWS_MAX`` runs as passes over the whole buffer, each behind an
optimization barrier so no two passes cancel.  Where a buffer lives is the
compiler's choice: on a TPU v5e a loop-carried buffer of up to about
96 MiB stays in VMEM.  The other blocks chain their applications on
operands of a few KiB to 128 KiB, which XLA may fuse (``hbm_stream``'s
chain is one pass) or share (``reduce_long``'s and ``gather_rand``'s
reads of an unchanged operand).

Calibration (the ``mini-proxy-app`` measurement producing matrix B, eq. 2)
runs the *same* jaxpr cost walker used to trace target programs, so the fit
is exactly self-consistent: the walker cost of generated proxy code equals
``B @ x`` by construction (tested in tests/test_blocks_qp.py).
"""
from __future__ import annotations

import functools
from typing import Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs
from repro.core.events import N_METRICS

BLOCK_NAMES: tuple[str, ...] = (
    "mxu_vmem", "mxu_small", "hbm_stream", "vpu_chain", "trans_chain",
    "gather_rand", "reduce_long", "scan_seq", "move_shift",
    "empty_loop", "loop_turn",
)
N_BLOCKS = len(BLOCK_NAMES)

# geometry constants (MXU dims are multiples of 128; operands of a few KiB
# to 128 KiB, and the move_shift rows sized by the program's unroll)
_MM = 128            # mxu_vmem tile
_MS = 8              # mxu_small M-dim (low arithmetic intensity)
_VEC = 1 << 15       # hbm_stream vector and move_shift row (128 KiB f32):
                     # small quanta limit integer-rounding error even for
                     # few-MB events; unroll absorbs the extra loop turns
_ROWS_MAX = 512      # rows of one move_shift buffer (64 MiB); a larger
                     # unroll runs as passes over it
_TILE = (32, 128)    # VPU tile
_TAB = 1 << 14       # gather table
_NIDX = 4096         # gather indices
_SCAN_LEN = 64       # scan_seq inner length


def row_count(unroll: int) -> int:
    """Rows of the buffer a ``move_shift`` turn at ``unroll`` runs over: the
    unroll, at most ``_ROWS_MAX``, of which a larger unroll must be a
    multiple (the turn then makes ``unroll // _ROWS_MAX`` passes)."""
    u = int(unroll)
    if u > _ROWS_MAX and u % _ROWS_MAX:
        raise ValueError(f"unroll {u} > {_ROWS_MAX} is not a multiple of it")
    return min(u, _ROWS_MAX)


def _row_key(rows: int) -> str:
    """State key of the ``rows``-row buffer."""
    return f"rows{rows}"


def row_unrolls(combos: Iterable) -> tuple[int, ...]:
    """The unrolls of the ``(x, unroll)`` combos that run ``move_shift``:
    what :func:`init_state` sizes the row buffers from."""
    i = BLOCK_NAMES.index("move_shift")
    return tuple(sorted({int(u) for x, u in combos if int(x[i]) > 0}))


@functools.partial(jax.jit, static_argnums=1)
def _fill_rows(seed, rows: int):
    """``rows`` rows of values in [0, 1), an integer hash of ``seed`` and
    each element's index: quick to compile, where a PRNG's fill takes
    seconds on a TPU."""
    x = lax.iota(jnp.uint32, rows * _VEC) * jnp.uint32(0x9E3779B1) + seed
    x = (x ^ (x >> 16)) * jnp.uint32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return ((x >> 8).astype(jnp.float32) * 2.0 ** -24).reshape(rows, _VEC)


def init_state(seed: int = 0, unrolls: Iterable[int] = (1,)) -> dict:
    """Fixed-shape pytree threaded through every block (DCE-proof carry),
    with one ``move_shift`` buffer for each row count that turns at
    ``unrolls`` run over, filled on the device from the seed."""
    rng = np.random.RandomState(seed)
    st = {
        "a": jnp.asarray(rng.uniform(-1, 1, (_MM, _MM)), jnp.bfloat16),
        # matmul operands carry the 1/128 contraction normalization baked in
        # so the MXU blocks emit *zero* VPU ops (pure-matmul targets must be
        # representable — see proxy_search feasibility notes)
        "b": jnp.asarray(rng.uniform(-1, 1, (_MM, _MM)) / _MM, jnp.bfloat16),
        "w": jnp.asarray(rng.uniform(-1, 1, (_MM, _MM)) / _MM, jnp.float32),
        "v": jnp.asarray(rng.uniform(0, 1, (_VEC,)), jnp.float32),
        "t": jnp.asarray(rng.uniform(-1, 1, _TILE), jnp.float32),
        "t8": jnp.asarray(rng.randint(-64, 64, _TILE), jnp.int8),
        "tab": jnp.asarray(rng.uniform(0, 1, (_TAB,)), jnp.float32),
        "idx": jnp.asarray(rng.randint(0, _TAB, (_NIDX,)), jnp.int32),
        "s": jnp.float32(0.0),
    }
    draw = int(rng.randint(0, 2 ** 31))
    for rows in sorted({row_count(u) for u in unrolls}):
        st[_row_key(rows)] = _fill_rows(
            np.uint32((draw + rows * 0x632BE5AB) % 2 ** 32), rows)
    return st


# -- the block bodies (one "application" each) --------------------------------


def mxu_vmem(st: dict) -> dict:
    """128x128x128 bf16 matmul on 32 KiB operands: high-AI MXU pressure."""
    st = dict(st)
    st["a"] = st["a"] @ st["b"]
    return st


def mxu_small(st: dict) -> dict:
    """8x128x128 f32 matmul: MXU flops at low arithmetic intensity."""
    st = dict(st)
    row = st["t"][:_MS]
    out = row @ st["w"]
    st["t"] = jnp.concatenate([out, st["t"][_MS:]], axis=0)
    return st


def hbm_stream(st: dict) -> dict:
    """Streaming f32 vector update: bytes/vpu ~ 8 (pure HBM pressure)."""
    st = dict(st)
    st["v"] = st["v"] * 0.999999 + 1e-6
    return st


def vpu_chain(st: dict) -> dict:
    """int8 ALU chain: lowest bytes-per-element VPU pressure (ratio ~2)."""
    st = dict(st)
    t = st["t8"]
    for _ in range(4):
        t = (t + jnp.int8(3)) ^ jnp.int8(21)
    st["t8"] = t
    return st


def trans_chain(st: dict) -> dict:
    """tanh chain: transcendental slow-path pressure."""
    st = dict(st)
    t = st["t"]
    for _ in range(2):
        t = jnp.tanh(t)
    st["t"] = t * 1.0009765625 	# escape the tanh fixed point at 0
    return st


def gather_rand(st: dict) -> dict:
    """random-index gather from a table: irregular-address pressure."""
    st = dict(st)
    g = st["tab"][st["idx"]]
    st["s"] = st["s"] * 0.5 + jnp.sum(g) * 1e-6
    return st


def reduce_long(st: dict) -> dict:
    """long reduction: vpu with bytes/elem ratio 4."""
    st = dict(st)
    st["s"] = st["s"] * 0.5 + jnp.sum(st["v"]) * 1e-9
    return st


def scan_seq(st: dict) -> dict:
    """sequential scalar scan: serialization hazard (scan_steps)."""
    st = dict(st)

    def body(c, _):
        return c * 0.9999 + 1e-7, None

    out, _ = lax.scan(body, st["s"], None, length=_SCAN_LEN)
    st["s"] = out
    return st


def move_shift(st: dict, k: str) -> dict:
    """pure data movement (slice+concat roll of every row of buffer ``k``):
    bytes with zero element ops.

    TPU has no branch predictor, so the paper's msp blocks have no analogue
    (DESIGN.md §2); the freed slot covers the pure-copy segments real traces
    contain (layout changes, halo packing) that no ALU block can represent."""
    st = dict(st)
    v = st[k]
    st[k] = jnp.concatenate([v[:, _VEC // 2:], v[:, :_VEC // 2]], axis=1)
    return st


BLOCK_FNS: dict[str, Callable[..., dict]] = {
    "mxu_vmem": mxu_vmem, "mxu_small": mxu_small, "hbm_stream": hbm_stream,
    "vpu_chain": vpu_chain, "trans_chain": trans_chain,
    "gather_rand": gather_rand, "reduce_long": reduce_long,
    "scan_seq": scan_seq, "move_shift": move_shift,
}


def repeat_block(name: str, n, st: dict, unroll: int = 1) -> dict:
    """Run block ``name`` for ``n`` loop turns of ``unroll`` inlined
    applications each (the paper places x_i block *instances* inside the
    block-11 loop body; unroll is that instance count — it decouples the
    application count from the loop-turn/serialization count).

    A ``move_shift`` turn instead makes ``unroll // row_count(unroll)``
    ops over the rows of its buffer, each behind an optimization barrier
    (free for the walker), and adds ``unroll`` to the trace-time counter
    ``blocks.byte_rows``."""
    fn = BLOCK_FNS[name]
    if name == "move_shift":
        obs.count("blocks.byte_rows", int(unroll))
        rows = row_count(unroll)
        k = _row_key(rows)

        def body(i, s):
            for _ in range(unroll // rows):
                s = dict(s)
                s[k] = lax.optimization_barrier(s[k])
                s = fn(s, k)
            return s
    else:
        def body(i, s):
            for _ in range(unroll):
                s = fn(s)
            return s

    return lax.fori_loop(0, n, body, st)


def empty_turns(n, st: dict) -> dict:
    """n empty loop turns (block10 / block11-padding)."""
    return lax.fori_loop(0, n, lambda i, s: s, st)


def run_combo(st: dict, x, unroll: int = 1) -> dict:
    """Execute the paper's block combination for count vector ``x`` (len 11).

    Blocks 1-9 run x_i loop turns of ``unroll`` applications each; then
    ``x11 - sum(x_1..9)`` empty padding turns (total combo-loop turns ==
    x11); then block10's standalone empty loop of x10 turns.  ``x`` entries
    must be static Python ints here (the generated code path);
    :func:`run_combo_dyn` takes a traced vector.
    """
    x = [int(v) for v in x]
    body = int(sum(x[:9]))
    if x[10] < body:
        raise ValueError(f"x11={x[10]} < sum(x1..9)={body}")
    for i, name in enumerate(BLOCK_NAMES[:9]):
        if x[i] > 0:
            with jax.named_scope(f"block.{name}"):
                st = repeat_block(name, x[i], st, unroll)
    pad = x[10] - body
    if pad > 0:
        with jax.named_scope("block.loop_turn"):
            st = empty_turns(pad, st)
    if x[9] > 0:
        with jax.named_scope("block.empty_loop"):
            st = empty_turns(x[9], st)
    return st


def run_combo_dyn(st: dict, x, unroll: int = 1) -> dict:
    """Traced-count variant (x: int32[11]); used by the jit replay engine."""
    x = jnp.asarray(x, jnp.int32)
    for i, name in enumerate(BLOCK_NAMES[:9]):
        with jax.named_scope(f"block.{name}"):
            st = repeat_block(name, x[i], st, unroll)
    pad = jnp.maximum(x[10] - jnp.sum(x[:9]), 0)
    with jax.named_scope("block.loop_turn"):
        st = empty_turns(pad, st)
    with jax.named_scope("block.empty_loop"):
        st = empty_turns(x[9], st)
    return st


# -- calibration: build matrix B (paper eq. 2) --------------------------------


@functools.lru_cache(maxsize=1)
def calibration_matrix() -> np.ndarray:
    """B[i, j]: metric i per single application of block j (walker-measured).

    Columns 1-9 are the *bare* block bodies, ``move_shift`` on one row (the
    loop turn each application carries at replay is column 11; proxy_search
    adds it via the constraint substitution).  Columns 10 and 11 are one
    empty loop turn each.
    """
    from repro.core.tracer import compute_cost  # local import: cycle-free

    st = jax.eval_shape(init_state)
    b = np.zeros((N_METRICS, N_BLOCKS))
    for j, name in enumerate(BLOCK_NAMES[:9]):
        fn = BLOCK_FNS[name]
        if name == "move_shift":
            fn = functools.partial(fn, k=_row_key(1))
        b[:, j] = compute_cost(fn, st)
    # one loop turn: fori_loop(0, K, identity) / K  ->  scan_steps == 1
    k = 1024
    turn = compute_cost(lambda s: empty_turns(k, s), st) / k
    b[:, 9] = turn
    b[:, 10] = turn
    return b


def combo_cost(x, unroll: int = 1) -> np.ndarray:
    """Predicted walker cost of ``run_combo(st, x, unroll)``: blocks 1-9
    contribute unroll applications per loop turn."""
    b = calibration_matrix()
    x = np.asarray(x, dtype=np.float64)
    scaled = b.copy()
    scaled[:, :9] *= unroll
    return scaled @ x
