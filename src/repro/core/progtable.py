"""Program-table lowering: grammar-shaped executables (paper §2.7).

The unrolled emitter (:mod:`repro.core.codegen_reference`) turns every
grammar symbol into one Python statement, so jaxpr size, compile time, and
host memory scale with the *trace*.  :class:`ProgramTable` is the compiled
alternative: the generated module ships the grammar itself — terminal
descriptors plus rule bodies as ``(opcode, ref, exponent)`` tuples — and
this lowering maps it onto rolled JAX control flow:

* a symbol with exponent ``n`` replays through :func:`repro.core.replay.rep`
  — unrolled up to :data:`~repro.core.replay.REP_UNROLL_THRESHOLD`, a rolled
  ``fori_loop``/``scan`` above it (one body trace regardless of n);
* a long heterogeneous symbol sequence becomes one ``lax.scan`` over a
  constant int32 opcode array whose step is a ``lax.switch`` over the
  sequence's *distinct* ``(callee, exponent)`` pairs — same-signature
  symbols share one switch branch, so the scan body is sized by the
  distinct-symbol count, not the sequence length;
* nested rules lower children-first, so rule exponents become nested scans;
* ``move_shift`` row buffers never enter the switch: XLA gives a
  conditional's result its own buffer, so every branch would copy every
  row buffer it passes through.  A switched step's branch takes the rest of
  the state; its terminal's row turns run after the switch, in the same
  step, as one loop per row unroll whose trip count is read from a
  constant per-step table (0 for a step without them).  A rule whose
  expansion turns rows is inlined into the opcode array (one step per
  symbol of its expansion, the branches still one per distinct symbol),
  so every step that turns rows is one terminal's and its row work stays
  between the same two collectives.

Executable size is therefore O(grammar): comm terminals keep their exact
traced parameters (the collective schedule stays lossless), while the jaxpr
equation count stops depending on how many times the trace repeats them.
"""
from __future__ import annotations

from typing import Mapping, Sequence

import numpy as np
from jax import lax
import jax.numpy as jnp

from repro import obs
from repro.core import blocks
from repro.core import noise as noise_mod
from repro.core.replay import REP_UNROLL_THRESHOLD, rep
from repro.sharding.collectives import runs, traced_runs

_MOVE_SHIFT = blocks.BLOCK_NAMES.index("move_shift")
#: one ``move_shift`` loop turn (x9 = x11 = 1): a switch step's row loop
#: runs it once a trip
_ROW_TURN = tuple(int(i in (_MOVE_SHIFT, 10))
                  for i in range(blocks.N_BLOCKS))

#: Symbol sequences shorter than this stay straight-line: a switch-scan
#: needs the opcode array + dispatch machinery, which only pays for itself
#: once the sequence is meaningfully longer than its distinct-symbol set.
SWITCH_MIN_LEN = 6


def topo_order(rules: Mapping[int, Sequence]) -> list[int]:
    """Children-first ordering of rule ids (deterministic)."""
    seen: set[int] = set()
    out: list[int] = []

    def visit(rid: int) -> None:
        if rid in seen:
            return
        seen.add(rid)
        for kind, ref, _ in rules[rid]:
            if kind == "r":
                visit(ref)
        out.append(rid)

    for rid in sorted(rules):
        visit(rid)
    return out


def expand_symbols(seq: Sequence, rules: Mapping[int, Sequence]) -> list[int]:
    """Symbolic expansion of a symbol sequence to its terminal-id stream.

    This is the comm-sequence oracle for compiled modules: expanding the
    emitted tables must reproduce ``MergedProgram.expand_rank`` exactly
    (losslessness survives the lowering), without executing anything.
    """
    out: list[int] = []

    def go(symbols: Sequence) -> None:
        for kind, ref, exp in symbols:
            if kind == "t":
                out.extend([int(ref)] * int(exp))
            else:
                for _ in range(int(exp)):
                    go(rules[ref])

    go(seq)
    return out


class ProgramTable:
    """Executable lowering of a generated module's grammar tables.

    ``terminals[gid]`` is ``("comm", buf_name, params_dict)`` or
    ``("compute", x_tuple, unroll)``; ``rules[rid]`` is a tuple of
    ``(kind, ref, exp)`` symbols; ``programs[gi]`` is signature group
    ``gi``'s flattened (guard-resolved) symbol sequence.  All lowered
    callables take ``(st, comm)`` and return the new state, exactly like
    the unrolled emitter's functions — the replay engine cannot tell the
    flavors apart.
    """

    def __init__(self, terminals: Sequence, rules: Mapping[int, Sequence],
                 programs: Sequence, noise: Sequence | None = None):
        self.terminals = tuple(tuple(t) for t in terminals)
        self.rules = {int(rid): tuple(tuple(s) for s in body)
                      for rid, body in dict(rules).items()}
        self.programs = tuple(tuple(tuple(s) for s in seq)
                              for seq in programs)
        # Per-terminal (sigma, shift) noise params (the module's
        # NOISE_MODELS table).  Lowered once through the shared
        # repro.core.noise helpers; the wrappers are trace-time no-ops
        # unless the replay state carries the noise key, so pre-noise
        # modules (noise=None) and noise-disabled replay trace identical
        # jaxprs.
        if noise is not None:
            self._noise = noise_mod.lower_params(noise, self.terminals)
        else:
            self._noise = (None,) * len(self.terminals)
        self._term_fns = [self._lower_terminal(t, nz) for t, nz
                          in zip(self.terminals, self._noise)]
        # terminal id -> (row-free twin, unroll, move_shift turns) for each
        # compute terminal that turns rows: what a switch step runs in its
        # branch and after it.  The twin drops the turns from x9 and from
        # x11, the combo loop's turn count.
        self._row_terms = {}
        for gid, (t, nz) in enumerate(zip(self.terminals, self._noise)):
            if t[0] == "compute" and int(t[1][_MOVE_SHIFT]) > 0:
                x = [int(v) for v in t[1]]
                turns = x[_MOVE_SHIFT]
                x[_MOVE_SHIFT], x[10] = 0, x[10] - turns
                self._row_terms[gid] = (
                    self._lower_terminal(("compute", x, t[2]), nz),
                    int(t[2]), turns)
        self._row_rules: dict[int, bool] = {}
        self._rule_fns: dict[int, object] = {}
        for rid in topo_order(self.rules):
            self._rule_fns[rid] = self._lower_seq(self.rules[rid])
        self._prog_fns = [self._lower_seq(seq) for seq in self.programs]

    # -- terminal lowering -----------------------------------------------------

    @staticmethod
    def _lower_terminal(desc, nz=None):
        kind = desc[0]
        if kind == "comm":
            _, buf, params = desc
            params = dict(params)

            def comm_fn(st, comm, _buf=buf, _p=params, _nz=nz):
                return noise_mod.perturb(comm.do(st, _buf, **_p), _nz)

            return comm_fn
        if kind == "compute":
            _, x, unroll = desc
            x = tuple(int(v) for v in x)
            unroll = int(unroll)

            def compute_fn(st, comm, _x=x, _u=unroll, _nz=nz):
                return noise_mod.perturb(blocks.run_combo(st, _x, unroll=_u),
                                         _nz)

            return compute_fn
        raise ValueError(f"unknown terminal kind: {kind!r}")

    # -- sequence lowering -----------------------------------------------------

    def _callee(self, kind: str, ref: int):
        return self._term_fns[ref] if kind == "t" else self._rule_fns[ref]

    def _turns_rows(self, kind: str, ref: int) -> bool:
        """Whether symbol ``(kind, ref)``'s expansion turns row buffers."""
        if kind == "t":
            return ref in self._row_terms
        if ref not in self._row_rules:
            self._row_rules[ref] = any(self._turns_rows(k, int(r))
                                       for k, r, _ in self.rules[ref])
        return self._row_rules[ref]

    def _inline_row_rules(self, seq: Sequence) -> list[tuple]:
        """``seq`` with every rule symbol that turns rows replaced by its
        body, ``exp`` times over, down to terminals: the symbols a switch
        runs with no row buffer in any branch."""
        out: list[tuple] = []
        for kind, ref, exp in seq:
            if kind == "r" and self._turns_rows(kind, int(ref)):
                body = self._inline_row_rules(self.rules[int(ref)])
                out.extend(body * int(exp))
            else:
                out.append((kind, int(ref), int(exp)))
        return out

    def _lower_seq(self, seq: Sequence):
        """Lower one symbol sequence to a ``(st, comm) -> st`` callable.

        Distinct ``(kind, ref, exp)`` symbols dedupe into switch branches;
        the sequence itself survives only as a constant int32 opcode array,
        so trace size is O(distinct symbols) + O(1) for the scan.  The
        branches take the state without its row buffers; a step's row
        turns follow its branch, one ``fori_loop`` per unroll, each trip a
        one-turn ``run_combo`` (every basis block still runs through it)."""
        if not seq:
            return lambda st, comm: st
        keys: list[tuple] = []
        index: dict[tuple, int] = {}
        for kind, ref, exp in seq:
            k = (kind, int(ref), int(exp))
            if k not in index:
                index[k] = len(keys)
                keys.append(k)
        if len(seq) < SWITCH_MIN_LEN or len(keys) < 2 \
                or len(keys) == len(seq):
            run = tuple((self._callee(kind, ref), int(exp))
                        for kind, ref, exp in seq)

            def straight(st, comm, _run=run):
                for fn, e in _run:
                    st = rep(fn, e, st, comm)
                return st

            return straight

        if any(k == "r" and self._turns_rows(k, r) for k, r, _ in keys):
            seq = self._inline_row_rules(seq)
            keys = list(dict.fromkeys(seq))
            index = {k: i for i, k in enumerate(keys)}
        rows = {o: self._row_terms[ref] for o, (kind, ref, _) in
                enumerate(keys) if kind == "t" and ref in self._row_terms}
        entries = [(rows[o][0] if o in rows else self._callee(kind, ref),
                    exp) for o, (kind, ref, exp) in enumerate(keys)]
        unrolls = sorted({u for _, u, _ in rows.values()})  # a row loop each
        # trips[o, j]: turns at unrolls[j] opcode o runs after its branch
        trips = np.zeros((len(keys), len(unrolls)), dtype=np.int32)
        for o, (_, u, turns) in rows.items():
            trips[o, unrolls.index(u)] = turns * keys[o][2]
        opcodes = np.asarray([index[(k, int(r), int(e))] for k, r, e in seq],
                             dtype=np.int32)
        taken = np.bincount(opcodes, minlength=len(entries))
        row_steps = int(trips.any(axis=1)[opcodes].sum())

        def switched(st, comm, _entries=entries, _ops=opcodes):
            obs.count("progtable.switch_steps", traced_runs() * len(_ops))
            obs.count("progtable.row_steps", traced_runs() * row_steps)

            def branch(s, fn, e, n):
                with runs(n):       # traced once, taken n times a scan
                    return rep(fn, e, s, comm)

            branches = [
                (lambda s, _fn=fn, _e=e, _n=int(n): branch(s, _fn, _e, _n))
                for (fn, e), n in zip(_entries, taken)
            ]

            def step(carry, xs):
                op, n = xs if unrolls else (xs, None)
                rest = {k: v for k, v in carry.items()
                        if not blocks.is_row_key(k)}
                out = {**carry, **lax.switch(op, branches, rest)}
                for j, u in enumerate(unrolls):
                    k = blocks.row_key(u)
                    out[k] = lax.fori_loop(
                        0, n[j], lambda i, r, _u=u: blocks.run_combo(
                            r, _ROW_TURN, unroll=_u), {k: out[k]})[k]
                return out, None

            xs = jnp.asarray(_ops)
            if unrolls:
                xs = (xs, jnp.asarray(trips[_ops]))
            st, _ = lax.scan(step, st, xs)
            return st

        return switched

    # -- execution + introspection ---------------------------------------------

    def run(self, gi: int, st: dict, comm) -> dict:
        """Execute signature group ``gi``'s program."""
        return self._prog_fns[gi](st, comm)

    def expand(self, gi: int) -> list[int]:
        """Terminal-id stream of group ``gi`` (symbolic, no execution)."""
        return expand_symbols(self.programs[gi], self.rules)


# ---------------------------------------------------------------------------
# executable-size accounting
# ---------------------------------------------------------------------------


def jaxpr_eqn_count(jaxpr) -> int:
    """Total equation count of a jaxpr, recursing into sub-jaxprs carried by
    higher-order primitives (each scan/cond body is counted once — exactly
    the traced-program size a rolled lowering keeps O(grammar))."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for v in eqn.params.values():
            if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                n += jaxpr_eqn_count(v)
            elif isinstance(v, (tuple, list)):
                for b in v:
                    if hasattr(b, "eqns") or hasattr(b, "jaxpr"):
                        n += jaxpr_eqn_count(b)
    return n
