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
* nested rules lower children-first, so rule exponents become nested scans.

Executable size is therefore O(grammar): comm terminals keep their exact
traced parameters (the collective schedule stays lossless), while the jaxpr
equation count stops depending on how many times the trace repeats them.
"""
from __future__ import annotations

from typing import Mapping, Sequence

import numpy as np
from jax import lax
import jax.numpy as jnp

from repro.core import blocks
from repro.core import noise as noise_mod
from repro.core.replay import REP_UNROLL_THRESHOLD, rep
from repro.sharding.collectives import runs

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

    def _lower_seq(self, seq: Sequence):
        """Lower one symbol sequence to a ``(st, comm) -> st`` callable.

        Distinct ``(kind, ref, exp)`` symbols dedupe into switch branches;
        the sequence itself survives only as a constant int32 opcode array,
        so trace size is O(distinct symbols) + O(1) for the scan."""
        if not seq:
            return lambda st, comm: st
        keys: list[tuple] = []
        index: dict[tuple, int] = {}
        for kind, ref, exp in seq:
            k = (kind, int(ref), int(exp))
            if k not in index:
                index[k] = len(keys)
                keys.append(k)
        entries = [(self._callee(kind, ref), exp) for kind, ref, exp in keys]
        if len(seq) < SWITCH_MIN_LEN or len(keys) < 2 \
                or len(keys) == len(seq):
            run = tuple((self._callee(kind, ref), int(exp))
                        for kind, ref, exp in seq)

            def straight(st, comm, _run=run):
                for fn, e in _run:
                    st = rep(fn, e, st, comm)
                return st

            return straight

        opcodes = np.asarray([index[(k, int(r), int(e))] for k, r, e in seq],
                             dtype=np.int32)
        taken = np.bincount(opcodes, minlength=len(entries))

        def switched(st, comm, _entries=entries, _ops=opcodes):
            def branch(s, fn, e, n):
                with runs(n):       # traced once, taken n times a scan
                    return rep(fn, e, s, comm)

            branches = [
                (lambda s, _fn=fn, _e=e, _n=int(n): branch(s, _fn, _e, _n))
                for (fn, e), n in zip(_entries, taken)
            ]

            def step(carry, op):
                return lax.switch(op, branches, carry), None

            st, _ = lax.scan(step, st, jnp.asarray(_ops))
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
