"""Row buffers stay out of the program table's switch.

A switched step's branch takes the state without its ``move_shift`` row
buffers; the step's row turns run after the switch, in loops whose trip
counts the exact walker resolves from the constant per-step table.  The
bar: the exact walker still reads what the fit asked for (the sum of
``blocks.combo_cost`` over the expanded terminals) and what the unrolled
reference reads, the collective sequence is the tables' expansion, and
sequences too short for a switch lower as they always have.

The NPB MG case runs in a subprocess with 4 forced host devices (the main
pytest process keeps one CPU device)."""
import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.compat import make_mesh
from repro.core import blocks
from repro.core.progtable import ProgramTable, SWITCH_MIN_LEN, expand_symbols
from repro.core.replay import rep
from repro.core.tracer import _contains_cond, trace_fn
from repro.sharding.collectives import DeviceComm, LocalSim

_PSUM = dict(kind="psum", shape=(16,), dtype="float32", axes=("x",),
             detail=())

#: a comm terminal, a compute terminal without ``move_shift``, and
#: ``move_shift`` terminals on two row buffers, one past ``_ROWS_MAX``
TERMS = (
    ("compute", (0, 0, 1, 0, 0, 0, 0, 0, 3, 0, 5), 8),
    ("compute", (0, 1, 0, 0, 0, 0, 0, 0, 0, 0, 1), 1),
    ("comm", "buf0", _PSUM),
    ("compute", (0, 0, 0, 0, 0, 0, 0, 0, 2, 0, 2), 2 * blocks._ROWS_MAX),
    ("compute", (0, 0, 0, 0, 1, 0, 0, 0, 1, 0, 3), 8),
)
#: rule 0 turns rows between two collectives (inlined into a switch);
#: rule 1 does not (one branch)
RULES = {0: (("t", 2, 1), ("t", 4, 1), ("t", 2, 1)),
         1: (("t", 1, 2), ("t", 2, 1))}
PROG = (("t", 0, 1), ("r", 0, 2), ("t", 1, 1), ("t", 3, 1), ("r", 1, 1),
        ("t", 0, 3), ("t", 1, 1), ("r", 0, 1))


def _state(terms):
    combos = [(t[1], t[2]) for t in terms if t[0] == "compute"]
    st = blocks.init_state(0, blocks.row_unrolls(combos))
    st["buf0"] = jnp.full((16,), 0.5, jnp.float32)
    return st


def _unrolled(seq, rules, terms):
    """The unrolled reference's program: one statement a symbol, as
    ``codegen_reference`` emits it."""
    def fn_of(kind, ref):
        if kind == "r":
            return lambda st, comm: _unrolled(rules[ref], rules, terms)(
                st, comm)
        t = terms[ref]
        if t[0] == "comm":
            return lambda st, comm: comm.do(st, t[1], **t[2])
        return lambda st, comm: blocks.run_combo(st, t[1], unroll=t[2])

    def run(st, comm):
        for kind, ref, exp in seq:
            st = rep(fn_of(kind, ref), exp, st, comm)
        return st

    return run


def _fitted(terms, ids) -> np.ndarray:
    return sum(blocks.combo_cost(terms[g][1], terms[g][2]) for g in ids
               if terms[g][0] == "compute")


def _comm_cost(comm, terms, ids) -> np.ndarray:
    """Walker cost of the comm terminals ``ids`` runs, one ``do`` each,
    on ``comm`` under a one-device mesh."""
    mesh = make_mesh((1,), ("x",))
    st = _state(terms)
    total = np.zeros(6)
    for g in ids:
        if terms[g][0] == "comm":
            _, buf, p = terms[g]
            one = jax.shard_map(lambda s: comm.do(s, buf, **p), mesh=mesh,
                                in_specs=P(), out_specs=P(), check_vma=False)
            total += trace_fn(one, st, exact_cond=True).total_compute()
    return total


def _local_case(prog, rules, terms):
    """Readings of one lowered program: the exact walker's totals on
    LocalSim, the fitted blocks', the unrolled reference's, and on a
    one-device mesh with real collectives, its totals and collectives."""
    pt = ProgramTable(terms, rules, [prog])
    st, comm = _state(terms), LocalSim()
    ids = expand_symbols(prog, rules)
    run = lambda s: pt.run(0, s, comm)       # noqa: E731
    dev = DeviceComm({"x": 1})
    meshed = trace_fn(jax.shard_map(
        lambda s: pt.run(0, s, dev), mesh=make_mesh((1,), ("x",)),
        in_specs=P(), out_specs=P(), check_vma=False), st, exact_cond=True)
    fitted = _fitted(terms, ids)
    return dict(
        walked=trace_fn(run, st, exact_cond=True).total_compute().tolist(),
        fitted=fitted.tolist(),
        reference=trace_fn(lambda s: _unrolled(prog, rules, terms)(s, comm),
                           st, exact_cond=True).total_compute().tolist(),
        mesh_walked=meshed.total_compute().tolist(),
        mesh_fitted=(fitted + _comm_cost(dev, terms, ids)).tolist(),
        comm=[[e.kind, list(e.shape)] for e in meshed.comm_events()],
        expanded=[[terms[g][2]["kind"], list(terms[g][2]["shape"])]
                  for g in ids if terms[g][0] == "comm"],
        cond=_contains_cond(jax.make_jaxpr(run)(st)),
        hlo=jax.jit(run).lower(st).as_text())


@pytest.fixture(scope="module")
def switched_local():
    return _local_case(PROG, RULES, TERMS)


@pytest.fixture(scope="module")
def decode_straight():
    """A decode proxy's shape: one program of one compute terminal and no
    rules.  Its HLO is what the terminal's combination lowers to alone."""
    x, u = (20, 0, 31, 0, 1, 0, 0, 0, 18, 0, 70), 512
    terms = (("compute", x, u),)
    out = _local_case((("t", 0, 1),), {}, terms)
    st = _state(terms)
    out["hlo_before"] = jax.jit(
        lambda s: blocks.run_combo(s, x, unroll=u)).lower(st).as_text()
    return out


_MG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro import obs
    from repro.compat import make_mesh
    from repro.core import blocks
    from repro.core.events import is_comm
    from repro.core.replay import init_replay_state
    from repro.core.synthesize import synthesize
    from repro.core.tracer import trace_fn
    from repro.models import npb_mg as M
    from repro.sharding.collectives import LocalSim

    mesh = make_mesh((2, 2), ("z", "y"))
    n = M.CLASSES["S"]["n"]
    args = (M.build("S", mesh, ("z", "y", None)),
            jax.ShapeDtypeStruct((n, n, n), jnp.float32))
    res = synthesize(*args, name="mg_rows_t")
    ref = synthesize(*args, name="mg_rows_u", codegen="unrolled")
    proxy, mod = res.proxy, res.proxy.module
    st = jax.eval_shape(lambda: init_replay_state(mod))
    table = res.merged.table
    sig = lambda evs: [[e.kind, list(e.shape), str(e.dtype), list(e.axes)]
                       for e in evs]
    out = {"cases": [], "unrolls": list(mod.ROW_UNROLLS)}
    for pl in proxy.mesh_sweep_plan(mesh):
        r = pl.ranks[0]
        ids = proxy.expand_rank_ids(r)
        fitted = sum(blocks.combo_cost(*proxy.combos[g])
                     for g in ids if g in proxy.combos)
        comm, sub = proxy._mesh_comm(pl), proxy._submesh_for(mesh, pl)
        comm_cost = np.zeros(6)
        for g in ids:
            if mod.TERMINALS[g][0] == "comm":
                _, buf, kw = mod.TERMINALS[g]
                one = jax.shard_map(lambda s: comm.do(s, buf, **kw), mesh=sub,
                                    in_specs=P(), out_specs=P(),
                                    check_vma=False)
                comm_cost += trace_fn(one, st, exact_cond=True).total_compute()
        meshed = trace_fn(proxy._fn_for_group_mesh(pl.sig, r, None, pl, mesh),
                          st, exact_cond=True)
        out["cases"].append(dict(
            walked=proxy.rank_metrics(r).tolist(),
            fitted=fitted.tolist(),
            reference=ref.proxy.rank_metrics(r).tolist(),
            mesh_walked=meshed.total_compute().tolist(),
            mesh_fitted=(fitted + comm_cost).tolist(),
            comm=sig(meshed.comm_events()),
            expanded=sig(table[g] for g in ids if is_comm(table[g]))))
    # the counters, at one trace of rank 0's program
    with obs.span("test.progtable") as sp:
        jax.make_jaxpr(lambda s: mod.run_rank(s, LocalSim(), 0))(st)
    out["counted"] = [sp.counts.get("progtable.switch_steps", 0),
                      sp.counts.get("progtable.row_steps", 0)]
    out["row_terminals"] = [g for g, t in enumerate(mod.TERMINALS)
                            if t[0] == "compute" and t[1][8] > 0]
    out["rules"] = {k: v for k, v in mod.RULES.items()}
    out["program"] = mod.GROUP_PROGRAMS[mod._GROUP_INDEX[0]]
    print("REPORT:" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def npb_mg_s():
    proc = subprocess.run([sys.executable, "-c", _MG], capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("REPORT:")]
    assert line, proc.stdout
    return json.loads(line[0][len("REPORT:"):])


@pytest.mark.parametrize("case", ["switched_local", "npb_mg_s",
                                  "decode_straight"])
def test_row_lowering_reads_as_the_fit_and_the_reference(case, request):
    """The exact walker's totals equal the fitted blocks' and the unrolled
    reference's, bit for bit (on a mesh, plus the collectives' own ops),
    and the collectives are the tables' expansion; a single-terminal
    program lowers as it always has."""
    got = request.getfixturevalue(case)
    for c in got.get("cases", [got]):
        assert c["walked"] == c["fitted"] == c["reference"], c
        assert c["mesh_walked"] == c["mesh_fitted"], c
        assert c["comm"] == c["expanded"]
    if case == "npb_mg_s":
        assert got["unrolls"] and got["cases"][0]["comm"]
    elif case == "switched_local":
        assert got["cond"] and got["expanded"]
    else:
        assert not got["cond"]
        assert got["hlo"] == got["hlo_before"]


def _symbolic_steps(program, rules, row_terminals) -> tuple[int, int]:
    """Switch steps and row steps a run of ``program``, counted from the
    tables alone: a sequence of at least ``SWITCH_MIN_LEN`` symbols, with
    some but not only repeats, is switched; there each rule whose expansion
    reaches a row terminal is spelled out, and every symbol left is a step,
    a row step if it is a row terminal.  Callees run as often as their
    steps, times their exponents."""
    def rows(kind, ref):
        return ref in row_terminals if kind == "t" else any(
            rows(k, r) for k, r, _ in rules[ref])

    def spelled(seq):
        out = []
        for kind, ref, exp in seq:
            out += spelled(rules[ref]) * exp if kind == "r" and rows(
                kind, ref) else [(kind, ref, exp)]
        return out

    counts = [0, 0]

    def run(seq, times):
        distinct = len(set(seq))
        if len(seq) >= SWITCH_MIN_LEN and 2 <= distinct < len(seq):
            seq = spelled(seq)
            counts[0] += times * len(seq)
            counts[1] += times * sum(k == "t" and r in row_terminals
                                     for k, r, _ in seq)
        for kind, ref, exp in seq:
            if kind == "r":
                run(rules[ref], times * exp)

    run([tuple(s) for s in program], 1)
    return counts[0], counts[1]


def test_switch_and_row_step_counters(npb_mg_s):
    """At class S, ``progtable.switch_steps`` and ``progtable.row_steps``
    (trace-time counters, times the runs around them) equal the counts
    taken from the module's tables."""
    rules = {int(k): [tuple(s) for s in v]
             for k, v in npb_mg_s["rules"].items()}
    want = _symbolic_steps(npb_mg_s["program"], rules,
                           set(npb_mg_s["row_terminals"]))
    assert 0 < want[1] < want[0]
    assert npb_mg_s["counted"] == list(want)


def test_exact_walker_costs_a_folded_bound_loop_by_its_trips():
    """A ``fori_loop`` whose traced bound folds from constants costs its
    body that many times in exact mode, its counter free; a bound that does
    not fold, or the default mode, costs one iteration as before."""
    table = np.asarray([3, 5], np.int32)

    def loop(v, n):
        return jax.lax.fori_loop(0, n, lambda _, x: x * 2.0, v)

    v = jnp.ones(8)
    body = trace_fn(lambda x: x * 2.0, v).total_compute()
    folded = trace_fn(lambda x: loop(x, jnp.asarray(table)[1]), v,
                      exact_cond=True).total_compute()
    np.testing.assert_array_equal(folded, 5 * body)
    legacy = trace_fn(lambda x: loop(x, jnp.asarray(table)[1]),
                      v).total_compute()
    assert legacy[5] == 1 and legacy[2] < 2 * body[2] + 64
    n = jnp.int32(5)
    np.testing.assert_array_equal(
        trace_fn(loop, v, n, exact_cond=True).total_compute(),
        trace_fn(loop, v, n).total_compute())
