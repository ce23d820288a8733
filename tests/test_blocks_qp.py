"""Proxy-block calibration + QP search unit tests (paper §2.4).

Hypothesis-based property tests live in test_blocks_qp_prop.py so this
module always runs, dependency or not."""
import re

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import blocks as B
from repro.core.proxy_search import (
    PGD_TERMINAL_THRESHOLD, choose_solver, fit_batch_pgd, fit_combination,
    rel_error, substituted_matrix,
)
from repro.core.tracer import compute_cost


def test_calibration_matrix_shape_and_signatures():
    b = B.calibration_matrix()
    assert b.shape == (6, 11)
    names = B.BLOCK_NAMES
    mxu = b[0]
    assert mxu[names.index("mxu_vmem")] > 0 and mxu[names.index("mxu_small")] > 0
    assert np.all(mxu[2:] == 0)                      # only mxu blocks hit MXU
    assert b[3][names.index("trans_chain")] > 0      # transcendentals
    assert np.count_nonzero(b[3]) == 1
    assert b[4][names.index("gather_rand")] > 0      # gather
    assert np.count_nonzero(b[4]) == 1
    assert b[5][names.index("scan_seq")] > 0         # scan steps
    assert b[5][names.index("empty_loop")] == 1
    assert b[5][names.index("loop_turn")] == 1


def test_combo_cost_equals_walker_exactly():
    """THE consistency theorem: combo_cost == jaxpr-walker cost of
    run_combo, bit-exact, for any (x, unroll), on the state sized for the
    unroll.  At 4096 ``move_shift`` alone (the chained blocks would trace
    4096 inlined applications each): passes over a capped buffer."""
    mixes = ([1, 0, 2, 0, 1, 0, 0, 1, 0, 3, 9],
             [5, 4, 3, 2, 1, 1, 2, 3, 4, 0, 25],
             [0, 0, 0, 0, 0, 0, 0, 0, 0, 7, 0])
    for u, xs in ((1, mixes), (8, mixes), (64, mixes), (512, mixes),
                  (4096, ([0, 0, 0, 0, 0, 0, 0, 0, 2, 3, 4],))):
        st_ = jax.eval_shape(lambda: B.init_state(unrolls=(u,)))
        for x in xs:
            traced = compute_cost(lambda s: B.run_combo(s, x, u), st_)
            pred = B.combo_cost(x, u)
            np.testing.assert_allclose(traced, pred, rtol=0, atol=0)


def _row_shapes(st) -> dict:
    return {k: v.shape for k, v in st.items() if k.startswith("rows")}


def test_calibration_matches_the_parent_basis():
    """``move_shift`` on one row costs what a half-swap of the 128 KiB
    vector ``v`` costs, so B, and every fit, are as before the rows."""
    import jax.numpy as jnp

    def on_vector(st):
        v, h = st["v"], B._VEC // 2
        return dict(st, v=jnp.concatenate([v[h:], v[:h]]))

    st_ = jax.eval_shape(B.init_state)
    j = B.BLOCK_NAMES.index("move_shift")
    np.testing.assert_array_equal(B.calibration_matrix()[:, j],
                                  compute_cost(on_vector, st_))


def test_mixed_unrolls_stay_exact():
    """A program whose ``move_shift`` turns run at two unrolls gets a buffer
    for each, so neither turn counts a slice of the other's: the sum of the
    combo costs is still the walker's count."""
    xa = [0, 0, 2, 0, 0, 0, 1, 0, 1, 0, 4]
    xb = [1, 0, 1, 0, 0, 0, 0, 0, 3, 0, 5]
    combos = [(xa, 8), (xb, 64), ([1, 0, 3] + [0] * 7 + [4], 512)]
    assert B.row_unrolls(combos) == (8, 64)
    st_ = jax.eval_shape(lambda: B.init_state(unrolls=B.row_unrolls(combos)))
    assert _row_shapes(st_) == {"rows8": (8, B._VEC), "rows64": (64, B._VEC)}
    traced = compute_cost(
        lambda s: B.run_combo(B.run_combo(s, xa, 8), xb, 64), st_)
    np.testing.assert_array_equal(traced, B.combo_cost(xa, 8)
                                  + B.combo_cost(xb, 64))


def _compiled_turns(name: str, unroll: int) -> str:
    st = B.init_state(unrolls=(unroll,))
    return jax.jit(lambda s: B.repeat_block(name, 3, s, unroll)).lower(
        st).compile().as_text()


def test_move_shift_turn_keeps_its_data_movement():
    """512 half-swaps of one vector compose to the identity and XLA deletes
    them; a turn that rotates 512 distinct rows keeps its slices and its
    concatenate."""
    text = _compiled_turns("move_shift", 512)
    assert re.search(r"f32\[512,16384\][^\n]*slice\(", text)
    assert re.search(r"f32\[512,32768\][^\n]*concatenate\(", text)


def test_move_shift_passes_past_the_cap():
    """At unroll 4096 a turn makes 8 passes over the 512-row buffer, kept
    apart by optimization barriers: 8 half-swaps do not cancel.  An unroll
    past the cap that is not a multiple of it is refused."""
    text = _compiled_turns("move_shift", 4096)
    assert len(re.findall(r"= f32\[512,32768\]\{[^}]*\} concatenate\(",
                          text)) == 8
    assert B.row_count(64) == 64 and B.row_count(4096) == 512
    with pytest.raises(ValueError):
        B.row_count(1000)


def test_byte_rows_counter():
    """Each lowered ``move_shift`` turn adds its unroll to
    ``blocks.byte_rows``; a combo without one adds nothing."""
    st_ = jax.eval_shape(lambda: B.init_state(unrolls=(512,)))
    counts = {}
    for label, x in (("rows", (20, 0, 31, 0, 1, 0, 0, 0, 18, 0, 70)),
                     ("none", (20, 4, 0, 1, 1, 0, 0, 0, 0, 0, 26))):
        with obs.span("test.byte_rows") as sp:
            jax.make_jaxpr(lambda s: B.run_combo(s, x, 512))(st_)
        counts[label] = sp.counts.get("blocks.byte_rows", 0)
    assert counts == {"rows": 512, "none": 0}


#: the mamba2-2.7b decode programs' fits (x, unroll) by batch
DECODE_FITS = {
    1: ((19, 0, 30, 0, 1, 0, 0, 0, 30, 0, 80), 64),
    2: ((32, 0, 52, 0, 3, 0, 0, 0, 0, 0, 87), 64),
    4: ((10, 4, 15, 1, 1, 0, 0, 0, 19, 14, 50), 512),
    8: ((20, 0, 31, 0, 1, 0, 0, 0, 18, 0, 70), 512),
    16: ((32, 0, 54, 0, 3, 0, 0, 0, 0, 0, 89), 512),
}


@pytest.mark.parametrize("batch", sorted(DECODE_FITS))
def test_decode_fits_and_row_buffers(batch):
    """The full-width mamba2-2.7b decode step, traced from shapes, keeps
    its fit, and its proxy's state holds one buffer of ``unroll`` rows
    where the fit runs ``move_shift`` (b = 1, 4, 8), none where not."""
    import jax.numpy as jnp
    from repro.configs.registry import get
    from repro.core.replay import init_replay_state
    from repro.core.synthesize import synthesize
    from repro.models.model import abstract_cache, build_forward, init_abstract

    cfg = get("mamba2-2.7b")
    decode = build_forward(cfg, "decode")

    def step(params, cache, tokens, pos):
        return decode(params, cache, tokens, pos, cfg)

    res = synthesize(step, init_abstract(cfg), abstract_cache(cfg, batch, 1),
                     {"tokens": jax.ShapeDtypeStruct((batch, 1), jnp.int32)},
                     jax.ShapeDtypeStruct((), jnp.int32), axis_sizes={})
    assert list(res.proxy.combos.values()) == [DECODE_FITS[batch]]
    st = jax.eval_shape(lambda: init_replay_state(res.proxy.module))
    x, u = DECODE_FITS[batch]
    want = {f"rows{u}": (u, B._VEC)} if x[8] else {}
    assert _row_shapes(st) == want
    assert res.proxy.module.ROW_UNROLLS == ((u,) if x[8] else ())


def test_run_combo_rejects_bad_coupling():
    st_ = B.init_state()
    with pytest.raises(ValueError):
        B.run_combo(st_, [5, 0, 0, 0, 0, 0, 0, 0, 0, 0, 2])


def test_run_combo_executes():
    st_ = B.init_state()
    out = B.run_combo(st_, [2, 1, 3, 1, 1, 1, 1, 1, 1, 4, 15])
    assert np.isfinite(np.asarray(out["a"], np.float32)).all()
    assert np.isfinite(float(out["s"]))


@pytest.mark.parametrize("dyn", [False, True], ids=["static", "traced"])
def test_block_scopes_reach_op_metadata(dyn):
    """Each active block's ops carry ``block.<name>`` in their metadata, so
    a profile can sum device time by block.  The padding loops are empty:
    they lower to no op and carry nothing."""
    x = (1,) * 9 + (2, 12)
    st = B.init_state()
    if dyn:
        low = jax.jit(B.run_combo_dyn).lower(st, np.asarray(x, np.int32))
    else:
        low = jax.jit(lambda s: B.run_combo(s, x)).lower(st)
    text = low.as_text(debug_info=True)
    scopes = set(re.findall(r"block\.([a-z_]+)", text))
    assert scopes == set(B.BLOCK_NAMES[:9])


def test_fit_recovers_exact_combination():
    """A target that IS a block mix must be recovered near-exactly."""
    b = B.calibration_matrix()
    x_true = np.array([40, 12, 25, 8, 5, 9, 3, 2, 7, 11, 130])
    t = b @ x_true
    fit = fit_combination(t)
    err = rel_error(t, fit.predicted)
    assert np.all(err[t > 0] < 0.08), (fit.x, err)


def test_fit_respects_constraints():
    rng = np.random.RandomState(0)
    b = B.calibration_matrix()
    for _ in range(20):
        t = b @ rng.randint(0, 200, 11).astype(float)
        fit = fit_combination(t)
        assert np.all(fit.x >= 0)
        assert fit.x[10] >= np.sum(fit.x[:9])          # paper's x11 coupling


def test_fit_large_targets():
    """Model-layer-scale targets (walker-realistic ratios): error < 1%."""
    t = np.array([3.2e12, 4.1e10, 8.0e11, 2.5e8, 1.1e8, 4.0e5])
    fit = fit_combination(t)
    assert np.all(fit.per_metric_rel_err[t > 0] < 0.01), fit.summary()
    assert fit.unroll > 1  # millions of applications, thousands of turns


def test_fit_pure_movement_segment():
    """Data-movement-only segments (bytes, no ALU) are representable."""
    t = np.array([0, 0, 2e9, 0, 0, 0])
    fit = fit_combination(t)
    assert fit.per_metric_rel_err[2] < 0.02, fit.summary()


def test_substitution_matrix_semantics():
    b = B.calibration_matrix()
    bs = substituted_matrix(b)
    np.testing.assert_allclose(bs[:, :9], b[:, :9] + b[:, 10:11])
    np.testing.assert_allclose(bs[:, 9], b[:, 9])


def test_pgd_matches_nnls():
    rng = np.random.RandomState(1)
    b = B.calibration_matrix()
    targets = np.stack([b @ rng.randint(1, 500, 11).astype(float)
                        for _ in range(8)])
    xs = fit_batch_pgd(targets, iters=600)
    for t, x in zip(targets, xs):
        pred = b @ x
        err = rel_error(t, pred)
        assert np.all(err[t > 0] < 0.25), (x, err)


def test_solver_auto_crossover():
    """Pin the pgd-by-default crossover: nnls at or below the terminal-count
    threshold, pgd strictly above, explicit choices untouched."""
    assert choose_solver(PGD_TERMINAL_THRESHOLD) == "nnls"
    assert choose_solver(PGD_TERMINAL_THRESHOLD + 1) == "pgd"
    assert choose_solver(0) == "nnls"
    assert choose_solver(10_000, solver="nnls") == "nnls"
    assert choose_solver(1, solver="pgd") == "pgd"
