"""Proxy-block calibration + QP search unit tests (paper §2.4).

Hypothesis-based property tests live in test_blocks_qp_prop.py so this
module always runs, dependency or not."""
import re

import jax
import numpy as np
import pytest

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
    run_combo, bit-exact, for any (x, unroll)."""
    st_ = jax.eval_shape(B.init_state)
    for x in ([1, 0, 2, 0, 1, 0, 0, 1, 0, 3, 9],
              [5, 4, 3, 2, 1, 1, 2, 3, 4, 0, 25],
              [0, 0, 0, 0, 0, 0, 0, 0, 0, 7, 0]):
        for u in (1, 8):
            traced = compute_cost(lambda s: B.run_combo(s, x, u), st_)
            pred = B.combo_cost(x, u)
            np.testing.assert_allclose(traced, pred, rtol=0, atol=0)


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
