"""The fused order search's FIT on the lockstep driver (ISSUE 36): the fused
fit against nine separate fits and their AICc argmin, ``auto_fit`` on the
kernels, and the system against the benchmark's plain reference on the
benchmark's own process.  ``test_grid_lockstep.py`` holds the kernels."""

import jax.numpy as jnp
import numpy as np
import pytest

from _grid_cases import ORDERS, SPECS, K, K_MAX, WIDTH, mix_panel
from benchmark.reference import arima_grid_css as ref
from benchmark.reference import check
from spark_timeseries_tpu.models import arima, auto


@pytest.fixture(scope="module")
def fits():
    y = jnp.asarray(mix_panel(32, 240, 21), jnp.float32)
    fused = arima.fit_grid(y, SPECS, backend="pallas-interpret")
    single = [arima.fit(y, o, backend="pallas-interpret") for o in ORDERS]
    return y, fused, single


@pytest.mark.parametrize("g", range(K), ids=[str(o) for o in ORDERS])
def test_fused_fit_is_nine_separate_fits(fits, g):
    # (b) per order: the pack's block against arima.fit of that order —
    # the same likelihood on rows both call converged (the optimum of an
    # over-specified order is flat, so its parameters may differ there)
    _, fused, single = fits
    blk = np.asarray(fused.params)[:, g * WIDTH:(g + 1) * WIDTH]
    one = single[g]
    k = one.params.shape[1]
    assert blk.shape[1] == WIDTH and not blk[:, k:K_MAX].any()
    # eligible: a finite likelihood (a Hannan-Rissanen start outside the
    # invertible region can overflow in f32: that order is then not a
    # candidate of that row, in the fused fit and the separate one alike)
    elig = blk[:, K_MAX + 1] != 0
    assert elig.mean() >= 0.9
    assert np.array_equal(elig, np.isfinite(
        np.asarray(one.neg_log_likelihood)))
    both = (blk[:, K_MAX + 2] != 0) & np.asarray(one.converged)
    assert both.mean() >= 0.75
    nll_gap = np.abs(blk[:, K_MAX] - np.asarray(one.neg_log_likelihood))
    assert np.median(nll_gap[both]) < 2e-3
    assert (nll_gap[both] < 0.05).mean() >= 0.9
    if ORDERS[g][0] + ORDERS[g][2] <= 2:  # identified: the same parameters
        np.testing.assert_allclose(blk[both, :k],
                                   np.asarray(one.params)[both], atol=0.02)


def test_fused_fit_selects_what_nine_fits_select(fits):
    # (b) the AICc argmin over the demuxed pack is the argmin over the
    # nine separate fits (near-ties of two fits of the same row may flip)
    y, fused, single = fits
    nv0 = np.full(y.shape[0], y.shape[1], np.int32)
    demuxed = auto._demux_fused(fused, auto.normalize_orders(ORDERS), True)
    sel_f = auto.select_orders(ORDERS, demuxed, nv0)
    sel_1 = auto.select_orders(ORDERS, single, nv0)
    same = sel_f["order_index"] == sel_1["order_index"]
    assert same.mean() >= 0.9
    # (another compiled program: a row on a flat stretch can end elsewhere)
    assert (np.abs(sel_f["criterion"] - sel_1["criterion"])
            <= 0.1).mean() >= 0.9
    # row-level summaries: the best outcome across the grid
    pack = np.asarray(fused.params).reshape(y.shape[0], K, WIDTH)
    assert np.array_equal(np.asarray(fused.iters), pack[:, :, K_MAX + 3].max(1))
    np.testing.assert_array_equal(
        np.asarray(fused.neg_log_likelihood),
        np.where(pack[:, :, K_MAX + 1] != 0, pack[:, :, K_MAX], np.inf).min(1))
    assert np.asarray(fused.converged).all()


def test_auto_fit_reaches_the_kernels_with_no_change_to_its_callers():
    # fused groups of one signature take an explicit Pallas backend now
    y = jnp.asarray(mix_panel(16, 120, 4), jnp.float32)
    orders = [(0, 1, 0), (1, 1, 0), (0, 1, 1), (1, 1, 1)]
    on_k = auto.auto_fit(y, orders, backend="pallas-interpret", max_iters=30)
    on_s = auto.auto_fit(y, orders, backend="scan", max_iters=30)
    assert [g["orders"] for g in on_k.meta["auto_fit"]["fusion_groups"]] \
        == [[0, 1, 2, 3]]
    assert (np.asarray(on_k.order_index)
            == np.asarray(on_s.order_index)).mean() >= 0.85
    np.testing.assert_allclose(on_k.criterion, on_s.criterion, atol=0.1)


def test_system_against_the_plain_reference():
    # (d) 16 seeded rows of the benchmark's process at the cell's length:
    # half the AICc gap between what the system chose and fitted and the
    # best the float64 reference finds over all nine orders, held as the
    # cell holds it (gap <= 0.1 on min_share of the rows: a single start in
    # f32 stops short on some over-specified orders, PERF.md section 6)
    y = mix_panel(16, 1000, 7)
    kw = {"specs": [[list(o), None] for o in ORDERS]}
    # (on the scan: 1,000 interpreted steps an evaluation take minutes, and
    # the tests above hold the kernels to it)
    res = arima.fit_grid(jnp.asarray(y, jnp.float32), SPECS, backend="scan")
    gaps = check.loglik_gaps(ref, kw, y, np.asarray(res.params))
    assert np.median(gaps) < 0.01
    assert np.mean(gaps <= 0.1) >= 0.75
    # the drift is what the (0,1,0) slot's intercept estimates
    assert abs(np.median(np.asarray(res.params)[:, 0]) - 0.1) < 0.03
