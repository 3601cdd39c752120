"""The plain references compute the objectives the models define, and the
comparison that decides ``correct`` is one-sided (tiny sizes, CPU)."""

import jax
import numpy as np
import pytest

from benchmark import generators as g
from benchmark.processes import integrated_arma, seasonal_level_trend
from benchmark.reference import arima_css, check, holtwinters_additive

ARIMA_KW = {"order": [1, 1, 1]}
HW_KW = {"period": 24, "model_type": "additive"}


@pytest.fixture(scope="module")
def arma_rows():
    return np.asarray(g.build_panel(
        integrated_arma.rows, {"phi": 0.6, "theta": 0.3}, {}, 11,
        jax.devices()[:1], 64, 400, 64))


@pytest.fixture(scope="module")
def hw_rows():
    return np.asarray(g.build_panel(
        seasonal_level_trend.rows,
        {"level": 10.0, "trend": 0.02, "amplitude": 2.0, "period": 24,
         "noise": 0.3}, {}, 11, jax.devices()[:1], 64, 240, 64))


def test_arima_reference_is_the_models_objective(arma_rows):
    """Same sum of squares as the system's scan objective (itself pinned to
    the kernels by the repo's parity tests), on arbitrary parameters."""
    from spark_timeseries_tpu.models import arima

    params = np.array([0.05, 0.4, -0.2])
    for y in arma_rows[:4]:
        ss, n_eff = arima_css.objective(params, y, ARIMA_KW)
        yd = np.diff(y.astype(np.float64))
        nll = float(arima.css_neg_loglik(
            jax.numpy.asarray(params, jax.numpy.float32),
            jax.numpy.asarray(yd, jax.numpy.float32), (1, 1, 1), True))
        ref = 0.5 * n_eff * (np.log(2 * np.pi * ss / n_eff) + 1.0)
        assert n_eff == len(yd) - 1
        assert nll == pytest.approx(ref, rel=2e-5)


def test_hw_reference_is_the_models_objective(hw_rows):
    from spark_timeseries_tpu.models import holtwinters

    params = np.array([0.3, 0.1, 0.2])
    for y in hw_rows[:4]:
        ss, n_eff = holtwinters_additive.objective(params, y, HW_KW)
        sse = float(holtwinters.sse(jax.numpy.asarray(params, "float32"),
                                    jax.numpy.asarray(y), 24, False))
        assert n_eff == len(y) - 24
        assert sse == pytest.approx(ss, rel=1e-4)


def test_optimum_beats_the_truth_and_the_gap_is_one_sided(arma_rows):
    rows = arma_rows[:8]
    best = np.array([arima_css.optimum(y, ARIMA_KW) for y in rows])
    truth = np.tile([0.0, 0.6, 0.3], (len(rows), 1))
    assert np.all(np.abs(check.loglik_gaps(arima_css, ARIMA_KW, rows, best))
                  < 1e-9)
    gaps = check.loglik_gaps(arima_css, ARIMA_KW, rows, truth)
    assert np.all(gaps > 0) and np.median(gaps) > 0.1
    truth[0, 1] = np.nan
    assert check.loglik_gaps(arima_css, ARIMA_KW, rows, truth)[0] == np.inf


def test_system_fits_pass_the_reference(arma_rows):
    from spark_timeseries_tpu.models import arima

    res = arima.fit(arma_rows, (1, 1, 1))
    gaps = check.loglik_gaps(arima_css, ARIMA_KW, arma_rows[:16],
                             np.asarray(res.params)[:16])
    assert gaps.max() < 0.1
    rec = check.recovery(res.params, [
        {"name": "phi", "index": 1, "value": 0.6, "tol": 0.1},
        {"name": "theta", "index": 2, "value": 0.9, "tol": 0.05}])
    assert [r["ok"] for r in rec] == [True, False]
