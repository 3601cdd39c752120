"""Seasonal ARIMA's whole fit (ISSUE 34) through ``lockstep.fit`` and through
``reliability.fit_chunked``, held to the float64 ``lax.scan`` fit over the
EXPANDED dense polynomial: the lazy pair and the composed program, the
stragglers stage 1 hands stage 2, the journaled walk and the refusals that
stay.  ``test_sarima_lockstep.py`` holds the kernels and the product map.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _obs_helpers import _span_lines
from _pallas_helpers import _dist_parity
from _sarima_cases import AIRLINE, AIRLINE4, LAZY_ROWS, airline_panel
from spark_timeseries_tpu import obs
from spark_timeseries_tpu import reliability as rel
from spark_timeseries_tpu.models import arima, base
from spark_timeseries_tpu.ops import pallas_kernels as pk
from spark_timeseries_tpu.reliability import faultinject as fi
from spark_timeseries_tpu.utils import optim


# -- (3) the whole fit ---------------------------------------------------------


def _gap(nll, ref):
    """Units of log-likelihood lost against ``ref``'s optimum, row by row."""
    return np.asarray(nll, np.float64) - np.asarray(ref, np.float64)


def test_fit_on_the_kernels_is_the_float64_scan_fit():
    """``backend="pallas-interpret"`` (f32, lockstep L-BFGS) against
    ``backend="scan"`` in float64 (the reference: per-series L-BFGS on the
    expanded polynomial) on a seeded airline panel.  Tolerances are the
    stopping rule's, as ``garch11``'s: both stop at a relative gradient
    norm (1e-4 in f32, 1e-6 in f64) on the MEAN log-likelihood, so the f32
    fit may stop short of the f64 optimum by a few hundredths of a unit of
    log-likelihood (0.1: a likelihood ratio of 1.1, ``arima111``'s limit in
    the benchmark) and by 0.02 in a coefficient whose standard error at
    T = 200 is 0.07; f32 rounding alone is 1e-4 of either."""
    order, seasonal = AIRLINE
    y, th, TH = airline_panel(1024, 200, 24, seed=4)
    got = arima.fit(jnp.asarray(y, jnp.float32), order, seasonal=seasonal,
                    backend="pallas-interpret")
    n = 96
    ref = arima.fit(jnp.asarray(y[:n], jnp.float64), order,
                    seasonal=seasonal, backend="scan")
    assert bool(np.all(np.asarray(ref.converged)))
    assert np.asarray(got.converged).mean() >= 0.99
    assert got.params.dtype == jnp.float32 and got.params.shape == (1024, 3)
    both = np.asarray(got.converged)[:n]
    gaps = _gap(got.neg_log_likelihood[:n], ref.neg_log_likelihood)[both]
    assert gaps.max() < 0.1 and np.median(np.abs(gaps)) < 0.01
    diff = np.abs(np.asarray(got.params)[:n] - np.asarray(ref.params))[both]
    assert diff[:, 1:].max() < 0.02
    # right, not merely alike: the generating coefficients' medians
    med = np.nanmedian(np.asarray(got.params), axis=0)
    assert abs(med[1] - np.median(th)) < 0.05
    assert abs(med[2] - np.median(TH)) < 0.05


def _lazy_airline4(rows=LAZY_ROWS, n_time=60, seed=5):
    order, seasonal = AIRLINE4
    y = jnp.asarray(airline_panel(rows, n_time, 4, seed)[0], jnp.float32)
    return y, lambda v=y, **kw: arima.fit(
        v, order, seasonal=seasonal, backend="pallas-interpret",
        max_iters=kw.pop("max_iters", 14), **kw)


def _assert_bitwise(a, b):
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)


def test_lazy_pair_count_evals_and_the_composed_program(monkeypatch,
                                                        tmp_path):
    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", LAZY_ROWS)
    y, fit = _lazy_airline4()
    plain = fit()
    p = str(tmp_path / "ev.jsonl")
    obs.enable(p)
    counted, info = fit(count_evals=True)
    obs.disable()
    # the flag selects no program: the counted fit is the fit that runs
    _assert_bitwise(counted, plain)
    spans = {s["name"]: s for s in _span_lines(p)}
    s1, s2 = spans["fit.stage1"]["attrs"], spans["fit.stage2"]["attrs"]
    assert int(info["cap"]) == optim.compaction_cap(LAZY_ROWS) == s2["rows"]
    assert int(info["compact_at"]) == s1["iters"] < 14
    assert s1["undone"] > 0
    # what a kernel step pays, on both stages: three live lags reaching 5
    # and the adjoint call's panel operands, y3 and e3 (ISSUE 35)
    for attrs in (s1, s2):
        assert (attrs["lag_terms"], attrs["lag_span"]) == (3, 5)
        assert attrs["adjoint_panels"] == pk.CSS_ADJOINT_PANELS == 2
    assert s1["series_block"] == pk.css_series_block(
        LAZY_ROWS, 55, ((), 0, (1, 4, 5)))
    # under a caller's jit the panel is a Tracer: stage 1 and stage 2 in
    # one trace, to the eager pair's answer (another compiled program)
    traced = jax.jit(lambda v: fit(v, align_mode="dense"))(y)
    _dist_parity(plain, traced, conv_floor=0.3)
    # compaction off: every row to the end in one lockstep loop
    _dist_parity(plain, fit(compact=False), conv_floor=0.3)
    # and the portable backend, the ladder's fallback rung
    scan = arima.fit(y[:256], *AIRLINE4[:1], seasonal=AIRLINE4[1],
                     backend="scan", max_iters=14, compact=False)
    sub = type(plain)(*(np.asarray(a)[:256] for a in plain))
    _dist_parity(scan, sub, conv_floor=0.3)


@pytest.mark.parametrize("ragged", [False, True], ids=["dense", "ragged"])
def test_stage1_hands_stage2_its_stragglers_folded(monkeypatch, ragged):
    """The doubly differenced panel is folded once, in stage 1; stage 2 is
    given the stragglers' COLUMNS of that fold (``take_series`` on the
    folded pytree) and their ``nvd``, and finishing them is the lazy fit."""
    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", LAZY_ROWS)
    order, seasonal = AIRLINE4
    y = np.array(_lazy_airline4(seed=6)[0])
    mode = "dense"
    if ragged:
        y[5, :9] = np.nan
        y[40, -4:] = np.nan
        mode = "general"
    y = jnp.asarray(y)
    static = (order, True, "pallas-interpret", 14, 1e-4)
    _, aux = arima._fit_stage1_program(*static, False, mode, False,
                                       seasonal)(y)
    (start,) = aux["starts"]
    assert 0 < int(start["carry"].undone) and int(start["carry"].k) < 14
    idxc = start["carry"].idxc
    assert idxc.shape == (optim.compaction_cap(LAZY_ROWS),)
    aligned, nv0 = base.maybe_align(y, mode)
    yd = jax.vmap(lambda v: arima._difference_seasonal(
        arima._difference(v, 1), 1, 4))(aligned)
    nvd = nv0 - 5
    want = pk.css_prefold(yd[idxc], (0, 0, 5), nvd[idxc])
    folded, rows, _ = start["sub"]
    assert folded.t == 55
    assert np.array_equal(np.asarray(folded.y3), np.asarray(want[0]))
    assert np.array_equal(np.asarray(folded.zb3), np.asarray(want[1]))
    assert np.array_equal(np.asarray(rows[0]), np.asarray(nvd[idxc]))
    out, _counts = arima._fit_stage2_program(*static, seasonal)(
        start, aux["fin"])
    fit = arima.fit(y, order, seasonal=seasonal, backend="pallas-interpret",
                    max_iters=14)
    _assert_bitwise(out, fit)


# -- (4) the normal path: the journaled walk and its ladder -------------------


def test_walk_journals_resumes_bitwise_and_reaches_the_ladder(tmp_path):
    order, seasonal = AIRLINE
    y = airline_panel(48, 200, 24, seed=7)[0].astype(np.float32)
    kw = dict(chunk_rows=16, order=order, seasonal=seasonal)
    first = rel.fit_chunked(arima.fit, y, checkpoint_dir=str(tmp_path / "j"),
                            **kw)
    assert first.meta["status_counts"]["OK"] == 48
    assert first.meta["journal"]["chunks_committed"] == 3
    again = rel.fit_chunked(arima.fit, y, checkpoint_dir=str(tmp_path / "j"),
                            **kw)
    assert again.meta["journal"]["chunks_resumed"] == 3
    _assert_bitwise(first[:-1], again[:-1])
    # one poisoned row fails the primary fit and the retry rung, and the
    # fallback rung (backend="scan", compact=False: the same keyword
    # arguments a plain ARIMA's ladder passes) converges it
    poisoned = rel.fit_chunked(
        fi.failing_fit(arima.fit, y, [21], n_failures=2), y, **kw)
    assert poisoned.meta["ladder_totals"]["retry"]["attempted"] == 1
    assert poisoned.meta["ladder_totals"]["fallback"]["rescued"] == 1
    assert poisoned.meta["status_counts"] == {
        **{k: 0 for k in poisoned.meta["status_counts"]},
        "OK": 47, "FALLBACK": 1}
    assert np.isfinite(poisoned.params[21]).all()
    others = np.arange(48) != 21
    assert np.array_equal(poisoned.params[others], first.params[others])
    np.testing.assert_allclose(poisoned.params[21], first.params[21],
                               atol=0.05)


def test_refusals_that_stay():
    y = jnp.asarray(airline_panel(8, 120, 24, seed=8)[0], jnp.float32)
    order, seasonal = AIRLINE
    with pytest.raises(ValueError, match="optimizing"):
        arima.fit(y, order, seasonal=seasonal, method="hannan-rissanen")
    with pytest.raises(ValueError, match="too short"):
        arima.fit(y[:, :40], order, seasonal=seasonal)
    with pytest.raises(ValueError, match="scan backend"):
        arima.fit(y, order, seasonal=seasonal, backend="pallas")
    with pytest.raises(ValueError, match="count_evals requires the pallas"):
        arima.fit(y, order, seasonal=seasonal, backend="scan",
                  count_evals=True)
    with pytest.raises(ValueError, match="seasonal member"):
        arima.fit_grid(y, ((order, seasonal),), backend="pallas-interpret")
    # a lag past half a time chunk cannot take the kernels: auto resolves
    # to the scan, an explicit kernel backend is refused at the kernel
    assert not pk.css_structural_ok(0, 1 + 600)
    with pytest.raises(ValueError, match="lags <= 512"):
        pk.css_errors(0, (1, 600), True, jnp.zeros((8, 3)),
                      jnp.zeros((8, 700)), jnp.zeros((8,)))
