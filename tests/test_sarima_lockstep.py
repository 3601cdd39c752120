"""Seasonal ARIMA on the CSS kernels and the lockstep driver (ISSUE 34).

The reference throughout is what the package already had: the float64
``lax.scan`` over the EXPANDED dense polynomial (``arima.sarima_neg_loglik``
-> ``_css_errors_poly``), which knows nothing of lag sets or of the product
map.  Held to it here, on seeded random parameters and panels at small
sizes: the lag-set kernels (interpreted), the map from the model's
parameters to the kernel's planes, and the whole fit through
``lockstep.fit`` and through ``reliability.fit_chunked``.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_timeseries_tpu import obs
from spark_timeseries_tpu import reliability as rel
from spark_timeseries_tpu.models import arima, base
from spark_timeseries_tpu.ops import pallas_kernels as pk
from spark_timeseries_tpu.reliability import faultinject as fi
from spark_timeseries_tpu.utils import optim

AIRLINE = ((0, 1, 1), (0, 1, 1, 24))  # M = {1, 24, 25}
AIRLINE4 = ((0, 1, 1), (0, 1, 1, 4))  # M = {1, 4, 5}: the same shape, short
SARMA4 = ((1, 0, 1), (1, 0, 1, 4))  # A = M = {1, 4, 5}
LAZY_ROWS = 2048  # the smallest batch whose compaction cap is under it


def airline_panel(rows, n_time, s, seed):
    """``[rows, n_time]`` f64 of ``(1-L)(1-L^s) y = (1 + th L)(1 + TH L^s) e``,
    one ``(th, TH)`` a row (the benchmark process's ranges) -> ``(y, th,
    TH)``."""
    rng = np.random.default_rng(seed)
    th = rng.uniform(-0.7, -0.2, rows)
    TH = rng.uniform(-0.8, -0.4, rows)
    e = rng.normal(size=(rows, n_time + 2 * s))
    w = e.copy()
    w[:, 1:] += th[:, None] * e[:, :-1]
    w[:, s:] += TH[:, None] * e[:, :-s]
    w[:, s + 1:] += (th * TH)[:, None] * e[:, :-s - 1]
    y = np.cumsum(w[:, 2 * s:], axis=1)
    for i in range(s, n_time):
        y[:, i] += y[:, i - s]
    return y, th, TH


def random_params(rng, rows, order, seasonal):
    """Seeded parameters inside the stable region (so that a thousand
    steps of the recursion stay finite), in the model's own layout."""
    k = arima._n_params_seasonal(order, seasonal, True)
    x = rng.uniform(-0.6, 0.6, (rows, k))
    x[:, 0] = 0.1 * rng.normal(size=rows)
    return x


def scan_value_and_grad(x, yd, nvd, order, seasonal):
    """The float64 scan objective and ``jax.grad`` of it, row by row."""
    f = lambda p, v, n: arima.sarima_neg_loglik(  # noqa: E731
        p, v, order, seasonal, True, n)
    x, yd = jnp.asarray(x, jnp.float64), jnp.asarray(yd, jnp.float64)
    return (np.asarray(jax.vmap(f)(x, yd, nvd)),
            np.asarray(jax.vmap(jax.grad(f))(x, yd, nvd)))


def kernel_value_and_grad(x, family, prepared):
    fb = family.objective(prepared.folded, prepared.rows)
    x = jnp.asarray(x, jnp.float32)
    return (np.asarray(fb(x)),
            np.asarray(jax.grad(lambda v: fb(v).sum())(x)))


# f32 kernel against the f64 scan: the sum of ~1e2-1e3 squared errors
# accumulates in f32 (1e-7 a term) and the likelihood takes its log, so the
# value agrees to 1e-5 relative; a gradient entry is a sum of as many
# products of O(1) terms and agrees to 1e-3 of (1 + its size)
VALUE_RTOL, GRAD_TOL = 1e-5, 1e-3


def assert_kernel_is_the_scan(x, y, order, seasonal, mode="dense"):
    fam = arima._sarima_family(order, seasonal, True, "pallas-interpret",
                               False, mode)
    y = jnp.asarray(y, jnp.float32)
    prepared = fam.prep(y)
    # the scan's rows: since ISSUE 46 the kernels' prep forms none of its own
    assert prepared.series == ()
    yd, nvd = arima._row_major(y, mode, order[1], seasonal[1], seasonal[3])
    f32, g32 = kernel_value_and_grad(x, fam, prepared)
    f64, g64 = scan_value_and_grad(x, yd, nvd, order, seasonal)
    ok = np.asarray(prepared.ok)
    assert ok.any() and np.isfinite(f64[ok]).all()
    np.testing.assert_allclose(f32[ok], f64[ok], rtol=VALUE_RTOL)
    assert np.max(np.abs(g32[ok] - g64[ok]) / (1 + np.abs(g64[ok]))) \
        < GRAD_TOL
    return prepared


# -- (1) the lag-set kernels: value and gradient ------------------------------


@pytest.mark.parametrize("order,seasonal,n_time", [
    (*AIRLINE, 120), (*AIRLINE4, 48), (*SARMA4, 48)],
    ids=["airline24-M1.24.25", "airline4-M1.4.5", "sarma4-A1.4.5-M1.4.5"])
def test_kernel_value_and_gradient_are_the_scans(order, seasonal, n_time):
    rng = np.random.default_rng(34)
    y, _, _ = airline_panel(1024, n_time, seasonal[3], seed=1)
    ar, ma = arima.seasonal_lag_sets(order, seasonal)
    assert (ar, ma) == {24: ((), (1, 24, 25))}.get(
        seasonal[3], ((1, 4, 5) if order[0] else (), (1, 4, 5)))
    assert_kernel_is_the_scan(random_params(rng, 1024, order, seasonal), y,
                              order, seasonal)


def test_two_time_chunks_at_the_real_chunk_length():
    # T - 25 = 1,055 differenced steps: two chunks of 1,024, and the lags
    # 24 and 25 of steps 1,024..1,048 reach back across the boundary (the
    # forward's error carry and neighbour block, the adjoint's carry)
    order, seasonal = AIRLINE
    assert pk._CHUNK_T == 1024
    rng = np.random.default_rng(35)
    y, _, _ = airline_panel(1024, 1080, 24, seed=2)
    prepared = assert_kernel_is_the_scan(
        random_params(rng, 1024, order, seasonal), y, order, seasonal)
    assert pk._time_layout(prepared.folded.t)[1:] == (1024, 2)


@pytest.mark.parametrize("order,seasonal", [AIRLINE, SARMA4],
                         ids=["airline24", "sarma4"])
def test_ragged_rows_over_two_chunks(monkeypatch, order, seasonal):
    # chunks of 64 steps (the largest lag, 25, still under half a chunk):
    # rows that start late, end early or both, right-aligned by the fit's
    # own prep; every row's valid span crosses the chunk boundary
    monkeypatch.setattr(pk, "_CHUNK_T", 64)
    rng = np.random.default_rng(36)
    s = seasonal[3]
    y, _, _ = airline_panel(1024, 150, s, seed=3)
    y[::3, :rng.integers(1, 20)] = np.nan
    y[1::5, -int(rng.integers(1, 15)):] = np.nan
    y[7, :11] = np.nan
    y[7, -6:] = np.nan
    prepared = assert_kernel_is_the_scan(
        random_params(rng, 1024, order, seasonal), y, order, seasonal,
        mode="general")
    assert pk._time_layout(prepared.folded.t)[2] >= 2
    assert len(set(np.asarray(prepared.rows[0]).tolist())) >= 4


def test_dense_lag_sets_are_the_dense_kernel_bit_for_bit():
    # one kernel body: the plain ARMA(2, 2) call, the same order written
    # as lag sets, and the seasonal entry over those sets agree in every
    # bit of the value, the saved errors, the tail and the gradient
    rng = np.random.default_rng(37)
    b, t = 1024, 40
    y = jnp.asarray(rng.normal(size=(b, t)).astype(np.float32))
    par = jnp.asarray(0.3 * rng.normal(size=(b, 5)).astype(np.float32))
    nv = jnp.asarray(rng.integers(t - 6, t + 1, b), jnp.int32)
    gbar = jnp.asarray(rng.normal(size=b).astype(np.float32))
    y3, zb3 = pk.css_prefold(y, (2, 0, 2), nv)
    sets = pk.css_prefold(y, ((1, 2), 0, (1, 2)), nv)
    assert all(np.array_equal(a, b_) for a, b_ in zip(sets, (y3, zb3)))

    def everything(p, q):
        outs = []
        for mode in ("sum", "both", "e", "tail"):
            got, (_, par3, _) = pk._css_fwd_call_f(p, q, True, mode, par,
                                                   y3, zb3, t)
            outs += list(got)
            if mode == "both":
                outs += list(pk._css_ss_f_bwd(
                    p, q, True, t, b, (y3, par3, zb3, got[0], ()), gbar))
        return [np.asarray(o) for o in outs]

    dense, as_sets = everything(2, 2), everything((1, 2), (1, 2))
    assert len(dense) == len(as_sets) == 8
    for a, b_ in zip(dense, as_sets):
        assert a.shape == b_.shape and a.tobytes() == b_.tobytes()
    plain = pk.css_neg_loglik_folded(par, y3, zb3, t, (2, 0, 2), True, nv,
                                     interpret=True)
    seasonal = pk.css_seasonal_neg_loglik_folded(
        par, y3, zb3, t, (1, 2), (1, 2), nv, interpret=True)
    assert np.asarray(plain).tobytes() == np.asarray(seasonal).tobytes()
    assert pk.css_series_block(4096, t, (2, 0, 2)) \
        == pk.css_series_block(4096, t, ((1, 2), 0, (1, 2)))


@pytest.mark.parametrize("mode", ["sum", "both", "e", "tail"])
@pytest.mark.parametrize("nchunk", [1, 2])
def test_block_width_is_bit_equal_over_lag_sets(monkeypatch, mode, nchunk):
    # R = 2 and R = 4 registers of series a step against R = 1, the lag
    # sets A = M = {1, 4, 5}, ragged rows; with "both" also the gradient
    # through the adjoint
    monkeypatch.setattr(pk, "_CHUNK_T", 16)
    b, t = 4096, (13 if nchunk == 1 else 29)
    ar = ma = (1, 4, 5)
    rng = np.random.default_rng(38)
    y = jnp.asarray(rng.normal(size=(b, t)).astype(np.float32))
    par = jnp.asarray(0.2 * rng.normal(size=(b, 7)).astype(np.float32))
    nv = jnp.asarray(rng.integers(t - 4, t + 1, b), jnp.int32)
    gbar = jnp.asarray(rng.normal(size=b).astype(np.float32))
    y3, zb3 = pk.css_prefold(y, (ar, 0, ma), nv)

    def run(r):
        outs, (_, par3, _) = pk._css_fwd_call_f(ar, ma, True, mode, par, y3,
                                                zb3, t, _r=r)
        if mode != "both":
            return [np.asarray(o) for o in outs]
        return [np.asarray(o) for o in list(outs) + list(pk._css_ss_f_bwd(
            ar, ma, True, t, b, (y3, par3, zb3, outs[0], None), gbar))]

    ref = run(1)
    assert all(np.isfinite(x).all() for x in ref)
    assert any(np.abs(x).max() > 0 for x in ref)
    if mode == "tail":  # as many trailing errors as the largest lag
        assert ref[0].shape[0] == 5
    for r in (2, 4):
        for x, want in zip(run(r), ref):
            assert x.shape == want.shape and x.tobytes() == want.tobytes(), r


def test_carries_and_vmem_follow_the_largest_lag():
    # the scratch the width rule counts: a parameter plane per LIVE lag,
    # the error carry as deep as the largest one
    dense = pk._css_fwd_layout(0, 25, "sum", 935)
    sparse = pk._css_fwd_layout((), (1, 24, 25), "sum", 935)
    assert [n for n, _ in dense[0]] == [936, 26, 1]
    assert [n for n, _ in sparse[0]] == [936, 4, 1]
    assert dense[2] == sparse[2] == [936, 25]
    assert pk._vmem_bytes(sparse) == pk._vmem_bytes(dense) - 2 * 22 * 4096
    assert pk.css_series_block(131072, 935, ((), 0, (1, 24, 25))) == 4096
    assert pk.css_structural_ok((1, 24, 25), (1, 512))
    assert not pk.css_structural_ok((), (1, 513))
    assert pk.css_structural_ok(512, 0) and not pk.css_structural_ok(513, 0)
    assert not pk.css_structural_ok(-1, 0)


# -- (2) the product map -------------------------------------------------------


@pytest.mark.parametrize("order,seasonal", [
    AIRLINE, SARMA4, ((2, 0, 1), (1, 0, 2, 3)), ((4, 0, 0), (1, 0, 0, 4))],
    ids=["airline24", "sarma4", "two-seasonal-ma", "lags-overlap"])
@pytest.mark.parametrize("intercept", [True, False])
def test_product_map_and_its_chain_rule(order, seasonal, intercept):
    rng = np.random.default_rng(39)
    k = arima._n_params_seasonal(order, seasonal, intercept)
    x = jnp.asarray(rng.normal(size=(6, k)))
    ar, ma = arima.seasonal_lag_sets(order, seasonal)
    planes = arima._seasonal_kernel_params(x, order, seasonal, intercept)
    assert planes.shape == (6, 1 + len(ar) + len(ma))
    # the map is _expand_seasonal_poly's, restricted to its live lags: the
    # dense vectors hold exactly these values there and zero elsewhere
    for row, got in zip(x, np.asarray(planes)):
        c, phi, theta, sphi, stheta = arima._split_params_seasonal(
            row, order, seasonal, intercept)
        for full, lags, live in (
                (arima._expand_seasonal_poly(phi, sphi, seasonal[3], -1.0),
                 ar, got[1:1 + len(ar)]),
                (arima._expand_seasonal_poly(theta, stheta, seasonal[3], 1.0),
                 ma, got[1 + len(ar):])):
            full = np.asarray(full)
            np.testing.assert_allclose(live, full[[lag - 1 for lag in lags]],
                                       rtol=1e-12, atol=1e-15)
            dead = np.setdiff1d(np.arange(full.size), np.asarray(lags) - 1)
            assert not full[dead.astype(int)].any()
        assert got[0] == (float(c) if intercept else 0.0)
    # the chain rule JAX takes through it, against central differences
    # (float64, step 1e-6: the map is bilinear, so the difference is exact
    # to rounding, 1e-9)
    g = jnp.asarray(rng.normal(size=planes.shape))
    f = jax.vmap(lambda v, gr: jnp.sum(  # one row's planes against its g
        gr * arima._seasonal_kernel_params(v[None], order, seasonal,
                                           intercept)[0]))
    grad = np.asarray(jax.vmap(jax.grad(
        lambda v, gr: f(v[None], gr[None])[0]))(x, g))
    h = 1e-6
    for j in range(k):
        step = jnp.zeros_like(x).at[:, j].set(h)
        fd = np.asarray(f(x + step, g) - f(x - step, g)) / (2 * h)
        np.testing.assert_allclose(grad[:, j], fd, rtol=1e-6, atol=1e-8)
    if (order, seasonal) == AIRLINE and intercept:
        # (b_1, b_24, b_25) = (th, TH, th TH):
        # d/dth = g_1 + TH g_25, d/dTH = g_24 + th g_25
        th, TH = np.asarray(x[:, 1]), np.asarray(x[:, 2])
        gn = np.asarray(g)
        np.testing.assert_allclose(grad[:, 1], gn[:, 1] + TH * gn[:, 3])
        np.testing.assert_allclose(grad[:, 2], gn[:, 2] + th * gn[:, 3])


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


def _span_lines(path):
    with open(path, encoding="utf-8") as f:
        events = [json.loads(line) for line in f]
    return [e for e in events if e.get("kind") == "span"]


def _assert_bitwise(a, b):
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)


def test_lazy_pair_count_evals_and_the_composed_program(monkeypatch,
                                                        tmp_path):
    from test_pallas import _dist_parity

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
