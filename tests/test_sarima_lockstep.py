"""Seasonal ARIMA on the CSS kernels and the lockstep driver (ISSUE 34).

The reference throughout is what the package already had: the float64
``lax.scan`` over the EXPANDED dense polynomial (``arima.sarima_neg_loglik``
-> ``_css_errors_poly``), which knows nothing of lag sets or of the product
map.  Held to it here, on seeded random parameters and panels at small
sizes: the lag-set kernels (interpreted), the map from the model's
parameters to the kernel's planes; ``test_sarima_fit.py`` holds the whole
fit through ``lockstep.fit`` and through ``reliability.fit_chunked``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _sarima_cases import AIRLINE, AIRLINE4, SARMA4, airline_panel
from spark_timeseries_tpu.models import arima
from spark_timeseries_tpu.ops import pallas_kernels as pk


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
