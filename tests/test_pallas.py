"""Pallas kernel correctness vs the portable lax.scan implementations.

Runs everywhere via ``interpret=True`` (the CPU-mesh conftest forces the
host platform); on a real TPU the same assertions hold for the native
lowering (checked manually / by the driver's bench run — the interpret and
native paths share one kernel body).
"""

import dataclasses
import functools

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from spark_timeseries_tpu.models import arima
from spark_timeseries_tpu.ops import pallas_kernels as pk
from spark_timeseries_tpu.utils import optim


def _arma_panel(b, t, phi=0.6, theta=0.3, d_int=False, seed=0):
    rng = np.random.default_rng(seed)
    e = rng.normal(size=(b, t)).astype(np.float32)
    y = np.zeros_like(e)
    y[:, 0] = e[:, 0]
    for i in range(1, t):
        y[:, i] = phi * y[:, i - 1] + e[:, i] + theta * e[:, i - 1]
    if d_int:
        y = np.cumsum(y, axis=1)
    return jnp.asarray(y)


@pytest.mark.parametrize("order", [(1, 0, 1), (2, 0, 1), (1, 0, 0), (0, 0, 2)])
@pytest.mark.parametrize("intercept", [True, False])
def test_css_neg_loglik_matches_scan(order, intercept):
    p, _, q = order
    b, t = 6, 53
    y = _arma_panel(b, t)
    k = int(intercept) + p + q
    rng = np.random.default_rng(1)
    params = jnp.asarray(rng.normal(size=(b, k)).astype(np.float32) * 0.3)
    nv = jnp.asarray([t, t - 4, t - 9, t, t - 1, t - 2], jnp.int32)

    ref = jax.vmap(
        lambda pr, v, n: arima.css_neg_loglik(pr, v, order, intercept, n)
    )(params, y, nv)
    got = pk.css_neg_loglik(params, y, order, intercept, nv, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("order", [(1, 0, 1), (0, 0, 2)])
def test_css_neg_loglik_folded_matches_unfolded(order):
    # the pre-folded objective (css_prefold + css_neg_loglik_folded) is the
    # fit hot path; it must agree with the fold-per-call API bit-for-bit
    b, t = 6, 53
    y = _arma_panel(b, t, seed=9)
    p, _, q = order
    rng = np.random.default_rng(10)
    params = jnp.asarray(rng.normal(size=(b, 1 + p + q)).astype(np.float32) * 0.3)
    nv = jnp.asarray([t, t - 4, t - 9, t, t - 1, t - 2], jnp.int32)
    ref = pk.css_neg_loglik(params, y, order, True, nv, interpret=True)
    y3, zb3 = pk.css_prefold(y, order, nv)
    got = pk.css_neg_loglik_folded(params, y3, zb3, t, order, True, nv,
                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    g_ref = jax.grad(lambda P: jnp.sum(
        pk.css_neg_loglik(P, y, order, True, nv, interpret=True)))(params)
    g_got = jax.grad(lambda P: jnp.sum(pk.css_neg_loglik_folded(
        P, y3, zb3, t, order, True, nv, interpret=True)))(params)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("order", [(1, 0, 1), (2, 0, 2)])
def test_css_gradient_matches_autodiff_of_scan(order):
    p, _, q = order
    b, t = 5, 41
    y = _arma_panel(b, t, seed=3)
    k = 1 + p + q
    rng = np.random.default_rng(2)
    params = jnp.asarray(rng.normal(size=(b, k)).astype(np.float32) * 0.25)
    nv = jnp.asarray([t, t - 3, t, t - 6, t], jnp.int32)

    def loss_scan(P):
        return jnp.sum(
            jax.vmap(lambda pr, v, n: arima.css_neg_loglik(pr, v, order, True, n))(
                P, y, nv
            )
        )

    def loss_pal(P):
        return jnp.sum(pk.css_neg_loglik(P, y, order, True, nv, interpret=True))

    g_ref = jax.grad(loss_scan)(params)
    g_got = jax.grad(loss_pal)(params)
    np.testing.assert_allclose(
        np.asarray(g_got), np.asarray(g_ref), rtol=1e-4, atol=1e-4
    )


@pytest.mark.slow  # minutes-scale interpret-mode sweep: tier-2 (`-m slow`), see pyproject markers
@pytest.mark.parametrize("order", [(1, 0, 1), (2, 0, 2), (0, 0, 1)])
@pytest.mark.parametrize("t", [41, 2100])  # single-chunk and chunked grids
def test_css_data_gradient_matches_autodiff_of_scan(order, t):
    # ADVICE r4: jax.grad of the fused CSS objective w.r.t. the DATA used to
    # silently return zeros; the adjoint kernel now emits the true data
    # cotangent dL/dy_t = a_t - sum_i phi_i a_{t+i} when (and only when) the
    # data is perturbed
    p, _, q = order
    b = 4
    y = _arma_panel(b, t, seed=7)
    k = 1 + p + q
    rng = np.random.default_rng(8)
    params = jnp.asarray(rng.normal(size=(b, k)).astype(np.float32) * 0.25)
    nv = jnp.asarray([t, t - 3, t - 6, max(t - t // 3, 12)], jnp.int32)

    def loss_scan(v):
        return jnp.sum(
            jax.vmap(lambda pr, row, n: arima.css_neg_loglik(
                pr, row, order, True, n))(params, v, nv)
        )

    def loss_pal(v):
        return jnp.sum(pk.css_neg_loglik(params, v, order, True, nv,
                                         interpret=True))

    gy_ref = jax.grad(loss_scan)(y)
    gy_got = jax.grad(loss_pal)(y)
    np.testing.assert_allclose(np.asarray(gy_got), np.asarray(gy_ref),
                               rtol=1e-4, atol=1e-4)

    # the raw error-panel op's data cotangent (weighted-sum pullback).  The
    # kernel's contract is "prefix already zeroed", so the zeroing mask is
    # applied INSIDE both loss functions — they are then the same function
    # of the raw panel and their gradients must agree everywhere
    w = jnp.asarray(rng.normal(size=(b, t)).astype(np.float32))
    start = (t - nv).astype(jnp.float32)
    zb = start + p

    def err_scan(v):
        e = jax.vmap(lambda pr, row, n: arima._css_errors(
            pr, row, order, True, n_valid=n))(params, v, nv)
        return jnp.sum(w * e)

    def err_pal(v):
        vz = jnp.where(jnp.arange(t)[None, :] >= start[:, None], v, 0.0)
        return jnp.sum(w * pk.css_errors(p, q, True, params, vz, zb))

    np.testing.assert_allclose(
        np.asarray(jax.grad(err_pal)(y)), np.asarray(jax.grad(err_scan)(y)),
        rtol=1e-4, atol=1e-4,
    )


def test_fit_backend_pallas_matches_scan():
    y = _arma_panel(8, 120, d_int=True, seed=5)
    r_scan = arima.fit(y, (1, 1, 1), backend="scan", max_iters=30)
    r_pal = arima.fit(y, (1, 1, 1), backend="pallas-interpret", max_iters=30)
    # the backends also use different (equation-identical) HR init
    # constructions, so f32 rounding can shift a converged point by a few
    # 1e-3 within the objective's flat basin
    np.testing.assert_allclose(
        np.asarray(r_pal.params), np.asarray(r_scan.params), rtol=4e-3, atol=4e-3
    )


@pytest.mark.parametrize("order,intercept", [((1, 1, 1), True),
                                             ((2, 0, 0), True),
                                             ((1, 1, 1), False),
                                             ((0, 1, 2), True)])
def test_forecast_backend_pallas_matches_scan(order, intercept):
    # the fused forecast path (in-sample error rebuild on the css_errors
    # kernel with zb=start, i.e. condition=False) must match the vmapped
    # scan rebuild, including ragged rows
    y = np.array(_arma_panel(6, 140, d_int=order[1] > 0, seed=11))
    y[1, :25] = np.nan  # ragged start
    y[4, :60] = np.nan
    r = arima.fit(jnp.asarray(y), order, include_intercept=intercept,
                  backend="scan", max_iters=30)
    fs = arima.forecast(r.params, jnp.asarray(y), order, 8,
                        include_intercept=intercept, backend="scan")
    fp = arima.forecast(r.params, jnp.asarray(y), order, 8,
                        include_intercept=intercept,
                        backend="pallas-interpret")
    fs, fp = np.asarray(fs), np.asarray(fp)
    finite = np.isfinite(fs).all(axis=1)  # non-invertible rows blow up in both
    assert finite.sum() >= 4
    np.testing.assert_allclose(fp[finite], fs[finite], rtol=2e-4, atol=2e-4)
    assert np.array_equal(np.isfinite(fp), np.isfinite(fs))


def test_fit_backend_pallas_ragged():
    y = np.array(_arma_panel(4, 90, d_int=True, seed=6))
    y[0, :17] = np.nan  # leading NaNs (ragged start)
    y[2, 80:] = np.nan  # trailing NaNs
    r_scan = arima.fit(jnp.asarray(y), (1, 1, 1), backend="scan", max_iters=30)
    r_pal = arima.fit(
        jnp.asarray(y), (1, 1, 1), backend="pallas-interpret", max_iters=30
    )
    np.testing.assert_allclose(
        np.asarray(r_pal.params), np.asarray(r_scan.params), rtol=1e-3, atol=1e-3
    )


def test_garch_variances_matches_scan():
    from spark_timeseries_tpu.models import garch

    b, t = 4, 37
    rng = np.random.default_rng(7)
    r = jnp.asarray(rng.normal(size=(b, t)).astype(np.float32))
    params = jnp.asarray(
        np.tile([[0.1, 0.15, 0.7]], (b, 1)).astype(np.float32)
    )
    nv = jnp.asarray([t, t - 5, t, t - 2], jnp.int32)
    ref = jax.vmap(lambda pr, rv, n: garch.variances(pr, rv, n))(params, r, nv)

    start = (t - nv).astype(jnp.float32)
    t_idx = jnp.arange(t, dtype=jnp.float32)
    rz = jnp.where(t_idx[None, :] >= start[:, None], r, 0.0)
    h0 = jax.vmap(garch._masked_var)(r, nv)
    got = pk.garch_variances(params, rz, h0, start, interpret=True)
    # compare only the live span: the scan reference seeds the prefix with
    # its own start-variance convention
    mask = t_idx[None, :] >= start[:, None]
    np.testing.assert_allclose(
        np.asarray(jnp.where(mask, got, 0.0)),
        np.asarray(jnp.where(mask, ref, 0.0)),
        rtol=2e-5,
        atol=2e-5,
    )


def test_minimize_lbfgs_batched_matches_vmapped():
    # convex quadratic with per-row optima
    rng = np.random.default_rng(8)
    b, d = 16, 4
    A = jnp.asarray(rng.normal(size=(b, d, d)).astype(np.float32))
    Q = jnp.einsum("bij,bkj->bik", A, A) + 0.5 * jnp.eye(d)[None]
    x_star = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))

    def fb(x):
        r = x - x_star
        return 0.5 * jnp.einsum("bi,bij,bj->b", r, Q, r)

    x0 = jnp.zeros((b, d), jnp.float32)
    res = optim.minimize_lbfgs_batched(fb, x0, max_iters=60, tol=1e-5)
    assert bool(jnp.all(res.converged))
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(x_star), atol=1e-3)

    res_v = optim.batched_minimize(
        lambda x, i: fb(jnp.zeros((b, d), jnp.float32).at[i].set(x))[i],
        x0,
        jnp.arange(b),
        max_iters=60,
        tol=1e-5,
    )
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(res_v.x), atol=1e-3)


# ---------------------------------------------------------------------------
# GARCH fused objective
# ---------------------------------------------------------------------------


def _returns_panel(b, t, seed=11):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(scale=0.02, size=(b, t)).astype(np.float32))


def test_garch_neg_loglik_matches_scan():
    from spark_timeseries_tpu.models import garch

    b, t = 5, 47
    r = _returns_panel(b, t)
    rng = np.random.default_rng(12)
    params = jnp.asarray(
        np.column_stack(
            [
                rng.uniform(0.01, 0.2, b),
                rng.uniform(0.05, 0.2, b),
                rng.uniform(0.5, 0.8, b),
            ]
        ).astype(np.float32)
    )
    nv = jnp.asarray([t, t - 4, t, t - 9, t - 1], jnp.int32)
    start = (t - nv).astype(jnp.float32)
    rz = jnp.where(jnp.arange(t)[None, :] >= start[:, None], r, 0.0)

    ref = jax.vmap(lambda pr, rv, n: garch.neg_log_likelihood(pr, rv, n))(
        params, rz, nv
    )
    got = pk.garch_neg_loglik(params, rz, nv, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=3e-5, atol=3e-5)


def test_garch_gradient_matches_autodiff_of_scan():
    from spark_timeseries_tpu.models import garch

    b, t = 4, 39
    r = _returns_panel(b, t, seed=13)
    rng = np.random.default_rng(14)
    params = jnp.asarray(
        np.column_stack(
            [
                rng.uniform(0.01, 0.2, b),
                rng.uniform(0.05, 0.2, b),
                rng.uniform(0.5, 0.8, b),
            ]
        ).astype(np.float32)
    )
    nv = jnp.asarray([t, t - 5, t - 2, t], jnp.int32)
    start = (t - nv).astype(jnp.float32)
    rz = jnp.where(jnp.arange(t)[None, :] >= start[:, None], r, 0.0)

    def loss_scan(P):
        return jnp.sum(
            jax.vmap(lambda pr, rv, n: garch.neg_log_likelihood(pr, rv, n))(
                P, rz, nv
            )
        )

    def loss_pal(P):
        return jnp.sum(pk.garch_neg_loglik(P, rz, nv, interpret=True))

    g_ref = jax.grad(loss_scan)(params)
    g_got = jax.grad(loss_pal)(params)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref), rtol=2e-4, atol=2e-4)


def test_argarch_objective_gradient_matches_scan():
    """Exercises the r^2 / h0 cotangent paths of the GARCH adjoint: the AR(1)
    mean parameters reach the variance recursion through the residuals."""
    from spark_timeseries_tpu.models import garch

    b, t = 4, 45
    key = jax.random.PRNGKey(0)
    pars_nat = jnp.asarray(
        np.tile([[0.05, 0.4, 0.02, 0.1, 0.7]], (b, 1)).astype(np.float32)
    )
    y = jax.vmap(lambda pr, k: garch.argarch_sample(pr, k, t))(
        pars_nat, jax.random.split(key, b)
    ).astype(jnp.float32)
    nv = jnp.asarray([t, t - 3, t, t - 7], jnp.int32)
    start = (t - nv)[:, None]
    t_idx = jnp.arange(t)[None, :]
    ya = jnp.where(t_idx >= start, y, 0.0)
    rng = np.random.default_rng(15)
    u = jnp.asarray(rng.normal(scale=0.3, size=(b, 5)).astype(np.float32))

    def loss_scan(U):
        nat = jax.vmap(garch._argarch_to_natural)(U)
        return jnp.sum(
            jax.vmap(lambda pr, yv, n: garch.argarch_neg_log_likelihood(pr, yv, n))(
                nat, ya, nv
            )
        )

    def loss_pal(U):
        nat = jax.vmap(garch._argarch_to_natural)(U)
        prev = jnp.concatenate([ya[:, :1], ya[:, :-1]], axis=1)
        r = ya - nat[:, 0:1] - nat[:, 1:2] * prev
        r = jnp.where(t_idx <= start, 0.0, r)
        return jnp.sum(pk.garch_neg_loglik(nat[:, 2:], r, nv - 1, interpret=True))

    np.testing.assert_allclose(
        np.asarray(loss_pal(u)), np.asarray(loss_scan(u)), rtol=3e-5
    )
    g_ref = jax.grad(loss_scan)(u)
    g_got = jax.grad(loss_pal)(u)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref), rtol=3e-4, atol=3e-4)


def test_garch_fit_backend_pallas_matches_scan():
    from spark_timeseries_tpu.models import garch

    b, t = 6, 200
    key = jax.random.PRNGKey(3)
    pars = jnp.asarray(np.tile([[0.05, 0.15, 0.7]], (b, 1)).astype(np.float32))
    r = jax.vmap(lambda pr, k: garch.sample(pr, k, t))(
        pars, jax.random.split(key, b)
    ).astype(jnp.float32)
    r_scan = garch.fit(r, backend="scan", max_iters=50)
    r_pal = garch.fit(r, backend="pallas-interpret", max_iters=50)
    np.testing.assert_allclose(
        np.asarray(r_pal.params), np.asarray(r_scan.params), rtol=5e-2, atol=5e-3
    )


def _garch_params(b, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(np.column_stack([
        rng.uniform(1e-5, 2e-4, b), rng.uniform(0.05, 0.2, b),
        rng.uniform(0.5, 0.8, b)]).astype(np.float32))


def _scan_nll(params, rz, nv):
    from spark_timeseries_tpu.models import garch

    return jax.vmap(garch.neg_log_likelihood)(params, rz, nv)


def _scan_nll_sum(params, rz, nv):
    return jnp.sum(_scan_nll(params, rz, nv))


@pytest.mark.parametrize("ragged,t", [
    (False, 80), (True, 80),
    (True, 1100),  # two time chunks: the adjoint's ``hp`` path
])
def test_garch_neg_loglik_folded_matches_unfolded(ragged, t):
    # the pre-folded objective (garch_prefold + garch_neg_loglik_folded) is
    # the fit hot path; it must agree with the fold-per-call API bit for
    # bit, both with the scan, and its straggler gather (folded COLUMNS)
    # with a row gather of the panel
    b = 5
    r = _returns_panel(b, t, seed=61)
    nv = jnp.full((b,), t, jnp.int32)
    if ragged:
        nv = jnp.asarray([t, t - 11, t - 29, t - 3, t - 1], jnp.int32)
        r = jnp.where(jnp.arange(t)[None, :] >= (t - nv)[:, None], r, 0.0)
    params = _garch_params(b, 62)
    folded = pk.garch_prefold(r, nv if ragged else None)
    assert folded.t == t and folded.r23.shape[1:] == (8, 128)
    ref = pk.garch_neg_loglik(params, r, nv, interpret=True)
    got = pk.garch_neg_loglik_folded(params, folded, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_scan_nll(params, r, nv)), rtol=3e-5)
    g_ref = jax.grad(lambda P: jnp.sum(
        pk.garch_neg_loglik(P, r, nv, interpret=True)))(params)
    g_got = jax.grad(lambda P: jnp.sum(
        pk.garch_neg_loglik_folded(P, folded, interpret=True)))(params)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref),
                               rtol=1e-6, atol=1e-6)
    g_scan = np.asarray(jax.grad(_scan_nll_sum)(params, r, nv))
    np.testing.assert_allclose(np.asarray(g_got), g_scan, rtol=2e-3,
                               atol=2e-3 * np.abs(g_scan).max())
    idx = jnp.asarray(np.random.default_rng(63).integers(0, b, 1024))
    ref_s = pk.garch_neg_loglik(params[idx], r[idx], nv[idx], interpret=True)
    got_s = pk.garch_neg_loglik_folded(params[idx], folded.take(idx),
                                       interpret=True)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(ref_s))


def _pallas_call_outputs(jaxpr):
    """The output shapes of every ``pallas_call`` of ``jaxpr``, in order."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append([v.aval.shape for v in eqn.outvars])
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_call_outputs(sub)
    return found


@pytest.mark.parametrize("t", [80, 1100])
def test_garch_data_cotangent_only_on_demand(t):
    # garch.fit differentiates in the parameters alone: its adjoint kernel
    # has ONE output and writes no panel; a caller whose returns depend on
    # what it differentiates (ARGARCH's AR(1) mean) gets the cotangents of
    # r^2 and h0 from the same adjoint, exact against the scan's autodiff
    b = 4
    r = _returns_panel(b, t, seed=71)
    nv = jnp.asarray([t, t - 7, t - 2, t], jnp.int32)
    rz = jnp.where(jnp.arange(t)[None, :] >= (t - nv)[:, None], r, 0.0)
    params = _garch_params(b, 72)
    folded = pk.garch_prefold(rz, nv)
    panel, plane = folded.r23.shape, folded.h03.shape
    par3 = (3,) + plane[1:]

    def loss(P, f):
        return jnp.sum(pk.garch_neg_loglik_folded(P, f, interpret=True))

    calls = _pallas_call_outputs(
        jax.make_jaxpr(jax.grad(loss))(params, folded).jaxpr)
    assert calls == [[panel, plane], [par3]]  # forward (h3, ll3); adjoint
    calls = _pallas_call_outputs(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, folded).jaxpr)
    assert calls == [[panel, plane], [par3, panel, plane]]
    # the natural-layout entry differentiates through the fold
    g_p, g_r = jax.grad(lambda P, rv: jnp.sum(pk.garch_neg_loglik(
        P, rv, nv, interpret=True)), argnums=(0, 1))(params, rz)
    s_p, s_r = jax.grad(_scan_nll_sum, argnums=(0, 1))(params, rz, nv)
    live = np.asarray(jnp.arange(t)[None, :] >= (t - nv)[:, None])
    scale = np.abs(np.asarray(s_r)).max()
    np.testing.assert_allclose(np.where(live, np.asarray(g_r), 0.0) / scale,
                               np.where(live, np.asarray(s_r), 0.0) / scale,
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(g_p), np.asarray(s_p), rtol=2e-3,
                               atol=2e-3 * np.abs(np.asarray(s_p)).max())


@pytest.mark.parametrize("align_mode", ["dense", "general"])
def test_garch_fit_programs_fold_outside_their_loops(monkeypatch, align_mode):
    # the CPU's stand-in for "``copy`` left the optimizer's loops" (PERF.md
    # S6, PR 29): the panel is folded once per fit program, so no while
    # body of stage 1, stage 2 or the inline program (with its straggler
    # compaction) relayouts a panel-sized operand
    from spark_timeseries_tpu.models import garch

    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    b, t = 2048, 48
    y = jax.ShapeDtypeStruct((b, t), jnp.float32)
    static = (13, 1e-4, "pallas-interpret")
    stage1 = garch._fit_stage1_program.__wrapped__(*static, align_mode)
    inline = garch._fit_program.__wrapped__(*static, align_mode, False, True)
    stage2 = garch._fit_stage2_program.__wrapped__(*static)
    aux = jax.eval_shape(stage1, y)[1]
    (start,), cap = aux["starts"], optim.compaction_cap(b)
    folded_s, rows_s, scale_s = start["sub"]
    assert folded_s.r23.shape == (t, cap // 128, 128)
    # beside the fold stage 2 is handed one row vector, no panel
    assert rows_s == () and scale_s.shape == (cap,)
    for fn, args, n_panel in ((stage1, (y,), b * t), (inline, (y,), b * t),
                              (stage2, (start, aux["fin"]), cap * t)):
        jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
        assert any(e.primitive.name == "while" for e in jaxpr.eqns)
        assert _panel_relayouts_in_loops(jaxpr, n_panel) == []
    # the detector sees what it is for: the fold-per-call API in a loop
    f32 = jnp.float32
    per_call = jax.make_jaxpr(lambda yv: jax.lax.while_loop(
        lambda acc: acc[0] < 1.0, lambda acc: acc + pk.garch_neg_loglik(
            jnp.full((b, 3), 0.1, f32), yv, interpret=True),
        jnp.zeros((b,), f32)))(y).jaxpr
    assert ("transpose", (b, t)) in _panel_relayouts_in_loops(per_call, b * t)


def _garch_pin_fit(path, backend="pallas-interpret"):
    """One fit of the fit-level pin: ``inline-*`` (24 rows, under the
    compaction gate) or ``lazy-*`` (2048 rows through stage 1 / stage 2;
    the caller lowers the gate), ``*-dense`` or ``*-ragged`` (NaN heads and
    a NaN tail: ``align_mode="general"``)."""
    from spark_timeseries_tpu.models import garch

    b, t = (2048, 64) if path.startswith("lazy") else (24, 120)
    rng = np.random.default_rng(29)
    omega = rng.uniform(1e-5, 6e-5, size=b)
    alpha = rng.uniform(0.03, 0.25, size=b)
    beta = rng.uniform(0.4, 0.7, size=b)
    z = rng.normal(size=(b, t))
    r = np.zeros((b, t))
    h = omega / (1.0 - alpha - beta)
    for i in range(t):
        r[:, i] = np.sqrt(h) * z[:, i]
        h = omega + alpha * r[:, i] ** 2 + beta * h
    r = r.astype(np.float32)
    if path.endswith("ragged"):
        r[1, :13] = np.nan
        r[3, -9:] = np.nan
        r[5, :3] = np.nan
    return garch.fit(jnp.asarray(r), backend=backend)


# recorded on the PARENT of PR 29 (commit ebc6e06: the objective masked,
# seeded and folded the panel on every call and formed its cotangent in
# [B, T]), f32 under this suite's jax_enable_x64, XLA:CPU of this container
_GARCH_PIN = {  # params sha, objective sha, rows converged, sum of iters
    "inline-dense": ("b20b71591a795f6a", "d32eee6ca477aed0", 24, 285),
    "inline-ragged": ("14ec30eca25da049", "d1abbeb9b417a981", 24, 278),
    "lazy-dense": ("fb5390df47ce9daa", "82d0db5b5ca228ba", 2042, 22242),
    "lazy-ragged": ("ed357e134934bd45", "93bb130fb6acf24d", 2042, 22243),
}
# the scan backend's digest of inline-dense there (see _HW_PIN_HOST)
_GARCH_PIN_HOST = ("dc07ad11499b9407", "1535d07ed973ef1a", 24, 285)


@pytest.mark.parametrize("path", sorted(_GARCH_PIN))
def test_garch_fit_pinned_to_the_fold_per_call_parent(monkeypatch, path):
    # PR 29 moved the mask, the variance seed and the fold out of the
    # optimizer's loops and the likelihood's cotangent into the folded
    # layout; the kernels' arithmetic, their operands and the cotangent's
    # formula are the same, so a fit takes the same path through the
    # optimizer: params and objective bit-equal to the parent's, row for row
    # the same iterations (HW's twin below says what a miss would mean)
    from spark_timeseries_tpu.models import garch

    host = _fit_pin_digest(_garch_pin_fit("inline-dense", "scan"))
    if host != _GARCH_PIN_HOST:
        pytest.skip("another XLA:CPU code generator than the recording's")
    if path.startswith("lazy"):
        monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    assert _fit_pin_digest(_garch_pin_fit(path)) == _GARCH_PIN[path]


# ---------------------------------------------------------------------------
# EWMA fused objective
# ---------------------------------------------------------------------------


def test_ewma_sse_and_grad_matches_scan():
    from spark_timeseries_tpu.models import ewma

    b, t = 5, 61
    rng = np.random.default_rng(21)
    x = jnp.asarray(rng.normal(size=(b, t)).astype(np.float32))
    nv = jnp.asarray([t, t - 6, t, t - 11, t - 1], jnp.int32)
    start = (t - nv).astype(jnp.float32)
    xz = jnp.where(jnp.arange(t)[None, :] >= start[:, None], x, 0.0)
    alpha = jnp.asarray(rng.uniform(0.1, 0.9, b).astype(np.float32))

    ref = jax.vmap(lambda a, v, n: ewma.sse(a, v, n))(alpha, xz, nv)
    got = pk.ewma_sse(alpha, xz, nv, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def loss_scan(A):
        return jnp.sum(jax.vmap(lambda a, v, n: ewma.sse(a, v, n))(A, xz, nv))

    def loss_pal(A):
        return jnp.sum(pk.ewma_sse(A, xz, nv, interpret=True))

    g_ref = jax.grad(loss_scan)(alpha)
    g_got = jax.grad(loss_pal)(alpha)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("t", [
    61, pytest.param(2100, marks=pytest.mark.slow)])  # single-chunk and
# chunked grids; the chunked grid runs in ci.sh's unfiltered pass
def test_ewma_data_gradient_matches_scan(t):
    # ADVICE r3: jax.grad of the fused EWMA objectives w.r.t. the DATA used
    # to silently return zeros; the adjoint kernel now emits the true x
    # cotangent when (and only when) x is perturbed
    from spark_timeseries_tpu.models import ewma

    b = 4
    rng = np.random.default_rng(23)
    x = jnp.asarray(np.cumsum(rng.normal(size=(b, t)), axis=1).astype(np.float32))
    nv = jnp.asarray([t, t - 7, t - 1, max(t - t // 3, 3)], jnp.int32)
    alpha = jnp.asarray(rng.uniform(0.2, 0.8, b).astype(np.float32))
    start = (t - nv).astype(jnp.float32)
    xz = jnp.where(jnp.arange(t)[None, :] >= start[:, None], x, 0.0)

    def sse_scan(x_):
        return jnp.sum(jax.vmap(lambda a, v, n: ewma.sse(a, v, n))(alpha, x_, nv))

    def sse_pal(x_):
        return jnp.sum(pk.ewma_sse(alpha, x_, nv, interpret=True))

    gx_ref = jax.grad(sse_scan)(xz)
    gx_got = jax.grad(sse_pal)(xz)
    np.testing.assert_allclose(np.asarray(gx_got), np.asarray(gx_ref),
                               rtol=1e-4, atol=1e-4)

    # the smoothing op's x cotangent (weighted-sum pullback)
    w = jnp.asarray(rng.normal(size=(b, t)).astype(np.float32))

    def sm_scan(x_):
        s = jax.vmap(lambda a, v, n: ewma.smooth(a, v, n))(alpha, x_, nv)
        return jnp.sum(w * s)

    def sm_pal(x_):
        return jnp.sum(w * pk.ewma_smooth(alpha, x_, start, interpret=True))

    np.testing.assert_allclose(
        np.asarray(jax.grad(sm_pal)(xz)), np.asarray(jax.grad(sm_scan)(xz)),
        rtol=1e-4, atol=1e-4,
    )


def test_ewma_fit_backend_pallas_matches_scan():
    from spark_timeseries_tpu.models import ewma

    rng = np.random.default_rng(22)
    b, t = 6, 90
    x = np.cumsum(rng.normal(size=(b, t)), axis=1).astype(np.float32)
    x[1, :13] = np.nan  # ragged head
    r_scan = ewma.fit(jnp.asarray(x), backend="scan")
    r_pal = ewma.fit(jnp.asarray(x), backend="pallas-interpret")
    np.testing.assert_allclose(
        np.asarray(r_pal.params), np.asarray(r_scan.params), rtol=1e-3, atol=1e-3
    )


# ---------------------------------------------------------------------------
# Holt-Winters additive fused objective
# ---------------------------------------------------------------------------


def _seasonal_panel(b, t, m, seed=31):
    rng = np.random.default_rng(seed)
    tt = np.arange(t)
    base = 10.0 + 0.05 * tt[None, :]
    seas = 2.0 * np.sin(2 * np.pi * tt[None, :] / m)
    noise = rng.normal(scale=0.3, size=(b, t))
    return jnp.asarray((base + seas + noise).astype(np.float32))


def test_hw_sse_and_grad_matches_scan():
    from spark_timeseries_tpu.models import holtwinters as hw

    b, t, m = 4, 73, 7
    y = _seasonal_panel(b, t, m)
    rng = np.random.default_rng(32)
    params = jnp.asarray(rng.uniform(0.05, 0.9, (b, 3)).astype(np.float32))

    ref = jax.vmap(lambda pr, v: hw.sse(pr, v, m, False))(params, y)
    got = pk.hw_additive_sse(params, y, m, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=1e-3)

    def loss_scan(P):
        return jnp.sum(jax.vmap(lambda pr, v: hw.sse(pr, v, m, False))(P, y))

    def loss_pal(P):
        return jnp.sum(pk.hw_additive_sse(P, y, m, interpret=True))

    g_ref = jax.grad(loss_scan)(params)
    g_got = jax.grad(loss_pal)(params)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref), rtol=1e-3, atol=1e-2)


def test_hw_fit_backend_pallas_matches_scan():
    from spark_timeseries_tpu.models import holtwinters as hw

    b, t, m = 5, 96, 8
    y = _seasonal_panel(b, t, m, seed=33)
    r_scan = hw.fit(y, m, "additive", backend="scan", max_iters=40)
    r_pal = hw.fit(y, m, "additive", backend="pallas-interpret", max_iters=40)
    np.testing.assert_allclose(
        np.asarray(r_pal.params), np.asarray(r_scan.params), rtol=2e-2, atol=2e-2
    )


def test_hw_multiplicative_sse_and_grad_matches_scan():
    from spark_timeseries_tpu.models import holtwinters as hw

    b, t, m = 4, 73, 7
    y = _seasonal_panel(b, t, m, seed=35) + 25.0  # positive level
    rng = np.random.default_rng(36)
    params = jnp.asarray(rng.uniform(0.05, 0.9, (b, 3)).astype(np.float32))

    ref = jax.vmap(lambda pr, v: hw.sse(pr, v, m, True))(params, y)
    got = pk.hw_sse(params, y, m, True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=1e-3)

    def loss_scan(P):
        return jnp.sum(jax.vmap(lambda pr, v: hw.sse(pr, v, m, True))(P, y))

    def loss_pal(P):
        return jnp.sum(pk.hw_sse(P, y, m, True, interpret=True))

    g_ref = jax.grad(loss_scan)(params)
    g_got = jax.grad(loss_pal)(params)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref), rtol=1e-3, atol=1e-2)


def _hw_mult_hard_case(case):
    """A few multiplicative rows the two-panel forward and its adjoint
    (ISSUE 45) have to hold: ``past-one-chunk`` (T > 1024: level, trend and
    both rings cross a time chunk, and no neighbour block is read any more),
    ``eps-clamp`` (one hour of every day structurally ZERO, so that slot's
    seasonal factor is 0 from its seed on — ``s_pass`` 0 there — and with
    ``alpha`` = 1 the level is 0 after it — ``l_pass`` 0 from the RECOMPUTED
    level) -> ``(y, params, m)``."""
    rng = np.random.default_rng(451)
    if case == "past-one-chunk":
        b, t, m = 3, 1100, 24
        y = _seasonal_panel(b, t, m, seed=452) + 25.0
        par = rng.uniform(0.05, 0.6, (b, 3))
    else:
        b, t, m = 6, 64, 4
        y = np.array(_seasonal_panel(b, t, m, seed=453)) + 25.0
        y[:, 2::m] = 0.0
        par = rng.uniform(0.05, 0.9, (b, 3))
        par[:2, 0] = 1.0  # nl = y / s: 0 at the zero hour
        par[1:3, 2] = 1.0  # snew = y / nl
    return jnp.asarray(y), jnp.asarray(par.astype(np.float32)), m


@pytest.mark.parametrize("case", ["past-one-chunk", "eps-clamp"])
def test_hw_multiplicative_two_panel_gradient_matches_scan(case):
    from spark_timeseries_tpu.models import holtwinters as hw

    y, params, m = _hw_mult_hard_case(case)

    def scan(P):
        return jax.vmap(lambda pr, v: hw.sse(pr, v, m, True))(P, y)

    def pal(P):
        return pk.hw_sse(P, y, m, True, interpret=True)

    ref, got = np.asarray(scan(params)), np.asarray(pal(params))
    assert np.isfinite(ref).all() and (ref > 0).all()
    np.testing.assert_allclose(got, ref, rtol=5e-4)
    w = jnp.asarray(1.0 / ref)  # every row's gradient at its own scale
    g_ref = np.asarray(jax.grad(lambda P: jnp.sum(w * scan(P)))(params))
    g_got = np.asarray(jax.grad(lambda P: jnp.sum(w * pal(P)))(params))
    assert np.isfinite(g_got).all() and np.abs(g_ref).max() > 0
    np.testing.assert_allclose(g_got, g_ref, rtol=2e-3,
                               atol=2e-3 * np.abs(g_ref).max())
    if case == "eps-clamp":
        # the clamps are AT WORK in these rows: the zero hour's factor is
        # under eps at every visit, and alpha = 1 leaves a level of 0
        f = pk.hw_prefold(y, pk.hw_seeds(y, m, True, None))
        (so3, p3, _), _ = pk._hw_fwd_call_f(True, m, True, True, params, f)
        so, p = (np.asarray(pk._unfold(x, y.shape[0])) for x in (so3, p3))
        assert (so[:, 2::m] < 1e-12).all() and (so[:, 1::m] > 0.1).all()
        lt = np.asarray(pk._hw_mult_level(params[:, :1], y, so, p))
        assert (lt[:2, 2::m] < 1e-12).all() and (lt[3:] > 1.0).all()


@pytest.mark.parametrize("case", ["past-one-chunk", "eps-clamp"])
def test_hw_multiplicative_recomputed_level_is_the_forwards(monkeypatch,
                                                            case):
    # ISSUE 45: the adjoint recomputes L_t from (y_t, S_t, P_t = L_{t-1} +
    # T_{t-1}) by the forward's own expression, and the clamp's subgradient
    # hangs on it.  With beta = 0 and a zero trend seed the trend stays an
    # exact 0, so the forward's carried level — what the replay saved as
    # ``lv3`` — IS the next step's saved P: L_t = P_{t+1} bit for bit, and
    # the recomputation from the two saved panels has to reproduce it
    y, params, m = _hw_mult_hard_case(case)
    if case == "past-one-chunk":  # two chunks, a short interpreted loop
        monkeypatch.setattr(pk, "_CHUNK_T", 16)
        y = y[:, :29]
    b, t = y.shape
    params = params.at[:, 1].set(0.0)
    f = pk.hw_prefold(y, pk.hw_seeds(y, m, True, None))
    f = dataclasses.replace(f, t03=jnp.zeros_like(f.t03))
    (so3, p3, _), par3 = pk._hw_fwd_call_f(True, m, True, True, params, f)
    assert pk._time_layout(t)[2] == (2 if case == "past-one-chunk" else 1)
    lt3 = jax.jit(pk._hw_mult_level)(par3[0], f.y3, so3, p3)
    lt, p = (np.asarray(x)[:t] for x in (lt3, p3))
    assert np.isfinite(lt).all() and np.abs(lt).max() > 1.0
    assert lt[:-1].tobytes() == p[1:].tobytes()


@pytest.mark.parametrize("mult", [False, True])
def test_hw_ragged_sse_and_grad_matches_scan(mult):
    from spark_timeseries_tpu.models import holtwinters as hw

    b, t, m = 4, 80, 6
    y = _seasonal_panel(b, t, m, seed=37) + (25.0 if mult else 0.0)
    nv = jnp.asarray([t, t - 11, t - 29, t - 3], jnp.int32)
    # right-aligned convention: zero the invalid prefix (align_right output)
    tt = jnp.arange(t)[None, :]
    y = jnp.where(tt >= (t - nv)[:, None], y, 0.0)
    rng = np.random.default_rng(38)
    params = jnp.asarray(rng.uniform(0.05, 0.9, (b, 3)).astype(np.float32))

    ref = jax.vmap(lambda pr, v, n: hw.sse(pr, v, m, mult, n))(params, y, nv)
    got = pk.hw_sse(params, y, m, mult, nv, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=1e-3)

    def loss_scan(P):
        return jnp.sum(jax.vmap(
            lambda pr, v, n: hw.sse(pr, v, m, mult, n))(P, y, nv))

    def loss_pal(P):
        return jnp.sum(pk.hw_sse(P, y, m, mult, nv, interpret=True))

    g_ref = jax.grad(loss_scan)(params)
    g_got = jax.grad(loss_pal)(params)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref), rtol=1e-3, atol=1e-2)


@pytest.mark.parametrize("mult,ragged,t", [
    (False, False, 80), (False, True, 80), (True, False, 80),
    (True, True, 80),
    (False, True, 1100),  # two time chunks: the adjoint's ``hp`` path
])
def test_hw_sse_folded_matches_unfolded(mult, ragged, t):
    # the pre-folded objective (hw_prefold + hw_sse_folded) is the fit hot
    # path; it must agree with the fold-per-call API bit-for-bit, and its
    # straggler gather (folded COLUMNS) with a row gather of the panel
    b, m = 5, 6
    y = _seasonal_panel(b, t, m, seed=51) + (25.0 if mult else 0.0)
    nv = None
    if ragged:
        nv = jnp.asarray([t, t - 11, t - 29, t - 3, t - 1], jnp.int32)
        y = jnp.where(jnp.arange(t)[None, :] >= (t - nv)[:, None], y, 0.0)
    rng = np.random.default_rng(52)
    params = jnp.asarray(rng.uniform(0.05, 0.9, (b, 3)).astype(np.float32))
    seeds = pk.hw_seeds(y, m, mult, nv)
    folded = pk.hw_prefold(y, seeds)
    ref = pk.hw_sse_seeded(params, y, seeds, m, mult, interpret=True)
    got = pk.hw_sse_folded(params, folded, m, mult, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    g_ref = jax.grad(lambda P: jnp.sum(
        pk.hw_sse_seeded(P, y, seeds, m, mult, interpret=True)))(params)
    g_got = jax.grad(lambda P: jnp.sum(
        pk.hw_sse_folded(P, folded, m, mult, interpret=True)))(params)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref),
                               rtol=1e-6, atol=1e-6)
    idx = jnp.asarray(rng.integers(0, b, 1024))
    ref_s = pk.hw_sse_seeded(params[idx], y[idx],
                             tuple(x[idx] for x in seeds), m, mult,
                             interpret=True)
    got_s = pk.hw_sse_folded(params[idx], folded.take(idx), m, mult,
                             interpret=True)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(ref_s))


def _panel_relayouts_in_loops(jaxpr, n_panel, in_loop=False):
    """``(primitive, operand shape)`` of every ``transpose`` / ``pad`` /
    ``copy`` of an operand with at least ``n_panel`` elements inside a
    ``while`` of ``jaxpr`` (kernel bodies aside: a ``pallas_call`` works on
    blocks)."""
    found = []
    for eqn in jaxpr.eqns:
        if (in_loop and eqn.primitive.name in ("transpose", "pad", "copy")
                and eqn.invars[0].aval.size >= n_panel):
            found.append((eqn.primitive.name, eqn.invars[0].aval.shape))
        if eqn.primitive.name == "pallas_call":
            continue
        inner = in_loop or eqn.primitive.name == "while"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _panel_relayouts_in_loops(sub, n_panel, inner)
    return found


@pytest.mark.parametrize("align_mode", ["dense", "general"])
@pytest.mark.parametrize("model_type", ["additive", "multiplicative"])
def test_hw_fit_programs_fold_outside_their_loops(monkeypatch, align_mode,
                                                  model_type):
    # the CPU's stand-in for "``copy`` left the optimizer's loops" (PERF.md
    # S6, PR 26): the panel is folded once per fit program, so no while
    # body of stage 1, stage 2 or the inline program (with its straggler
    # compaction) relayouts a panel-sized operand
    from spark_timeseries_tpu.models import holtwinters as hw

    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    b, t, m = 2048, 48, 6
    mult = model_type == "multiplicative"
    n_starts = 3 if mult else 1
    y = jax.ShapeDtypeStruct((b, t), jnp.float32)
    static = (m, mult, 13, 1e-4, "pallas-interpret")
    stage1 = hw._fit_stage1_program.__wrapped__(*static, align_mode, n_starts)
    inline = hw._fit_program.__wrapped__(*static, align_mode, False, True,
                                         n_starts)
    stage2 = hw._fit_stage2_program.__wrapped__(*static)
    aux = jax.eval_shape(stage1, y)[1]["starts"][0]
    cap = optim.compaction_cap(b)
    assert aux["sub"][0].y3.shape == (t, cap // 128, 128)
    for fn, arg, n_panel in ((stage1, y, b * t), (inline, y, b * t),
                             (stage2, aux, cap * t)):
        jaxpr = jax.make_jaxpr(fn)(arg).jaxpr
        assert any(e.primitive.name == "while" for e in jaxpr.eqns)
        assert _panel_relayouts_in_loops(jaxpr, n_panel) == []
    # the detector sees what it is for: the fold-per-call API in a loop
    f32 = jnp.float32
    seeds = pk.hw_seeds(jnp.ones((b, t), f32), m, mult, None)
    per_call = jax.make_jaxpr(lambda yv: jax.lax.while_loop(
        lambda acc: acc[0] < 1.0, lambda acc: acc + pk.hw_sse_seeded(
            jnp.full((b, 3), 0.5, f32), yv, seeds, m, mult, interpret=True),
        jnp.zeros((b,), f32)))(y).jaxpr
    assert ("transpose", (b, t)) in _panel_relayouts_in_loops(per_call, b * t)


def _panel_ops(eqns, n_panel, names=("mul", "select_n", "div")):
    """``(primitive, result shape)`` of every ``names`` equation among
    ``eqns``, nested jaxprs included (kernel bodies aside), with a result of
    at least ``n_panel`` elements."""
    found = []
    for eqn in eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name in names:
            found += [(eqn.primitive.name, v.aval.shape)
                      for v in eqn.outvars if v.aval.size >= n_panel]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _panel_ops(sub.eqns, n_panel, names)
    return found


def _objective_adjoints(jaxpr, n_panel):
    """Every objective gradient of ``jaxpr`` at any depth, as ``(panel
    operands of the adjoint call, panel-sized mul / select_n / div between
    the forward call and it)``: an adjoint ``pallas_call`` is one that reads
    a panel an earlier ``pallas_call`` of the same jaxpr wrote (the saved
    residuals), the forward call the latest such."""
    found, wrote = [], {}
    for i, eqn in enumerate(jaxpr.eqns):
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += _objective_adjoints(sub, n_panel)
            continue
        panels = {v for v in eqn.invars
                  if not isinstance(v, jax.extend.core.Literal)
                  and v.aval.size >= n_panel}
        fwd = [wrote[v] for v in panels if v in wrote]
        if fwd:
            found.append((len(panels), _panel_ops(
                jaxpr.eqns[max(fwd) + 1:i], n_panel)))
        wrote.update({v: i for v in eqn.outvars if v.aval.size >= n_panel})
    return found


def _stage_programs(family, b, t):
    """-> ``adjoint_panels``, then stage 1, stage 2 and the inline program
    of a family's lazy fit as ``(fn, args, rows)``, on shapes alone."""
    from spark_timeseries_tpu.models import garch
    from spark_timeseries_tpu.models import holtwinters as hw

    y = jax.ShapeDtypeStruct((b, t), jnp.float32)
    if family == "arima-grid3":
        # a fused order search: 3 orders a row, so a third of the rows make
        # the same cells; the adjoint reads the ONE panel and the cells'
        # error panels
        specs = (((1, 1, 0), None), ((0, 1, 1), None), ((2, 1, 2), None))
        static = (specs, True, "pallas-interpret", 13, 1e-4)
        y = jax.ShapeDtypeStruct((b // 2, t), jnp.float32)
        stage1 = arima._grid_stage1_program.__wrapped__(*static, "dense")
        aux = jax.eval_shape(stage1, y)[1]
        cap = arima._grid_cap(3 * b // 2, "pallas-interpret", True)
        return pk.CSS_ADJOINT_PANELS, (
            (stage1, (y,), b // 2),
            (arima._grid_fit_program.__wrapped__(*static, "dense"), (y,),
             b // 2),
            (arima._grid_stage2_program.__wrapped__(*static),
             (aux["starts"][0], aux["fin"]), cap))
    if family in ("arima111", "sarima-airline4"):
        seasonal = (0, 1, 1, 4) if family == "sarima-airline4" else None
        order = (0, 1, 1) if seasonal else (1, 1, 1)
        static = (order, True, "pallas-interpret", 13, 1e-4)
        stage1 = arima._fit_stage1_program.__wrapped__(
            *static, False, "dense", False, seasonal)
        stage2 = arima._fit_stage2_program.__wrapped__(*static, seasonal)
        inline = arima._fit_program.__wrapped__(
            order, True, "css-lbfgs", *static[2:], False, "dense", False,
            True, seasonal)
        panels = pk.CSS_ADJOINT_PANELS
    elif family.startswith("hw"):
        mult = family == "hw-mult"
        n_starts = 3 if mult else 1
        static = (4, mult, 13, 1e-4, "pallas-interpret")
        stage1 = hw._fit_stage1_program.__wrapped__(*static, "dense",
                                                    n_starts)
        stage2 = hw._fit_stage2_program.__wrapped__(*static)
        inline = hw._fit_program.__wrapped__(*static, "dense", False, True,
                                             n_starts)
        panels = pk.HW_ADJOINT_PANELS[mult]
    else:
        static = (13, 1e-4, "pallas-interpret")
        stage1 = garch._fit_stage1_program.__wrapped__(*static, "dense")
        stage2 = garch._fit_stage2_program.__wrapped__(*static)
        inline = garch._fit_program.__wrapped__(*static, "dense", False, True)
        panels = pk.GARCH_ADJOINT_PANELS
    aux = jax.eval_shape(stage1, y)[1]
    start, cap = aux["starts"][0], optim.compaction_cap(b)
    args2 = (start,) if family.startswith("hw") else (start, aux["fin"])
    return panels, ((stage1, (y,), b), (inline, (y,), b),
                    (stage2, args2, cap))


@pytest.mark.parametrize("family", ["arima111", "sarima-airline4", "hw-add",
                                    "hw-mult", "garch11", "arima-grid3"])
def test_fit_programs_form_no_cotangent_panel(monkeypatch, family):
    # the CPU's stand-in for "``broadcast_multiply_fusion`` /
    # ``multiply_select_fusion`` left the device's ops" (PERF.md §6, PR 35):
    # in stage 1, stage 2 and the inline program no panel-sized mul /
    # select_n / div sits between an objective's forward call and its
    # adjoint call — the kernel forms the cotangent from the plane — and
    # the adjoint takes the panels the stage spans report
    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    b, t = 2048, 48
    panels, programs = _stage_programs(family, b, t)
    for fn, args, rows in programs:
        # the differenced panel is the shortest: d = 1 + 1 + 4 at most
        n_panel = rows * (t - 6)
        adjoints = _objective_adjoints(jax.make_jaxpr(fn)(*args).jaxpr,
                                       n_panel)
        assert adjoints, "every program takes gradients"
        assert adjoints == [(panels, [])] * len(adjoints)
    # the detector sees what it is for: the parent's idiom, a cotangent
    # panel formed by XLA between the two calls
    f32 = jnp.float32
    y3 = jnp.zeros((t, b // 128, 128), f32)
    zb3 = jnp.zeros((1, b // 128, 128), f32)
    par = jnp.zeros((b, 3), f32)

    def parent_idiom(P):
        (e3, css3), (_, par3, _) = pk._css_fwd_call_f(
            1, 1, True, "both", P, y3, zb3, t)
        return pk._css_errors_bwd_f(1, 1, True, (y3, par3, zb3, e3),
                                    2.0 * e3 * css3, b, t)

    assert _objective_adjoints(jax.make_jaxpr(parent_idiom)(par).jaxpr,
                               b * t) == [
        (3, [("mul", y3.shape), ("mul", y3.shape)])]


# What each stage program computes, as a dataflow DAG (``_dag_hash``), on
# the parent of PR 44 (``git archive e3e06aa``, the same shapes, this
# container's jax): that PR gave a several-start family's merge a second
# return value and its stage-1 program one more output, and a ONE-start
# family's programs had to stay what they were — ARIMA, the seasonal orders,
# the order grid, GARCH and the additive Holt-Winters, whose "merge" is its
# finalize.  The multiplicative model's stage 1 gains exactly the
# ``merge_switched`` leaf: every other output is the parent's.  A PR that
# means to change a program re-records its line: PR 45 moved the three
# ``hw-mult`` lines (its parent's: 6284d0f464699f72, 7eb401bf999fa167,
# edfa229a270e3d3c) — the multiplicative ``save_resid`` forward writes two
# panels where it wrote four and the adjoint call takes three panel
# operands and no seed where it took five and two — and no other.  PR 46
# moved the stage-1 and inline lines of the three families that fold through
# ``css_prefold`` (its parent's: ``arima111`` 7638fd7c49350acf,
# 619ba31e5ca33f0d; ``sarima-airline4`` d496a5a60103e978, e89b410f8d8ac809;
# ``arima-grid3`` 8f73780391495fd1, 705ce5312a4620d1) — the panel is folded
# first and differenced, masked and padded in that layout — and no stage 2,
# no Holt-Winters and no GARCH line.
_PARENT_DAG = {
    ("arima111", "stage1"): "a19ed580c0b9f8c9",
    ("arima111", "inline"): "f5dcf78cae09274c",
    ("arima111", "stage2"): "db7b40ae1b1950c5",
    ("sarima-airline4", "stage1"): "ae607e8a25cfb1da",
    ("sarima-airline4", "inline"): "4bc27d111ab4968f",
    ("sarima-airline4", "stage2"): "584758d6364c5372",
    ("hw-add", "stage1"): "31a63398f6c929bc",
    ("hw-add", "inline"): "2c21ba1b533f9c39",
    ("hw-add", "stage2"): "05e3ef9b497efa76",
    ("garch11", "stage1"): "8c8d22c437eea059",
    ("garch11", "inline"): "fd2ddb045539d854",
    ("garch11", "stage2"): "716af38572ef7c22",
    ("arima-grid3", "stage1"): "bd9f6f6b08b284c4",
    ("arima-grid3", "inline"): "c8f9ca653165f8a7",
    ("arima-grid3", "stage2"): "5aeb3b8446744b3c",
    ("hw-mult", "stage1"): "7a3e4b9d7f71986d",
    ("hw-mult", "inline"): "0a6f1ae43d2ee7ac",
    ("hw-mult", "stage2"): "72b9c939507db870",
}


@pytest.mark.parametrize("family,program", sorted(_PARENT_DAG))
def test_stage_programs_are_the_parents_dataflow(monkeypatch, family,
                                                 program):
    from _dag_hash import dag_hash

    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    _, programs = _stage_programs(family, 2048, 48)
    fn, args, _ = programs[("stage1", "inline", "stage2").index(program)]
    paths = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(
                 jax.eval_shape(fn, *args))[0]]
    new = [i for i, path in enumerate(paths) if "merge_switched" in path]
    assert len(new) == ((family, program) == ("hw-mult", "stage1"))
    assert dag_hash(fn, *args, drop=new) == _PARENT_DAG[family, program]


def _row_major_prefold(y, order, n_valid=None, *, lags=()):
    """``pk.css_prefold`` as it stood before ISSUE 46: the differences at
    ``lags`` taken row by row (lane shifts of the ``[B, T]`` panel), the
    mask and the pad in that layout, the fold last."""
    yd = y
    for lag in lags:
        yd = yd[:, lag:] - yd[:, :-lag]
    p = pk._span(pk._lags(order[0]))
    b, n = yd.shape
    nv = (jnp.full((b,), n, yd.dtype) if n_valid is None
          else n_valid.astype(yd.dtype))
    start = n - nv
    t_idx = jnp.arange(n, dtype=yd.dtype)
    ydz = jnp.where(t_idx[None, :] >= start[:, None], yd, 0.0)
    tp, _, _ = pk._time_layout(n)
    y3 = pk._fold(jnp.pad(ydz, ((0, 0), (0, tp - n))))
    zb3 = pk._fold((start + p).astype(yd.dtype)[:, None])
    return y3, zb3


def _ragged_heads(y, lag_sum, seed):
    """-> (``y`` with ragged unobserved heads, the aligned rows' valid
    lengths): a full row, an empty one, one as long as the lags reach and
    one shorter; the heads NaN in the even rows and 0 in the odd ones."""
    b, t = y.shape
    nv0 = np.random.default_rng(seed).integers(0, t + 1, size=b)
    nv0[:4] = [t, 0, lag_sum, max(lag_sum - 1, 0)]
    head = np.arange(t)[None, :] < (t - nv0)[:, None]
    fill = np.where(np.arange(b) % 2 == 0, np.nan, 0.0)[:, None]
    return (jnp.where(head, jnp.asarray(fill, y.dtype), y),
            jnp.asarray(nv0, jnp.int32))


def _same_bits(got, want):
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(np.signbit(got), np.signbit(want))


@pytest.mark.parametrize("t", [37, 960, 1000, 2100])
@pytest.mark.parametrize("ragged", [False, True], ids=["dense", "ragged"])
@pytest.mark.parametrize("seasonal", [(0, 0), (1, 24)])
@pytest.mark.parametrize("d", [0, 1, 2])
def test_css_prefold_differences_in_the_folded_layout(d, seasonal, ragged, t):
    # ISSUE 46: fold first, then the differences as shifts of the major
    # axis, the mask and the padded tail in one pass — bit for bit what the
    # row-by-row differences and the fold-last ``css_prefold`` gave, on one
    # time chunk and on three, over rows that are no multiple of 1,024
    D, s = seasonal
    lags = (1,) * d + (s,) * D
    b = 1100
    y = _arma_panel(b, t, d_int=True, seed=t + d)
    nvd = None
    if ragged:
        y, nv0 = _ragged_heads(y, sum(lags), seed=7)
        nvd = nv0 - sum(lags)
    yd = jax.vmap(lambda v: arima._difference_seasonal(
        arima._difference(v, d), D, s))(y)
    want = _row_major_prefold(yd, (2, 0, 1), nvd)
    got = jax.jit(lambda y, nv: pk.css_prefold(y, (2, 0, 1), nv, lags=lags))(
        y, nvd)
    tp = pk._time_layout(t - sum(lags))[0]
    assert got[0].shape == (tp, 2048 // 128, 128)
    for g, w in zip(got, want):
        _same_bits(g, w)


@pytest.mark.parametrize("case", ["arima111-dense", "arima111-ragged",
                                  "airline4-dense", "airline4-ragged"])
def test_arima_fit_is_the_row_major_preps_fit(monkeypatch, case):
    # ISSUE 46: the order of the prep's operations is all that changed, so
    # ``arima.fit`` on a 2,048-row panel returns what the row-major prep's
    # program returns, bit for bit
    model, shape = case.split("-")
    seasonal = (0, 1, 1, 4) if model == "airline4" else None
    order = (0, 1, 1) if seasonal else (1, 1, 1)
    b, t = 2048, 64
    y = _arma_panel(b, t, d_int=True, seed=46)
    if shape == "ragged":  # unobserved heads, and one unobserved tail
        nv0 = _ragged_heads(y, 0, seed=8)[1]
        y = jnp.where(jnp.arange(t)[None, :] < (t - nv0)[:, None], jnp.nan, y)
        y = y.at[5, -3:].set(jnp.nan)
    got = arima.fit(y, order, seasonal=seasonal, backend="pallas-interpret",
                    max_iters=6)
    from spark_timeseries_tpu.models.base import resolve_align_mode

    mode = resolve_align_mode(y, None)
    assert mode == ("dense" if shape == "dense" else "general")
    monkeypatch.setattr(pk, "css_prefold", _row_major_prefold)
    want = jax.jit(arima._fit_program.__wrapped__(
        order, True, "css-lbfgs", "pallas-interpret", 6, 1e-4, False, mode,
        False, True, seasonal))(y)
    assert int(jnp.sum(want.converged)) > 0
    for name in ("params", "neg_log_likelihood", "iters", "converged",
                 "status"):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(want, name)),
                                      err_msg=name)


@pytest.mark.parametrize("family", ["arima111", "sarima-airline4"])
def test_arima_prep_transposes_the_panel_twice(monkeypatch, family):
    # ISSUE 46, beside the cotangent pin above: the stage-1 program of a
    # dense fit folds the panel FIRST and differences it there — no
    # panel-sized ``sub`` has a ``[B, n]`` row-major result — and transposes
    # a panel exactly TWICE, both times ahead of the lockstep loop: the fold,
    # and the differenced panel back to rows for the straggler gathers
    # (``pk.series_major``: a column gather of ``y3`` inside the loop is a
    # transpose of the whole panel every call on the chip)
    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    b, t = 2048, 48
    n_panel = b * (t - 6)

    def relayouts():
        _, programs = _stage_programs(family, b, t)
        (stage1, args, _), *rest = programs
        jaxpr = jax.make_jaxpr(stage1)(*args).jaxpr
        loop = [e.primitive.name for e in jaxpr.eqns].index("while")
        return (_panel_ops(jaxpr.eqns[:loop], n_panel, ("transpose",)),
                _panel_ops(jaxpr.eqns, n_panel, ("transpose",)),
                [shape for _, shape in _panel_ops(jaxpr.eqns, n_panel,
                                                  ("sub",))
                 if len(shape) == 2],
                [_panel_ops(jax.make_jaxpr(fn)(*a).jaxpr.eqns, n_panel,
                            ("transpose",)) for fn, a, _ in rest])

    fold_and_back = [("transpose", (t, b)), ("transpose", (b, t))]
    ahead, anywhere, row_major_subs, others = relayouts()
    assert ahead == anywhere == fold_and_back
    assert row_major_subs == []
    # the inline program is the same prep; stage 2 takes gathered columns
    assert others == [fold_and_back, []]
    # the detector sees what it is for: the row-major prep's lane-shifted
    # differences, one a lag
    monkeypatch.setattr(pk, "css_prefold", _row_major_prefold)
    lags = 2 if family == "sarima-airline4" else 1
    assert len(relayouts()[2]) == lags


def test_straggler_gathers_read_the_series_major_panel():
    # the gather of a straggler subset is ``take_series``' columns, bit for
    # bit, read as rows of ``series_major``'s copy — for a fit's panel and
    # for the order grid's cells
    rng = np.random.default_rng(46)
    y = jnp.asarray(rng.normal(size=(3000, 37)).astype(np.float32))
    nv = jnp.asarray(rng.integers(0, 37, size=3000), jnp.int32)
    idxc = jnp.asarray(rng.permutation(3000)[:1024])
    f = arima._CssFolded.of(y, (2, 0, 1), nv - 1, (1,))
    assert f.y_rows.shape == (3072, 40)
    sub = f.take(idxc)
    assert sub.y_rows is None and sub.t == f.t
    want = pk.take_series((f.y3, f.zb3), idxc)
    _same_bits(sub.y3, want[0])
    _same_bits(sub.zb3, want[1])
    g = pk.css_grid_prefold(y, [0, 2, 1], nv - 1, lags=(1,))
    cells = jnp.asarray(rng.permutation(3 * 3000)[:2048])
    sub = pk.take_cells(g, cells)
    assert sub.y_rows is None and (sub.b, sub.k) == (2048, 1)
    _same_bits(sub.y3, pk.take_series(g.y3, cells % 3000))


def test_hw_additive_gradient_moves_one_panel_each_way():
    # ISSUE 43: the additive ``save_resid`` forward writes ONE panel-sized
    # output (the raw one-step errors) and the adjoint call reads ONE
    # panel-sized operand (it).  ISSUE 45: the multiplicative forward writes
    # TWO (the old season, L + T) and its adjoint reads THREE (them and the
    # panel) where the replay wrote four and read five.  The value a
    # gradient pass returns is the value-only call's
    b, t, m = 1024, 29, 4
    par = jnp.asarray(np.random.default_rng(92).uniform(
        0.05, 0.9, (b, 3)).astype(np.float32))
    for mult, wrote, read in ((False, 1, 1), (True, 2, 3)):
        y = _seasonal_panel(b, t, m, seed=91) + (25.0 if mult else 0.0)
        f = pk.hw_prefold(y, pk.hw_seeds(y, m, mult, None))
        sse = functools.partial(pk._hw_ss_f, True, m, mult)
        ones = jnp.ones((b,), jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda P: jax.vjp(lambda q: sse(q, f), P)[1](ones))(par)
        fwd, adj = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        n_panel = f.y3.size
        assert sum(v.aval.size >= n_panel for v in fwd.outvars) == wrote
        assert sum(v.aval.size >= n_panel for v in adj.invars) == read
        assert read == pk.HW_ADJOINT_PANELS[mult]
        # what the forward saved is all the adjoint reads of that size, but
        # the multiplicative panel itself
        assert ({v for v in adj.invars if v.aval.size >= n_panel}
                - set(fwd.outvars) == ({fwd.invars[0]} if mult else set()))
        value, _ = jax.vjp(lambda q: sse(q, f), par)
        assert np.asarray(value).tobytes() == np.asarray(sse(par, f)).tobytes()
        assert np.isfinite(np.asarray(value)).all()


def _hw_pin_fit(path, model_type, backend="pallas-interpret"):
    """One fit of the fit-level pin: ``inline`` (24 rows, under the
    compaction gate), ``ragged`` (the same with NaN heads and a NaN tail:
    ``align_mode="general"``) or ``lazy`` (2048 rows through stage 1 /
    stage 2; the caller lowers the gate)."""
    from spark_timeseries_tpu.models import holtwinters as hw

    b, t, m = (2048, 48, 8) if path == "lazy" else (24, 72, 6)
    rng = np.random.default_rng(7)
    tt = np.arange(t, dtype=np.float32)
    amp = rng.uniform(0.5, 3.0, size=(b, 1))
    y = (10.0 + 0.05 * tt[None, :] + amp * np.sin(2 * np.pi * tt[None, :] / m)
         + rng.uniform(0.05, 0.6, size=(b, 1)) * rng.normal(size=(b, t)))
    y = (y + (25.0 if model_type == "multiplicative" else 0.0)).astype(
        np.float32)
    if path == "ragged":
        y[1, :13] = np.nan
        y[3, -9:] = np.nan
        y[5, :3] = np.nan
    return hw.fit(jnp.asarray(y), m, model_type, backend=backend)


def _sha(*arrays):
    import hashlib

    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:16]


def _fit_pin_digest(r):
    return (_sha(r.params), _sha(r.neg_log_likelihood),
            int(np.sum(np.asarray(r.converged))),
            int(np.sum(np.asarray(r.iters))))


# recorded on the PARENT of PR 26 (commit 31c2558: the objective folded the
# panel on every call), f32 under this suite's jax_enable_x64, XLA:CPU of
# this container.  The three ``-additive`` entries were RE-RECORDED by PR 43
# on the same host (``_HW_PIN_HOST`` matched): the additive adjoint forms
# ``a r_t`` where the replay formed ``L_t - L_{t-1} - T_{t-1}``, the
# gradient moves in its last place and a fit takes another step here and
# there (the parent's: inline 22442193b2ba36d9 / 3aacc24fed3e51c3 / 24 / 179,
# ragged 9227f7f8898068d1 / 70b6f731343fb122 / 24 / 179, lazy
# be22edd45bafdb3b / 2c89d60dc07122c1 / 2015 / 22905).  The three
# ``-multiplicative`` entries were RE-RECORDED by PR 45 on the same host: its
# adjoint recomputes the error and the level from ``y``, the old season and
# ``P = L + T`` and subtracts ``P`` where the replay subtracted ``L_{t-1}``
# and ``T_{t-1}`` one after the other, the gradient moves in its last place
# and a fit that stops at its noise floor stops elsewhere — of the 24 inline
# rows one takes 37 iterations for 6 and one 16 for 8, to objectives within
# 2e-2 of the parent's either way (the parent's: inline b7fb28aff75e13cd /
# 9df77601ac081e4e / 24 / 177, ragged 425b60129f439f96 / 2aaefd5267317d00 /
# 24 / 180, lazy 21be488db7502e0b / 0e15cce7da45c373 / 2048 / 19054)
_HW_PIN = {  # params sha, objective sha, rows converged, sum of iters
    "inline-additive": ("e73277ec52d2363e", "48b6170cf3f8339b", 24, 178),
    "inline-multiplicative": ("b5f2e565689ee170", "b765f1abfad164a7", 24, 212),
    "ragged-additive": ("c3e03379797cd457", "fff87034ea54a730", 24, 178),
    "ragged-multiplicative": ("32a5fc407d060400", "a61cbcaeafb08b0a", 24, 208),
    "lazy-additive": ("91b2073e4dd0292f", "ef962b3a455abed9", 2014, 22871),
    "lazy-multiplicative": ("2e75e1260fdc952f", "61fd9a4b5cea9f24", 2048, 19043),
}
# the scan backend's digest of inline-additive there: no Pallas code in it,
# so it tells the recording's code generator from another
_HW_PIN_HOST = ("f1b7f4abc1c47fbd", "656b298ad31a845d", 24, 178)


@pytest.mark.parametrize("path", ["inline", "ragged", "lazy"])
@pytest.mark.parametrize("model_type", ["additive", "multiplicative"])
def test_hw_fit_pinned_to_the_fold_per_call_parent(monkeypatch, path,
                                                   model_type):
    # PR 26 moved the fold out of the optimizer's loops; the kernels, their
    # operands and the adjoint's product are the same, so a fit takes the
    # same path through the optimizer: params and objective bit-equal to
    # the parent's, row for row the same iterations.  (The lazy panel is
    # one where XLA:CPU compiles the interpreted kernel alike for both
    # placements of the fold: on 3 of 4 other additive panels tried, f
    # after the first iteration differed in its last bit at the SAME x,
    # and tracing the fold back into the loop reproduced the parent's bits
    # with the new adjoint -- the CPU compiler's contraction choice, which
    # a Mosaic kernel on the chip does not share.)
    from spark_timeseries_tpu.models import holtwinters as hw

    host = _fit_pin_digest(_hw_pin_fit("inline", "additive", "scan"))
    if host != _HW_PIN_HOST:
        pytest.skip("another XLA:CPU code generator than the recording's")
    if path == "lazy":
        monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
        # the pin is the fit of the ONE-LOOP line search: without a
        # ``tail_fun`` stage 1 is still the parent's program, bit for bit.
        # With its tail (ISSUE 39) it is another compiled program whose
        # [cap]-row passes XLA:CPU contracts differently in the last place
        # (tests/test_linesearch_tail.py holds the two row for row)
        from spark_timeseries_tpu.models import lockstep

        monkeypatch.setattr(lockstep, "_straggler_fun",
                            lambda family, p: None)
        build = hw._fit_stage1_program.__wrapped__  # past the program cache
        monkeypatch.setattr(hw, "_fit_stage1_program",
                            lambda *static: jax.jit(build(*static)))
    assert _fit_pin_digest(_hw_pin_fit(path, model_type)) == _HW_PIN[
        f"{path}-{model_type}"]


@pytest.mark.slow  # tier-1 budget: the big grid runs in ci.sh's unfiltered pass
def test_hw_fit_multiplicative_and_ragged_pallas_matches_scan():
    from spark_timeseries_tpu.models import holtwinters as hw

    b, t, m = 5, 96, 8
    y = np.array(_seasonal_panel(b, t, m, seed=39)) + 25.0
    y[1, :13] = np.nan  # ragged head
    y[3, -9:] = np.nan  # ragged tail
    y = jnp.asarray(y)
    r_scan = hw.fit(y, m, "multiplicative", backend="scan", max_iters=40)
    r_pal = hw.fit(y, m, "multiplicative", backend="pallas-interpret", max_iters=40)
    both = np.asarray(r_scan.converged & r_pal.converged)
    assert both.mean() > 0.5
    np.testing.assert_allclose(
        np.asarray(r_pal.params)[both], np.asarray(r_scan.params)[both],
        rtol=5e-2, atol=5e-2,
    )


@pytest.mark.parametrize("t", [53, 2100])  # single-chunk and 3-chunk grids
def test_css_last_errors_matches_full(t):
    p, q = 2, 2
    b = 5
    y = _arma_panel(b, t, seed=23)
    rng = np.random.default_rng(24)
    params = jnp.asarray(rng.normal(size=(b, 1 + p + q)).astype(np.float32) * 0.25)
    zb = jnp.asarray([0.0, 3.0, 17.0, 0.0, float(t - q - 1)], jnp.float32)
    full = pk.css_errors(p, q, True, params, y, zb)
    tail = pk.css_last_errors(p, q, True, params, y, zb)
    assert tail.shape == (b, q)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(full)[:, -q:],
                               rtol=1e-6, atol=1e-6)
    # q == 0: no errors to rebuild
    z = pk.css_last_errors(p, 0, True, params[:, :3], y, zb)
    assert z.shape == (b, 0)


# ---------------------------------------------------------------------------
# Time-chunked grids: series longer than one chunk (_CHUNK_T) must agree
# with the scan references across chunk boundaries (values AND adjoints).
# ---------------------------------------------------------------------------


@pytest.mark.slow  # minutes-scale interpret-mode sweep: tier-2 (`-m slow`), see pyproject markers
def test_chunked_css_matches_scan_long_series():
    assert pk._CHUNK_T >= 512  # chunk-boundary sizes below assume >= 512
    order = (2, 0, 2)
    b, t = 3, 2100  # 3 chunks; boundary lags cross chunks
    y = _arma_panel(b, t, seed=41)
    rng = np.random.default_rng(42)
    params = jnp.asarray(rng.normal(size=(b, 5)).astype(np.float32) * 0.25)
    nv = jnp.asarray([t, t - 37, t - 1400], jnp.int32)

    ref = jax.vmap(
        lambda pr, v, n: arima.css_neg_loglik(pr, v, order, True, n)
    )(params, y, nv)
    got = pk.css_neg_loglik(params, y, order, True, nv, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=3e-5)

    def loss_scan(P):
        return jnp.sum(jax.vmap(
            lambda pr, v, n: arima.css_neg_loglik(pr, v, order, True, n)
        )(P, y, nv))

    def loss_pal(P):
        return jnp.sum(pk.css_neg_loglik(P, y, order, True, nv, interpret=True))

    g_ref = jax.grad(loss_scan)(params)
    g_got = jax.grad(loss_pal)(params)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref), rtol=2e-4, atol=2e-4)


@pytest.mark.slow  # tier-1 budget: the big grid runs in ci.sh's unfiltered pass
def test_chunked_garch_matches_scan_long_series():
    from spark_timeseries_tpu.models import garch

    b, t = 3, 2100
    r = _returns_panel(b, t, seed=43)
    params = jnp.asarray(
        np.tile([[0.02, 0.1, 0.8]], (b, 1)).astype(np.float32)
    )
    nv = jnp.asarray([t, t - 1200, t - 41], jnp.int32)
    start = (t - nv).astype(jnp.float32)
    rz = jnp.where(jnp.arange(t)[None, :] >= start[:, None], r, 0.0)

    ref = jax.vmap(lambda pr, rv, n: garch.neg_log_likelihood(pr, rv, n))(
        params, rz, nv
    )
    got = pk.garch_neg_loglik(params, rz, nv, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=3e-5)

    def loss_scan(P):
        return jnp.sum(jax.vmap(
            lambda pr, rv, n: garch.neg_log_likelihood(pr, rv, n)
        )(P, rz, nv))

    def loss_pal(P):
        return jnp.sum(pk.garch_neg_loglik(P, rz, nv, interpret=True))

    g_ref = jax.grad(loss_scan)(params)
    g_got = jax.grad(loss_pal)(params)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref), rtol=3e-4, atol=3e-4)


def test_chunked_ewma_matches_scan_long_series():
    from spark_timeseries_tpu.models import ewma

    b, t = 3, 2100
    rng = np.random.default_rng(44)
    x = jnp.asarray(rng.normal(size=(b, t)).astype(np.float32))
    nv = jnp.asarray([t, t - 1100, t - 13], jnp.int32)
    start = (t - nv).astype(jnp.float32)
    xz = jnp.where(jnp.arange(t)[None, :] >= start[:, None], x, 0.0)
    alpha = jnp.asarray(rng.uniform(0.1, 0.9, b).astype(np.float32))

    ref = jax.vmap(lambda a, v, n: ewma.sse(a, v, n))(alpha, xz, nv)
    got = pk.ewma_sse(alpha, xz, nv, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=3e-5)

    g_ref = jax.grad(lambda A: jnp.sum(
        jax.vmap(lambda a, v, n: ewma.sse(a, v, n))(A, xz, nv)))(alpha)
    g_got = jax.grad(lambda A: jnp.sum(pk.ewma_sse(A, xz, nv, interpret=True)))(alpha)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref), rtol=2e-4, atol=2e-4)


@pytest.mark.slow  # tier-1 budget: the big grid runs in ci.sh's unfiltered pass
def test_chunked_hw_matches_scan_long_series():
    from spark_timeseries_tpu.models import holtwinters as hw

    b, t, m = 2, 2112, 24  # 2112 = 88 seasons; > 2 chunks
    y = _seasonal_panel(b, t, m, seed=45)
    rng = np.random.default_rng(46)
    params = jnp.asarray(rng.uniform(0.05, 0.9, (b, 3)).astype(np.float32))

    ref = jax.vmap(lambda pr, v: hw.sse(pr, v, m, False))(params, y)
    got = pk.hw_additive_sse(params, y, m, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=5e-4)

    g_ref = jax.grad(lambda P: jnp.sum(
        jax.vmap(lambda pr, v: hw.sse(pr, v, m, False))(P, y)))(params)
    g_got = jax.grad(lambda P: jnp.sum(pk.hw_additive_sse(P, y, m, interpret=True)))(params)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref), rtol=2e-3, atol=5e-2)


def test_structural_guards():
    # the chunked layouts have static bounds (ADVICE round 2): large orders /
    # periods must raise a clear ValueError at the kernel entry, and the
    # auto backend must resolve to scan instead of tripping them
    from spark_timeseries_tpu.models.base import resolve_backend

    assert pk.css_structural_ok(1, 1)
    assert not pk.css_structural_ok(2048, 1)
    assert pk.hw_structural_ok(24)
    assert not pk.hw_structural_ok(5000)
    with pytest.raises(ValueError, match="fused CSS"):
        pk.css_errors(2048, 1, True, jnp.zeros((1, 2050)), jnp.zeros((1, 8)),
                      jnp.zeros((1,)))
    with pytest.raises(ValueError, match="fused Holt-Winters"):
        pk.hw_additive_sse(jnp.zeros((1, 3)), jnp.zeros((1, 16)), 5000,
                           interpret=True)
    # auto never picks pallas for a structurally unsupported config
    assert resolve_backend("auto", jnp.float32, 100, structural_ok=False) == "scan"


def _gappy(b, t, seed=0, edge_nans=True):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(b, t)).cumsum(axis=1).astype(np.float32)
    gaps = rng.random(size=(b, t)) < 0.25
    x[gaps] = np.nan
    if edge_nans:
        x[0, :3] = np.nan   # leading edge
        x[1, -4:] = np.nan  # trailing edge
        x[2, :] = np.nan    # all-NaN series
    return jnp.asarray(x)


@pytest.mark.parametrize("t", [
    37, pytest.param(200, marks=pytest.mark.slow)])  # the long chain
# runs in ci.sh's unfiltered pass
def test_fill_linear_chain_matches_portable(t):
    from spark_timeseries_tpu.ops import univariate as uv

    y = _gappy(6, t, seed=11)
    f_ref = jax.vmap(uv.fill_linear)(y)
    d_ref = jax.vmap(lambda v: uv.differences_at_lag(v, 1))(f_ref)
    l_ref = jax.vmap(lambda v: uv.lag(v, 1))(f_ref)
    f, d, lg = pk.fill_linear_chain(y, interpret=True)
    np.testing.assert_allclose(np.asarray(f), np.asarray(f_ref), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(d), np.asarray(d_ref), rtol=1e-6, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(l_ref), rtol=1e-6, atol=1e-6)


@pytest.mark.slow  # minutes-scale interpret-mode sweep: tier-2 (`-m slow`), see pyproject markers
def test_fill_linear_chain_chunked_long_series():
    from spark_timeseries_tpu.ops import univariate as uv

    # time axis spanning multiple VMEM chunks: carries must cross boundaries
    y = _gappy(3, 2 * pk._CHUNK_T + 57, seed=12)
    f_ref = jax.vmap(uv.fill_linear)(y)
    f, d, lg = pk.fill_linear_chain(y, interpret=True)
    np.testing.assert_allclose(np.asarray(f), np.asarray(f_ref), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(d[:, 1:]), np.asarray((f_ref[:, 1:] - f_ref[:, :-1])),
        rtol=1e-6, atol=1e-5,
    )
    np.testing.assert_allclose(np.asarray(lg[:, 1:]), np.asarray(f_ref[:, :-1]),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("t", [64, 333])
def test_batch_autocorr_matches_portable(t):
    from spark_timeseries_tpu.ops import univariate as uv

    y = _gappy(5, t, seed=13, edge_nans=False)
    ref = uv.batch_autocorr(7, backend="scan")(y)
    got = pk.batch_autocorr(y, 7, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_batch_autocorr_chunked_long_series():
    y = _gappy(3, pk._CHUNK_T + 100, seed=14, edge_nans=False)
    from spark_timeseries_tpu.ops import univariate as uv

    ref = uv.batch_autocorr(5, backend="scan")(y)
    got = pk.batch_autocorr(y, 5, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("order,intercept", [((1, 0, 1), True), ((2, 0, 1), False),
                                             ((1, 0, 0), True), ((0, 0, 2), True)])
def test_hr_init_matches_batched(order, intercept):
    from spark_timeseries_tpu.models.arima import hannan_rissanen_batched

    b, t = 6, 160
    y = _arma_panel(b, t, seed=51)
    nv = jnp.asarray([t, t - 9, t - 33, t, t - 2, t - 60], jnp.int32)
    tt = jnp.arange(t)[None, :]
    yz = jnp.where(tt >= (t - nv)[:, None], y, 0.0)
    ref = hannan_rissanen_batched(yz, order, intercept, nv)
    got = pk.hr_init(yz, order, intercept, nv, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.slow  # minutes-scale interpret-mode sweep: tier-2 (`-m slow`), see pyproject markers
def test_hr_init_chunked_long_series():
    from spark_timeseries_tpu.models.arima import hannan_rissanen_batched

    order = (2, 0, 2)
    b, t = 3, pk._CHUNK_T + 211
    y = _arma_panel(b, t, seed=52)
    nv = jnp.asarray([t, t - 41, t - 1100], jnp.int32)
    tt = jnp.arange(t)[None, :]
    yz = jnp.where(tt >= (t - nv)[:, None], y, 0.0)
    ref = hannan_rissanen_batched(yz, order, True, nv)
    got = pk.hr_init(yz, order, True, nv, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.slow  # minutes-scale interpret-mode sweep: tier-2 (`-m slow`), see pyproject markers
def test_fill_linear_fill_only_matches_portable():
    # the singleton-output variant (no difference/lag stores) — regression
    # for the pallas_call sequence-return handling
    from spark_timeseries_tpu.ops import univariate as uv

    y = _gappy(5, 90, seed=15)
    f = pk.fill_linear(y, interpret=True)
    ref = jax.vmap(uv.fill_linear)(y)
    np.testing.assert_allclose(np.asarray(f), np.asarray(ref), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Resident folded layout (ops.layout)
# ---------------------------------------------------------------------------


def test_fold_unfold_roundtrip():
    from spark_timeseries_tpu.ops.layout import fold_panel, unfold_panel

    y = _gappy(5, 333, seed=21)
    fp = fold_panel(y)
    assert fp.shape == (5, 333)
    back = unfold_panel(fp)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(y))


def test_folded_panel_is_a_pytree():
    from spark_timeseries_tpu.ops.layout import FoldedPanel, fold_panel

    y = _gappy(4, 64, seed=22)
    fp = fold_panel(y)

    @jax.jit
    def through(p):
        return FoldedPanel(p.data * 2.0, p.b, p.t)

    out = through(fp)
    assert isinstance(out, FoldedPanel)
    assert (out.b, out.t) == (fp.b, fp.t)
    np.testing.assert_allclose(np.asarray(out.data), np.asarray(fp.data) * 2.0)


@pytest.mark.parametrize("t", [90, 2 * pk._CHUNK_T + 57])
def test_fill_chain_folded_matches_natural(t):
    from spark_timeseries_tpu.ops.layout import fold_panel, unfold_panel

    y = _gappy(5, t, seed=23)
    f_ref, d_ref, l_ref = pk.fill_linear_chain(y, interpret=True)
    fps = pk.fill_linear_chain_folded(fold_panel(y), interpret=True)
    for fp, ref in zip(fps, (f_ref, d_ref, l_ref)):
        np.testing.assert_allclose(
            np.asarray(unfold_panel(fp)), np.asarray(ref), rtol=1e-6, atol=1e-6
        )


@pytest.mark.parametrize("outputs", [("diff", "lag"), ("lag",), ("lag", "filled")])
def test_fill_chain_output_selection(outputs):
    from spark_timeseries_tpu.ops.layout import fold_panel, unfold_panel

    y = _gappy(5, 200, seed=24)
    full = dict(zip(("filled", "diff", "lag"), pk.fill_linear_chain(y, interpret=True)))
    fps = pk.fill_linear_chain_folded(fold_panel(y), outputs, interpret=True)
    assert len(fps) == len(outputs)
    for name, fp in zip(outputs, fps):
        np.testing.assert_allclose(
            np.asarray(unfold_panel(fp)), np.asarray(full[name]),
            rtol=1e-6, atol=1e-6,
        )


def test_fill_chain_output_selection_rejects_unknown():
    from spark_timeseries_tpu.ops.layout import fold_panel

    y = _gappy(3, 50, seed=25)
    with pytest.raises(ValueError, match="subset"):
        pk.fill_linear_chain_folded(fold_panel(y), ("diff", "bogus"))
    with pytest.raises(ValueError, match="subset"):
        pk.fill_linear_chain_folded(fold_panel(y), ())


@pytest.mark.parametrize("t", [200, pk._CHUNK_T + 100])
def test_batch_autocorr_folded_matches_natural(t):
    from spark_timeseries_tpu.ops.layout import fold_panel

    y = _gappy(5, t, seed=26, edge_nans=False)
    ref = pk.batch_autocorr(y, 7, interpret=True)
    got = pk.batch_autocorr_folded(fold_panel(y), 7, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6, atol=1e-6)


@pytest.mark.slow  # minutes-scale interpret-mode sweep: tier-2 (`-m slow`), see pyproject markers
def test_univariate_dispatch_accepts_folded_off_tpu():
    # off-TPU (this suite is CPU-pinned) the folded input falls back to the
    # portable path via unfold, preserving results and — for the chain —
    # returning folded outputs
    from spark_timeseries_tpu.ops import univariate as uv
    from spark_timeseries_tpu.ops.layout import FoldedPanel, fold_panel, unfold_panel

    y = _gappy(4, 96, seed=27, edge_nans=False)
    fp = fold_panel(y)
    ref = uv.batch_autocorr(5, backend="scan")(y)
    got = uv.batch_autocorr(5)(fp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)

    f_ref, d_ref, l_ref = uv.batch_fill_linear_chain(y, backend="scan")
    outs = uv.batch_fill_linear_chain(fp, outputs=("diff", "filled"))
    assert all(isinstance(o, FoldedPanel) for o in outs)
    np.testing.assert_allclose(np.asarray(unfold_panel(outs[0])), np.asarray(d_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(unfold_panel(outs[1])), np.asarray(f_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.slow  # minutes-scale interpret-mode sweep: tier-2 (`-m slow`), see pyproject markers
def test_batch_fill_chain_outputs_natural_subset():
    from spark_timeseries_tpu.ops import univariate as uv

    y = _gappy(4, 80, seed=28)
    f_ref, d_ref, l_ref = uv.batch_fill_linear_chain(y, backend="scan")
    d, = uv.batch_fill_linear_chain(y, backend="scan", outputs=("diff",))
    np.testing.assert_allclose(np.asarray(d), np.asarray(d_ref), rtol=1e-6, atol=1e-6)


@pytest.mark.slow  # minutes-scale interpret-mode sweep: tier-2 (`-m slow`), see pyproject markers
def test_arima_fit_straggler_compaction_parity(monkeypatch):
    # force the compaction stage on at a test-tractable batch size and check
    # it preserves FIT QUALITY vs the uncompacted program.  The two are
    # distinct compiled programs (extra loop clause + a second stage), so
    # f32 fusion differences exist and rows on flat/non-convex stretches of
    # the MA surface may legitimately take different paths — the contract is
    # the bench parity gates' (converged fraction, achieved objective,
    # typical params), not bitwise trajectories.
    b, t = 2048, 64
    y = jnp.asarray(_arma_panel(b, t, seed=77))
    # ref MUST trace before the monkeypatch so it runs the uncompacted
    # program; max_iters=14 is unique to this test so jit_program's cache
    # cannot hand either fit a program traced under the other's threshold
    ref = arima.fit(y, (1, 1, 1), backend="pallas-interpret", max_iters=14)
    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    (got, info) = arima.fit(y, (1, 1, 1), backend="pallas-interpret",
                            max_iters=14, count_evals=True)
    assert int(info["cap"]) == 1024
    assert int(info["compact_at"]) < 14  # compaction actually engaged
    conv_ref = np.asarray(ref.converged)
    conv_got = np.asarray(got.converged)
    assert abs(conv_ref.mean() - conv_got.mean()) < 0.02
    both = conv_ref & conv_got
    # short series + a 14-iteration budget converge only ~55% of rows (the
    # point is a test-tractable straggler tail); the quality gates below
    # carry the parity claim, this floor just guards a meaningful sample
    assert both.mean() > 0.45
    nll_r = np.asarray(ref.neg_log_likelihood)[both]
    nll_g = np.asarray(got.neg_log_likelihood)[both]
    rel = np.abs(nll_r - nll_g) / np.maximum(np.abs(nll_r), 1e-6)
    assert float(np.percentile(rel, 99)) < 1e-2
    med = float(np.nanmedian(np.abs(
        np.asarray(ref.params)[both] - np.asarray(got.params)[both])))
    assert med < 1e-2


@pytest.mark.slow  # minutes-scale interpret-mode sweep: tier-2 (`-m slow`), see pyproject markers
def test_arima_lazy_stage2_split_parity(monkeypatch):
    # the lazily compiled stage-1/stage-2 split (ISSUE 4 satellite, ADVICE
    # r5) replaces the inline compaction on the default no-count_evals
    # path: it must hold the same distribution-level parity bar vs the
    # uncompacted program (the split is a different pair of compiled
    # programs, so bitwise trajectories are out of scope — same contract
    # as test_arima_fit_straggler_compaction_parity above)
    b, t = 2048, 64
    y = jnp.asarray(_arma_panel(b, t, seed=78))
    ref = arima.fit(y, (1, 1, 1), backend="pallas-interpret", max_iters=15,
                    compact=False)
    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    got = arima.fit(y, (1, 1, 1), backend="pallas-interpret", max_iters=15)
    _dist_parity(ref, got)


def _dist_parity(ref, got, conv_floor=0.45):
    conv_ref = np.asarray(ref.converged)
    conv_got = np.asarray(got.converged)
    assert abs(conv_ref.mean() - conv_got.mean()) < 0.02
    both = conv_ref & conv_got
    assert both.mean() > conv_floor
    nll_r = np.asarray(ref.neg_log_likelihood)[both]
    nll_g = np.asarray(got.neg_log_likelihood)[both]
    rel = np.abs(nll_r - nll_g) / np.maximum(np.abs(nll_r), 1e-6)
    assert float(np.percentile(rel, 99)) < 1e-2
    med = float(np.nanmedian(np.abs(
        np.asarray(ref.params)[both] - np.asarray(got.params)[both])))
    assert med < 1e-2


@pytest.mark.slow  # minutes-scale interpret-mode sweep: tier-2 (`-m slow`), see pyproject markers
def test_garch_fit_straggler_compaction_parity(monkeypatch):
    from spark_timeseries_tpu.models import garch

    rng = np.random.default_rng(31)
    r = jnp.asarray((rng.normal(size=(2048, 96)) * 0.1).astype(np.float32))
    ref = garch.fit(r, backend="pallas-interpret", max_iters=13)
    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    got, info = garch.fit(r, backend="pallas-interpret", max_iters=13,
                          count_evals=True)
    assert int(info["cap"]) == 1024
    assert int(info["compact_at"]) < 13
    _dist_parity(ref, got)
    _traced_fit_parity(got, lambda v: garch.fit(
        v, backend="pallas-interpret", max_iters=13, align_mode="dense"), r)


def _traced_fit_parity(lazy, fit, panel, **kw):
    # the same fit under a caller's jit (the panel a Tracer: stage 1 and
    # stage 2 composed in one trace) against the eager lazy pair
    _dist_parity(lazy, jax.jit(fit)(panel), **kw)


@pytest.mark.slow  # minutes-scale interpret-mode sweep: tier-2 (`-m slow`), see pyproject markers
def test_hw_fit_straggler_compaction_parity(monkeypatch):
    from spark_timeseries_tpu.models import holtwinters as hw

    rng = np.random.default_rng(32)
    tt = np.arange(96, dtype=np.float32)
    w = (10 + 0.02 * tt[None, :] + 2 * np.sin(2 * np.pi * tt[None, :] / 24)
         + 0.3 * rng.normal(size=(2048, 96))).astype(np.float32)
    w = jnp.asarray(w)
    ref = hw.fit(w, 24, "additive", backend="pallas-interpret", max_iters=13)
    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    got, info = hw.fit(w, 24, "additive", backend="pallas-interpret",
                       max_iters=13, count_evals=True)
    assert int(info["cap"]) == 1024
    assert int(info["compact_at"]) < 13
    _dist_parity(ref, got)


@pytest.mark.slow  # minutes-scale interpret-mode sweep: tier-2 (`-m slow`), see pyproject markers
@pytest.mark.parametrize("model_type", ["additive", "multiplicative"])
def test_hw_lazy_stage2_split_parity(monkeypatch, model_type):
    # ISSUE 5 satellite: Holt-Winters through optim.lbfgs_batched_stage1/2
    # with a PER-START carry (the seeded multi-start runs several optimizer
    # passes per fit; multiplicative exercises n_starts=3 and the
    # _merge_starts_program re-merge).  Same distribution-level parity
    # contract as test_arima_lazy_stage2_split_parity — the split is a
    # different set of compiled programs, so bitwise is out of scope.
    from spark_timeseries_tpu.models import holtwinters as hw

    rng = np.random.default_rng(32)
    tt = np.arange(96, dtype=np.float32)
    w = (10 + 0.02 * tt[None, :] + 2 * np.sin(2 * np.pi * tt[None, :] / 24)
         + 0.3 * rng.normal(size=(2048, 96))).astype(np.float32)
    w = jnp.asarray(w)
    ref = hw.fit(w, 24, model_type, backend="pallas-interpret", max_iters=13,
                 compact=False)
    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    got = hw.fit(w, 24, model_type, backend="pallas-interpret", max_iters=13)
    _dist_parity(ref, got)
    _traced_fit_parity(got, lambda v: hw.fit(
        v, 24, model_type, backend="pallas-interpret", max_iters=13,
        align_mode="dense"), w)


@pytest.mark.slow  # minutes-scale interpret-mode sweep: tier-2 (`-m slow`), see pyproject markers
def test_argarch_lazy_stage2_split_parity(monkeypatch):
    # ISSUE 5 satellite: ARGARCH through optim.lbfgs_batched_stage1/2,
    # matching arima/garch — same parity contract as the tests above
    from spark_timeseries_tpu.models import garch

    rng = np.random.default_rng(33)
    y = jnp.asarray((rng.normal(size=(2048, 96)) * 0.1).astype(np.float32))
    ref = garch.fit_argarch(y, backend="pallas-interpret", max_iters=13,
                            compact=False)
    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    got = garch.fit_argarch(y, backend="pallas-interpret", max_iters=13)
    # the 5-param AR(1)+GARCH objective converges ~37% of rows in a
    # 13-iteration test budget (~760 rows both-converged — still a
    # meaningful parity sample; the quality gates carry the claim)
    _dist_parity(ref, got, conv_floor=0.30)
    _traced_fit_parity(got, lambda v: garch.fit_argarch(
        v, backend="pallas-interpret", max_iters=13, align_mode="dense"), y,
        conv_floor=0.30)


@pytest.mark.parametrize("mult", [False, True])
def test_hw_seeds_dense_path_matches_general(mult):
    # n_valid=None takes the gather-free static-slice path; it must produce
    # the exact seeds of the general path with a zero start vector
    rng = np.random.default_rng(41)
    tt = np.arange(120, dtype=np.float32)
    y = (10 + 0.05 * tt[None, :] + 2 * np.sin(2 * np.pi * tt[None, :] / 24)
         + 0.2 * rng.normal(size=(7, 120))).astype(np.float32)
    y = jnp.asarray(y)
    nv = jnp.full((7,), 120, jnp.int32)
    dense = pk.hw_seeds(y, 24, mult, None)
    general = pk.hw_seeds(y, 24, mult, nv)
    for d, g in zip(dense, general):
        np.testing.assert_allclose(np.asarray(d), np.asarray(g),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# The forward objective kernels' series-block width (pk.series_rows): R
# vector registers of series a time step is the SAME arithmetic per series
# ---------------------------------------------------------------------------


def _block_width_cases():
    for ragged in (False, True):
        for nchunk in (1, 2):
            tag = f"{'ragged' if ragged else 'dense'}-nchunk{nchunk}"
            for mode in ("sum", "both", "e", "tail"):
                yield pytest.param("css", mode, False, ragged, nchunk,
                                   id=f"css-{mode}-{tag}")
            for mode in ("sum", "both", "e"):
                yield pytest.param("garch", mode, False, ragged, nchunk,
                                   id=f"garch-{mode}-{tag}")
            for mult in (False, True):
                for mode in ("sum", "save_resid"):
                    yield pytest.param(
                        "hw", mode, mult, ragged, nchunk,
                        id=f"hw-{'mult' if mult else 'add'}-{mode}-{tag}")


def _block_width_runner(family, mode, mult, ragged, nchunk):
    """-> run(r): every output of the forward call at forced width ``r``
    and, where the mode saves residuals, the gradient through the adjoint."""
    # R = 4 needs Bp / 128 divisible by 32: 4096 series.  Chunks of 16
    # steps (patched by the caller) keep the interpreted loops short
    b, m = 4096, 4
    t = 13 if nchunk == 1 else 29
    rng = np.random.default_rng(71)
    nv = None
    if ragged:
        nv = jnp.asarray(rng.integers(t - 4, t + 1, b), jnp.int32)
    gbar = jnp.asarray(rng.normal(size=b).astype(np.float32))
    if family == "css":
        y = jnp.asarray(rng.normal(size=(b, t)).astype(np.float32))
        par = jnp.asarray(rng.normal(size=(b, 3)).astype(np.float32) * 0.3)
        y3, zb3 = pk.css_prefold(y, (1, 0, 1), nv)

        def run(r):
            outs, (_, par3, _) = pk._css_fwd_call_f(
                1, 1, True, mode, par, y3, zb3, t, _r=r)
            if mode != "both":
                return list(outs)
            return list(outs) + list(pk._css_ss_f_bwd(
                1, 1, True, t, b, (y3, par3, zb3, outs[0], None), gbar))
    elif family == "garch":
        r_ = jnp.asarray(0.01 * rng.normal(size=(b, t)).astype(np.float32))
        par = jnp.asarray(np.stack(
            [rng.uniform(1e-6, 1e-5, b), rng.uniform(0.03, 0.15, b),
             rng.uniform(0.7, 0.8, b)], axis=1).astype(np.float32))
        f = pk.garch_prefold(r_, nv)

        def run(r):
            outs, par3 = pk._garch_fwd_call_f(True, mode, par, f, _r=r)
            if mode != "both":
                return list(outs)
            gpar, _ = pk._garch_ll_f_bwd(True, (f, par3, outs[0], None), gbar)
            return list(outs) + [gpar]
    else:
        y = _seasonal_panel(b, t, m, seed=72) + (25.0 if mult else 0.0)
        if ragged:
            y = jnp.where(jnp.arange(t)[None, :] >= (t - nv)[:, None], y, 0.0)
        par = jnp.asarray(rng.uniform(0.05, 0.9, (b, 3)).astype(np.float32))
        f = pk.hw_prefold(y, pk.hw_seeds(y, m, mult, nv))

        def run(r):
            save = mode == "save_resid"
            outs, par3 = pk._hw_fwd_call_f(True, m, mult, save, par, f, _r=r)
            if not save:
                return list(outs)
            gpar, _ = pk._hw_ss_f_bwd(True, m, mult, (f, par3, *outs[:-1]),
                                      gbar)
            return list(outs) + [gpar]

    return run


@pytest.mark.parametrize("family,mode,mult,ragged,nchunk",
                         list(_block_width_cases()))
def test_forward_block_width_is_bit_equal(monkeypatch, family, mode, mult,
                                          ragged, nchunk):
    # value, saved residuals and the gradient through the unchanged adjoint
    # at forced R = 2 and R = 4 against R = 1, bit for bit
    monkeypatch.setattr(pk, "_CHUNK_T", 16)
    run = _block_width_runner(family, mode, mult, ragged, nchunk)
    ref = [np.asarray(x) for x in run(1)]
    assert all(np.isfinite(x).all() for x in ref)
    assert any(np.abs(x).max() > 0 for x in ref)
    for r in (2, 4):
        got = [np.asarray(x) for x in run(r)]
        assert len(got) == len(ref)
        for x, y in zip(got, ref):
            assert x.shape == y.shape and x.tobytes() == y.tobytes(), r


def _adjoint_width_cases():
    for case in ("css-dense", "css-lagset", "css-nchunk2", "css-want-gy",
                 "css-panel-want-gy", "garch", "garch-nchunk2",
                 "garch-want-gdata", "garch-panel", "grid-k9", "grid-cells",
                 "hw-add", "hw-mult-nchunk2"):
        yield pytest.param(case, id=case)


def _adjoint_width_runner(case):
    """-> ``(widths, run)`` under the caller's ``_CHUNK_T``: the forced
    blocks (R, or the grid's ``(G, R)``; the first is the reference) and
    ``run(width)``, every output of that adjoint call on the residuals its
    own ``both`` / ``save_resid`` forward saved."""
    b, m = 4096, 4  # R = 4 needs Bp / 128 divisible by 32
    rng = np.random.default_rng(371)
    chunk = pk._CHUNK_T
    t = 2 * chunk - 3 if "nchunk2" in case else chunk - 3
    gbar = jnp.asarray(rng.normal(size=b).astype(np.float32))
    panel = lambda: jnp.asarray(  # noqa: E731
        rng.normal(size=(b, t)).astype(np.float32))
    widths = (1, 2, 4)
    if case.startswith("css"):
        p, q = ((), (1, 24, 25)) if case == "css-lagset" else (1, 1)
        k = 1 + len(pk._lags(p)) + len(pk._lags(q))
        par = jnp.asarray(rng.normal(size=(b, k)).astype(np.float32) * 0.3)
        y3, zb3 = pk.css_prefold(panel(), (pk._span(pk._lags(p)), 0, 0))
        g3 = pk._fold(jnp.pad(panel(), ((0, 0), (0, y3.shape[0] - t))))

        (e3, _), (_, par3, _) = pk._css_fwd_call_f(
            p, q, True, "both", par, y3, zb3, t)

        def run(r):
            if case == "css-panel-want-gy":  # ``css_errors``' own rule
                return pk._css_errors_bwd_f(
                    p, q, True, (y3, par3, zb3, e3), g3, b, t, want_gy=True,
                    _r=r)
            marker = () if case == "css-want-gy" else None
            return pk._css_ss_f_bwd(p, q, True, t, b,
                                    (y3, par3, zb3, e3, marker), gbar, _r=r)
    elif case.startswith("garch"):
        par = _garch_params(b, 372)
        f = pk.garch_prefold(0.01 * panel())
        g3 = pk._fold(jnp.pad(panel(), ((0, 0), (0, f.r23.shape[0] - t))))

        (h3, _), par3 = pk._garch_fwd_call_f(True, "both", par, f)

        def run(r):
            if case == "garch-panel":  # ``garch_variances``' own rule
                return pk._garch_bwd_call_f(True, f, par3, h3, g3, True,
                                            _r=r)
            marker = () if case == "garch-want-gdata" else None
            gpar, gf = pk._garch_ll_f_bwd(True, (f, par3, h3, marker), gbar,
                                          _r=r)
            return gpar, gf.r23, gf.h03
    elif case.startswith("grid"):
        # the order search's cells: nine orders over one panel at every
        # (G, R) the rule can return, or one order over gathered cells
        kk, rows = 9, b if case == "grid-k9" else b // 8
        f = pk.css_grid_prefold(
            jnp.asarray(rng.normal(size=(rows, t)).astype(np.float32)),
            [max(o // 3, 0) for o in range(kk)])
        par = jnp.asarray(
            rng.normal(size=(kk * rows, 5)).astype(np.float32) * 0.2)
        gb = jnp.asarray(rng.normal(size=kk * rows).astype(np.float32))
        if case == "grid-cells":
            idx = jnp.asarray(rng.permutation(kk * rows)[:b])
            f, par, gb = pk.take_cells(f, idx), par[idx], gb[idx]
            widths = ((1, 1), (1, 2), (1, 4))
        else:
            widths = tuple((g, r) for g in (1, 3) for r in (1, 2, 4))
        gb4 = pk._fold_cells(gb[:, None], f.k)

        (e4, _), par4 = pk._css_grid_fwd_call(
            (1, 2), (1, 2), True, "both", par, f)

        def run(gr):
            return [pk._css_grid_bwd_call((1, 2), (1, 2), True, f, par4, e4,
                                          gb4, _g=gr[0], _r=gr[1])]
    else:
        mult = "mult" in case
        y = _seasonal_panel(b, t, m, seed=373) + (25.0 if mult else 0.0)
        par = jnp.asarray(rng.uniform(0.05, 0.9, (b, 3)).astype(np.float32))
        f = pk.hw_prefold(y, pk.hw_seeds(y, m, mult, None))

        outs, par3 = pk._hw_fwd_call_f(True, m, mult, True, par, f)

        def run(r):
            return [pk._hw_ss_f_bwd(True, m, mult, (f, par3, *outs[:-1]),
                                    gbar, _r=r)[0]]

    return widths, run


@pytest.mark.parametrize("case", list(_adjoint_width_cases()))
def test_adjoint_block_width_is_bit_equal(monkeypatch, case):
    # every output of each objective's adjoint call — parameter gradients,
    # and the data cotangents where a caller perturbs the data — at forced
    # R = 2 and R = 4 (the grid: every (G, R)) against one register of
    # series a step, bit for bit: the chains never mix
    monkeypatch.setattr(pk, "_CHUNK_T", 64 if case == "css-lagset" else 16)
    widths, run = _adjoint_width_runner(case)
    ref = [np.asarray(x) for x in run(widths[0])]
    assert all(np.isfinite(x).all() for x in ref)
    # (a zero is a cotangent the rule does not form: ``zb``'s, the data's
    # on the params-only path)
    live = [np.abs(x).max() > 0 for x in ref]
    assert live[0] and sum(live) >= (2 if "want" in case or "panel" in case
                                     else 1)
    for width in widths[1:]:
        got = [np.asarray(x) for x in run(width)]
        assert len(got) == len(ref)
        for x, y in zip(got, ref):
            assert x.shape == y.shape and x.tobytes() == y.tobytes(), width


@pytest.mark.parametrize("what,block,layout", [
    # a 256-row serving batch pads to one 1,024-series block
    ("serving-256", lambda: pk.css_series_block(256, 999, (1, 1, 1)), None),
    ("ladder-1-row", lambda: pk.hw_series_block(1, 960, 24), None),
    # a compaction cap that is 1,024- but not 2,048-aligned
    ("cap-3072", lambda: pk.garch_series_block(3072, 1000), None),
    ("cap-2048-takes-2", lambda: pk.css_series_block(2048, 999, (1, 1, 1)),
     None),
    # the cells' chunk and stage-2 compaction take the widest block
    ("arima-chunk", lambda: pk.css_series_block(131072, 999, (1, 1, 1)),
     lambda: pk._css_fwd_layout(1, 1, "sum", 999)),
    ("arima-chunk-both",
     lambda: pk.css_series_block(131072, 999, (1, 1, 1), "both"),
     lambda: pk._css_fwd_layout(1, 1, "both", 999)),
    ("garch-stage2", lambda: pk.garch_series_block(16384, 1000),
     lambda: pk._garch_fwd_layout("sum", 1000)),
    # HW save_resid, additive: 1 input + 1 output (the raw errors), four
    # buffers of 3.9 MB a register of series; the multiplicative model's
    # 1 input + 2 outputs (ISSUE 45) are six: 91.4 MiB at R = 4
    ("hw-save-resid",
     lambda: pk.hw_series_block(131072, 960, 24, "save_resid"),
     lambda: pk._hw_fwd_layout(24, False, True, 960)),
    ("hw-mult-save-resid",
     lambda: pk.hw_series_block(131072, 960, 24, "save_resid", True),
     lambda: pk._hw_fwd_layout(24, True, True, 960)),
    # the ring is m x 4 KB x R, thrice (input twice, scratch once)
    ("hw-m1024-T4096", lambda: pk.hw_series_block(131072, 4096, 1024),
     lambda: pk._hw_fwd_layout(1024, False, False, 4096)),
    ("hw-m1024-T4096-save",
     lambda: pk.hw_series_block(131072, 4096, 1024, "save_resid"),
     lambda: pk._hw_fwd_layout(1024, False, True, 4096)),
    # series past one chunk: the _prev neighbour doubles the input buffers
    ("css-T4096-both",
     lambda: pk.css_series_block(131072, 4096, (1, 1, 1), "both"),
     lambda: pk._css_fwd_layout(1, 1, "both", 4096)),
    # the ADJOINT calls by the same rule (ISSUE 37): a serving batch and a
    # one-row retry keep today's block
    ("adjoint-serving-256",
     lambda: pk.css_series_block(256, 999, (1, 1, 1), "adjoint"), None),
    ("adjoint-ladder-1-row",
     lambda: pk.garch_series_block(1, 1000, "adjoint"), None),
    # the cells' chunk and stage-2 compaction take the table's width
    ("adjoint-arima-chunk",
     lambda: pk.css_series_block(131072, 999, (1, 1, 1), "adjoint"),
     lambda: pk._css_bwd_layout(1, 1, 999)),
    ("adjoint-arima-stage2",
     lambda: pk.css_series_block(16384, 999, (1, 1, 1), "adjoint"),
     lambda: pk._css_bwd_layout(1, 1, 999)),
    ("adjoint-seasonal-chunk",
     lambda: pk.css_series_block(131072, 935, ((), 0, (1, 24, 25)),
                                 "adjoint"),
     lambda: pk._css_bwd_layout((), (1, 24, 25), 935)),
    ("adjoint-garch-chunk",
     lambda: pk.garch_series_block(131072, 1000, "adjoint"),
     lambda: pk._garch_bwd_layout(1000)),
    ("adjoint-garch-stage2",
     lambda: pk.garch_series_block(16384, 1000, "adjoint"),
     lambda: pk._garch_bwd_layout(1000)),
    # a perturbed panel adds a panel out (``want_gy`` / ``want_gdata``), a
    # series past one chunk the neighbour blocks: 27.4 / 23.5 / 36.1 / 32.1
    # MiB a register of series, so VMEM stops each at two
    ("adjoint-css-want-gy",
     lambda: _rule_block(pk._css_bwd_layout(1, 1, 999, want_gy=True), "css"),
     lambda: pk._css_bwd_layout(1, 1, 999, want_gy=True)),
    ("adjoint-garch-want-gdata",
     lambda: _rule_block(pk._garch_bwd_layout(1000, True), "garch"),
     lambda: pk._garch_bwd_layout(1000, True)),
    ("adjoint-css-T4096",
     lambda: pk.css_series_block(131072, 4096, (1, 1, 1), "adjoint"),
     lambda: pk._css_bwd_layout(1, 1, 4096)),
    ("adjoint-garch-T4096",
     lambda: pk.garch_series_block(131072, 4096, "adjoint"),
     lambda: pk._garch_bwd_layout(4096)),
    # Holt-Winters' additive adjoint reads one panel and takes the table's
    # width; the multiplicative one reads three (ISSUE 45), 90.7 MiB at
    # R = 4, and takes it too; a series past one chunk brings no neighbour
    # block, 96 MiB of panel blocks at R = 4: two
    ("adjoint-hw", lambda: pk.hw_series_block(131072, 960, 24, "adjoint"),
     lambda: pk._hw_bwd_layout(24, False, 960)),
    ("adjoint-hw-mult",
     lambda: pk.hw_series_block(131072, 960, 24, "adjoint", True),
     lambda: pk._hw_bwd_layout(24, True, 960)),
    ("adjoint-hw-mult-T4096",
     lambda: pk.hw_series_block(131072, 4096, 24, "adjoint", True),
     lambda: pk._hw_bwd_layout(24, True, 4096)),
    # the order search: stage 2's one order over the cap's gathered cells
    # takes the plain rule's width, stage 1's nine orders what fits beside G
    ("adjoint-grid-stage2",
     lambda: pk.css_grid_series_block(1, 73728, 999, 2, 2, "adjoint"),
     lambda: pk._css_bwd_layout(2, 2, 999)),
    ("adjoint-grid-stage1",
     lambda: pk.css_grid_series_block(9, 131072, 999, 2, 2, "adjoint"),
     None),
])
def test_series_block_rule_on_shapes(what, block, layout):
    # the width rule from static facts alone: no kernel runs
    sb = block()
    r = sb // pk._SBLK
    assert sb == r * pk._SBLK and r in (1, 2, 4)
    if what in ("serving-256", "ladder-1-row", "cap-3072",
                "adjoint-serving-256", "adjoint-ladder-1-row"):
        assert r == 1
    if what == "adjoint-hw":
        assert r == pk._ADJOINT_R["hw"][False]
    if what == "adjoint-hw-mult":
        assert r == pk._ADJOINT_R["hw"][True]
    if what.startswith(("adjoint-arima", "adjoint-seasonal")):
        assert r == pk._ADJOINT_R["css"]
    if what.startswith("adjoint-garch-") and what[14:] in ("chunk", "stage2"):
        assert r == pk._ADJOINT_R["garch"]
    if what in ("adjoint-css-want-gy", "adjoint-garch-want-gdata",
                "adjoint-css-T4096", "adjoint-garch-T4096",
                "adjoint-hw-mult-T4096"):
        assert r == 2
    if what == "adjoint-grid-stage2":
        assert sb == pk.css_series_block(73728, 999, (2, 1, 2), "adjoint")
    if what == "adjoint-grid-stage1":
        g, r_ = pk.css_grid_block(9, 1024, pk._css_bwd_layout(2, 2, 999),
                                  "adjoint")
        assert (g, r_) == (3, r) and r <= 2  # (3, 4) is 173 MiB
    if what == "cap-2048-takes-2":
        assert r == min(2, pk._CSS_R["sum"])
    if what == "hw-save-resid":
        assert r == pk._HW_R[True][False]
    if what == "hw-mult-save-resid":
        assert r == pk._HW_R[True][True]
    if layout is not None:
        assert pk._vmem_bytes(layout(), r) <= pk._VMEM_BLOCK_BUDGET
        assert pk._VMEM_BLOCK_BUDGET < pk._VMEM_PARAMS.vmem_limit_bytes
        # the next wider block is refused for a stated reason
        wider = {1: 2, 2: 4}.get(r)
        if wider and what != "garch-stage2":
            best = {"arima-chunk": pk._CSS_R["sum"],
                    "arima-chunk-both": pk._CSS_R["both"],
                    "css-T4096-both": pk._CSS_R["both"],
                    "hw-save-resid": pk._HW_R[True][False],
                    "hw-mult-save-resid": pk._HW_R[True][True],
                    "hw-m1024-T4096": pk._HW_R[False][False],
                    "hw-m1024-T4096-save": pk._HW_R[True][False],
                    "adjoint-hw": pk._ADJOINT_R["hw"][False],
                    "adjoint-hw-mult": pk._ADJOINT_R["hw"][True],
                    "adjoint-hw-mult-T4096": pk._ADJOINT_R["hw"][True],
                    "adjoint-grid-stage2": pk._ADJOINT_R["css"],
                    **{f"adjoint-{k}": pk._ADJOINT_R[k.split("-")[0]]
                       for k in ("css-want-gy", "garch-want-gdata",
                                 "css-T4096", "garch-T4096", "garch-chunk",
                                 "garch-stage2")},
                    **{f"adjoint-{k}": pk._ADJOINT_R["css"]
                       for k in ("arima-chunk", "arima-stage2",
                                 "seasonal-chunk")}}[what]
            assert (wider > best or pk._vmem_bytes(layout(), wider)
                    > pk._VMEM_BLOCK_BUDGET)


def _rule_block(layout, kernel, rows=131072):
    """The block of an adjoint call that no ``*_series_block`` names."""
    return pk._SBLK * pk.series_rows(pk._nsub(rows), layout,
                                     pk._ADJOINT_R[kernel])


def test_series_rows_is_a_function_of_static_facts():
    # divisibility, the VMEM budget, the chip's best: in that order of refusal
    lay = pk._css_fwd_layout(1, 1, "sum", 999)
    tiles = 2 * (1000 + 3 + 1 + 1) + 1000 + 1
    assert pk._vmem_bytes(lay) == tiles * 4096
    assert pk._vmem_bytes(lay, 4) == 4 * tiles * 4096
    assert pk.series_rows(1024, lay, 4) == 4
    assert pk.series_rows(1024, lay, 2) == 2
    assert pk.series_rows(1024, lay, 1) == 1
    assert pk.series_rows(16, lay, 4) == 2  # 2,048 series
    assert pk.series_rows(24, lay, 4) == 1  # 3,072 series
    assert pk.series_rows(8, lay, 4) == 1
    over = pk._VMEM_BLOCK_BUDGET // pk._TILE_BYTES
    scratch_only = lambda n: ([], [], [n])  # noqa: E731
    assert pk.series_rows(1024, scratch_only(over // 4), 4) == 4
    assert pk.series_rows(1024, scratch_only(over // 4 + 1), 4) == 2
    assert pk.series_rows(1024, scratch_only(over // 2 + 1), 4) == 1
    # an adjoint's layout by the same count: two panels and the mask, the
    # parameter planes in and out, the cotangent's plane; the adjoint path
    # and its carry in scratch
    adj = pk._css_bwd_layout(1, 1, 999)
    tiles = 2 * (2 * 1000 + 3 + 1 + 1 + 3) + 1000 + 1
    assert pk._vmem_bytes(adj) == tiles * 4096
    assert [pk.series_rows(n, adj, 4) for n in (1024, 128, 16, 24, 8)] == [
        4, 4, 2, 1, 1]
    assert pk.series_rows(1024, adj, 1) == 1
    # a panel more (``want_gy``) is refused R = 4 by VMEM, not by the table
    assert pk.series_rows(1024, pk._css_bwd_layout(1, 1, 999, True), 4) == 2


def test_forward_call_grid_follows_the_rule():
    # the pallas_call the fit objective traces takes the rule's block: at
    # 4,096 series one grid step of (cs, 8 R, 128) where R = 1 takes four
    b, t = 4096, 40
    y3 = jnp.zeros((t, b // 128, 128), jnp.float32)
    zb3 = jnp.ones((1, b // 128, 128), jnp.float32)
    par = jnp.zeros((b, 3), jnp.float32)

    def grid(**kw):
        jaxpr = jax.make_jaxpr(lambda P: pk._css_fwd_call_f(
            1, 1, True, "sum", P, y3, zb3, t, **kw)[0])(par)
        (eqn,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        gm = eqn.params["grid_mapping"]
        return tuple(gm.grid), gm.block_mappings[0].block_shape

    r = pk.css_series_block(b, t, (1, 0, 1)) // pk._SBLK
    g, blk = grid()
    assert g == (4 // r, 1)
    assert tuple(getattr(x, "block_size", x) for x in blk) == (40, 8 * r, 128)
    assert grid(_r=1)[0] == (4, 1) and grid(_r=4)[0] == (1, 1)


def test_kernel_block_sweep_cases_trace():
    # tools/kernel_block_sweep.py (the chip-side R sweep, and each
    # objective's adjoint as its custom_vjp calls it): every case's
    # arguments and call trace at every width, on shapes alone
    from tools import kernel_block_sweep as sweep

    def series_grid(call, r, args):
        # -> (series-axis grid steps, sublane rows of the panel's block)
        jaxpr = jax.make_jaxpr(functools.partial(call, r))(*args)
        (eqn,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        gm = eqn.params["grid_mapping"]
        blk = gm.block_mappings[0].block_shape
        return gm.grid[0], getattr(blk[1], "block_size", blk[1])

    seen, grid = set(), set()
    for name, mode, rows, t, make, call in sweep.cases():
        args = jax.eval_shape(make, jax.random.key(0))
        tp, _, _ = pk._time_layout(t)
        if mode.startswith("adjoint"):
            # the adjoint cases force their width too (ISSUE 37): 8 R
            # sublane rows a block, G orders a group of the grid's nine
            cells = rows * 9 // 4 if mode.endswith("cells") else rows
            kg = 9 // int(mode[-1]) if ".g" in mode else 1
            assert [series_grid(call, r, args) for r in (1, 2, 4)] == [
                (cells // (1024 * r) * kg, 8 * r) for r in (1, 2, 4)]
        if name == "css_grid_neg_loglik":
            # the order search's cells: 9 orders over one panel at G orders
            # a grid step, or one order over a quarter of the cells
            k, b = (1, 9 * rows // 4) if mode.endswith("cells") else (9, rows)
            for r in (1, 2, 4):
                outs = jax.eval_shape(functools.partial(call, r), *args)
                if mode.startswith("adjoint"):  # five planes, folded flat
                    assert [o.shape for o in outs] == [(5, k * b // 128, 128)]
                else:
                    assert all(o.shape[1:] == (k, b // 128, 128)
                               and o.shape[0] in (1, tp) for o in outs)
            grid.add(mode)
            continue
        for r in (1, 2, 4):
            outs = jax.eval_shape(functools.partial(call, r), *args)
            assert outs[-1].shape[1:] == (rows // 128, 128)
            # a panel or a plane; an adjoint's parameter planes, folded
            assert all(o.shape[0] in ((3, 4) if mode.startswith("adjoint")
                                      else (1, tp)) for o in outs)
        seen.add((name, mode))
    assert grid == {f"{m}.{tag}" for m in ("sum", "both", "adjoint")
                    for tag in ("g1", "g3", "g9", "cells")}
    kernels = {"css_neg_loglik", "hw_sse", "garch_neg_loglik"}
    assert {(n, m) for n, m in seen if m == "adjoint"} == {
        (n, "adjoint") for n in kernels | {"css_seasonal_neg_loglik"}}
    # Holt-Winters' additive calls, and the multiplicative model's pair
    assert {m for n, m in seen if n == "hw_sse"} == {
        "sum", "save_resid", "adjoint", "save_resid.mult", "adjoint.mult"}
    assert len(seen) == 14 and {n for n, m in seen
                                if not m.startswith("adjoint")} == kernels


# ---------------------------------------------------------------------------
# The objective's cotangent is formed INSIDE the adjoint kernels (PR 35):
# the folded objectives hand their adjoint the plane gb3, not a panel
# ---------------------------------------------------------------------------


def _cotangent_case(family, variant, ragged, nchunk):
    """One small gradient of a folded fit objective -> ``(chunk, run)``:
    ``_CHUNK_T`` to patch (``nchunk`` time chunks) and ``run() -> dict`` of
    gradients — ``plane_*`` through the objective's ``custom_vjp`` (its
    adjoint forms the cotangent), ``panel_*`` the same gradient composed
    from the general-cotangent ``custom_vjp`` where the family has one
    (``css_errors``, ``garch_variances``), ``scan_*`` the scan backend's."""
    b, m = 40, 4
    rng = np.random.default_rng(351)
    w = jnp.asarray(rng.normal(size=b).astype(np.float32))
    chunk = 64 if variant == "lagset" else 16
    t = (chunk if nchunk == 1 else 2 * chunk) - 3
    nv = (jnp.asarray(rng.integers(t - 4, t + 1, b), jnp.int32) if ragged
          else jnp.full((b,), t, jnp.int32))
    start = (t - nv).astype(jnp.float32)
    live = jnp.arange(t)[None, :] >= start[:, None]
    if family == "css":
        p, q = ((), (1, 24, 25)) if variant == "lagset" else (1, 1)
        k = 1 + len(pk._lags(p)) + len(pk._lags(q))
        yd = jnp.where(live, jnp.asarray(
            rng.normal(size=(b, t)).astype(np.float32)), 0.0)
        par = jnp.asarray(rng.normal(size=(b, k)).astype(np.float32) * 0.3)
        zb = start + pk._span(pk._lags(p))

        def run():
            y3, zb3 = pk.css_prefold(yd, (pk._span(pk._lags(p)), 0, 0), nv)
            g_p, g_y3 = jax.grad(lambda P, Y3: jnp.sum(w * pk._css_ss_f(
                p, q, True, t, b, P, Y3, zb3)), argnums=(0, 1))(par, y3)
            g_only = jax.grad(lambda P: jnp.sum(w * pk._css_ss_f(
                p, q, True, t, b, P, y3, zb3)))(par)
            r_p, r_y = jax.grad(lambda P, Y: jnp.sum(w * jnp.sum(
                pk.css_errors(p, q, True, P, Y, zb) ** 2, axis=1)),
                argnums=(0, 1))(par, yd)
            return {"plane_params": g_only, "plane_params_gy": g_p,
                    "plane_data": pk._unfold(g_y3, b)[:, :t],
                    "panel_params": r_p, "panel_data": r_y}
    elif family == "garch":
        r = jnp.where(live, _returns_panel(b, t, seed=352), 0.0)
        par = _garch_params(b, 353)

        def general(P, rv):
            # the likelihood written over ``garch_variances``, masked and
            # seeded as ``garch_prefold`` has it
            rz = jnp.where(live, rv, 0.0)
            nvf = nv.astype(rv.dtype)
            mean = jnp.sum(rz, axis=1) / nvf
            h0 = jnp.sum(jnp.where(live, (rz - mean[:, None]) ** 2, 0.0),
                         axis=1) / nvf
            hc = jnp.maximum(pk.garch_variances(P, rz, h0, start,
                                                interpret=True), 1e-12)
            return jnp.sum(w * jnp.sum(jnp.where(
                live, jnp.log(2.0 * jnp.pi * hc) + rz * rz / hc, 0.0),
                axis=1))

        def run():
            f = pk.garch_prefold(r, nv)
            g_only = jax.grad(lambda P: jnp.sum(
                w * pk._garch_ll_f(True, P, f)))(par)
            g_p, g_r = jax.grad(lambda P, rv: jnp.sum(w * pk._garch_ll_f(
                True, P, pk.garch_prefold(rv, nv))), argnums=(0, 1))(par, r)
            r_p, r_r = jax.grad(general, argnums=(0, 1))(par, r)
            s_p = jax.grad(lambda P: 2.0 * jnp.sum(
                w * _scan_nll(P, r, nv)))(par)
            return {"plane_params": g_only, "plane_params_gy": g_p,
                    "plane_data": g_r, "panel_params": r_p,
                    "panel_data": r_r, "scan_params": s_p}
    else:
        from spark_timeseries_tpu.models import holtwinters as hw

        mult = variant == "mult"
        y = jnp.where(live, _seasonal_panel(b, t, m, seed=354)
                      + (25.0 if mult else 0.0), 0.0)
        par = jnp.asarray(rng.uniform(0.05, 0.9, (b, 3)).astype(np.float32))

        def run():
            f = pk.hw_prefold(y, pk.hw_seeds(y, m, mult, nv))
            g = jax.grad(lambda P: jnp.sum(
                w * pk._hw_ss_f(True, m, mult, P, f)))(par)
            s = jax.grad(lambda P: jnp.sum(w * jax.vmap(
                lambda pr, v, n: hw.sse(pr, v, m, mult, n))(P, y, nv)))(par)
            return {"plane_params": g, "scan_params": s}

    return chunk, run


def _cotangent_cases():
    for family, variants in (("css", ("plain", "lagset")),
                             ("garch", ("g11",)), ("hw", ("add", "mult"))):
        for variant in variants:
            for ragged in (False, True):
                for nchunk in (1, 2):
                    yield pytest.param(
                        family, variant, ragged, nchunk,
                        id=f"{family}-{variant}-"
                           f"{'ragged' if ragged else 'dense'}-nchunk{nchunk}")


# recorded on the PARENT of PR 35 (commit 63975b1: XLA formed every
# objective's cotangent as a panel and the adjoint kernels read it back), f32
# under this suite's jax_enable_x64, XLA:CPU of this container: the sha of
# (the parameter gradient, and where the family has them the parameter and
# data gradients of the data-perturbed branch — ``want_gy`` / ``want_gdata``).
# The four ``hw-add-*`` digests were RE-RECORDED by PR 43 on the same host
# (``_COTANGENT_PIN_HOST``, the scan's gradient, matched): the additive
# adjoint reads the raw errors alone and forms ``a r_t`` where the replay
# formed ``L_t - L_{t-1} - T_{t-1}`` (and ``r_t``, ``(1 - a) r_t`` for the
# other two factors, the last as ``(1 - a) sum(r_t uS)``) — equal
# algebraically, another rounding in the last place; against the scan they
# hold the tolerance of the test above.  The digests also hold the FORM of
# those sums: which equal form it is decides whether a row of the
# benchmark's million exhausts its line search (PERF.md §6, PR 43) (the
# parent's: 794754ac43b85e34, 022899e4bbecafc5, 5e3f0ec1fe2454dd,
# ea2e77ce3985d914).  The four ``hw-mult-*`` digests were RE-RECORDED by PR
# 45 on the same host: the multiplicative adjoint reads ``y``, the old
# season and ``P = L + T``, recomputes the error and the level in the
# forward's own expressions and forms ``y / sc - P`` and ``L - P`` where the
# replay formed ``y / sc - L_{t-1} - T_{t-1}`` and ``L - L_{t-1} - T_{t-1}``
# (the parent's: 25b747b5614beeae, 901837f5bd588f9d, 69f822208684638d,
# cdb183f37db397a9).  Every ``css-*`` and ``garch-*`` digest is the
# recording's
_COTANGENT_PIN = {
    "css-plain-0-1": "83949e9651f167db",
    "css-plain-0-2": "0442ba21effdc497",
    "css-plain-1-1": "f82903eed5f82691",
    "css-plain-1-2": "d01b5cf74329ae3e",
    "css-lagset-0-1": "0b2ca0da662a82c4",
    "css-lagset-0-2": "ff4657ac8522248f",
    "css-lagset-1-1": "1ba1fd76c938eae9",
    "css-lagset-1-2": "ba8134aceb9894be",
    "garch-g11-0-1": "9794d51a0dbaf612",
    "garch-g11-0-2": "b6a8fadac3fe0ad4",
    "garch-g11-1-1": "7a62e4568a65c167",
    "garch-g11-1-2": "ea2428b91ae3dd98",
    "hw-add-0-1": "71098cec072acb50",
    "hw-add-0-2": "3205a7a9b39bb4d4",
    "hw-add-1-1": "44876bd5d08fb223",
    "hw-add-1-2": "1bd57283ca296e35",
    "hw-mult-0-1": "2d070198e90dce8a",
    "hw-mult-0-2": "1db70821a6c1eb10",
    "hw-mult-1-1": "c078bb7d569516c7",
    "hw-mult-1-2": "f2dd645fd48e2971",
}
# the scan backend's digest of one case there: no Pallas code in it, so it
# tells the recording's code generator from another
_COTANGENT_PIN_HOST = "640b9ad50e48fcd1"


@functools.lru_cache(maxsize=None)
def _cotangent_pin_host():
    _, run = _cotangent_case("hw", "add", False, 1)
    return _sha(run()["scan_params"])


def _cotangent_digest(out):
    return _sha(out["plane_params"], *(
        (out["plane_params_gy"], out["plane_data"])
        if "plane_data" in out else ()))


@pytest.mark.parametrize("family,variant,ragged,nchunk",
                         list(_cotangent_cases()))
def test_objective_adjoint_forms_the_cotangent_itself(monkeypatch, family,
                                                      variant, ragged,
                                                      nchunk):
    # the gradient of each folded objective through the plane-taking
    # adjoint against the SAME gradient composed from the untouched
    # general-cotangent path: (2 e) gb rounds alike wherever it is formed,
    # so CSS is bit-equal in the parameters AND the data; GARCH's quotient
    # is another expression than autodiff's, so it is close.  Holt-Winters'
    # adjoint has one caller and no panel mode: its gradient against the
    # scan backend's here, against the parent's digits below.
    chunk, run = _cotangent_case(family, variant, ragged, nchunk)
    monkeypatch.setattr(pk, "_CHUNK_T", chunk)
    out = {k: np.asarray(v) for k, v in run().items()}
    assert all(np.isfinite(v).all() for v in out.values())
    assert np.abs(out["plane_params"]).max() > 0
    if family == "css":
        for a, b_ in (("plane_params", "panel_params"),
                      ("plane_params_gy", "panel_params"),
                      ("plane_data", "panel_data")):
            assert out[a].tobytes() == out[b_].tobytes(), a
        assert np.abs(out["plane_data"]).max() > 0
    elif family == "garch":
        # the params-only branch and the data-perturbed one run the same
        # recursion adjoint on the same in-kernel cotangent
        assert out["plane_params"].tobytes() == out[
            "plane_params_gy"].tobytes()
        for a, b_ in (("plane_params", "panel_params"),
                      ("plane_data", "panel_data")):
            scale = np.abs(out[b_]).max(axis=0, keepdims=True)
            np.testing.assert_allclose(out[a] / scale, out[b_] / scale,
                                       rtol=1e-6, atol=1e-6, err_msg=a)
    scan = out.get("scan_params")
    if scan is not None:
        np.testing.assert_allclose(out["plane_params"], scan, rtol=2e-3,
                                   atol=2e-3 * np.abs(scan).max())


@pytest.mark.parametrize("family,variant,ragged,nchunk",
                         list(_cotangent_cases()))
def test_objective_gradient_pinned_to_the_panel_cotangent_parent(
        monkeypatch, family, variant, ragged, nchunk):
    # the parent's digits, bit for bit on this code generator: the parameter
    # gradient of every folded objective and the data-perturbed branches
    # (``want_gy``, ``want_gdata``: forecasting, ``fit_argarch``)
    if _cotangent_pin_host() != _COTANGENT_PIN_HOST:
        pytest.skip("another XLA:CPU code generator than the recording's")
    chunk, run = _cotangent_case(family, variant, ragged, nchunk)
    monkeypatch.setattr(pk, "_CHUNK_T", chunk)
    key = f"{family}-{variant}-{int(ragged)}-{nchunk}"
    assert _cotangent_digest(run()) == _COTANGENT_PIN[key]
