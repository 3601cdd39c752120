"""ARIMA(p, d, q) — the flagship model family (L4).

TPU-native rebuild of the reference's ``sparkts/models/ARIMA.scala``
(SURVEY.md Sections 2.2 and 3.3, upstream path unverified).  Same algorithm
family, redesigned for batch execution:

===============================  ==========================================
reference (per series, JVM)      here (whole panel, one XLA computation)
===============================  ==========================================
order-d differencing             static slicing (``ops.univariate``)
Hannan-Rissanen init             batched OLS via ``jnp.linalg.lstsq`` on
                                 stacked lag matrices (MXU matmuls)
conditional-sum-of-squares       ``lax.scan`` over time computing one-step
likelihood (hand-coded loop)     prediction errors; vmapped over series
hand-derived CSS gradient        ``jax.grad`` through the scan
Commons-Math CG / BOBYQA         fixed-budget vmapped L-BFGS
                                 (``utils.optim``) with per-series
                                 convergence masks
===============================  ==========================================

Parameter vector layout (matching the reference's ``coefficients``):
``[c (if intercept), phi_1..phi_p, theta_1..theta_q]``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import univariate as uv
from ..utils import optim
from ..utils.linalg import ols as _ols
from ..utils.linalg import ridge_solve as _ridge_solve
from . import lockstep
from .base import (FitResult, align_mode_on_host, align_right, debatch,
                   debatch_fit, derive_status, ensure_batched, jit_program,
                   maybe_align, require_pallas_for_count_evals,
                   resolve_align_mode, resolve_backend)

Order = Tuple[int, int, int]
Seasonal = Tuple[int, int, int, int]  # (P, D, Q, s)


def _n_params(order: Order, include_intercept: bool) -> int:
    p, _, q = order
    return int(include_intercept) + p + q


def _split_params(params, order: Order, include_intercept: bool):
    p, _, q = order
    i = int(include_intercept)
    c = params[0] if include_intercept else jnp.zeros((), params.dtype)
    phi = params[i : i + p]
    theta = params[i + p : i + p + q]
    return c, phi, theta


def _difference(y, d: int):
    """Order-d differencing with the first d entries dropped (static shape)."""
    for _ in range(d):
        y = y[1:] - y[:-1]
    return y


def _lagged(yd, p: int):
    """``[n, p]`` matrix of lags 1..p, zero-padded before the start."""
    n = yd.shape[0]
    cols = []
    for k in range(1, p + 1):
        cols.append(jnp.concatenate([jnp.zeros((k,), yd.dtype), yd[: n - k]]))
    if not cols:
        return jnp.zeros((n, 0), yd.dtype)
    return jnp.stack(cols, axis=1)


# ---------------------------------------------------------------------------
# CSS likelihood
# ---------------------------------------------------------------------------


def _css_errors_poly(c, phi, theta, yd, condition: bool = True, n_valid=None,
                     condition_lags=None):
    """One-step-ahead prediction errors of the ARMA recursion with EXPLICIT
    lag-coefficient vectors ``phi [p_full]`` / ``theta [q_full]`` — the one
    scan the plain ARMA path (:func:`_css_errors`), the seasonal
    expanded-polynomial path (:func:`_sarima_css_errors`), and the fused
    multi-order grid fit (:func:`fit_grid`) all run.

    ``condition=True`` zeroes errors for the first ``p_full`` valid steps
    (conditional likelihood — the reference's CSS); ``condition=False``
    keeps zero-padded-lag errors for every valid t, which makes the
    transform exactly invertible (remove/add_time_dependent_effects).

    ``n_valid`` (traced scalar) marks a right-aligned valid span (see
    ``base.align_right``): errors in the zero prefix are forced to 0 so
    padded series contribute nothing there.

    ``condition_lags`` overrides the conditioning depth: the fused grid
    fit zero-pads every order's coefficient vectors to the grid maximum
    (``phi.shape[0]`` is then the GRID's depth, not this order's), but
    the likelihood must still condition out exactly this order's
    ``p_full`` steps — the padded slots multiply by exact 0.0 and change
    nothing else.
    """
    p = phi.shape[0]
    q = theta.shape[0]
    n = yd.shape[0]
    t_idx = jnp.arange(n)
    start = 0
    if n_valid is not None:
        start = n - n_valid
        # differencing across the padding boundary leaves a garbage raw-level
        # value at yd[start-1]; zero the prefix so lags reaching below start
        # bring exactly the zeros a trimmed series would see
        yd = jnp.where(t_idx >= start, yd, 0.0)
    ylags = _lagged(yd, p)  # [n, p]
    cond_p = p if condition_lags is None else condition_lags
    zero_before = start + cond_p if condition else start

    def step(errs, inp):
        yt, yl, t = inp
        pred = c + jnp.dot(phi, yl) + (jnp.dot(theta, errs) if q else 0.0)
        e = yt - pred
        e = jnp.where(t >= zero_before, e, 0.0)
        new_errs = jnp.concatenate([e[None], errs[:-1]]) if q else errs
        return new_errs, e

    errs0 = jnp.zeros((max(q, 1),), yd.dtype)
    _, e = lax.scan(step, errs0, (yd, ylags, t_idx))
    return e


def _css_errors(params, yd, order: Order, include_intercept: bool, condition: bool = True,
                n_valid=None):
    """ARMA(p,q) CSS errors from the packed parameter vector (see
    :func:`_css_errors_poly` for the recursion's contract)."""
    c, phi, theta = _split_params(params, order, include_intercept)
    return _css_errors_poly(c, phi, theta, yd, condition=condition,
                            n_valid=n_valid)


def css_neg_loglik(params, yd, order: Order, include_intercept: bool, n_valid=None):
    """Negative conditional-sum-of-squares Gaussian log-likelihood with the
    innovation variance concentrated out (sigma^2 = CSS / n_eff)."""
    p = order[0]
    nv = yd.shape[0] if n_valid is None else n_valid
    e = _css_errors(params, yd, order, include_intercept, n_valid=n_valid)
    n_eff = nv - p
    css = jnp.sum(e * e)
    sigma2 = css / n_eff
    return 0.5 * n_eff * (jnp.log(2.0 * jnp.pi * sigma2) + 1.0)


def approx_aic(params, yd, order: Order, include_intercept: bool):
    k = _n_params(order, include_intercept)
    return 2.0 * css_neg_loglik(params, yd, order, include_intercept) + 2.0 * k


# ---------------------------------------------------------------------------
# Seasonal extension: SARIMA(p,d,q)(P,D,Q)_s through the same CSS recursion
# ---------------------------------------------------------------------------
#
# The multiplicative seasonal model
#   Phi(L^s) phi(L) (1-L)^d (1-L^s)^D y_t = c + Theta(L^s) theta(L) e_t
# is the paper's most-missed scenario (PAPER.md section 0/L-map: upstream
# spark-ts users pick seasonal orders as part of model selection).  Rather
# than a second likelihood implementation, the seasonal polynomials are
# EXPANDED into plain lag-coefficient vectors (static shapes: p+P*s AR lags,
# q+Q*s MA lags) and run through the exact `_css_errors_poly` scan the
# non-seasonal fit uses — one recursion, one conditioning rule, one
# concentrated-variance likelihood.  That scan is the `scan` backend and the
# reference.  On the Pallas backends the same recursion runs in the CSS
# kernels over the product polynomials' LIVE lags only (`seasonal_lag_sets`:
# the airline model (0,1,1)(0,1,1)_24 has the MA lags 1, 24 and 25 among the
# 25 of its expanded vector), the parameters reaching the kernel's planes
# through `_seasonal_kernel_params`; a seasonal fit takes the lockstep driver
# like a plain one (`_sarima_family`).  `fit_grid` and `models.auto`'s
# searches take the CSS grid kernels for groups of plain orders; a group
# with a seasonal member stays on the scan, whose fused objective zero-pads
# every order to the grid's depth.


def _validate_seasonal(seasonal) -> Optional[Seasonal]:
    """Normalize a ``(P, D, Q, s)`` seasonal spec; ``None`` (or an all-zero
    structure) means "no seasonal terms" and returns None."""
    if seasonal is None:
        return None
    try:
        P, D, Q, s = (int(v) for v in seasonal)
    except (TypeError, ValueError) as e:
        raise ValueError(
            f"seasonal must be a (P, D, Q, s) tuple, got {seasonal!r}") from e
    if P == 0 and D == 0 and Q == 0:
        return None
    if min(P, D, Q) < 0:
        raise ValueError(f"seasonal orders must be >= 0, got {seasonal!r}")
    if s < 2:
        raise ValueError(
            f"seasonal period s must be >= 2 when (P, D, Q) != 0, "
            f"got {seasonal!r}")
    return (P, D, Q, s)


def _difference_seasonal(y, D: int, s: int):
    """Order-D seasonal differencing at lag s (static shapes: drops D*s)."""
    for _ in range(D):
        y = y[s:] - y[:-s]
    return y


def _n_params_seasonal(order: Order, seasonal: Seasonal,
                       include_intercept: bool) -> int:
    p, _, q = order
    P, _, Q, _ = seasonal
    return int(include_intercept) + p + q + P + Q


def _split_params_seasonal(params, order: Order, seasonal: Seasonal,
                           include_intercept: bool):
    """Layout: ``[c (if intercept), phi_1..p, theta_1..q, PHI_1..P,
    THETA_1..Q]`` — the non-seasonal prefix matches :func:`_split_params`
    so a caller can warm-start a seasonal fit from a plain ARMA one."""
    p, _, q = order
    P, _, Q, _ = seasonal
    i = int(include_intercept)
    c = params[0] if include_intercept else jnp.zeros((), params.dtype)
    phi = params[i: i + p]
    theta = params[i + p: i + p + q]
    sphi = params[i + p + q: i + p + q + P]
    stheta = params[i + p + q + P: i + p + q + P + Q]
    return c, phi, theta, sphi, stheta


def _expand_seasonal_poly(vals, svals, s: int, cross: float):
    """Lag coefficients of the multiplicative polynomial product.

    For the AR side (``cross=-1``): ``(1 - sum v_i L^i)(1 - sum w_j L^js)``
    gives the recursion coefficients ``a`` with ``y_t = c + sum a_k y_{t-k}
    + ...`` — ``a[:p] = v``, ``a[js-1] = w_j``, ``a[js+i-1] = -v_i w_j``.
    For the MA side (``cross=+1``): ``(1 + sum v L)(1 + sum w L^js)`` gives
    ``b`` with the cross terms ADDED.  All shapes static (p, P, s are
    Python ints), so the expansion unrolls into a handful of scatter-adds
    at trace time.
    """
    p = int(vals.shape[0])
    P = int(svals.shape[0])
    n = p + P * s
    if n == 0:
        return jnp.zeros((0,), vals.dtype)
    full = jnp.zeros((n,), vals.dtype)
    if p:
        full = full.at[:p].add(vals)
    for j in range(P):
        lag = (j + 1) * s
        full = full.at[lag - 1].add(svals[j])
        if p:
            full = full.at[lag: lag + p].add(cross * svals[j] * vals)
    return full


def _sarima_css_errors(params, yd, order: Order, seasonal: Seasonal,
                       include_intercept: bool, condition: bool = True,
                       n_valid=None):
    """CSS errors of the expanded seasonal recursion (``yd`` already both
    plain- and seasonally-differenced)."""
    _, _, _, s = seasonal
    c, phi, theta, sphi, stheta = _split_params_seasonal(
        params, order, seasonal, include_intercept)
    phi_full = _expand_seasonal_poly(phi, sphi, s, -1.0)
    theta_full = _expand_seasonal_poly(theta, stheta, s, 1.0)
    return _css_errors_poly(c, phi_full, theta_full, yd,
                            condition=condition, n_valid=n_valid)


def _live_lags(n: int, N: int, s: int) -> dict:
    """``lag -> [(i, j), ...]``: the terms of ``(1 -+ sum_i v_i L^i)(1 -+
    sum_j w_j L^(js))`` that land on each lag, ascending — ``(i, 0)`` is
    ``v_i``, ``(0, j)`` is ``w_j``, ``(i, j)`` their product at ``js + i``
    (several terms on one lag once ``n >= s``).  Static: the lags
    :func:`_expand_seasonal_poly` can make non-zero."""
    terms: dict = {}
    for j in range(N + 1):
        for i in range(n + 1):
            if i or j:
                terms.setdefault(j * s + i, []).append((i, j))
    return dict(sorted(terms.items()))


def seasonal_lag_sets(order: Order, seasonal: Optional[Seasonal]):
    """``(AR lags, MA lags)`` of the expanded recursion that can carry a
    non-zero coefficient, each ascending — the lag sets the CSS kernels
    take in place of the dense ranges ``1..p + P s`` / ``1..q + Q s``
    (which they are without a seasonal part)."""
    p, _, q = order
    P, _, Q, s = seasonal or (0, 0, 0, 1)
    return tuple(_live_lags(p, P, s)), tuple(_live_lags(q, Q, s))


def _live_coefs(vals, svals, s: int, cross: float):
    """:func:`_expand_seasonal_poly`'s map restricted to its live lags, on
    batches: ``vals [B, n]``, ``svals [B, N]`` -> ``[B, lags]``, a column
    per lag of :func:`_live_lags`."""
    def term(i, j):
        if not j:
            return vals[:, i - 1]
        if not i:
            return svals[:, j - 1]
        return cross * vals[:, i - 1] * svals[:, j - 1]

    cols = [sum(term(i, j) for i, j in terms) for terms in
            _live_lags(vals.shape[1], svals.shape[1], s).values()]
    return jnp.stack(cols, axis=1)


def _seasonal_kernel_params(params, order: Order, seasonal: Seasonal,
                            include_intercept: bool):
    """The model's parameters ``[B, k]`` -> the CSS kernel's planes ``[B, 1
    + |A| + |M|]`` = ``[c, a_i (i in A), b_j (j in M)]`` over the lag sets
    of :func:`seasonal_lag_sets`: the product map.  For the airline model
    ``(b_1, b_24, b_25) = (theta, THETA, theta THETA)``, so the chain rule
    JAX takes through it is ``d/dtheta = g_1 + THETA g_25``, ``d/dTHETA =
    g_24 + theta g_25``: the map's transpose applied to the kernel's
    gradient."""
    s = seasonal[3]
    c, phi, theta, sphi, stheta = jax.vmap(
        lambda row: _split_params_seasonal(row, order, seasonal,
                                           include_intercept))(params)
    cols = [c[:, None]]
    if phi.shape[1] + sphi.shape[1]:
        cols.append(_live_coefs(phi, sphi, s, -1.0))
    if theta.shape[1] + stheta.shape[1]:
        cols.append(_live_coefs(theta, stheta, s, 1.0))
    return jnp.concatenate(cols, axis=1)


def seasonal_lag_span(order: Order, seasonal: Optional[Seasonal]
                      ) -> Tuple[int, int, int]:
    """``(p_full, q_full, d_full)`` — the expanded AR/MA lag depths and the
    total differencing the (optionally seasonal) model conditions on.
    The criterion layer (``models.auto``) uses these to compute the same
    effective sample size the concentrated likelihood divides by."""
    p, d, q = order
    if seasonal is None:
        return p, q, d
    P, D, Q, s = seasonal
    return p + P * s, q + Q * s, d + D * s


def sarima_neg_loglik(params, yd, order: Order, seasonal: Seasonal,
                      include_intercept: bool, n_valid=None):
    """Concentrated Gaussian CSS likelihood of the seasonal recursion —
    same concentration rule as :func:`css_neg_loglik` with the expanded
    AR depth ``p + P*s`` conditioned out."""
    p_full, _, _ = seasonal_lag_span(order, seasonal)
    nv = yd.shape[0] if n_valid is None else n_valid
    e = _sarima_css_errors(params, yd, order, seasonal, include_intercept,
                           n_valid=n_valid)
    n_eff = nv - p_full
    css = jnp.sum(e * e)
    sigma2 = css / n_eff
    return 0.5 * n_eff * (jnp.log(2.0 * jnp.pi * sigma2) + 1.0)


# ---------------------------------------------------------------------------
# Hannan-Rissanen initialization
# ---------------------------------------------------------------------------


def hannan_rissanen(yd, order: Order, include_intercept: bool, n_valid=None):
    """Two-stage startup values: long-AR residuals stand in for the
    unobserved MA innovations, then one OLS of y on [1, y-lags, e-lags].

    With ``n_valid`` (right-aligned span), row selection becomes 0/1 row
    weights — zeroed rows add nothing to the normal equations, keeping the
    math identical to the static-slice full-series case.
    """
    p, _, q = order
    n = yd.shape[0]
    m = min(p + q + 1, max(n // 4, 1))  # long-AR order, static
    start = 0 if n_valid is None else n - n_valid
    t = jnp.arange(n)

    # stage 1: AR(m) by OLS -> residual estimates of the innovations
    ylags_m = _lagged(yd, m)
    ones = jnp.ones((n, 1), yd.dtype)
    Xar = jnp.concatenate([ones, ylags_m], axis=1)
    # rows with any zero-padded lag (t < start + m) get weight 0
    w1 = (t >= start + m).astype(yd.dtype)
    beta_ar = _ols(Xar * w1[:, None], yd * w1)
    ehat = (yd - Xar @ beta_ar) * w1

    # stage 2: OLS of y on [1?, y-lags 1..p, e-lags 1..q]
    cols = []
    if include_intercept:
        cols.append(ones)
    if p:
        cols.append(_lagged(yd, p))
    if q:
        cols.append(_lagged(ehat, q))
    if not cols:
        return jnp.zeros((0,), yd.dtype)
    X = jnp.concatenate(cols, axis=1)
    w2 = (t >= start + m + q).astype(yd.dtype)  # rows where every regressor is real
    return _ols(X * w2[:, None], yd * w2)


def _shift_cols(x2, k: int):
    """``[B, T]`` shifted right by ``k`` along time (zero-fill), static k."""
    if k == 0:
        return x2
    return jnp.pad(x2, ((0, 0), (k, 0)))[:, : x2.shape[1]]


def _wols_cols(cols, y2, w, ridge: float = 1e-8):
    """Weighted OLS from ``[B, T]`` column vectors: the same ridge-stabilized
    normal equations as ``utils.linalg.ols`` on the design ``X * w`` (binary
    weights: w^2 = w), assembled from masked inner products so no
    ``[B, T, k]`` design matrix is ever materialized."""
    XtX = jnp.stack(
        [jnp.stack([jnp.sum(w * ci * cj, axis=1) for cj in cols], -1)
         for ci in cols], -2,
    )  # [B, k, k]
    Xty = jnp.stack([jnp.sum(w * ci * y2, axis=1) for ci in cols], -1)  # [B, k]
    return _ridge_solve(XtX, Xty, ridge)


def hannan_rissanen_batched(yd, order: Order, include_intercept: bool, nvd):
    """Whole-batch Hannan-Rissanen init ``[B, k]`` — same math as
    ``vmap(hannan_rissanen)`` (identical weighted normal equations), built
    from masked lagged products with STATIC shifts.

    The vmapped version materializes a ``[B, T, m+1]`` lag design and runs
    batched small solves per stage; at panel scale (100k x 1k) building and
    re-reading those designs costs more than the entire L-BFGS fit.  Here
    every Gram entry is a masked elementwise product + row reduction that
    XLA fuses over a handful of shifted views.
    """
    p, _, q = order
    b, n = yd.shape
    m = min(p + q + 1, max(n // 4, 1))
    t = jnp.arange(n)[None, :]
    start = n - nvd  # [B]
    w1 = (t >= (start + m)[:, None]).astype(yd.dtype)

    shifts = [_shift_cols(yd, i) for i in range(max(m, p) + 1)]
    ones = jnp.ones_like(yd)

    # stage 1: AR(m) of yd on [1, lags 1..m] -> innovation estimates
    cols1 = [ones] + shifts[1 : m + 1]
    beta1 = _wols_cols(cols1, yd, w1)  # [B, m+1]
    pred = sum(beta1[:, j, None] * c for j, c in enumerate(cols1))
    ehat = (yd - pred) * w1

    # stage 2: OLS of yd on [1?, y-lags 1..p, e-lags 1..q]
    cols2 = ([ones] if include_intercept else [])
    cols2 += shifts[1 : p + 1]
    cols2 += [_shift_cols(ehat, j) for j in range(1, q + 1)]
    if not cols2:
        return jnp.zeros((b, 0), yd.dtype)
    w2 = (t >= (start + m + q)[:, None]).astype(yd.dtype)
    return _wols_cols(cols2, yd, w2)


# ---------------------------------------------------------------------------
# Fitting
# ---------------------------------------------------------------------------


def fit(
    y,
    order: Order,
    include_intercept: bool = True,
    *,
    seasonal: Optional[Seasonal] = None,
    method: str = "css-lbfgs",
    init_params: Optional[jax.Array] = None,
    max_iters: int = 60,
    tol: Optional[float] = None,
    backend: str = "auto",
    count_evals: bool = False,
    compact: bool = True,
    align_mode: Optional[str] = None,
) -> FitResult:
    """Fit ARIMA(p,d,q) to one series ``[time]`` or a batch ``[batch, time]``.

    The entire batch is one jitted computation: differencing -> vmapped
    Hannan-Rissanen -> batched L-BFGS on the CSS objective.  ``method``
    accepts ``"css-lbfgs"`` (also aliased from the reference's ``"css-cgd"``
    and ``"css-bobyqa"``) and ``"hannan-rissanen"`` (init only, no MLE).

    ``backend`` selects the CSS objective implementation: ``"scan"``
    (``vmap(lax.scan)``, runs everywhere), ``"pallas"`` (fused TPU kernel
    with hand-derived adjoint, ``ops.pallas_kernels``), or ``"auto"``
    (pallas whenever :func:`ops.pallas_kernels.supported` says so).

    ``count_evals=True`` (pallas backend only) returns ``(FitResult, info)``
    where ``info`` is the optimizer's pass-accounting dict
    (``utils.optim.minimize_lbfgs_batched``), so "how many objective passes
    does a fit spend" is a recorded number, not an estimate; the fit that is
    counted is the fit that runs without the flag.

    ``compact=False`` disables straggler compaction (``utils.optim``) for
    run-to-run reproducibility: compaction engages automatically on the
    pallas backend at batches >= ``utils.optim.COMPACT_MIN_BATCH`` (4096)
    and — while parity-gated at the distribution level — is a different
    compiled program, so individual rows on flat/non-convex stretches can
    reach different (equally valid) optima than an uncompacted run.

    ``align_mode`` (``"dense"`` / ``"no-trailing"`` / ``"general"``) is a
    static alignment hint that skips the per-panel NaN probe and its host
    sync (``base.resolve_align_mode``) — the chunk driver threads the
    panel-level mode into every sliced chunk fit.  Hint contract: an
    unknown name raises; a hint too strong for the data surfaces as
    flagged rows (DIVERGED under ``"dense"``, EXCLUDED with NaN params
    under ``"no-trailing"``), never as silently wrong estimates.

    ``seasonal=(P, D, Q, s)`` extends the recursion with multiplicative
    seasonal terms (SARIMA), with the parameter layout ``[c?, phi_1..p,
    theta_1..q, PHI_1..P, THETA_1..Q]``: both differencings, then the same
    driver, backends and flags as a plain fit.  On ``"scan"`` the seasonal
    polynomials are expanded into plain lag coefficients and run through
    the SAME CSS scan; the Pallas kernels take the expansion's live lags
    only (:func:`seasonal_lag_sets`).  The optimizing CSS methods only.

    ``FitResult.status`` reports per-row ``reliability.FitStatus`` codes
    (OK / DIVERGED / EXCLUDED for a plain fit).
    """
    if method not in ("css-lbfgs", "css-cgd", "css-bobyqa", "hannan-rissanen"):
        raise ValueError(f"unknown method {method!r}")
    if count_evals and method == "hannan-rissanen":
        raise ValueError("count_evals requires an optimizing method")
    seasonal = _validate_seasonal(seasonal)
    # the expanded recursion's reach and total differencing (the order's
    # own p, q, d without a seasonal part)
    p_full, q_full, d_full = seasonal_lag_span(order, seasonal)
    yb, single = ensure_batched(y)
    n = yb.shape[1] - d_full
    if seasonal is not None:
        if method == "hannan-rissanen":
            raise ValueError(
                "seasonal orders require an optimizing CSS method "
                "(hannan-rissanen has no seasonal init stage)")
        if n < max(p_full + q_full + 2, 2):
            raise ValueError(
                f"series of length {yb.shape[1]} too short for seasonal "
                f"order {order} x {seasonal} (needs > "
                f"{d_full + p_full + q_full + 2} observations)")
    if tol is None:
        # f32 gradients of a ~1k-term CSS bottom out near 1e-4 relative noise
        tol = 1e-6 if yb.dtype == jnp.float64 else 1e-4
    from ..ops import pallas_kernels as pk

    backend = resolve_backend(
        backend, yb.dtype, n,
        structural_ok=pk.css_structural_ok(p_full, q_full))
    require_pallas_for_count_evals(count_evals, backend)

    align_mode = resolve_align_mode(yb, align_mode)
    has_init = init_params is not None
    static = (order, include_intercept, backend, max_iters, float(tol))
    # what a kernel step pays: the live lag terms, and how far they reach
    ar, ma = seasonal_lag_sets(order, seasonal)
    out = lockstep.fit(
        (yb, jnp.asarray(init_params)) if has_init else (yb,),
        backend=backend, max_iters=max_iters,
        compact=compact and method != "hannan-rissanen",
        inline=lambda: _fit_program(
            order, include_intercept, method, backend, max_iters, float(tol),
            has_init, align_mode, count_evals, compact, seasonal),
        stage1=lambda: _fit_stage1_program(*static, has_init, align_mode,
                                           count_evals, seasonal),
        stage2=lambda: _fit_stage2_program(*static, seasonal),
        series_block=lambda rows, mode: pk.css_series_block(
            rows, n, (ar, 0, ma), mode),
        stage_attrs={"lag_terms": len(ar) + len(ma),
                     "lag_span": max(ar + ma, default=0),
                     "adjoint_panels": pk.CSS_ADJOINT_PANELS})
    return debatch_fit(out, single, count_evals)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["y3", "zb3", "y_rows"], meta_fields=["t"])
@dataclasses.dataclass(frozen=True)
class _CssFolded:
    """A differenced panel in the CSS kernel layout
    (``pallas_kernels.css_prefold``); ``t`` is its true length (static: it
    rides the treedef through a ``jit`` boundary); ``y_rows`` the same panel
    ``pallas_kernels.series_major``, what the stragglers are gathered from
    (``None`` on a gathered subset)."""

    y3: jax.Array
    zb3: jax.Array
    t: int
    y_rows: Optional[jax.Array] = None

    @classmethod
    def of(cls, y, order: Order, nvd, lags) -> "_CssFolded":
        """The aligned panel ``y`` folded FIRST and differenced at ``lags``
        in that layout: the differences are shifts of the folded panel's
        major axis, and the init sweeps and every optimizer evaluation
        share the result."""
        from ..ops import pallas_kernels as pk

        y3, zb3 = pk.css_prefold(y, order, nvd, lags=lags)
        return cls(y3, zb3, y.shape[1] - sum(lags), pk.series_major(y3))

    def take(self, idxc) -> "_CssFolded":
        """The series ``idxc`` (``lockstep.Family.take``)."""
        from ..ops import pallas_kernels as pk

        return _CssFolded(pk.take_rows(self.y_rows, idxc),
                          pk.take_series(self.zb3, idxc), self.t)


def _row_major(yb, align_mode, d: int, D: int = 0, s: int = 0):
    """``(yd, nvd)``: the aligned panel differenced row by row, and its
    valid lengths — what the scan objective and
    :func:`hannan_rissanen_batched` read.  The Pallas backends form the same
    differences in the folded layout (``pallas_kernels.css_prefold``), and
    nothing panel-sized there reads this one."""
    ya, nv0 = maybe_align(yb, align_mode)  # ragged: NaN head/tail
    yd = jax.vmap(
        lambda v: _difference_seasonal(_difference(v, d), D, s))(ya)
    return yd, nv0 - d - D * s


def _css_family(order: Order, include_intercept: bool, backend: str,
                has_init: bool = False,
                align_mode: Optional[str] = None) -> lockstep.Family:
    from ..ops import pallas_kernels as _pk

    p, d, q = order
    k = _n_params(order, include_intercept)
    on_kernels = backend in lockstep.PALLAS
    interp = backend == "pallas-interpret"

    def prep(yb, init_params=None):
        bsz, n = yb.shape[0], yb.shape[1] - d
        series, folded = (), ()
        with jax.named_scope("arima.align_and_difference"):
            if on_kernels:
                ya, nv0 = maybe_align(yb, align_mode)  # ragged: NaN head/tail
                nvd = nv0 - d  # valid length after differencing
                folded = _CssFolded.of(ya, order, nvd, (1,) * d)
            else:
                series = _row_major(yb, align_mode, d)
                nvd = series[1]
        with jax.named_scope("arima.hannan_rissanen_init"):
            if has_init:
                init = jnp.broadcast_to(init_params, (bsz, k))
            elif on_kernels and _pk.hr_structural_ok(p, q):
                # fused two-sweep moment kernels: same normal equations,
                # ~15x less HBM traffic than the shifted-reduce construction
                init = _pk.hr_init_folded(folded.y3, bsz, n, order,
                                          include_intercept, nvd,
                                          interpret=interp)
            else:
                yd = (series or _row_major(yb, align_mode, d))[0]
                init = hannan_rissanen_batched(yd, order, include_intercept,
                                               nvd)
        # too-short series cannot be fit: need lags + a few dof.  The gate
        # reads the panel's content only, so a row's eligibility does not
        # depend on the batch it arrives in
        ok = nvd >= p + q + max(p + q + 1, 1) + k + 2
        if not has_init:
            # Hannan-Rissanen's long-AR order m = min(p+q+1, n//4) is static
            # (shapes), so it is computed from the PADDED length; requiring
            # nvd >= 4*(p+q+1) ensures m would be p+q+1 either way, keeping
            # padded and trimmed inits identical inside the supported region
            ok = ok & (nvd >= 4 * (p + q + 1))
        n_eff = jnp.maximum(nvd - p, 1).astype(yb.dtype)
        return lockstep.Prepared((init,), ok, n_eff, series, folded,
                                 (nvd,), uniform=align_mode == "dense")

    def objective(folded, rows):
        (nvd,) = rows
        return lambda P: _pk.css_neg_loglik_folded(
            P, folded.y3, folded.zb3, folded.t, order, include_intercept,
            nvd, interpret=interp)

    def scan_objective(pr, data):
        yv, n = data
        return css_neg_loglik(pr, yv, order, include_intercept, n)

    return lockstep.Family(backend, prep, objective, scan_objective,
                           lambda x: x, take=_CssFolded.take)


def _sarima_family(order: Order, seasonal: Seasonal, include_intercept: bool,
                   backend: str, has_init: bool = False,
                   align_mode: Optional[str] = None) -> lockstep.Family:
    """The seasonal fit, as :func:`_css_family` states the plain one: align,
    both differencings, ONE fold; the non-seasonal Hannan-Rissanen warm
    start on the fully differenced panel (the ``P + Q`` seasonal terms start
    at 0: the optimizer owns them, the init is deterministic, and HR's
    long-AR order stays static under the same ``nvd >= 4 (p + q + 1)``
    contract); the CSS kernels over the live lags under the product map;
    the expanded-polynomial scan as the portable objective."""
    from ..ops import pallas_kernels as _pk

    p, d, q = order
    P, D, Q, s = seasonal
    k = _n_params_seasonal(order, seasonal, include_intercept)
    p_full, q_full, d_full = seasonal_lag_span(order, seasonal)
    ar, ma = seasonal_lag_sets(order, seasonal)
    on_kernels = backend in lockstep.PALLAS
    interp = backend == "pallas-interpret"

    def prep(yb, init_params=None):
        bsz, n = yb.shape[0], yb.shape[1] - d_full
        series, folded = (), ()
        with jax.named_scope("arima.sarima_align_and_difference"):
            if on_kernels:
                ya, nv0 = maybe_align(yb, align_mode)  # ragged: NaN head/tail
                nvd = nv0 - d_full  # valid length after both differencings
                folded = _CssFolded.of(ya, (p_full, 0, q_full), nvd,
                                       (1,) * d + (s,) * D)
            else:
                series = _row_major(yb, align_mode, d, D, s)
                nvd = series[1]
        with jax.named_scope("arima.sarima_init"):
            if has_init:
                init = jnp.broadcast_to(init_params, (bsz, k))
            else:
                if on_kernels and _pk.hr_structural_ok(p, q):
                    base = _pk.hr_init_folded(folded.y3, bsz, n, (p, 0, q),
                                              include_intercept, nvd,
                                              interpret=interp)
                else:
                    yd = (series or _row_major(yb, align_mode, d, D, s))[0]
                    base = hannan_rissanen_batched(
                        yd, (p, 0, q), include_intercept, nvd)
                init = jnp.concatenate(
                    [base, jnp.zeros((bsz, P + Q), yb.dtype)], axis=1)
        ok = nvd >= p_full + q_full + max(p_full + q_full + 1, 1) + k + 2
        if not has_init:
            ok = ok & (nvd >= 4 * (p + q + 1))
        n_eff = jnp.maximum(nvd - p_full, 1).astype(yb.dtype)
        return lockstep.Prepared((init,), ok, n_eff, series, folded,
                                 (nvd,), uniform=align_mode == "dense")

    def objective(folded, rows):
        (nvd,) = rows
        return lambda X: _pk.css_seasonal_neg_loglik_folded(
            _seasonal_kernel_params(X, order, seasonal, include_intercept),
            folded.y3, folded.zb3, folded.t, ar, ma, nvd, interpret=interp)

    def scan_objective(pr, data):
        yv, n = data
        return sarima_neg_loglik(pr, yv, order, seasonal, include_intercept,
                                 n)

    return lockstep.Family(backend, prep, objective, scan_objective,
                           lambda x: x, take=_CssFolded.take)


def _family(order: Order, seasonal: Optional[Seasonal], *args):
    if seasonal is None:
        return _css_family(order, *args)
    return _sarima_family(order, seasonal, *args)


@jit_program
def _fit_program(order: Order, include_intercept: bool, method: str,
                 backend: str, max_iters: int, tol: float, has_init: bool,
                 align_mode: str = "general", count_evals: bool = False,
                 compact: bool = True, seasonal: Optional[Seasonal] = None):
    family = _family(order, seasonal, include_intercept, backend, has_init,
                     align_mode)
    if method != "hannan-rissanen":
        return lockstep.fit_program(family, max_iters, tol, count_evals,
                                    compact)

    def run(yb, init_params=None):
        prepared = family.prep(yb, init_params)
        (init,), ok = prepared.x0s, prepared.ok
        # the start's likelihood is the scan's on every backend
        yd, nvd = prepared.series or _row_major(yb, align_mode, order[1])
        nll = jax.vmap(
            lambda pr, v, n: css_neg_loglik(pr, v, order, include_intercept, n)
        )(init, yd, nvd)
        z = jnp.zeros((yd.shape[0],), jnp.int32)
        params = jnp.where(ok[:, None], init, jnp.nan)
        return FitResult(params, jnp.where(ok, nll, jnp.nan), ok, z,
                         derive_status(ok, ok, params))

    return run


@jit_program
def _fit_stage1_program(order, include_intercept, backend, max_iters, tol,
                        has_init, align_mode="general", count_evals=False,
                        seasonal=None):
    return lockstep.stage1_program(
        _family(order, seasonal, include_intercept, backend, has_init,
                align_mode),
        max_iters, tol, count_evals)


@jit_program
def _fit_stage2_program(order, include_intercept, backend, max_iters, tol,
                        seasonal=None):
    return lockstep.stage2_program(
        _family(order, seasonal, include_intercept, backend), max_iters, tol)


# ---------------------------------------------------------------------------
# Fused multi-order grid fit (ISSUE 10): K same-d orders, ONE program
# ---------------------------------------------------------------------------
#
# The auto-fit order search (models.auto) runs one chunk walk per candidate
# order, so a G-order search stages/prefetches/journals every chunk G times.
# fit_grid makes the candidate grid a BATCH dimension instead of a loop: K
# orders that share the plain differencing order d are fitted by ONE
# fit of the K x B CELLS (cell g*B + r is order g of row r), a
# lockstep.Family like every other (`_grid_family`): one lockstep batched
# L-BFGS over the flattened [K*B] problem, straggler compaction over cells,
# the lazy stage pair and its spans on the Pallas backends.  Its ONE batched
# objective is, for a group of plain orders on the Pallas backends, the CSS
# grid kernels over the union lag ranges (one folded panel, every order of
# a series block in one call, an order's missing terms zero planes); else
# every order's AR/MA lag-coefficient vectors are expanded
# (_expand_seasonal_poly) and zero-padded to the grid's max (p+P*s, q+Q*s)
# and the CSS objective runs as a [K]-leading-axis vmap of the one
# _css_errors_poly scan (conditioning depth stays per-order via
# condition_lags).  Orders whose FULL differencing signature (d, D, s)
# matches share one differenced panel through a per-trace cache (the
# shared-prep half of the tentpole); variants are embedded right-aligned
# into the group's common length so every order sees one static shape.
#
# The K per-order results are PACKED into the params matrix after the
# cells' finalize — per row, per order: [params(k_max), nll, eligible,
# converged, iters, status] — so a fused chunk rides the
# journal/commit/resume machinery of fit_chunked unchanged (one npz shard
# per chunk carries the whole fusion group) and models.auto demuxes
# per-order results after the walk.

GRID_PACK_COLS = 5  # nll, eligible, converged, iters, status per order


def _grid_spec_info(order: Order, seasonal: Optional[Seasonal],
                    include_intercept: bool) -> dict:
    p, d, q = order
    seasonal = _validate_seasonal(seasonal)
    if seasonal is None:
        k = _n_params(order, include_intercept)
        P = D = Q = 0
        s = 0
    else:
        P, D, Q, s = seasonal
        k = _n_params_seasonal(order, seasonal, include_intercept)
    p_full, q_full, d_full = seasonal_lag_span(order, seasonal)
    return dict(order=(p, d, q), seasonal=seasonal, k=k, P=P, D=D, Q=Q, s=s,
                p_full=p_full, q_full=q_full, d_full=d_full)


def grid_pack_width(specs, include_intercept: bool = True) -> int:
    """Packed-row width of a :func:`fit_grid` result for ``specs``."""
    infos = [_grid_spec_info(tuple(o), sea, include_intercept)
             for o, sea in specs]
    k_max = max(i["k"] for i in infos)
    return len(infos) * (k_max + GRID_PACK_COLS)


def grid_diff_cache_keys(specs) -> int:
    """Distinct differencing signatures ``(d, D, s)`` a fused group of
    ``specs`` needs — the group differences the panel once per key, so
    ``len(specs) - grid_diff_cache_keys(specs)`` orders hit the shared-prep
    cache instead of re-differencing."""
    keys = set()
    for order, seasonal in specs:
        seasonal = _validate_seasonal(seasonal)
        d = int(order[1])
        if seasonal is None or seasonal[1] == 0:
            keys.add((d, 0, 0))
        else:
            keys.add((d, int(seasonal[1]), int(seasonal[3])))
    return len(keys)


def _grid_coef_maps(infos, include_intercept: bool, k_max: int, p_max: int,
                    q_max: int):
    """Per-order packed-params -> expanded-lag-coefficient maps, as
    CONSTANTS: ``phi_full = lin_phi[g] @ P + P^T quad_phi[g] P`` (and the
    theta analog, cross ``+1``), ``c = lin_c[g] @ P``.

    The multiplicative seasonal expansion (:func:`_expand_seasonal_poly`)
    is linear in the own-lag and seasonal coefficients plus BILINEAR
    cross terms — so per order it is exactly a (linear, quadratic-form)
    pair of 0/±1 constant tensors.  That makes the fused grid objective a
    uniform per-cell computation gatherable by CELL index, which is what
    lets straggler compaction run on the flattened ``[K*B]`` problem
    (the static-unrolled main objective cannot be gathered across mixed
    orders)."""
    K = len(infos)
    lin_c = np.zeros((K, k_max), np.float32)
    lin_phi = np.zeros((K, max(p_max, 1), k_max), np.float32)
    quad_phi = np.zeros((K, max(p_max, 1), k_max, k_max), np.float32)
    lin_th = np.zeros((K, max(q_max, 1), k_max), np.float32)
    quad_th = np.zeros((K, max(q_max, 1), k_max, k_max), np.float32)
    i0 = int(include_intercept)
    for g, info in enumerate(infos):
        p, _, q = info["order"]
        P, Q, s = info["P"], info["Q"], info["s"]
        if include_intercept:
            lin_c[g, 0] = 1.0
        for i in range(p):
            lin_phi[g, i, i0 + i] = 1.0
        for j in range(q):
            lin_th[g, j, i0 + p + j] = 1.0
        for j in range(P):  # seasonal AR: lag (j+1)s - 1, cross = -1
            lag = (j + 1) * s
            lin_phi[g, lag - 1, i0 + p + q + j] += 1.0
            for i in range(p):
                quad_phi[g, lag + i, i0 + p + q + j, i0 + i] += -1.0
        for j in range(Q):  # seasonal MA: cross = +1
            lag = (j + 1) * s
            lin_th[g, lag - 1, i0 + p + q + P + j] += 1.0
            for i in range(q):
                quad_th[g, lag + i, i0 + p + q + P + j,
                        i0 + p + i] += 1.0
    return lin_c, lin_phi, quad_phi, lin_th, quad_th


def grid_kernels_refusal(specs) -> Optional[str]:
    """Why a fused group of ``(order, seasonal)`` specs cannot take the
    Pallas CSS kernels, ``None`` where it can: the grid kernels run ONE
    differenced panel under the union of dense lag ranges, so every member
    is a plain order within the kernels' reach (same-``d`` plain orders
    have one differencing signature, ``grid_diff_cache_keys(specs) == 1``)."""
    from ..ops import pallas_kernels as pk

    specs = tuple(specs)
    if any(_validate_seasonal(sea) is not None for _, sea in specs):
        # (and with it, possibly, several differencing signatures: plain
        # orders of one d always share theirs)
        return "the group has a seasonal member"
    if not pk.css_structural_ok(max(int(o[0]) for o, _ in specs),
                                max(int(o[2]) for o, _ in specs)):
        return "an order's lags reach past the kernels' half chunk"
    return None


def resolve_grid_backend(backend: str, specs, dtype, n_time: int) -> str:
    """:func:`base.resolve_backend` for a fused group: ``"auto"`` takes the
    grid kernels where the platform and the group allow
    (:func:`grid_kernels_refusal`), an explicit Pallas backend the group
    cannot take is refused with the reason."""
    why = grid_kernels_refusal(specs)
    if backend in lockstep.PALLAS and why is not None:
        raise ValueError(
            f"fit_grid runs backend={backend!r} on groups of plain orders "
            f"(one differencing signature); {why}: use backend='auto' or "
            "'scan', or search per order (auto_fit's fuse=1)")
    return resolve_backend(backend, dtype, n_time, structural_ok=why is None)


def _grid_cap(cells: int, backend: str, one_signature: bool) -> Optional[int]:
    """The fused grid's straggler cap over its ``cells`` = orders x rows.
    On the scan a QUARTER of them, aligned to 128, from 512 cells on (the
    cross-order skew makes the tail fat — a whole order can sit converged
    while another runs — so leaving the full-width lockstep early buys more
    than the compacted problem's extra width costs).  On the Pallas
    backends a SIXTEENTH, aligned to the kernels' 1,024-series blocks, from
    ``optim.COMPACT_MIN_BATCH`` cells on: stage 2 runs its cells one order
    a cell, at 2.4 times stage 1's cost a cell, and to ``max_iters`` for
    the few over-specified cells that never converge, so the chip's walk
    is shortest where stage 1 keeps all but the last sixteenth (PERF.md
    §6, PR 36: 5.10 / 3.60 / 3.03 / 3.16 s at a 4th / 8th / 16th / 32nd).
    ``None``: no compaction — small grids, and groups of several
    signatures, whose cells would each need their own panel."""
    floor, align, div = ((optim.COMPACT_MIN_BATCH, 1024, 16)
                         if backend in lockstep.PALLAS else (512, 128, 4))
    if not one_signature or cells < floor:
        return None
    cap = -(-max(align, cells // div) // align) * align
    return cap if cap < cells else None


def fit_grid(
    y,
    specs,
    include_intercept: bool = True,
    *,
    method: str = "css-lbfgs",
    max_iters: int = 60,
    tol: Optional[float] = None,
    backend: str = "auto",
    align_mode: Optional[str] = None,
) -> FitResult:
    """Fit a fused grid of K same-``d`` (S)ARIMA candidates in ONE program.

    ``specs`` is a sequence of ``(order, seasonal_or_None)`` pairs that all
    share the plain differencing order ``d`` (seasonal ``(D, s)`` may vary
    — each distinct ``(d, D, s)`` signature differences the panel once
    through the shared-prep cache).  Returns a :class:`FitResult` whose
    ``params`` matrix packs the K per-order results per row — ALL-FINITE
    by construction, with per-order eligibility as its own column
    (layout: :data:`GRID_PACK_COLS`; width :func:`grid_pack_width`) —
    and whose row-level nll/converged/iters/status summarize the row's
    BEST outcome across the grid (min nll / any-converged / max iters /
    min-severity status): a resilient caller therefore retries only rows
    with NO usable candidate, and an all-excluded row keeps the
    retry-cannot-help shield.  ``models.auto`` demuxes the pack into
    per-order results.

    The fit is a ``lockstep.Family`` over the ``K x B`` CELLS (cell ``g B +
    r`` is order ``g`` of row ``r``) driven by ``lockstep.fit`` like every
    other: ``backend`` resolves through :func:`resolve_grid_backend` —
    ``"auto"`` on a TPU in float32 takes the Pallas CSS grid kernels
    (``pallas_kernels.css_grid_neg_loglik_folded``: one panel in HBM, every
    order of a series block in one call) for groups of plain orders with one
    differencing signature, lazy stage 1 / stage 2 with the ``fit.stage1`` /
    ``fit.stage2`` spans counting cells; groups with a seasonal member or
    several signatures run the ``[K]``-vmapped padded-polynomial scan, and
    an explicit ``"pallas"`` on them raises.  The optimizing CSS methods
    only.  Numerics match the per-order fits up to f32 fusion differences
    (zero coefficient slots and the shared lockstep loop) — selection built
    on top is tested to agree with the per-order search; ``fuse=1`` in
    ``auto_fit`` remains the bitwise per-order path.
    """
    if method not in ("css-lbfgs", "css-cgd", "css-bobyqa"):
        raise ValueError(
            f"fit_grid requires an optimizing CSS method, got {method!r}")
    specs = tuple((tuple(int(v) for v in o),
                   _validate_seasonal(sea)) for o, sea in specs)
    if not specs:
        raise ValueError("fit_grid needs at least one order spec")
    d0 = specs[0][0][1]
    if any(o[1] != d0 for o, _ in specs):
        raise ValueError(
            f"fit_grid fuses same-d orders only (shared differencing); got "
            f"d values {sorted({o[1] for o, _ in specs})}")
    yb, single = ensure_batched(y)
    if tol is None:
        tol = 1e-6 if yb.dtype == jnp.float64 else 1e-4
    n = yb.shape[1] - d0
    backend = resolve_grid_backend(backend, specs, yb.dtype, n)
    align_mode = resolve_align_mode(yb, align_mode)
    static = (specs, include_intercept, backend, max_iters, float(tol))
    K, bsz = len(specs), yb.shape[0]
    p_max = max(seasonal_lag_span(o, sea)[0] for o, sea in specs)
    q_max = max(seasonal_lag_span(o, sea)[1] for o, sea in specs)
    from ..ops import pallas_kernels as pk

    out = lockstep.fit(
        (yb,), backend=backend, max_iters=max_iters, compact=True,
        inline=lambda: _grid_fit_program(*static, align_mode),
        stage1=lambda: _grid_stage1_program(*static, align_mode),
        stage2=lambda: _grid_stage2_program(*static),
        # stage 1 runs the K orders of every row, stage 2 one order a cell
        series_block=lambda rows, mode: pk.css_grid_series_block(
            *((K, bsz) if rows == K * bsz else (1, rows)), n, p_max, q_max,
            mode),
        stage_attrs={"orders": K, "cells": K * bsz,
                     "lag_terms": p_max + q_max,
                     "lag_span": max(p_max, q_max),
                     "adjoint_panels": pk.CSS_ADJOINT_PANELS},
        cells=K * bsz,
        cap=lambda cells: _grid_cap(cells, backend,
                                    grid_diff_cache_keys(specs) == 1))
    return debatch_fit(out, single, False)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["yds"], meta_fields=["cells"])
@dataclasses.dataclass(frozen=True)
class _GridPanels:
    """The scan objective's data: one differenced ``[B, n]`` panel per
    differencing signature of the group, shared by its orders through a
    broadcast — or (``cells``) a straggler subset's ``[cap, n]`` gather,
    each cell its own row."""

    yds: tuple
    cells: bool = False


def _grid_family(specs, include_intercept: bool, backend: str,
                 align_mode: Optional[str] = None):
    """``(family, pack)`` of a fused grid: the fit of its ``K x B`` cells as
    a :class:`lockstep.Family`, and the map from the cells' finalized
    ``FitResult`` to the rows' packed one.  Shared align + per-``(d, D, s)``
    differencing, per-order Hannan-Rissanen warm starts zero-padded to the
    widest order, ONE batched objective over the flattened cells on every
    backend: the CSS grid kernels over the union lag ranges, or the
    ``[K]``-axis vmapped padded-polynomial scan."""
    from ..ops import pallas_kernels as _pk

    infos = [_grid_spec_info(o, sea, include_intercept) for o, sea in specs]
    K = len(infos)
    d = infos[0]["order"][1]
    k_max = max(i["k"] for i in infos)
    p_max = max(i["p_full"] for i in infos)
    q_max = max(i["q_full"] for i in infos)
    i0 = int(include_intercept)
    any_seasonal = any(i["seasonal"] is not None for i in infos)
    on_kernels = backend in lockstep.PALLAS
    interp = backend == "pallas-interpret"
    def sig(info):  # an order's differencing signature beyond the shared d
        return (info["D"], info["s"]) if info["D"] else (0, 0)

    # the groups of orders that share a differenced panel, in spec order
    # (one per differencing signature: grid_diff_cache_keys of them)
    by_sig: dict = {}
    for g, info in enumerate(infos):
        by_sig.setdefault(sig(info), []).append(g)

    def prep(yb):
        bsz, t_len = yb.shape
        with jax.named_scope("arima.grid_align"):
            ya, nv0 = maybe_align(yb, align_mode)  # ragged: NaN head/tail
        n = t_len - d
        # shared-prep cache: ONE differencing per (d, D, s) signature
        # across the fusion group; seasonal variants embed right-aligned
        # into the group's common length n (the scan's n_valid masking
        # zeroes the pad, so the embedded recursion sees the bytes a
        # per-order fit of length n - D*s would)
        @functools.cache
        def differenced(D: int, s: int):
            with jax.named_scope("arima.grid_difference"):
                yd = jax.vmap(lambda v: _difference(v, d))(ya)
                if D:
                    yd = jax.vmap(
                        lambda v: _difference_seasonal(v, D, s))(yd)
                    yd = jnp.pad(yd, ((0, 0), (n - yd.shape[1], 0)))
            return yd

        if on_kernels:
            # plain orders of one d (grid_kernels_refusal): ONE fold for the
            # init sweeps and every evaluation of every order, differenced
            # in the folded layout as a plain fit's panel is; each order
            # conditions on its own AR depth
            folded = _pk.css_grid_prefold(
                ya, [i["p_full"] for i in infos], nv0 - d, lags=(1,) * d)
        else:
            folded = _GridPanels(tuple(differenced(*sig) for sig in by_sig))

        inits, oks, n_effs, nvds = [], [], [], []
        for info in infos:
            p, _, q = info["order"]
            nvd = nv0 - info["d_full"]
            with jax.named_scope("arima.grid_init"):
                # non-seasonal HR warm start on the (fully) differenced
                # panel; seasonal terms start at 0 (same contract as
                # _sarima_family's prep).  Inside the ok region the
                # embedding cannot change HR's static long-AR order m
                # (the nvd >= 4*(p+q+1) gate pins m = p+q+1 either way).
                if on_kernels and _pk.hr_structural_ok(p, q):
                    base = _pk.hr_init_folded(folded.y3, bsz, n, (p, 0, q),
                                              include_intercept, nvd,
                                              interpret=interp)
                else:
                    base = hannan_rissanen_batched(
                        differenced(*sig(info)), (p, 0, q),
                        include_intercept, nvd)
                if info["P"] + info["Q"]:
                    base = jnp.concatenate(
                        [base, jnp.zeros((bsz, info["P"] + info["Q"]),
                                         yb.dtype)], axis=1)
            # zero-pad to k_max: the objective never reads the pad, so its
            # gradient (and therefore its trajectory) stays exactly 0
            inits.append(jnp.pad(base, ((0, 0), (0, k_max - info["k"]))))
            pf, qf, k = info["p_full"], info["q_full"], info["k"]
            ok = nvd >= pf + qf + max(pf + qf + 1, 1) + k + 2
            oks.append(ok & (nvd >= 4 * (p + q + 1)))
            # optimize the MEAN log-likelihood (lockstep.Prepared.scale)
            n_effs.append(jnp.maximum(nvd - pf, 1).astype(yb.dtype))
            nvds.append(nvd)
        ne = jnp.concatenate(n_effs)
        # what the objective reads cell by cell: valid length, effective
        # observations, conditioning depth, order index
        rows = (jnp.concatenate(nvds), ne,
                jnp.repeat(jnp.asarray([i["p_full"] for i in infos],
                                       jnp.int32), bsz),
                jnp.repeat(jnp.arange(K, dtype=jnp.int32), bsz))
        return lockstep.Prepared((jnp.concatenate(inits),),
                                 jnp.concatenate(oks), ne, (), folded, rows)

    # -- the Pallas objective: kernel planes [c, a_1..p_max, b_1..q_max]
    # over the union lag ranges, an order's missing terms zero planes.  The
    # map from a cell's packed parameters is a constant 0/1 matrix per
    # order, gathered by the cell's order index, so a straggler subset of
    # mixed orders is one uniform problem; its transpose (JAX's) keeps a
    # zero plane's gradient from every parameter
    planes = np.zeros((K, 1 + p_max + q_max, k_max), np.float32)
    for g, info in enumerate(infos):
        p, _, q = info["order"]
        if include_intercept:
            planes[g, 0, 0] = 1.0
        for i in range(p):
            planes[g, 1 + i, i0 + i] = 1.0
        for j in range(q):
            planes[g, 1 + p_max + j, i0 + p + j] = 1.0

    def kernel_objective(folded, rows):
        _, ne, _, gcell = rows
        sel = jnp.asarray(planes)[gcell]  # [cells, planes, k_max]
        return lambda X: _pk.css_grid_neg_loglik_folded(
            jnp.einsum("cjk,ck->cj", sel, X), folded, p_max, q_max, ne,
            interpret=interp)

    # -- the scan objective
    def row_nll(c, phi_f, theta_f, ydr, nvr, cond_p, ner):
        e = _css_errors_poly(c, phi_f, theta_f, ydr, n_valid=nvr,
                             condition_lags=cond_p)
        css = jnp.sum(e * e)
        sigma2 = css / ner
        return 0.5 * ner * (jnp.log(2.0 * jnp.pi * sigma2) + 1.0)

    # over rows; the panel (ydr) is per row, the conditioning depth is
    # shared by the order
    nll_rows = jax.vmap(row_nll, in_axes=(0, 0, 0, 0, 0, None, 0))
    # over the leading [K] order axis of one diff-signature's stack; the
    # shared differenced panel broadcasts instead of tiling K x B
    nll_grid = jax.vmap(nll_rows, in_axes=(0, 0, 0, None, 0, 0, 0))
    lin_c, lin_phi, quad_phi, lin_th, quad_th = _grid_coef_maps(
        infos, include_intercept, k_max, p_max, q_max)

    def scan_objective(folded, rows):
        nvd, ne, cp, gcell = rows
        if folded.cells:
            # a straggler subset: each cell's expanded coefficients from
            # the per-order (linear, quadratic) constant maps
            # (_grid_coef_maps) — gatherable by cell index, which the
            # static-unrolled full objective is not
            (yd_s,) = folded.yds
            lc_s = jnp.asarray(lin_c)[gcell]
            lphi_s = jnp.asarray(lin_phi)[gcell]
            lth_s = jnp.asarray(lin_th)[gcell]
            qphi_s = jnp.asarray(quad_phi)[gcell] if any_seasonal else None
            qth_s = jnp.asarray(quad_th)[gcell] if any_seasonal else None
            cell_nll = jax.vmap(row_nll)

            def fb_s(p_sub):
                c = jnp.einsum("ck,ck->c", lc_s, p_sub)
                phi = jnp.einsum("cpk,ck->cp", lphi_s, p_sub)
                th = jnp.einsum("cqk,ck->cq", lth_s, p_sub)
                if any_seasonal:
                    phi = phi + jnp.einsum("cpkl,ck,cl->cp", qphi_s,
                                           p_sub, p_sub)
                    th = th + jnp.einsum("cqkl,ck,cl->cq", qth_s,
                                         p_sub, p_sub)
                return cell_nll(c, phi, th, yd_s, nvd, cp, ne)

            return fb_s
        bsz = folded.yds[0].shape[0]
        nvds, n_effs = nvd.reshape(K, bsz), ne.reshape(K, bsz)

        def fb(p_flat):
            pk = p_flat.reshape(K, bsz, k_max)
            cs, phis, thetas = [], [], []
            for g, info in enumerate(infos):
                p, _, q = info["order"]
                pg = pk[g]
                c = (pg[:, 0] if include_intercept
                     else jnp.zeros((bsz,), pg.dtype))
                phi = pg[:, i0: i0 + p]
                theta = pg[:, i0 + p: i0 + p + q]
                if info["seasonal"] is not None:
                    P, Q, s = info["P"], info["Q"], info["s"]
                    sphi = pg[:, i0 + p + q: i0 + p + q + P]
                    stheta = pg[:, i0 + p + q + P: i0 + p + q + P + Q]
                    phi = jax.vmap(
                        lambda a, b: _expand_seasonal_poly(a, b, s, -1.0)
                    )(phi, sphi)
                    theta = jax.vmap(
                        lambda a, b: _expand_seasonal_poly(a, b, s, 1.0)
                    )(theta, stheta)
                phi = jnp.pad(phi, ((0, 0), (0, p_max - phi.shape[1])))
                theta = jnp.pad(theta, ((0, 0), (0, q_max - theta.shape[1])))
                cs.append(c)
                phis.append(phi)
                thetas.append(theta)
            # one vmapped objective per diff signature: the [K_sig] stack
            # shares its differenced panel via broadcast (in_axes=None)
            out = [None] * K
            for yd, gs in zip(folded.yds, by_sig.values()):
                nll_sig = nll_grid(
                    jnp.stack([cs[g] for g in gs]),
                    jnp.stack([phis[g] for g in gs]),
                    jnp.stack([thetas[g] for g in gs]),
                    yd,
                    jnp.stack([nvds[g] for g in gs]),
                    jnp.asarray([infos[g]["p_full"] for g in gs]),
                    jnp.stack([n_effs[g] for g in gs]),
                )  # [K_sig, B]
                for j, g in enumerate(gs):
                    out[g] = nll_sig[j]
            return jnp.concatenate(out)  # [K*B]

        return fb

    def take_rows(folded, idxc):
        (yd,) = folded.yds
        return _GridPanels((yd[idxc % yd.shape[0]],), cells=True)

    family = lockstep.Family(
        backend, prep, kernel_objective if on_kernels else scan_objective,
        None, lambda x: x,
        cap=lambda cells: _grid_cap(cells, backend, len(by_sig) == 1),
        take=_pk.take_cells if on_kernels else take_rows)

    def pack(res: FitResult) -> FitResult:
        """The cells' results -> per row, per order ``[params(k_max), nll,
        eligible, converged, iters, status]``."""
        bsz = res.params.shape[0] // K
        xk = res.params.reshape(K, bsz, k_max)
        nllk = res.neg_log_likelihood.reshape(K, bsz)
        convk = res.converged.reshape(K, bsz)
        itk = res.iters.reshape(K, bsz)
        statk = res.status.reshape(K, bsz)
        blocks, nlls = [], []
        for g, info in enumerate(infos):
            # THIS order's own parameter columns: the k_max padding is NaN
            # by the pack convention (the cells' status never read it: a
            # padded slot of the optimizer's x stays exactly 0)
            params_g = jnp.where((jnp.arange(k_max) < info["k"])[None, :],
                                 xk[g], jnp.nan)
            # the PACK must be all-finite: the resilient runner's
            # failed-row mask requires finite(params).all(axis=-1) per
            # ROW, and the pack IS the row — NaN slots (excluded orders,
            # k_g padding) would mark every row failed and feed the
            # whole panel through the retry ladder.  Eligibility rides
            # as its own column; _demux_fused restores the per-order NaN
            # conventions from it and the status column.
            elig_g = jnp.isfinite(nllk[g])
            dt = params_g.dtype
            blocks += [jnp.where(jnp.isfinite(params_g), params_g, 0.0),
                       jnp.where(elig_g, nllk[g], 0.0)[:, None],
                       elig_g.astype(dt)[:, None],
                       convk[g].astype(dt)[:, None],
                       itk[g].astype(dt)[:, None],
                       statk[g].astype(dt)[:, None]]
            nlls.append(jnp.where(elig_g, nllk[g], jnp.nan))
        wide = jnp.concatenate(blocks, axis=1)  # [B, K*(k_max+5)]
        nll_all = jnp.stack(nlls)
        best = jnp.min(jnp.where(jnp.isnan(nll_all), jnp.inf, nll_all),
                       axis=0)
        # row-level summaries feed the DRIVER's accounting and the
        # resilient runner's per-ROW decisions — the per-order truth
        # lives in the pack.  A row's summary is its BEST outcome across
        # the grid: converged = ANY order usable (the ladder retries
        # rows with NO usable candidate — a single stubborn order must
        # not send the row through the ladder, and an exhausted ladder
        # must not wipe the orders that DID fit; per-candidate rescue is
        # fuse=1's contract), status = min severity (EXCLUDED only when
        # EVERY order structurally refused the row, which is when the
        # runner's retry-cannot-help shield is actually true).
        return FitResult(
            wide, jnp.where(jnp.isfinite(best), best, jnp.nan),
            jnp.any(convk, axis=0), jnp.max(itk, axis=0),
            jnp.min(statk, axis=0))

    return family, pack


def _count_diff_cache_hits(specs) -> None:
    """Trace-time shared-prep accounting: the differencings a fused group
    saves, recorded once per compile of a program that prepares the panel
    (like ``optim.stage2_compact_traces``)."""
    from .. import obs as _obs

    saved = len(specs) - grid_diff_cache_keys(specs)
    if saved > 0:
        _obs.counter("auto_fit.diff_cache_hits").add(saved)


@jit_program
def _grid_fit_program(specs, include_intercept, backend, max_iters, tol,
                      align_mode="general"):
    """The whole fused fit as one program (``lockstep.fit_program``): the
    scan backend, and a grid too small for the lazy pair."""
    _count_diff_cache_hits(specs)
    family, pack = _grid_family(specs, include_intercept, backend, align_mode)
    run = lockstep.fit_program(family, max_iters, tol)
    return lambda yb: pack(run(yb))


@jit_program
def _grid_stage1_program(specs, include_intercept, backend, max_iters, tol,
                         align_mode="general"):
    _count_diff_cache_hits(specs)
    family, pack = _grid_family(specs, include_intercept, backend, align_mode)
    run = lockstep.stage1_program(family, max_iters, tol)

    def run1(yb):
        out, aux = run(yb)
        return pack(out), aux

    return run1


@jit_program
def _grid_stage2_program(specs, include_intercept, backend, max_iters, tol):
    family, pack = _grid_family(specs, include_intercept, backend)
    run = lockstep.stage2_program(family, max_iters, tol)

    def run2(start, fin):
        out, counts = run(start, fin)
        return pack(out), counts

    return run2


# ---------------------------------------------------------------------------
# Forecasting / sampling / effects
# ---------------------------------------------------------------------------


def forecast(params, y, order: Order, n_future: int, include_intercept: bool = True,
             *, backend: str = "auto"):
    """Forecast ``n_future`` steps ahead -> ``[batch?, n_future]``.

    In-sample errors are rebuilt with the CSS recursion, then the ARMA
    recursion runs forward with future innovations set to zero and the
    order-d differencing is inverted step by step (reference
    ``ARIMAModel.forecast`` semantics).

    ``backend`` mirrors :func:`fit`: the in-sample error rebuild — the whole
    panel-scale cost of a forecast — runs on the fused Pallas ``css_errors``
    kernel when available (``"auto"``/``"pallas"``), so fit + forecast share
    one kernel family; the forward extension and inverse differencing are
    O(batch * n_future) jnp either way.
    """
    yb, single = ensure_batched(y)
    params_b = jnp.atleast_2d(params)
    p, d, q = order
    from ..ops import pallas_kernels as pk

    backend = resolve_backend(backend, yb.dtype, yb.shape[1] - d,
                              structural_ok=pk.css_structural_ok(p, q))
    out = _forecast_program(order, n_future, include_intercept, backend,
                            align_mode_on_host(yb))(params_b, yb)
    return out[0] if single else out


@jit_program
def _forecast_program(order, n_future, include_intercept, backend="scan",
                      align_mode="general"):
    p, d, q = order

    def run(params_b, yb):
        b = yb.shape[0]
        with jax.named_scope("arima.forecast_errors"):
            ya, nv0 = maybe_align(yb, align_mode)  # ragged: NaN head/tail
            yd = ya
            for _ in range(d):
                yd = yd[:, 1:] - yd[:, :-1]
            nvd = nv0 - d
            n = yd.shape[1]
            start = (n - nvd).astype(yd.dtype)  # [B]
            # differencing across the padding boundary leaves garbage at
            # yd[start-1]; zero the prefix (same contract as the fit path)
            t_idx = jnp.arange(n, dtype=yd.dtype)
            ydz = jnp.where(t_idx[None, :] >= start[:, None], yd, 0.0)
            if q == 0:
                # pure-AR forecasts never read past errors: skip the rebuild
                elast = jnp.zeros((b, 1), yd.dtype)
            elif backend in ("pallas", "pallas-interpret"):
                from ..ops import pallas_kernels as _pk

                if include_intercept:
                    params_k = params_b
                else:  # kernel layout always carries an intercept slot
                    params_k = jnp.concatenate(
                        [jnp.zeros((b, 1), params_b.dtype), params_b], axis=1
                    )
                # zb = start (not start + p) is exactly condition=False;
                # only the last q errors leave the kernel (read-only pass)
                tail = _pk.css_last_errors(p, q, backend == "pallas-interpret",
                                           params_k, ydz, start)
                elast = tail[:, ::-1]  # newest first
            else:
                e = jax.vmap(
                    lambda pr, v, nv: _css_errors(
                        pr, v, order, include_intercept, condition=False,
                        n_valid=nv)
                )(params_b, ydz, nvd)
                elast = e[:, ::-1][:, :q]
        with jax.named_scope("arima.forecast_extend"):
            i0 = int(include_intercept)
            c = params_b[:, 0] if include_intercept else jnp.zeros((b,), yd.dtype)
            phi = params_b[:, i0 : i0 + p]
            theta = params_b[:, i0 + p : i0 + p + q]
            # carries: last p differenced values (newest first); elast (the
            # last q errors, newest first) was built above
            ydlast = ydz[:, ::-1][:, :p] if p else jnp.zeros((b, 0), yd.dtype)
            # last value of each difference level 0..d-1 for integration
            levels = []
            lv = ya
            for _ in range(d):
                levels.append(lv[:, -1])
                lv = lv[:, 1:] - lv[:, :-1]
            levels = (jnp.stack(levels, axis=1) if d
                      else jnp.zeros((b, 0), yd.dtype))

            def step(carry, _):
                ydl, el, lvl = carry
                pred = c
                if p:
                    pred = pred + jnp.einsum("bi,bi->b", phi, ydl)
                if q:
                    pred = pred + jnp.einsum("bj,bj->b", theta, el)
                new_ydl = (jnp.concatenate([pred[:, None], ydl[:, :-1]], axis=1)
                           if p else ydl)
                new_el = (jnp.concatenate(
                    [jnp.zeros((b, 1), el.dtype), el[:, :-1]], axis=1)
                    if q else el)
                # integrate: v_d = pred; v_i = lvl[i] + v_{i+1}
                acc = pred
                new_lvl = lvl
                for i in reversed(range(d)):
                    acc = lvl[:, i] + acc
                    new_lvl = new_lvl.at[:, i].set(acc)
                out = acc if d else pred
                return (new_ydl, new_el, new_lvl), out

            _, future = lax.scan(step, (ydlast, elast, levels), None,
                                 length=n_future)
            return future.T  # [n_future, B] -> [B, n_future]

    return run


def sample(params, key, n: int, order: Order, include_intercept: bool = True, sigma: float = 1.0):
    """Generate a series of length ``n`` from the model with N(0, sigma^2)
    innovations (reference ``ARIMAModel.sample``)."""
    return _sample_program(order, n, include_intercept, float(sigma))(params, key)


@jit_program
def _sample_program(order, n, include_intercept, sigma):
    p, d, q = order

    def run(params, key):
        params = jnp.asarray(params, jnp.result_type(float))
        c, phi, theta = _split_params(params, order, include_intercept)
        e = sigma * jax.random.normal(key, (n + d,), params.dtype)

        def step(carry, et):
            ydl, el = carry
            yt = c + (jnp.dot(phi, ydl) if p else 0.0) + (jnp.dot(theta, el) if q else 0.0) + et
            new_ydl = jnp.concatenate([yt[None], ydl[:-1]]) if p else ydl
            new_el = jnp.concatenate([et[None], el[:-1]]) if q else el
            return (new_ydl, new_el), yt

        init = (jnp.zeros((max(p, 1),), e.dtype), jnp.zeros((max(q, 1),), e.dtype))
        _, yd = lax.scan(step, init, e)
        y = yd
        for _ in range(d):
            y = jnp.cumsum(y)
        return y[d:] if d else y

    return run


def remove_time_dependent_effects(params, y, order: Order, include_intercept: bool = True):
    """Destructure a series into its innovations (zero-padded-lag recursion;
    exactly inverted by :func:`add_time_dependent_effects`).  The first ``d``
    output entries carry the integration constants."""
    yb, single = ensure_batched(y)
    params_b = jnp.atleast_2d(params)
    out = _remove_effects_program(order, include_intercept)(params_b, yb)
    return out[0] if single else out


@jit_program
def _remove_effects_program(order, include_intercept):
    _, d, _ = order

    def run(params_b, yb):
        def one(pr, yv):
            # integration constants: the FIRST value of each difference level
            lv = yv
            inits = []
            for _ in range(d):
                inits.append(lv[0])
                lv = lv[1:] - lv[:-1]
            yd = lv
            e = _css_errors(pr, yd, order, include_intercept, condition=False)
            inits_arr = (
                jnp.stack(inits) if d else jnp.zeros((0,), yv.dtype)
            )
            return jnp.concatenate([inits_arr, e])

        return jax.vmap(one)(params_b, yb)

    return run


def add_time_dependent_effects(params, x, order: Order, include_intercept: bool = True):
    """Inverse of :func:`remove_time_dependent_effects`: innovations (with
    integration constants in the first ``d`` slots) -> the observed series."""
    xb, single = ensure_batched(x)
    params_b = jnp.atleast_2d(params)
    out = _add_effects_program(order, include_intercept)(params_b, xb)
    return out[0] if single else out


@jit_program
def _add_effects_program(order, include_intercept):
    p, d, q = order

    def run(params_b, xb):
        def one(pr, xv):
            c, phi, theta = _split_params(pr, order, include_intercept)
            init_vals, e = xv[:d], xv[d:]

            def step(carry, et):
                ydl, el = carry
                yt = (
                    c
                    + (jnp.dot(phi, ydl) if p else 0.0)
                    + (jnp.dot(theta, el) if q else 0.0)
                    + et
                )
                new_ydl = jnp.concatenate([yt[None], ydl[:-1]]) if p else ydl
                new_el = jnp.concatenate([et[None], el[:-1]]) if q else el
                return (new_ydl, new_el), yt

            init = (jnp.zeros((max(p, 1),), xv.dtype), jnp.zeros((max(q, 1),), xv.dtype))
            _, yd = lax.scan(step, init, e)
            # integrate d times using the stored initial values
            y = yd
            for i in reversed(range(d)):
                y = init_vals[i] + jnp.cumsum(y)
                y = jnp.concatenate([init_vals[i][None], y])
            return y

        return jax.vmap(one)(params_b, xb)

    return run


def is_stationary(params, order: Order, include_intercept: bool = True) -> np.ndarray:
    """AR-polynomial roots outside the unit circle (host-side diagnostic)."""
    p, _, _ = order
    if p == 0:
        return np.asarray(True)
    c, phi, _ = _split_params(np.asarray(params), order, include_intercept)
    if not np.all(np.isfinite(phi)):  # failed fit (e.g. all-NaN series)
        return np.asarray(False)
    roots = np.roots(np.concatenate([[1.0], -np.asarray(phi)])[::-1])
    return np.asarray(np.all(np.abs(roots) > 1.0 + 1e-9))


def is_invertible(params, order: Order, include_intercept: bool = True) -> np.ndarray:
    """MA-polynomial roots outside the unit circle (host-side diagnostic)."""
    _, _, q = order
    if q == 0:
        return np.asarray(True)
    _, _, theta = _split_params(np.asarray(params), order, include_intercept)
    if not np.all(np.isfinite(theta)):  # failed fit (e.g. all-NaN series)
        return np.asarray(False)
    roots = np.roots(np.concatenate([[1.0], np.asarray(theta)])[::-1])
    return np.asarray(np.all(np.abs(roots) > 1.0 + 1e-9))
