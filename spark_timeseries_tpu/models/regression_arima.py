"""Regression with serially-correlated errors (L4): two entries.

Rebuild of the reference's ``sparkts/models/RegressionARIMA.scala``
(SURVEY.md Section 2.2, upstream path unverified): ``y = X beta + u`` with
``u`` an autoregressive-moving-average process.

===========================  ================================================
:func:`fit_cochrane_orcutt`  a PER-ROW design ``X [batch, n, k]``, AR(1)
(``fit(y, X, method=``       errors ``u_t = rho u_{t-1} + e_t``, the iterative
``"cochrane-orcutt")``)      Cochrane-Orcutt procedure: OLS -> AR(1) on the
                             residuals -> quasi-difference, a fixed-trip
                             ``lax.fori_loop`` of vmapped normal-equations
                             solves.  Two positional arrays, so the chunk
                             walk (``reliability.fit_chunked``) cannot take
                             it.  ``params = [beta_0 .. beta_k, rho]``,
                             ``beta_0`` the intercept it prepends.
:func:`fit_shared`,          ONE design ``X [T, k]`` shared by every row
:func:`fit_harmonic`         (the reference's regressors ride the closure of
                             ``mapSeries``: calendar columns, Fourier terms
                             of the clock), ARMA(p, q) errors by joint CSS:
                             a ``lockstep.Family`` through ``lockstep.fit``
                             on the CSS kernels, keyword-only, so the walk,
                             the ladder and the journal take it like any
                             other fit.  ``params = [beta_0 .. beta_{k-1},
                             phi_1..p, theta_1..q]``; the intercept is a
                             column of ``X``, not a flag.
===========================  ================================================

The shared-design model, in the package's sign convention ``(1 - phi(L))
u_t = (1 + theta(L)) e_t``::

    u_t = y_t - x_t' beta
    e_t = u_t - sum_i phi_i u_{t-i} - sum_j theta_j e_{t-j}   (t >= p; 0 before)
    S   = sum_t e_t^2,   n_eff = T - p          (``arima.fit``'s CSS at d = 0,
                                                 no intercept, on u)

The residual plane ``X @ beta'`` is ``[T, B]``, which IS the CSS kernels'
folded layout, so the design enters the objective as two matrix products a
gradient — ``u = y - X @ beta'`` and ``dS/dbeta = -X' @ g_u`` over the
adjoint's data cotangent ``g_u = dS/du`` — and never as a ``[B, T, k]``
array; both are formed in VMEM INSIDE the CSS kernel calls, which take ``X``
and the coefficient planes as operands (``pallas_kernels``' CSS section), so
no panel-sized product runs beside them.  Start: ``beta0 = (X'X)^-1 X'y``
(one Cholesky for all rows, on the host; one product a call), ``(phi0,
theta0)`` Hannan-Rissanen on ``u(beta0)`` (that residual a panel, from the
same kernel); the optimizer moves each coefficient in its own unit at that
start, its standard error under the start's error filter, from the columns'
autocovariances and never from a filtered column.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import obs
from ..utils.linalg import ols as _ols
from . import lockstep
from .base import (ALIGN_MODES, FitResult, debatch, derive_status,
                   ensure_batched, jit_program, resolve_align_mode,
                   resolve_backend)


def _design(X):
    """Prepend an intercept column."""
    return jnp.concatenate([jnp.ones((X.shape[0], 1), X.dtype), X], axis=1)


def fit_cochrane_orcutt(y, X, *, max_iter: int = 10) -> FitResult:
    """Fit y ``[batch?, n]`` on regressors X ``[batch?, n, k]``.

    Returns ``params = [batch?, k+2]``: intercept, k slopes, rho.
    """
    y = jnp.asarray(y)
    X = jnp.asarray(X)
    single = y.ndim == 1
    yb = y[None] if single else y
    Xb = X[None] if single else X
    return debatch(_co_program(max_iter)(yb, Xb), single)


@jit_program
def _co_program(max_iter):
    def run(yb, Xb):
        def one(yv, Xv):
            Xd = _design(Xv)  # [n, k+1]

            def body(_, carry):
                beta, rho = carry
                u = yv - Xd @ beta
                # AR(1) on residuals (no intercept)
                rho = jnp.sum(u[1:] * u[:-1]) / jnp.maximum(jnp.sum(u[:-1] ** 2), 1e-12)
                rho = jnp.clip(rho, -0.999, 0.999)
                # quasi-difference transform and re-estimate beta
                ys = yv[1:] - rho * yv[:-1]
                Xs = Xd[1:] - rho * Xd[:-1]
                # intercept column becomes (1 - rho); solve in transformed space
                beta_t = _ols(Xs, ys)
                # map intercept back: beta_0 = beta_t0 (Xs keeps scaled ones)
                return beta_t, rho

            beta0 = _ols(Xd, yv)
            beta, rho = lax.fori_loop(
                0, max_iter, body, (beta0, jnp.zeros((), yv.dtype))
            )
            u = yv - Xd @ beta
            e = u[1:] - rho * u[:-1]
            n = e.shape[0]
            sigma2 = jnp.sum(e * e) / n
            nll = 0.5 * n * (jnp.log(2.0 * jnp.pi * sigma2) + 1.0)
            return jnp.concatenate([beta, rho[None]]), nll

        params, nll = jax.vmap(one)(yb, Xb)
        b = yb.shape[0]
        ones = jnp.ones((b,), bool)
        return FitResult(params, nll, ones,
                         jnp.full((b,), max_iter, jnp.int32),
                         derive_status(ones, ones, params))

    return run


def fit(y, X, method: str = "cochrane-orcutt", *,
        align_mode: Optional[str] = None, **kwargs) -> FitResult:
    """Reference ``RegressionARIMA.fitModel`` dispatcher.

    ``align_mode`` is accepted for chunk-driver uniformity
    (``base.resolve_align_mode``): the name is validated, but Cochrane-
    Orcutt has no ragged-panel alignment — the design requires dense
    ``y``/``X`` (NaNs propagate to NaN params, flagged by ``status``).
    """
    if align_mode is not None and align_mode not in ALIGN_MODES:
        raise ValueError(
            f"unknown align_mode {align_mode!r} (one of {ALIGN_MODES})")
    if method not in ("cochrane-orcutt", "cochrane_orcutt"):
        raise ValueError(f"unknown method {method!r} (supported: cochrane-orcutt)")
    return fit_cochrane_orcutt(y, X, **kwargs)


def predict(params, X):
    """Regression part only: X ``[batch?, n, k]`` -> fitted values."""
    X = jnp.asarray(X)
    single = X.ndim == 2
    Xb = X[None] if single else X
    pb = jnp.atleast_2d(params)
    out = _predict_batched(pb, Xb)
    return out[0] if single else out


_predict_batched = jax.jit(jax.vmap(lambda pr, Xv: _design(Xv) @ pr[:-1]))


# ---------------------------------------------------------------------------
# A SHARED design with ARMA errors: a lockstep family on the CSS kernels
# ---------------------------------------------------------------------------

# the panel-sized operands and results a gradient pays FOR THE DESIGN, beside
# the CSS pair's own (``pallas_kernels.CSS_ADJOINT_PANELS``): the both-mode
# forward call writes the residual ``u3`` it formed beside its errors, and
# the adjoint reads it where a plain fit's reads ``y3``.  (4 in the
# composition this replaced, PR 49: ``y3`` in and ``u3`` out of an XLA
# residual, the data cotangent ``g_u`` out of the adjoint and into ``X' @
# g_u``.)  A stage span's ``xreg_panel_moves``; tests/test_regression_arma.py
# holds it to the traced programs.
XREG_PANEL_MOVES = 1

_NOT_WRITTEN = (
    "fit_shared fits ONE design X [time, k] shared by every row with "
    "non-seasonal ARMA(p, q) errors at d = 0 (order = (p, 0, q)); not "
    "written: a differenced design (d > 0: difference y and X's columns "
    "first), seasonal error terms, and a per-row design X [batch, time, k] "
    "(that stays with fit_cochrane_orcutt)")


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["y3", "zb3", "x", "y_rows"],
                   meta_fields=["t"])
@dataclasses.dataclass(frozen=True)
class _DesignFolded:
    """A panel in the CSS kernel layout (``pallas_kernels.css_prefold`` at
    d = 0) beside the shared design ``x [tp, k]``, zero rows past the true
    length ``t`` (static); ``y_rows`` the panel ``series_major``, what the
    stragglers are gathered from (``None`` on a gathered subset)."""

    y3: jax.Array
    zb3: jax.Array
    x: jax.Array
    t: int
    y_rows: Optional[jax.Array] = None

    def take(self, idxc) -> "_DesignFolded":
        """The series ``idxc`` (``lockstep.Family.take``); the design is
        everybody's."""
        from ..ops import pallas_kernels as pk

        return _DesignFolded(pk.take_rows(self.y_rows, idxc),
                             pk.take_series(self.zb3, idxc), self.x, self.t)


_UNIT_LAGS = 32  # lags of the columns' autocovariances the units sum over


def _coefficient_units(arma, css, cov, order):
    """``[B, k]``: each coefficient's unit at a row's start, ``sqrt(css /
    |F x_j|^2)`` — its standard error there, correlations aside — where ``F``
    is the row's error filter ``e = pi(L) u`` at ``arma = [phi.., theta..]``.
    No column is filtered (a ``[B, T, k]`` array): ``|F x_j|^2 = sum_h
    rho_pi(h) c_j(h)`` over the filter's own autocorrelation ``rho_pi`` and
    the columns' autocovariances ``cov [k, 1 + _UNIT_LAGS]`` (the host's,
    once), both cut at ``_UNIT_LAGS`` lags, the series' ends aside.  A start
    outside the invertible region, where the weights do not decay, takes the
    unfiltered column's."""
    p, _, q = order
    # [B] vectors throughout, the rows on the lanes: a [B, lags] array pads
    # its short axis to 128 lanes, and thirty of them are gigabytes
    pi = [jnp.ones_like(css)]
    for i in range(1, _UNIT_LAGS + 1):
        w = -arma[:, i - 1] if i <= p else jnp.zeros_like(css)
        for j in range(1, min(i, q) + 1):
            w = w - arma[:, p + j - 1] * pi[i - j]
        pi.append(w)
    rho = jnp.stack([  # both signs of a lag
        (1.0 if h == 0 else 2.0) * sum(a * b for a, b in zip(pi, pi[h:]))
        for h in range(_UNIT_LAGS + 1)])  # [1 + lags, B]
    filtered = jnp.dot(cov, rho, precision=lax.Precision.HIGHEST).T
    plain = cov[:, 0][None, :]
    filtered = jnp.where(jnp.isfinite(filtered) & (filtered > 1e-6 * plain),
                         filtered, plain)
    return jnp.sqrt(css[:, None] / filtered)


def _shared_family(order, backend: str, align_mode: Optional[str] = None,
                   x=None) -> lockstep.Family:
    """The shared-design fit, as ``arima._css_family`` states the plain one:
    ONE fold, the least-squares start by one product, Hannan-Rissanen on its
    residual, the CSS kernels on ``(y3, x, beta)`` (they form ``u = y - x @
    beta'`` and the coefficients' gradient ``-x' dS/du`` in VMEM), the same
    mathematics on ``lax.scan`` as the portable objective.

    The optimizer's point is ``[b, phi, theta]`` with ``beta = beta0 +
    units b``: a row's coefficients as offsets from its least-squares start
    ``beta0``, each in its own unit at that start
    (:func:`_coefficient_units`; ``Prepared.natural``), so that the
    coefficients' block of the mean objective's Hessian starts near the
    identity.  In ``beta`` itself a row at level 50 has a parameter norm of
    50, and ``optim``'s stopping rule — a gradient norm under ``tol max(1,
    |x|)`` — calls it converged at its start, up to 0.13 units of
    log-likelihood short (CPU, 32 rows of the benchmark's population); in
    ONE unit a row (the residual's r.m.s.) the columns at the frequencies
    where the errors' spectrum is high (the constant, the week's harmonics)
    are a hundred times flatter than ``(phi, theta)``, L-BFGS creeps along
    them, and its relative-decrease rule stops one row in some 450 up to 0.19
    units short (the chip, 7 runs x 64 rows; PERF.md §6, PR 49).

    ``x``: the design as the inline program traces it, for the scan
    objective to close over (the stage programs read the folded pytree's)."""
    from ..ops import pallas_kernels as pk
    from . import arima

    p, _, q = order
    on_kernels = backend in lockstep.PALLAS
    interp = backend == "pallas-interpret"
    highest = lax.Precision.HIGHEST

    def coefficients(b, beta0, units):
        return beta0 + units * b

    def prep(yb, x, w, cov):
        bsz, n = yb.shape
        k = x.shape[1]
        # the design's rows are the panel's clock, so a row is never
        # shifted: one with a missing observation is excluded (a "dense"
        # hint skips the look, and a NaN then poisons its own row)
        ok = jnp.ones((bsz,), bool)
        if align_mode != "dense":
            ok = jnp.all(jnp.isfinite(yb), axis=1)
            yb = jnp.where(ok[:, None], yb, 0.0)
        nvd = jnp.full((bsz,), n, jnp.int32)
        n_eff = jnp.maximum(nvd - p, 1).astype(yb.dtype)
        series, folded = (), ()
        with jax.named_scope("regression.least_squares_start"):
            if on_kernels:
                y3, zb3 = pk.css_prefold(yb, order, nvd)
                pad = y3.shape[0] - n  # zero rows: the kernels' padded tail
                folded = _DesignFolded(y3, zb3, jnp.pad(x, ((0, pad), (0, 0))),
                                       n, pk.series_major(y3))
                beta0 = pk.design_project(jnp.pad(w, ((0, 0), (0, pad))), y3,
                                          bsz)
                # the residual as a PANEL, once a chunk: Hannan-Rissanen's
                # sweeps read it (the objective's calls form it in VMEM)
                u3 = pk.css_design_residual(y3, folded.x, beta0, n,
                                            interpret=interp)
            if not on_kernels or not pk.hr_structural_ok(p, q):
                beta0 = jnp.dot(yb, w.T, precision=highest)
                u = yb - jnp.dot(beta0, x.T, precision=highest)
        with jax.named_scope("regression.hannan_rissanen_init"):
            if on_kernels and pk.hr_structural_ok(p, q):
                arma0 = pk.hr_init_folded(u3, bsz, n, order, False, nvd,
                                          interpret=interp)
            else:
                arma0 = arima.hannan_rissanen_batched(u, order, False, nvd)
        with jax.named_scope("regression.coefficient_units"):
            if on_kernels:
                nll0 = pk.css_neg_loglik_folded(
                    arma0, y3, zb3, n, order, False, nvd,
                    design=(folded.x, beta0), interpret=interp)
            else:
                nll0 = jax.vmap(lambda a, v, m: arima.css_neg_loglik(
                    a, v, order, False, m))(arma0, u, nvd)
            # the start's sum of squares, back from its concentrated
            # likelihood 0.5 n_eff (log(2 pi css / n_eff) + 1)
            css0 = n_eff / (2.0 * jnp.pi) * jnp.exp(2.0 * nll0 / n_eff - 1.0)
            units = _coefficient_units(arma0, css0, cov, order)
            units = jnp.where(jnp.isfinite(units) & (units > 0.0), units, 1.0)
        if not on_kernels:
            series = (yb, nvd, beta0, units)
        x0 = jnp.concatenate([jnp.zeros((bsz, k), yb.dtype), arma0], axis=1)
        return lockstep.Prepared((x0,), ok, n_eff, series, folded,
                                 (nvd, beta0, units), natural=(beta0, units))

    def objective(folded, rows):
        nvd, beta0, units = rows
        k = folded.x.shape[1]
        return lambda P: pk.css_neg_loglik_folded(
            P[:, k:], folded.y3, folded.zb3, folded.t, order, False, nvd,
            design=(folded.x, coefficients(P[:, :k], beta0, units)),
            interpret=interp)

    def scan_objective(pr, data):
        yv, n, beta0, units = data
        k = x.shape[1]
        u = yv - jnp.dot(x, coefficients(pr[:k], beta0, units),
                         precision=highest)
        return arima.css_neg_loglik(pr[k:], u, order, False, n)

    def to_natural(v, beta0, units):
        k = beta0.shape[1]
        return jnp.concatenate(
            [coefficients(v[:, :k], beta0, units), v[:, k:]], axis=1)

    return lockstep.Family(backend, prep, objective, scan_objective,
                           to_natural, take=_DesignFolded.take)


@jit_program
def _shared_fit_program(order, backend, max_iters, tol, align_mode, compact):
    def run(yb, x, w, cov):
        family = _shared_family(order, backend, align_mode, x)
        return lockstep.fit_program(family, max_iters, tol, False, compact)(
            yb, x, w, cov)

    return run


@jit_program
def _shared_stage1_program(order, backend, max_iters, tol, align_mode):
    return lockstep.stage1_program(
        _shared_family(order, backend, align_mode), max_iters, tol)


@jit_program
def _shared_stage2_program(order, backend, max_iters, tol):
    return lockstep.stage2_program(_shared_family(order, backend), max_iters,
                                   tol)


def harmonic_design(n_time: int, periods: Sequence[int],
                    harmonics: Sequence[int]) -> np.ndarray:
    """``[n_time, 1 + 2 sum(harmonics)]`` float64 (read-only: one array a
    set of arguments), ``t = 0 .. n_time - 1``: the constant, then for each
    period ``P`` with ``K`` harmonics the pairs ``sin(2 pi h t / P), cos(2
    pi h t / P)``, ``h = 1 .. K`` (FPP3's ``fourier(period = P, K = K)``, one
    group after the other)."""
    return _harmonic_design(int(n_time), tuple(map(int, periods)),
                            tuple(map(int, harmonics)))


@functools.lru_cache(maxsize=16)
def _harmonic_design(n_time, periods, harmonics):
    if len(periods) != len(harmonics) or not periods:
        raise ValueError(f"periods {periods} and harmonics {harmonics} name "
                         "one K a period")
    t = np.arange(n_time)
    cols = [np.ones(t.shape)]
    for period, k in zip(periods, harmonics):
        if period < 2 or not 1 <= k < period / 2:
            raise ValueError(f"period {period} takes 1 <= K < {period / 2} "
                             f"harmonics (got {k})")
        for h in range(1, k + 1):
            # the angle reduced in integers: exact at any t
            angle = 2.0 * np.pi * ((h * t) % period) / period
            cols += [np.sin(angle), np.cos(angle)]
    x = np.stack(cols, axis=1)
    x.setflags(write=False)
    return x


def _design_operands(x, n_time: int, dtype):
    """``(x [T, k], w = (x'x)^-1 x' [k, T], cov [k, 1 + _UNIT_LAGS])`` on
    the device in ``dtype``, from ONE float64 Cholesky of the Gram on the
    host: every row's least-squares start is then one product with ``w``;
    ``cov[j, h] = sum_t x_j(t) x_j(t + h)``, the columns' autocovariances
    (:func:`_coefficient_units`).  Kept by the design's CONTENT: a walk
    calls once a chunk with one design, and the few matrix operations on
    ``[960, 31]`` took 12-21 ms of wall a call on a chip machine's shared
    host (130 ms of CPU over its BLAS threads; PERF.md §6, PR 49)."""
    if np.ndim(x) != 2:
        raise ValueError(f"{_NOT_WRITTEN}; got X of shape {np.shape(x)}")
    x = np.ascontiguousarray(x, np.float64)
    if x.shape[0] != n_time or not 0 < x.shape[1] < n_time:
        raise ValueError(f"X {x.shape} does not pair with series of "
                         f"{n_time} observations: [time, k], k < time")
    return _operands_of(x.tobytes(), x.shape, jnp.dtype(dtype).name)


@functools.lru_cache(maxsize=8)
def _operands_of(x_bytes: bytes, shape, dtype_name: str):
    x = np.frombuffer(x_bytes, np.float64).reshape(shape)
    try:
        chol = np.linalg.cholesky(x.T @ x)
    except np.linalg.LinAlgError:
        raise ValueError("X'X is not positive definite: the design's columns "
                         "are collinear") from None
    w = np.linalg.solve(chol.T, np.linalg.solve(chol, x.T))
    n = shape[0]
    cov = np.stack([np.sum(x[:max(n - h, 0)] * x[h:], axis=0)
                    for h in range(_UNIT_LAGS + 1)], axis=1)
    return tuple(jnp.asarray(a, dtype_name) for a in (x, w, cov))


def _fit_design(y, design, design_attrs: dict, order, backend, max_iters, tol,
                compact, align_mode) -> FitResult:
    """Both shared-design entries: ``design(n_time) -> X``."""
    from ..ops import pallas_kernels as pk

    order = tuple(order)
    if len(order) != 3 or order[1] != 0 or min(order) < 0:
        raise ValueError(f"{_NOT_WRITTEN}; got order {order}")
    p, _, q = order
    yb, single = ensure_batched(y)
    n = yb.shape[1]
    with obs.span("fit.design", **design_attrs) as span:
        operands = _design_operands(design(n), n, yb.dtype)
        k = operands[0].shape[1]
        span.set(columns=k)
    # lags, Hannan-Rissanen's long regression and a few degrees of freedom
    need = max(k + 2 * (p + q) + max(p + q + 1, 1) + 2, 4 * (p + q + 1),
               _UNIT_LAGS + 1)
    if n < need:
        raise ValueError(f"series of {n} observations are too short for {k} "
                         f"columns and ARMA({p}, {q}) errors (needs {need})")
    if tol is None:
        tol = 1e-6 if yb.dtype == jnp.float64 else 1e-4
    backend = resolve_backend(backend, yb.dtype, n,
                              structural_ok=pk.css_structural_ok(p, q))
    align_mode = resolve_align_mode(yb, align_mode)
    static = (order, backend, max_iters, float(tol))
    out = lockstep.fit(
        (yb, *operands), backend=backend, max_iters=max_iters,
        compact=compact,
        inline=lambda: _shared_fit_program(*static, align_mode, compact),
        stage1=lambda: _shared_stage1_program(*static, align_mode),
        stage2=lambda: _shared_stage2_program(*static),
        series_block=lambda rows, mode: pk.css_series_block(
            rows, n, order, mode, design=k),
        stage_attrs={"xreg_columns": k, "xreg_panel_moves": XREG_PANEL_MOVES,
                     "lag_terms": p + q, "lag_span": max(p, q),
                     "adjoint_panels": pk.CSS_ADJOINT_PANELS})
    return debatch(out, single)


def fit_shared(y, *, X, order=(1, 0, 1), backend: str = "auto",
               max_iters: int = 60, tol: Optional[float] = None,
               compact: bool = True,
               align_mode: Optional[str] = None) -> FitResult:
    """Regression on ONE design ``X [time, k]`` shared by every row of ``y
    [batch?, time]`` with ARMA(p, q) errors, ``order = (p, 0, q)``, by joint
    conditional sum of squares (module docstring).  The intercept is a
    column of ``X``.  ``params = [beta_0 .. beta_{k-1}, phi_1..p,
    theta_1..q]``, ``neg_log_likelihood`` the concentrated Gaussian one, as
    every CSS family reports it.

    Keyword-only beside the panel, so ``reliability.fit_chunked(fit_shared,
    panel, X=..., ...)`` walks it; ``backend``, ``max_iters``, ``tol``,
    ``compact`` and ``align_mode`` as ``arima.fit`` takes them.  The
    design's rows are the panel's clock, so no row is shifted to align it:
    a row with a missing observation is ``EXCLUDED`` (NaN params), under
    every ``align_mode`` but a ``"dense"`` hint, which skips the look (the
    hint contract: a NaN then flags its own row ``DIVERGED``).

    Raises ``ValueError`` for what is not written: a differenced design,
    seasonal error terms, a per-row ``[batch, time, k]`` design."""
    return _fit_design(y, lambda n: X, {}, order, backend, max_iters, tol,
                       compact, align_mode)


def fit_harmonic(y, *, periods: Sequence[int], harmonics: Sequence[int],
                 order=(1, 0, 1), backend: str = "auto", max_iters: int = 60,
                 tol: Optional[float] = None, compact: bool = True,
                 align_mode: Optional[str] = None) -> FitResult:
    """Dynamic harmonic regression (Hyndman & Athanasopoulos, FPP3 §12.1):
    :func:`fit_shared` on :func:`harmonic_design` — a constant and ``K``
    Fourier pairs for each seasonal period, ``t = 0`` the panel's first
    column — with ARMA errors.  Every argument is static and JSON (two
    integer lists), so a configuration file can name this entry.  ``params =
    [constant, (sin, cos) x K_1 of period 1, (sin, cos) x K_2 of period 2,
    .., phi_1..p, theta_1..q]``."""
    periods, harmonics = tuple(periods), tuple(harmonics)
    return _fit_design(
        y, lambda n: harmonic_design(n, periods, harmonics),
        {"periods": periods, "harmonics": harmonics}, order, backend,
        max_iters, tol, compact, align_mode)
