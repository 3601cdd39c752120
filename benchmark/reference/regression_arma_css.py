"""Plain reference for dynamic harmonic regression: a constant and Fourier
pairs of each seasonal period with ARMA(p, q) errors, conditional sum of
squares — float64 numpy, a plain Python loop for the errors, a Fourier
matrix of its own, no kernel, no ``utils/optim.py``, no code of the package.

The model (Hyndman & Athanasopoulos, FPP3 section 12.1; the system's
``models.regression_arima.fit_harmonic``), in the package's signs ``(1 -
phi(L)) u_t = (1 + theta(L)) e_t``::

    x_t = [1, sin(2 pi h t / P), cos(2 pi h t / P)  h = 1..K,  for each (P, K)]
    u_t = y_t - x_t' beta
    e_t = u_t - sum_i phi_i u_{t-i} - sum_j theta_j e_{t-j}   t >= p, 0 before

The objective is the sum of squared errors; the Gaussian log-likelihood with
the variance concentrated out is ``-0.5 n_eff (log(2 pi css / n_eff) + 1)``
with ``n_eff = T - p``.  Parameter layout ``[beta_0 .. beta_{k-1}, phi_1..p,
theta_1..q]``, ``k = 1 + 2 sum(K)``.

The optimum by PROFILING the coefficients out, a route the system does not
take (it runs L-BFGS over all ``k + p + q`` parameters jointly): the errors
are linear in ``u``, so for a given ``(phi, theta)`` the filtered row is a
linear function of ``beta`` — filter the row and every column with the same
recursion, solve the least-squares problem for ``beta`` — and the outer
search is ``scipy.optimize`` over ``p + q`` parameters from several starts.

The tolerance (``configs/harmonic-arma24x168.json``): ``loglik_gap_max`` 0.1,
the CSS family's (``README.md`` beside this file: a likelihood ratio of 1.1).
What the nearest precision below leaves is in that file's ``assumed`` and in
``PERF.md`` section 6 (PR 49); ``tests/test_harmonic_arma.py`` reads it anew.
"""

import numpy as np
from scipy.optimize import minimize

STARTS = ((0.3, 0.1), (0.7, 0.0), (0.0, 0.0))  # (every phi, every theta)


def design(n_time, model_kwargs):
    """``[n_time, k]`` float64: the constant, then period by period the
    pairs ``sin, cos`` of harmonic ``h = 1 .. K`` at ``t = 0 .. n_time - 1``."""
    t = np.arange(n_time, dtype=np.float64)
    cols = [np.ones(n_time)]
    for period, k in zip(model_kwargs["periods"], model_kwargs["harmonics"]):
        for h in range(1, int(k) + 1):
            w = 2.0 * np.pi * h / float(period)
            cols += [np.sin(w * t), np.cos(w * t)]
    return np.column_stack(cols)


def errors(u, phi, theta):
    """The recursion on ``u [T]`` or on every column of ``u [T, m]``: it is
    linear in ``u``, which is what the profile uses."""
    p, q = len(phi), len(theta)
    v = np.array(u, np.float64)
    for i in range(1, p + 1):
        v[i:] -= phi[i - 1] * u[:-i]
    e = np.zeros_like(v)
    for t in range(p, v.shape[0]):
        acc = v[t]
        for j in range(1, min(q, t) + 1):
            acc = acc - theta[j - 1] * e[t - j]
        e[t] = acc
    return e


def _split(params, model_kwargs):
    p, _, q = model_kwargs["order"]
    params = np.asarray(params, np.float64)
    k = params.shape[0] - p - q
    return params[:k], params[k:k + p], params[k + p:]


def objective(params, y, model_kwargs):
    """``(sum of squared errors, n_eff)`` of ``params`` on one row."""
    y = np.asarray(y, np.float64)
    beta, phi, theta = _split(params, model_kwargs)
    e = errors(y - design(y.shape[0], model_kwargs) @ beta, phi, theta)
    return float(e @ e), y.shape[0] - len(phi)


def profile(arma, y, x, p):
    """``(beta, css)`` at ``arma = [phi.., theta..]``: the least-squares
    coefficients of the filtered row on the filtered columns (their normal
    equations: Fourier columns stay near-orthogonal under the filter)."""
    f = errors(np.column_stack([y, x]), arma[:p], arma[p:])[p:]
    fy, fx = f[:, 0], f[:, 1:]
    beta = np.linalg.solve(fx.T @ fx, fx.T @ fy)
    r = fy - fx @ beta
    return beta, float(r @ r)


def optimum(y, model_kwargs):
    """The best of the outer searches (``L-BFGS-B``, numeric gradient, over
    ``log css`` of the profile) from :data:`STARTS`."""
    p, _, q = model_kwargs["order"]
    y = np.asarray(y, np.float64)
    x = design(y.shape[0], model_kwargs)

    def log_css(arma):
        css = profile(arma, y, x, p)[1]
        return np.log(css) if np.isfinite(css) and css > 0 else 1e300

    best = None
    for a, b in STARTS:
        res = minimize(log_css, np.concatenate([np.full(p, a), np.full(q, b)]),
                       method="L-BFGS-B", bounds=[(-0.999, 0.999)] * (p + q),
                       options={"maxiter": 200, "ftol": 1e-12, "gtol": 1e-8})
        if best is None or res.fun < best.fun:
            best = res
    return np.concatenate([profile(best.x, y, x, p)[0], best.x])
