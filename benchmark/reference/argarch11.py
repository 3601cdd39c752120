"""Plain reference for AR(1) + Gaussian GARCH(1,1): float64 numpy and
``scipy.signal.lfilter``, no kernel, no ``utils/optim.py``, no code of the
package.

The model (upstream ``ARGARCH.fitModel``; the system's
``models.garch.fit_argarch``): on the row's valid span ``y_0 .. y_{n-1}``,

    r_t = y_t - c - phi y_{t-1}                       t = 1 .. n-1
    h_t = omega + alpha r_{t-1}^2 + beta h_{t-1}
    nll = 0.5 sum_{t >= 1} (log 2 pi + log h_t + r_t^2 / h_t)

Parameters ``[c, phi, omega, alpha, beta]``.  Departures from the textbook,
all of them ``models.garch.argarch_neg_log_likelihood``'s conventions: the
fit CONDITIONS on the first valid observation, so there are ``n - 1``
returns and as many likelihood terms; the recursion is seeded with the
sample variance of those returns (about their mean, divided by ``n - 1``),
which stands in for ``h_0`` AND for the unobserved ``r_0^2``, so ``h_1 =
omega + (alpha + beta) var`` — a seed that moves with ``phi``; the returns
are not demeaned in the likelihood itself; ``h`` is floored at 1e-12.

As ``garch11.py`` explains, the likelihood is not a concentrated sum of
squares and ``check.py`` computes ``0.5 n_eff log(ss_sys / ss_ref)``: so
:func:`objective` returns ``(exp(2 nll / n_eff), n_eff)`` with ``n_eff = n -
1``, and that formula gives EXACTLY ``nll_sys - nll_ref``, the
log-likelihood gap in the units the other references' gaps have.

The tolerance (``configs/argarch11.json``, whose ``assumed`` list and
``PERF.md`` §6, PR 52, give the readings behind it) is ``garch11``'s kind:
``loglik_gap_max`` units on ``min_share`` of 64 sampled rows, one-sided.
"""

import numpy as np
from scipy.optimize import minimize
from scipy.signal import lfilter

H_FLOOR = 1e-12
# (omega / resid var, alpha + beta, alpha / (alpha + beta)) of the two
# starts: the library's own (0.1 var, 0.1, 0.8) from its moment start of the
# mean, and (alpha 0.05, beta 0.90) from the least-squares AR(1)
STARTS = ((0.1, 0.9, 1.0 / 9.0), (0.05, 0.95, 0.05 / 0.95))
# c in units of the row's standard deviation; phi strictly inside the unit
# circle; the GARCH box holds exactly omega > 0, alpha, beta >= 0 and
# alpha + beta < 1
BOUNDS = ((-10.0, 10.0), (-0.999, 0.999), (1e-8, 10.0), (0.0, 1.0 - 1e-6),
          (0.0, 1.0))


def _span(y):
    v = np.asarray(y, np.float64)
    return v[np.isfinite(v)]


def _returns(c, phi, v):
    return v[1:] - c - phi * v[:-1]


def _nll(params, v):
    c, phi, omega, alpha, beta = (float(p) for p in params)
    r = _returns(c, phi, v)
    var = np.var(r)
    x = omega + alpha * np.concatenate([[var], r[:-1] ** 2])
    h, _ = lfilter([1.0], [1.0, -beta], x, zi=[beta * var])
    h = np.maximum(h, H_FLOOR)
    return 0.5 * float(np.sum(np.log(2.0 * np.pi * h) + r * r / h))


def nll(params, y):
    """Negative Gaussian log-likelihood of ``[c, phi, omega, alpha, beta]``
    on one row (NaNs outside the valid span)."""
    return _nll(params, _span(y))


def objective(params, y, model_kwargs):
    """``(exp(2 nll / n_eff), n_eff)``: the pair ``check.loglik_gaps`` turns
    into ``nll(system) - nll(optimum)``."""
    n_eff = len(_span(y)) - 1
    return np.exp(2.0 * nll(params, y) / n_eff), n_eff


def _mean_starts(v):
    """``(c, phi)`` twice: the library's moment start (lag-1 autocorrelation
    over the whole span, clipped, ``c`` from the mean) and least squares of
    ``y_t`` on ``(1, y_{t-1})``."""
    d = v - v.mean()
    phi_m = float(np.clip(d[1:] @ d[:-1] / max(d @ d, 1e-12), -0.95, 0.95))
    design = np.column_stack([np.ones(len(v) - 1), v[:-1]])
    c_ls, phi_ls = np.linalg.lstsq(design, v[1:], rcond=None)[0]
    return ((v.mean() * (1.0 - phi_m), phi_m),
            (float(c_ls), float(np.clip(phi_ls, -0.95, 0.95))))


def _natural(z, sd, var):
    return np.array([z[0] * sd, z[1], z[2] * var, z[3] * z[4],
                     z[3] * (1.0 - z[4])])


def optimum(y, model_kwargs):
    """The best of what ``scipy.optimize`` finds from the two starts, over
    ``(c / sd, phi, omega / var, alpha + beta, alpha / (alpha + beta))``
    inside :data:`BOUNDS`, ``sd`` and ``var`` the row's own (of the first
    start's returns) so that the numeric gradient's step means the same on
    every row."""
    v = _span(y)
    means = _mean_starts(v)
    var = max(np.var(_returns(*means[0], v)), 1e-300)
    sd = np.sqrt(var)
    runs = [minimize(lambda z: _nll(_natural(z, sd, var), v) / (len(v) - 1),
                     (c / sd, phi, *garch), method="L-BFGS-B", bounds=BOUNDS,
                     options={"maxiter": 500, "ftol": 1e-14, "gtol": 1e-9})
            for (c, phi), garch in zip(means, STARTS)]
    return _natural(min(runs, key=lambda res: res.fun).x, sd, var)
