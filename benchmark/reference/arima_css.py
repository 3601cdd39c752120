"""Plain reference for ARIMA(p,d,q) with intercept, conditional sum of
squares: float64 numpy and ``scipy.signal.lfilter``, no kernel, no
``utils/optim.py``.

The model (``PAPER.md``; the system's ``models.arima``): difference the row
``d`` times; then ``e_t = x_t - c - sum_k phi_k x_{t-k} - sum_j theta_j
e_{t-j}`` with lags before the start taken as zero and the first ``p``
errors conditioned to zero.  The objective is the sum of squared errors;
the Gaussian log-likelihood with the variance concentrated out is
``-0.5 n_eff (log(2 pi css / n_eff) + 1)`` with ``n_eff = n - p``.
Parameter layout ``[c, phi_1..p, theta_1..q]``.
"""

import numpy as np
from scipy.optimize import minimize
from scipy.signal import lfilter


def _prepare(y, order):
    p, d, q = order
    x = np.diff(np.asarray(y, np.float64), n=d) if d else np.asarray(
        y, np.float64)
    return x[np.isfinite(x)], p, q


def _css(params, x, p, q):
    c, phi, theta = params[0], params[1:1 + p], params[1 + p:1 + p + q]
    u = x - c
    for k in range(1, p + 1):
        u[k:] -= phi[k - 1] * x[:-k]
    u[:p] = 0.0
    e = lfilter([1.0], np.concatenate([[1.0], theta]), u)
    return float(e @ e)


def objective(params, y, model_kwargs):
    """``(sum of squared errors, n_eff)`` of ``params`` on one row."""
    x, p, q = _prepare(y, model_kwargs["order"])
    return _css(np.asarray(params, np.float64), x, p, q), x.shape[0] - p


def optimum(y, model_kwargs):
    """The parameters ``scipy.optimize`` finds from a plain start (zero
    intercept, small positive AR and MA terms), maximising the concentrated
    likelihood, i.e. minimising ``log css``."""
    x, p, q = _prepare(y, model_kwargs["order"])
    start = np.concatenate([[0.0], np.full(p, 0.3), np.full(q, 0.1)])
    res = minimize(lambda v: np.log(_css(v, x, p, q)), start,
                   method="L-BFGS-B", options={"maxiter": 200})
    return res.x
