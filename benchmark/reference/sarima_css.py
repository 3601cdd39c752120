"""Plain reference for the seasonal ARIMA(p,d,q)(P,D,Q)_s with intercept,
conditional sum of squares: float64 numpy and ``scipy.signal.lfilter`` over
the EXPANDED polynomials, no kernel, no lag sets, no ``utils/optim.py``, no
code of the package.

The model (Box, Jenkins & Reinsel; the system's ``models.arima`` with
``seasonal=(P, D, Q, s)``): difference the row ``d`` times at lag 1 and ``D``
times at lag ``s``; then with ``a(L) = (1 - sum_i phi_i L^i)(1 - sum_j PHI_j
L^(js))`` and ``b(L) = (1 + sum_i theta_i L^i)(1 + sum_j THETA_j L^(js))``,

    e_t = a(L) x_t - c - (b(L) - 1) e_t

with lags before the start taken as zero and the first ``p + P s`` errors
conditioned to zero.  For the airline model (0,1,1)(0,1,1)_24 the moving
average polynomial is ``[1, theta, 0, ..., 0, THETA, theta THETA]``: 26
coefficients, 3 of them free to be non-zero.  The objective is the sum of
squared errors; the Gaussian log-likelihood with the variance concentrated
out is ``-0.5 n_eff (log(2 pi css / n_eff) + 1)`` with ``n_eff = n - (p +
P s)``.  Parameter layout ``[c, phi_1..p, theta_1..q, PHI_1..P,
THETA_1..Q]``.

The tolerance (``configs/sarima-airline24.json``): ``loglik_gap_max`` 0.1,
the CSS family's (``README.md`` beside this file: a likelihood ratio of 1.1;
both fits stop at a relative gradient norm on the MEAN log-likelihood), on
``min_share`` 0.9 of 64 sampled rows.  Not every row: on the chip one row
in some 700 is called converged after three iterations by the optimizer's
relative-decrease rule and stops 0.66 units short, on the kernels and on
the scan backend alike; every other row stays under 0.03.  The same
recursion in bfloat16 loses more than 0.1 on 28% of rows.  Both readings
are in the configuration's ``assumed`` and in ``PERF.md`` section 6 (PR 34).
"""

import numpy as np
from scipy.optimize import minimize
from scipy.signal import lfilter


def _poly(own, seasonal, s, sign):
    """``(1 + sign sum_i own_i L^i)(1 + sign sum_j seasonal_j L^(js))`` as
    its coefficient vector, constant term first."""
    first = np.concatenate([[1.0], sign * np.asarray(own, np.float64)])
    second = np.zeros(len(seasonal) * s + 1)
    second[0] = 1.0
    second[s::s] = sign * np.asarray(seasonal, np.float64)
    return np.convolve(first, second)


def _prepare(y, model_kwargs):
    p, d, q = model_kwargs["order"]
    P, D, Q, s = model_kwargs["seasonal"]
    x = np.asarray(y, np.float64)
    x = x[np.isfinite(x)]
    for _ in range(d):
        x = x[1:] - x[:-1]
    for _ in range(D):
        x = x[s:] - x[:-s]
    return x, (p, q, P, Q, s)


def _css(params, x, shape):
    p, q, P, Q, s = shape
    cut = np.cumsum([1, p, q, P, Q])
    phi, theta, sphi, stheta = (params[lo:hi]
                                for lo, hi in zip(cut[:-1], cut[1:]))
    u = lfilter(_poly(phi, sphi, s, -1.0), [1.0], x) - params[0]
    u[:p + P * s] = 0.0
    e = lfilter([1.0], _poly(theta, stheta, s, 1.0), u)
    return float(e @ e)


def objective(params, y, model_kwargs):
    """``(sum of squared errors, n_eff)`` of ``params`` on one row."""
    x, shape = _prepare(y, model_kwargs)
    p, _, P, _, s = shape
    return (_css(np.asarray(params, np.float64), x, shape),
            x.shape[0] - (p + P * s))


def optimum(y, model_kwargs):
    """The parameters ``scipy.optimize`` finds from a plain start (zero
    intercept, small AR terms, small negative MA terms: differencing a
    series that needed less of it leaves negative ones), maximising the
    concentrated likelihood, i.e. minimising ``log css``."""
    x, shape = _prepare(y, model_kwargs)
    p, q, P, Q, _ = shape
    start = np.concatenate([[0.0], np.full(p, 0.1), np.full(q, -0.1),
                            np.full(P, 0.1), np.full(Q, -0.1)])
    res = minimize(lambda v: np.log(_css(v, x, shape)), start,
                   method="L-BFGS-B",
                   options={"maxiter": 500, "ftol": 1e-14, "gtol": 1e-9})
    return res.x
