"""Plain reference for additive Holt-Winters: the textbook recursion in a
float64 Python loop, no kernel, no ``utils/optim.py``.

The model (``PAPER.md``; the system's ``models.holtwinters``): level, trend
and season start from the first two seasons (level = mean of season one,
trend = difference of the two season means over the period, season = season
one less its mean); one-step forecast ``level + trend + season``; the
objective is the sum of squared one-step errors from the second season on.
Parameters ``[alpha, beta, gamma]``, each in [0, 1].
"""

import numpy as np
from scipy.optimize import minimize


def _sse(params, y, m):
    alpha, beta, gamma = (float(v) for v in params)
    level = sum(y[:m]) / m
    trend = (sum(y[m:2 * m]) / m - level) / m
    season = [v - level for v in y[:m]]
    sse = 0.0
    for t, yt in enumerate(y):
        s = season[t % m]
        base = level + trend
        if t >= m:
            sse += (yt - base - s) ** 2
        new_level = alpha * (yt - s) + (1.0 - alpha) * base
        trend = beta * (new_level - level) + (1.0 - beta) * trend
        season[t % m] = gamma * (yt - new_level) + (1.0 - gamma) * s
        level = new_level
    return sse


def _row(y):
    y = np.asarray(y, np.float64)
    return [float(v) for v in y[np.isfinite(y)]]


def objective(params, y, model_kwargs):
    """``(sum of squared errors, n_eff)`` of ``params`` on one row."""
    m = int(model_kwargs["period"])
    row = _row(y)
    return _sse(params, row, m), len(row) - m


def optimum(y, model_kwargs):
    """The parameters ``scipy.optimize`` finds inside the unit cube from
    the library's own documented start (0.3, 0.1, 0.1), minimising
    ``log sse``."""
    m = int(model_kwargs["period"])
    row = _row(y)
    res = minimize(lambda v: np.log(_sse(v, row, m)), [0.3, 0.1, 0.1],
                   method="L-BFGS-B", bounds=[(0.0, 1.0)] * 3,
                   options={"maxiter": 200})
    return res.x
