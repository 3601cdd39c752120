"""Plain reference for multiplicative Holt-Winters: the textbook recursion
in a float64 Python loop, no kernel, no ``utils/optim.py``, no code of the
package.

The model (``PAPER.md``; the system's ``models.holtwinters`` with
``model_type="multiplicative"``): level, trend and season start from the
first two seasons (level = mean of season one, trend = difference of the two
season means over the period, season = season one OVER its mean); one-step
forecast ``(level + trend) season``; ``level' = alpha y / season + (1 -
alpha)(level + trend)``, ``trend' = beta (level' - level) + (1 - beta)
trend``, ``season' = gamma y / level' + (1 - gamma) season``; the objective
is the sum of squared one-step errors from the second season on.  Parameters
``[alpha, beta, gamma]``, each in [0, 1].

The one departure from the textbook: the three quotients' denominators are
clamped below at 1e-12, as the system's are, so that a row that touches zero
gives a number and not an exception (no row of a positive panel gets there).
"""

import numpy as np
from scipy.optimize import minimize

# the library's three documented starts (``holtwinters._MULTISTART_NATS``,
# written out here: the reference imports nothing of the package)
STARTS = ((0.3, 0.1, 0.1), (0.7, 0.25, 0.4), (0.12, 0.05, 0.6))
TINY = 1e-12


def _sse(params, y, m):
    alpha, beta, gamma = (float(v) for v in params)
    level = sum(y[:m]) / m
    trend = (sum(y[m:2 * m]) / m - level) / m
    season = [v / max(level, TINY) for v in y[:m]]
    sse = 0.0
    for t, yt in enumerate(y):
        s = season[t % m]
        base = level + trend
        if t >= m:
            sse += (yt - base * s) ** 2
        new_level = alpha * yt / max(s, TINY) + (1.0 - alpha) * base
        trend = beta * (new_level - level) + (1.0 - beta) * trend
        season[t % m] = gamma * yt / max(new_level, TINY) + (1.0 - gamma) * s
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
    """The best of what ``scipy.optimize`` finds inside the unit cube from
    each of the library's three documented starts, minimising ``log sse``
    (the surface is not convex: one start can end in a worse basin)."""
    m = int(model_kwargs["period"])
    row = _row(y)
    found = [minimize(lambda v: np.log(_sse(v, row, m)), start,
                      method="L-BFGS-B", bounds=[(0.0, 1.0)] * 3,
                      options={"maxiter": 200}) for start in STARTS]
    return min(found, key=lambda res: res.fun).x
