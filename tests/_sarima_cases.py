"""The seasonal orders and the airline-model panel that the seasonal ARIMA
test files share (``test_sarima_lockstep.py``, the lag-set kernels and the
product map; ``test_sarima_fit.py``, the fit and the walk)."""

import numpy as np

AIRLINE = ((0, 1, 1), (0, 1, 1, 24))  # M = {1, 24, 25}
AIRLINE4 = ((0, 1, 1), (0, 1, 1, 4))  # M = {1, 4, 5}: the same shape, short
SARMA4 = ((1, 0, 1), (1, 0, 1, 4))  # A = M = {1, 4, 5}
LAZY_ROWS = 2048  # the smallest batch whose compaction cap is under it


def airline_panel(rows, n_time, s, seed):
    """``[rows, n_time]`` f64 of ``(1-L)(1-L^s) y = (1 + th L)(1 + TH L^s) e``,
    one ``(th, TH)`` a row (the benchmark process's ranges) -> ``(y, th,
    TH)``."""
    rng = np.random.default_rng(seed)
    th = rng.uniform(-0.7, -0.2, rows)
    TH = rng.uniform(-0.8, -0.4, rows)
    e = rng.normal(size=(rows, n_time + 2 * s))
    w = e.copy()
    w[:, 1:] += th[:, None] * e[:, :-1]
    w[:, s:] += TH[:, None] * e[:, :-s]
    w[:, s + 1:] += (th * TH)[:, None] * e[:, :-s - 1]
    y = np.cumsum(w[:, 2 * s:], axis=1)
    for i in range(s, n_time):
        y[:, i] += y[:, i - s]
    return y, th, TH
