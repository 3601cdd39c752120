"""What the order search's test files share (``test_auto.py``,
``test_auto_winners.py``): the panels with a known answer, the bitwise
comparison of two searches, and ``tools/`` on the import path."""

import os
import sys

import numpy as np

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")
sys.path.insert(0, TOOLS)

FIELDS = ("params", "neg_log_likelihood", "converged", "iters", "status",
          "order_index", "criterion")


def _eq(a, b):
    a = np.asarray(a)
    return np.array_equal(a, np.asarray(b), equal_nan=a.dtype.kind == "f")


def assert_results_equal(r1, r2, fields=FIELDS):
    for f in fields:
        assert _eq(getattr(r1, f), getattr(r2, f)), f


def make_known_panel(rows_per=8, t=120, seed=0):
    """Rows 0..7 AR(1), 8..15 MA(1), 16..23 ARIMA(1,1,0) — each block's
    true order is on the grid, so selection has a known answer."""
    rng = np.random.default_rng(seed)
    b = 3 * rows_per
    e = rng.normal(size=(b, t)).astype(np.float32)
    y = np.zeros_like(e)
    for i in range(t):
        y[:rows_per, i] = (0.7 * y[:rows_per, i - 1] if i else 0) \
            + e[:rows_per, i]
    y[rows_per:2 * rows_per] = e[rows_per:2 * rows_per]
    y[rows_per:2 * rows_per, 1:] += 0.6 * e[rows_per:2 * rows_per, :-1]
    w = y[2 * rows_per:]
    for i in range(1, t):
        w[:, i] = (w[:, i - 1]
                   + 0.6 * (w[:, i - 1] - (w[:, i - 2] if i > 1 else 0))
                   + e[2 * rows_per:, i])
    return y


KNOWN_ORDERS = [(1, 0, 0), (0, 0, 1), (1, 1, 0)]


def make_ar_panel(b=24, t=120, seed=0, phi=0.7):
    rng = np.random.default_rng(seed)
    e = rng.normal(size=(b, t)).astype(np.float32)
    y = np.zeros_like(e)
    for i in range(t):
        y[:, i] = (phi * y[:, i - 1] if i else 0) + e[:, i]
    return y


def make_seasonal_panel(b=12, t=160, s=4, seed=3, sphi=0.7):
    rng = np.random.default_rng(seed)
    e = rng.normal(size=(b, t)).astype(np.float32)
    y = np.zeros_like(e)
    for i in range(t):
        y[:, i] = (sphi * y[:, i - s] if i >= s else 0) + e[:, i]
    return y
