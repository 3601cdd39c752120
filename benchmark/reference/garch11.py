"""Plain reference for Gaussian GARCH(1,1): float64 numpy and
``scipy.signal.lfilter``, no kernel, no ``utils/optim.py``, no code of the
package.

The model (``PAPER.md``; the system's ``models.garch``): on the row's valid
span ``r_0 .. r_{n-1}``,

    h_t = omega + alpha r_{t-1}^2 + beta h_{t-1}
    nll = 0.5 sum_t (log 2 pi + log h_t + r_t^2 / h_t)

Parameters ``[omega, alpha, beta]``.  Departures from the textbook, all of
them ``models.garch.neg_log_likelihood``'s conventions: the recursion is
seeded with the sample variance of the span (about its mean, divided by
``n``), which stands in for ``h_{-1}`` AND for the unobserved ``r_{-1}^2``,
so ``h_0 = omega + (alpha + beta) var`` and the likelihood sums all ``n``
terms; the returns are not demeaned in the likelihood itself; ``h`` is
floored at 1e-12.

The likelihood is not a concentrated sum of squares, and ``check.py`` (not
this PR's to edit) computes ``0.5 n_eff log(ss_sys / ss_ref)``.  So
:func:`objective` returns ``(exp(2 nll / n), n)``: put into that formula
it is EXACTLY ``nll_sys - nll_ref``, the log-likelihood gap in the units
the other references' gaps have.

The tolerance (``configs/garch11.json``): ``loglik_gap_max`` 1.0 on
``min_share`` 0.9 of 64 sampled rows.  One unit of log-likelihood is a
likelihood ratio of e, a difference of 2 in AIC: by the usual convention two
parameter vectors that close have equal support from the row.  It is set
between two readings (``PERF.md`` §6, PR 28).  What the system leaves, on
the chip: the library's ``tol`` of 1e-4 stops the f32 fit short of the
optimum along the flat omega-beta valley, by 0.01-0.05 units in the median,
0.21 at the 90th percentile, over 1.0 on 1.2% of rows (3.4 at most, of
1,280 rows); so a run of 64 rows has one such row on average and may have
six.  What a fit in the nearest precision below float32 leaves: the same
recursion rounded to bfloat16 at every step, its optimum found in full by a
derivative-free search, loses 0.33 units in the median and 1.23 at the 90th
percentile, 15% of rows over 1.0; a ``tol`` of 1e-3 leaves 71% of rows over
1.0 and 3e-4 leaves 11%.  All three read ``correct: false``; ``hw-add24``'s
3.9 would pass the first.  ARIMA's 0.1 on every row is out of reach of the
library's defaults: a fifth of the rows stop further out than that, and one
row in a hundred ends in another basin than scipy's best of two starts
(which is why ``min_share`` is not 1.0).
"""

import numpy as np
from scipy.optimize import minimize
from scipy.signal import lfilter

H_FLOOR = 1e-12
# (omega / var, alpha + beta, alpha / (alpha + beta)): the library's own
# documented start (0.1 var, 0.1, 0.8), and one near the integrated edge
STARTS = ((0.1, 0.9, 1.0 / 9.0), (0.02, 0.98, 0.05 / 0.98))
BOUNDS = ((1e-8, 10.0), (0.0, 1.0 - 1e-6), (0.0, 1.0))


def _span(y):
    r = np.asarray(y, np.float64)
    return r[np.isfinite(r)]


def _nll(params, r, var):
    omega, alpha, beta = (float(v) for v in params)
    x = omega + alpha * np.concatenate([[var], r[:-1] ** 2])
    h, _ = lfilter([1.0], [1.0, -beta], x, zi=[beta * var])
    h = np.maximum(h, H_FLOOR)
    return 0.5 * float(np.sum(np.log(2.0 * np.pi * h) + r * r / h))


def nll(params, y):
    """Negative Gaussian log-likelihood of ``[omega, alpha, beta]`` on one
    row (NaNs outside the valid span)."""
    r = _span(y)
    return _nll(params, r, np.var(r))


def objective(params, y, model_kwargs):
    """``(exp(2 nll / n), n)``: the pair ``check.loglik_gaps`` turns into
    ``nll(system) - nll(optimum)``."""
    n = len(_span(y))
    return np.exp(2.0 * nll(params, y) / n), n


def _natural(v, var):
    return np.array([v[0] * var, v[1] * v[2], v[1] * (1.0 - v[2])])


def optimum(y, model_kwargs):
    """The best of what ``scipy.optimize`` finds from :data:`STARTS`, over
    ``(omega / var, alpha + beta, alpha / (alpha + beta))`` inside
    :data:`BOUNDS` — a box that holds exactly omega > 0, alpha, beta >= 0
    and alpha + beta < 1, scaled by the row's variance so that the numeric
    gradient's step means the same on every row."""
    r = _span(y)
    var = np.var(r)
    runs = [minimize(lambda v: _nll(_natural(v, var), r, var) / len(r),
                     start, method="L-BFGS-B", bounds=BOUNDS,
                     options={"maxiter": 500, "ftol": 1e-14, "gtol": 1e-9})
            for start in STARTS]
    return _natural(min(runs, key=lambda res: res.fun).x, var)
