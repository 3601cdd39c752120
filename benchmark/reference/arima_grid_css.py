"""Plain reference for the auto-ARIMA order search over a grid of ``(p, d,
q)`` orders (``models.arima.fit_grid`` and the selection ``models.auto``
documents): float64 numpy and scipy, the CSS recursion of ``arima_css.py``
beside this file, no code of the package.

The search (Hyndman & Khandakar 2008, with the conditional sum of squares
as the likelihood): fit every order ``g`` of ``specs`` by CSS; its
concentrated Gaussian likelihood is ``nll_g = 0.5 n_g (log(2 pi css_g /
n_g) + 1)`` with ``n_g = n - p_g`` (``n`` the differenced length); its
criterion ``AICc_g = 2 nll_g + 2 pen_g`` with ``pen_g = k_g + k_g (k_g + 1)
/ (n_g - k_g - 1)``, ``k_g = 1 + p_g + q_g``; the row's order is the argmin
over the eligible orders, ties to the earlier entry.

The pack (``fit_grid``'s layout, ``grid_pack_width``): per row, per order,
``[params (k_max, zero-padded), nll, eligible, converged, iters, status]``.

One number holds the system to its fits AND to its choice:
:func:`objective` applies the selection rule to the pack's OWN ``nll`` /
``eligible`` columns (what the system would choose), evaluates the chosen
order's parameters under the float64 recursion, and returns ``(exp(2 h /
n), n)`` with ``h = nll + pen`` the chosen order's half AICc.  Then
``check.loglik_gaps``' ``0.5 n log(ss_sys / ss_ref)`` IS ``h_sys - h_ref``:
half the AICc gap between what the system chose and fitted and the best
the reference finds over the whole grid.  (ISSUE 36 wrote ``(ss exp(2 pen /
n_eff), n_eff)``; with ``n_g`` differing between orders that is the AICc
gap only where both sides choose the same ``p``, so the half AICc itself is
handed over, on one common ``n``.)
"""

import numpy as np
from scipy.optimize import minimize

from benchmark.reference import arima_css

PACK_COLS = 5  # nll, eligible, converged, iters, status


def _orders(model_kwargs):
    orders = []
    for order, seasonal in model_kwargs["specs"]:
        if seasonal is not None:
            raise ValueError("the reference covers plain (p, d, q) orders")
        orders.append(tuple(int(v) for v in order))
    return orders


def _k_max(orders):
    return max(1 + p + q for p, _, q in orders)


def half_aicc(css: float, n: int, p: int, q: int) -> float:
    """``nll + pen`` of one order from its sum of squares."""
    n_g, k = n - p, 1 + p + q
    nll = 0.5 * n_g * (np.log(2.0 * np.pi * css / n_g) + 1.0)
    return nll + k + k * (k + 1.0) / (n_g - k - 1.0)


def select(pack, orders, n: int) -> int:
    """The order the documented rule chooses from a pack's own ``nll`` and
    ``eligible`` columns: argmin of AICc over the eligible, first on ties;
    -1 where none is."""
    width = _k_max(orders) + PACK_COLS
    best, best_c = -1, np.inf
    for g, (p, _, q) in enumerate(orders):
        blk = pack[g * width:(g + 1) * width]
        n_g, k = n - p, 1 + p + q
        if not blk[width - 4] or n_g - k - 1 <= 0:
            continue
        c = 2.0 * blk[width - 5] + 2.0 * k + 2.0 * k * (k + 1.0) / (
            n_g - k - 1.0)
        if np.isfinite(c) and c < best_c:
            best, best_c = g, c
    return best


def objective(pack, y, model_kwargs):
    """``(exp(2 h / n), n)``: the half AICc ``h`` of the order the pack's
    own columns select, its parameters evaluated under the float64
    recursion (``inf`` where no order is eligible)."""
    orders = _orders(model_kwargs)
    pack = np.asarray(pack, np.float64)
    x, _, _ = arima_css._prepare(y, orders[0])
    n = x.shape[0]
    g = select(pack, orders, n)
    if g < 0:
        return np.inf, n
    p, _, q = orders[g]
    width = _k_max(orders) + PACK_COLS
    css = arima_css._css(pack[g * width:g * width + 1 + p + q], x, p, q)
    return float(np.exp(2.0 * half_aicc(css, n, p, q) / n)), n


def _fit(x, p, q, starts):
    best = None
    for start in starts:
        res = minimize(lambda v: np.log(arima_css._css(v, x, p, q)), start,
                       method="L-BFGS-B", options={"maxiter": 200})
        if best is None or res.fun < best.fun:
            best = res
    return best.x


def optimum(y, model_kwargs):
    """The pack of the reference's own fits: every order by L-BFGS-B from
    ``arima_css.py``'s plain start and, so that a nested order never fits
    worse than the order it contains, from each already fitted order of one
    AR or one MA term fewer with the new coefficient at zero."""
    orders = _orders(model_kwargs)
    x, _, _ = arima_css._prepare(y, orders[0])
    n = x.shape[0]
    k_max = _k_max(orders)
    fitted, pack = {}, []
    for p, _, q in orders:
        starts = [np.concatenate([[0.0], np.full(p, 0.3), np.full(q, 0.1)])]
        for pp, qq in ((p - 1, q), (p, q - 1)):
            if (pp, qq) in fitted:
                v = fitted[pp, qq]
                starts.append(np.concatenate(
                    [v[:1 + pp], np.zeros(p - pp), v[1 + pp:],
                     np.zeros(q - qq)]))
        v = fitted[p, q] = _fit(x, p, q, starts)
        n_g = n - p
        css = arima_css._css(v, x, p, q)
        nll = 0.5 * n_g * (np.log(2.0 * np.pi * css / n_g) + 1.0)
        pack.append(np.concatenate(
            [v, np.zeros(k_max - v.shape[0]), [nll, 1.0, 1.0, 0.0, 0.0]]))
    return np.concatenate(pack)
