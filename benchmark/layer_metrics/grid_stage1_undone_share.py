"""Optimizer (``models/lockstep.py`` over a grid of orders): the share of
CELLS (order x row) stage 1 hands to stage 2 — ``undone / rows`` summed over
the ``fit.stage1`` spans of the traced walks that carry ``orders`` (a grid's
spans count cells).  What the grid's cap (a sixteenth of the cells on the
kernels, ``arima._grid_cap``) and the cross-order skew decide: near the cap's
share when stage 1 stops at the cap, lower when the budget or the convergence
of whole orders ends it first.  A program whose spans carry no ``orders``
gives nothing to read."""

from benchmark import span_idle


def read(run):
    undone = cells = 0
    for s in span_idle.window_spans(run, "fit.stage1"):
        a = s.get("attrs", {})
        if "orders" in a and "undone" in a:
            undone += a["undone"]
            cells += a["rows"]
    return undone / cells if cells else None
