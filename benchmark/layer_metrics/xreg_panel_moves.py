"""Optimizer (``models/regression_arima.py``, the shared-design family):
panel-sized operands and results a gradient pays FOR THE DESIGN, beside the
CSS kernel pair's own — the mean ``xreg_panel_moves`` attribute of the
``fit.stage1`` spans of the traced walks (``fit_shared`` hands it to
``lockstep.fit`` from its constant ``XREG_PANEL_MOVES``, which a tier-1 test
holds to the traced programs).  4 in the composition on the kernels the repo
has: the residual ``u3 = y3 - X @ beta'`` reads the panel and writes one, the
adjoint writes the data cotangent ``g_u`` and ``X' @ g_u`` reads it; 0 the
day the CSS kernels form ``u_t`` and ``X' g_u`` themselves, where
``css_neg_loglik_roofline`` would only show the kernels at the HBM's pace
for whatever they are handed.  A program whose spans carry no such
attribute gives nothing to read."""

from benchmark import span_idle


def read(run):
    moves = [s["attrs"]["xreg_panel_moves"]
             for s in span_idle.window_spans(run, "fit.stage1")
             if "xreg_panel_moves" in s.get("attrs", {})]
    return sum(moves) / len(moves) if moves else None
