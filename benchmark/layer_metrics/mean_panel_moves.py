"""Optimizer (``models/garch.py``, ``fit_argarch``): panel-sized operands
and results a gradient pays FOR THE MEAN EQUATION, beside the GARCH kernel
pair's own — the mean ``mean_panel_moves`` attribute of the ``fit.stage1``
spans of the traced walks (``fit_argarch`` hands it to ``lockstep.fit`` from
its constant ``ARGARCH_MEAN_PANEL_MOVES``, which a tier-1 test holds to the
traced programs).  0 where the kernel calls form the returns and reduce
``dL/dc``, ``dL/dphi`` themselves; a program that builds the returns panel
in XLA (the series and its shifted copy read, the returns written, squared
and folded a pass, the r^2 cotangent written by the adjoint, unfolded and
chained back) would pay about 18, where ``argarch_neg_loglik_roofline``
would only show the kernels at the HBM's pace for whatever they are handed.
A program whose spans carry no such attribute gives nothing to read."""

from benchmark import span_idle


def read(run):
    moves = [s["attrs"]["mean_panel_moves"]
             for s in span_idle.window_spans(run, "fit.stage1")
             if "mean_panel_moves" in s.get("attrs", {})]
    return sum(moves) / len(moves) if moves else None
