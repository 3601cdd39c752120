"""Optimizer (``utils/optim.py``): of the row-iterations the lockstep
optimizer spent, the share in which the row was still working —
``sum(iters_sum) / sum(rows x iters_max)`` over the ``fit.readback`` spans
of the traced walks (the per-row ``iters`` the runner fetches anyway).
What compaction and a cheaper line search can and cannot win."""

from benchmark import span_idle


def read(run):
    spent = useful = 0
    for s in span_idle.window_spans(run, "fit.readback"):
        a = s.get("attrs", {})
        if "iters_max" in a:
            spent += a["rows"] * a["iters_max"]
            useful += a["iters_sum"]
    return useful / spent if spent else None
