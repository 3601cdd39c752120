"""Optimizer (``utils/optim.py``): lockstep L-BFGS iterations of stage 1 per
chunk — the mean ``iters`` attribute (the carry's ``k`` the stage gate
syncs anyway) over the ``fit.stage1`` spans of the traced walks.
``kernel_calls_per_chunk`` over it is kernel events per iteration: the
line search's cost."""

from benchmark import span_idle


def read(run):
    iters = [s["attrs"]["iters"]
             for s in span_idle.window_spans(run, "fit.stage1")
             if "iters" in s.get("attrs", {})]
    return sum(iters) / len(iters) if iters else None
