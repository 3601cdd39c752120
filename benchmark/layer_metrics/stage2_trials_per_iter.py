"""Optimizer (``utils/optim.py``): line-search trials an iteration of stage
2's loops — ``sum(stage2_trials) / sum(stage2_iters)`` over the
``fit.readback`` spans of the traced walks; nothing where no stage 2 ran."""

from benchmark import span_idle


def read(run):
    trials = iters = 0
    for s in span_idle.window_spans(run, "fit.readback"):
        a = s.get("attrs", {})
        trials += a.get("stage2_trials", 0)
        iters += a.get("stage2_iters", 0)
    return trials / iters if iters else None
