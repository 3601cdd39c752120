"""Optimizer (``utils/optim.py``): line-search trials an iteration of stage
1's lockstep loops — ``sum(trials) / sum(iter_passes)`` over the
``fit.stage1`` spans of the traced walks (the carry's own count, not the
trace's events).  A program without the counter gives nothing to read."""

from benchmark import span_idle


def read(run):
    trials = passes = 0
    for s in span_idle.window_spans(run, "fit.stage1"):
        a = s.get("attrs", {})
        if "trials" in a:
            trials += a["trials"]
            passes += a["iter_passes"]
    return trials / passes if passes else None
