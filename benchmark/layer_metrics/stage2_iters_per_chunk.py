"""Optimizer (``models/lockstep.py``): iterations of stage 2 per chunk — the
mean ``stage2_iters`` of the ``fit.readback`` spans of the traced walks (0
for a chunk whose stage 1 left nothing).  ``optimizer_iters_per_chunk`` is
stage 1's."""

from benchmark import span_idle


def read(run):
    iters = [s["attrs"]["stage2_iters"]
             for s in span_idle.window_spans(run, "fit.readback")
             if "stage2_iters" in s.get("attrs", {})]
    return sum(iters) / len(iters) if iters else None
