"""Optimizer (``utils/optim.py``): passes over the panel a chunk, from the
loops' own counters — value-only passes (``trials`` + ``stage2_trials``)
plus value-and-gradient passes (``iter_passes`` + one initial pass a start,
+ ``stage2_iters``), summed over the ``fit.stage1`` / ``fit.readback`` spans
of the traced walks and divided by their chunks.  Exact where
``kernel_calls_per_chunk`` loses device events; the ladder's rungs run the
inline program and are not in it."""

from benchmark import span_idle


def read(run):
    gate = [s["attrs"] for s in span_idle.window_spans(run, "fit.stage1")
            if "trials" in s.get("attrs", {})]
    chunks = len(span_idle.window_spans(run, span_idle.DRIVER_SPAN))
    if not gate or not chunks:
        return None
    passes = sum(a["trials"] + a["iter_passes"] + a["starts"] for a in gate)
    for s in span_idle.window_spans(run, "fit.readback"):
        a = s.get("attrs", {})
        passes += a.get("stage2_trials", 0) + a.get("stage2_iters", 0)
    return passes / chunks
