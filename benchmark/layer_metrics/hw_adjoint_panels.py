"""Objective kernel (``ops/pallas_kernels.py``, the Holt-Winters
recurrence): panel-sized operands of the objective's adjoint call — the mean
``adjoint_panels`` attribute of the ``fit.stage1`` spans of the traced walks
(``models/holtwinters.py`` hands it to ``lockstep.fit`` from the kernel
file's ``HW_ADJOINT_PANELS``).  1 for the additive model, whose adjoint
reads the raw one-step errors alone; 5 where it replays saved level, trend
and season trajectories beside the panel and the errors (the multiplicative
model, and the additive one before PR 43), where ``hw_sse_roofline`` would
only show the kernels at the HBM's pace for whatever they are handed.  A
program whose spans carry no such attribute gives nothing to read."""

from benchmark import span_idle


def read(run):
    panels = [s["attrs"]["adjoint_panels"]
              for s in span_idle.window_spans(run, "fit.stage1")
              if "adjoint_panels" in s.get("attrs", {})]
    return sum(panels) / len(panels) if panels else None
