"""Objective kernel (``ops/pallas_kernels.py``, the CSS grid kernels):
orders one kernel call carries — the mean ``orders`` attribute of the
``fit.stage1`` spans of the traced walks (``models/arima.py``'s ``fit_grid``
hands it to ``lockstep.fit``: the K of the fused group).  9 while one call
evaluates the whole group over the shared panel; 1 the day somebody routes
the search back through per-order calls, where ``kernel_step_ns`` would only
show a time.  A program whose spans carry no such attribute gives nothing
to read."""

from benchmark import span_idle


def read(run):
    orders = [s["attrs"]["orders"]
              for s in span_idle.window_spans(run, "fit.stage1")
              if "orders" in s.get("attrs", {})]
    return sum(orders) / len(orders) if orders else None
