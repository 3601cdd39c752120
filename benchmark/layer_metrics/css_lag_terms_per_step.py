"""Objective kernel (``ops/pallas_kernels.py``, the CSS recurrence): lag
terms a kernel time step pays — the mean ``lag_terms`` attribute of the
``fit.stage1`` spans of the traced walks (``models/arima.py`` hands it to
``lockstep.fit``: the live AR and MA lags of the model's expanded
polynomials).  3 for the airline model ARIMA(0,1,1)(0,1,1)_24 on the sparse
lag sets {1, 24, 25}; 25 the day somebody routes it back through the dense
range ``1..25``, where ``kernel_step_ns`` would only show a time.  A program
whose spans carry no such attribute gives nothing to read."""

from benchmark import span_idle


def read(run):
    terms = [s["attrs"]["lag_terms"]
             for s in span_idle.window_spans(run, "fit.stage1")
             if "lag_terms" in s.get("attrs", {})]
    return sum(terms) / len(terms) if terms else None
