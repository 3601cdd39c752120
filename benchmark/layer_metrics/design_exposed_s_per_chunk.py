"""Optimizer (``models/regression_arima.py``): device-idle seconds per chunk
with the driver under ``fit.design`` — what a shared-design fit does on the
host before its first dispatch: the design's columns, the Gram's Cholesky,
the two operands' transfers (``benchmark/span_idle.py``: idle time goes to
the INNERMOST open span, so this is not in ``dispatch_exposed_s_per_chunk``).
A program without the span gives nothing to read."""

from benchmark import span_idle


def read(run):
    return span_idle.per_chunk(run.trace, ("fit.design",))
