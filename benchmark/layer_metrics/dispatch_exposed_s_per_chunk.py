"""Optimizer (``models/*.fit``, ``utils/optim.py``): device-idle seconds per
chunk with the driver under ``fit.primary`` or its children ``fit.stage1``
/ ``fit.stage2`` — Python dispatch before the first device operation of a
fit, and the stage gate's round trip (``benchmark/span_idle.py``)."""

from benchmark import span_idle


def read(run):
    return span_idle.per_chunk(run.trace,
                               ("fit.primary", "fit.stage1", "fit.stage2"))
