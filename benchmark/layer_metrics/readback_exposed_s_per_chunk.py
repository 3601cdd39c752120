"""Ladder (``reliability/runner.py``): device-idle seconds per chunk with the
driver under ``fit.readback`` — the fetch of params, likelihood, converged
and iters and the status masks over them, which holds the next chunk's
dispatch back (``benchmark/span_idle.py``)."""

from benchmark import span_idle


def read(run):
    return span_idle.per_chunk(run.trace, ("fit.readback",))
