"""Retry ladder (``reliability/runner.py``): what the EXPENSIVE rung still
does — ``sum(iters)`` of the ``fit.rung.fallback`` spans of the traced walks
(a span's ``iters`` is the rung's largest iteration count over its real
rows: the lockstep iterations its bucket ran) over the window's chunks.  The
fallback rung is the portable ``scan`` backend, a vmapped per-row L-BFGS
whose objective is a ``lax.scan`` over the series, a few microseconds a time
step for a handful of rows: tens of iterations a chunk where it refits a row
from the start, a few (or 0: no such span) where the row arrives at the
point the rung before it reached.  A program whose rung spans carry no
``iters`` (a commit before the rule) gives nothing to read; one that says
``continued`` on a retry rung and never reaches the fallback reads 0."""

from benchmark import span_idle


def _says(run, name):
    return [s["attrs"] for s in span_idle.window_spans(run, name)
            if "iters" in s.get("attrs", {})]


def read(run):
    fallback = _says(run, "fit.rung.fallback")
    chunks = len(span_idle.window_spans(run, span_idle.DRIVER_SPAN))
    if not chunks or not (fallback or _says(run, "fit.rung.retry")):
        return None
    return sum(a["iters"] for a in fallback) / chunks
