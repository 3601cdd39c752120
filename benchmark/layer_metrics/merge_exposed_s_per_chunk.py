"""Optimizer (``models/lockstep.py``): device-idle seconds per chunk with
the driver under ``fit.merge``, the dispatch of the re-merge of a
several-start fit's results after a stage 2 (``benchmark/span_idle.py``:
idle time goes to the INNERMOST open span, so this is not in
``dispatch_exposed_s_per_chunk``).  A program without the span gives nothing
to read."""

from benchmark import span_idle


def read(run):
    return span_idle.per_chunk(run.trace, ("fit.merge",))
