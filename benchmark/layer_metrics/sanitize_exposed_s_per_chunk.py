"""Sanitizer (``reliability/sanitize.py``): device-idle seconds per chunk
with the driver under the ``sanitize`` span — the blocking probe of the
chunk and its host pass, before any fit is dispatched
(``benchmark/span_idle.py``)."""

from benchmark import span_idle


def read(run):
    return span_idle.per_chunk(run.trace, ("sanitize",))
