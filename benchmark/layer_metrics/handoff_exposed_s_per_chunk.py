"""Walk driver (``reliability/plan.py``): device-idle seconds per chunk with
the driver under ``chunk.plan`` (committer error poll, resume lookup,
boundary decision, deadline check; the walk's final drain too) or
``chunk.submit`` (telemetry row, hand-over to the committer with its
backpressure) — the driver's own cost between two chunks
(``benchmark/span_idle.py``)."""

from benchmark import span_idle


def read(run):
    return span_idle.per_chunk(run.trace, ("chunk.plan", "chunk.submit"))
