"""Optimizer (``models/lockstep.py``): stage-2 dispatches per chunk of a
several-start fit — the ``fit.stage2`` spans that name their ``start`` over
the ``fit.stage1`` spans that report per start (``undone_by_start``), one a
chunk, of the traced walks: 0 where every start finished inside stage 1, up
to the number of starts.  A program whose stage spans do not report per start
(one start a row, or a commit before the attributes) gives nothing to
read."""

from benchmark import span_idle


def read(run):
    chunks = [s for s in span_idle.window_spans(run, "fit.stage1")
              if "undone_by_start" in s.get("attrs", {})]
    if not chunks:
        return None
    return sum("start" in s.get("attrs", {})
               for s in span_idle.window_spans(run, "fit.stage2")) \
        / len(chunks)
