"""Optimizer (``models/lockstep.py``, ``models/holtwinters.py``): the share
of rows whose merged result is NOT the first start's —
``sum(merge_switched) / sum(rows)`` over the ``fit.readback`` spans of the
traced walks: what the later starts of a several-start fit bought
(``holtwinters._select_best_start`` counts the rows, ``lockstep.fit`` defers
the one scalar of the merge whose result it returns).  A program whose
read-backs carry no such attribute (one start a row, or a commit before the
counter) gives nothing to read."""

from benchmark import span_idle


def read(run):
    backs = [s["attrs"] for s in span_idle.window_spans(run, "fit.readback")
             if "merge_switched" in s.get("attrs", {})]
    rows = sum(a["rows"] for a in backs)
    return sum(a["merge_switched"] for a in backs) / rows if rows else None
