"""Retry ladder (``reliability/runner.py``): how often a rung CONTINUES a
row — ``sum(continued) / sum(rows)`` over the ``fit.rung.*`` spans of the
traced walks.  A row is continued when the attempt before the rung stopped
it at its iteration budget with a finite point: it enters the rung at that
point, unperturbed, where every other failed row starts from a perturbed
point or from the fit's own start.  1.0 where every ladder row ran out of
budget (``garch11``'s one row), 0 where they stalled
(``harmonic-arma24x168``'s).  A program whose rung spans carry no such
attribute (a commit before the rule), or a window in which no rung ran,
gives nothing to read."""

from benchmark import span_idle


def read(run):
    names = {s["name"] for s in run.spans or ()
             if s["name"].startswith("fit.rung.")}
    rungs = [s["attrs"] for name in sorted(names)
             for s in span_idle.window_spans(run, name)
             if "continued" in s.get("attrs", {})]
    rows = sum(a["rows"] for a in rungs)
    return sum(a["continued"] for a in rungs) / rows if rows else None
