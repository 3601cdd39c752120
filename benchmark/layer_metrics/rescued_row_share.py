"""Sanitize + ladder (``reliability/sanitize.py``, ``runner.py``): rows
that did not end ``OK`` on the primary fit — repaired, retried, fallen back
or lost — over all rows, from the walks' ``status_counts`` (a count)."""


def read(run):
    walks = run.result.get("walks", ())
    rows = sum(w["rows"] for w in walks)
    if not rows:
        return None
    return 1.0 - sum(w["status_counts"]["OK"] for w in walks) / rows
