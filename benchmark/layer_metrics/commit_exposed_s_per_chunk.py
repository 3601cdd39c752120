"""Commit (``reliability/committer.py``, ``journal.py``): seconds per chunk
the walk's driver waited for the background committer
(``meta["pipeline"]["driver_blocked_s"]`` over chunks), over the window's
walks.  What of the journal's cost the pipeline does not hide."""


def read(run):
    walks = [w for w in run.result.get("walks", ())
             if "driver_blocked_s" in w["pipeline"]]
    if not walks:
        return None
    return sum(w["pipeline"]["driver_blocked_s"] for w in walks) \
        / sum(w["n_chunks"] for w in walks)
