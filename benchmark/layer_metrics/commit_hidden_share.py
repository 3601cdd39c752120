"""Commit (``reliability/committer.py``): the share of the commit wall the
driver never waited for (``hidden_commit_s`` over ``commit_wall_s``, the
walk's own ``overlap_efficiency``), over the window's walks."""


def read(run):
    walks = [w["pipeline"] for w in run.result.get("walks", ())
             if w["pipeline"].get("commit_wall_s")]
    if not walks:
        return None
    return sum(p["hidden_commit_s"] for p in walks) \
        / sum(p["commit_wall_s"] for p in walks)
