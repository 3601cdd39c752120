"""Walk driver (``reliability/plan.py`` ``LaneRunner``, the fit-ahead slot of
``reliability/prefetcher.py``): of the chunks that had a predecessor in their
lane's walk (every chunk of a walk but its first), the share whose result
CAME from a fit started one chunk ahead: ``sum(fits_ahead_taken) /
sum(n_chunks - 1)`` over the window's walks, from the walk's own
``meta["pipeline"]`` — so a fit never started and a fit dropped both show.
Nothing from a program without the counter."""


def read(run):
    walks = [w for w in run.result.get("walks", ())
             if "fits_ahead_taken" in w["pipeline"] and w["n_chunks"] > 1]
    if not walks:
        return None
    return sum(w["pipeline"]["fits_ahead_taken"] for w in walks) \
        / sum(w["n_chunks"] - 1 for w in walks)
