"""Walk driver (``reliability/plan.py``): the median host wall of a chunk,
as the journal's manifest records it (``wall_s``: dispatch, the ladder's
status read-back, until the piece is handed to the committer), over every
chunk of every walk in the window."""

import numpy as np


def read(run):
    walls = [w for walk in run.result.get("walks", ())
             for lane in walk["chunk_walls"].values() for w in lane]
    return float(np.median(walls)) if walls else None
