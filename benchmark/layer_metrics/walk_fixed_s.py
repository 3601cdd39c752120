"""Walk driver (``reliability/chunked.py``): what a walk costs beside its
chunks — its wall less the longest lane's sum of chunk walls (fingerprint,
align probe, journal open, lane start-up, final commit drain, manifest
merge, result assembly); median over the window's walks."""

import numpy as np


def read(run):
    fixed = [walk["wall_s"] - max(sum(lane) for lane in
                                  walk["chunk_walls"].values())
             for walk in run.result.get("walks", ())]
    return float(np.median(fixed)) if fixed else None
