"""Admission / batching (``serving/``): the median wall of one batch walk,
from the durations of the ``server.batch`` spans ``obs`` records around
``fit_chunked`` in ``FitServer._execute_batch``.  A request waits for the
batch before it and then for its own, so this is the unit ``request_p50_s``
is made of."""

import numpy as np


def read(run):
    walls = [s["wall_s"] for s in run.spans if s["name"] == "server.batch"]
    return float(np.median(walls)) if walls else None
