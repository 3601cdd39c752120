"""Admission / batching (``serving/batcher.py``): real rows over the rows
the server fits once every request is padded up to a multiple of
``cell_rows`` — computed from the request sizes the benchmark generated and
the ``cell_rows`` the server reports in ``health()["knobs"]``."""

import numpy as np


def read(run):
    if "schedule" not in run.result:
        return None
    rows = np.asarray(run.result["schedule"]["rows"])
    cell = int(run.result["knobs"]["cell_rows"])
    return float(rows.sum() / (np.ceil(rows / cell) * cell).sum())
