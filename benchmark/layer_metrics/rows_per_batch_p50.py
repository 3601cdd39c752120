"""Admission / batching (``serving/``): the median rows of a packed batch
(padding included), from the ``rows`` attribute of the ``server.batch``
spans.  Small batches mean the per-batch host cost is paid often."""

import numpy as np


def read(run):
    rows = [s["attrs"]["rows"] for s in run.spans
            if s["name"] == "server.batch" and "rows" in s.get("attrs", {})]
    return float(np.median(rows)) if rows else None
