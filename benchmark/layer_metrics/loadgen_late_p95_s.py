"""Load generator (the benchmark's own): how late it called ``submit``
against the open-loop schedule, 95th percentile.  It shares the host's
cores with the server; a starved generator must not read as a fast
server."""

import numpy as np


def read(run):
    if "submitted_s" not in run.result:
        return None
    late = run.result["submitted_s"] - run.result["schedule"]["due_s"]
    return float(np.percentile(late[np.isfinite(late)], 95))
