"""Walk driver and the host under it: the share of the window that walks
slower than the median took — the window's wall less (walks x the median
walk period), over the wall.  ``series_per_s_chip`` is taken at the median
period so that a stall of the host does not move it; this is where a stall
shows.  The sandboxed filesystem of a one-chip machine stalls a walk for a
second or more about once in 70 walks; a program that stalls itself (a
pause every so many chunks) would raise this and not the end-to-end
metric, so read the two together."""

import numpy as np


def read(run):
    periods = run.result.get("walk_periods_s")
    if periods is None or not len(periods):
        return None
    total = float(np.sum(periods))
    return max(0.0, total - len(periods) * float(np.median(periods))) / total
