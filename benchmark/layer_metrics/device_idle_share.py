"""Device, walk cells: 1 - the union of device-operation intervals over the
traced window, per chip, the worst chip reported (``trace_reduce.Trace``).
Says whether a chunk costs its device time or its host time."""


def read(run):
    return run.trace.idle_share_worst() if run.trace else None
