"""Device, serving cells: 1 - the union of device-operation intervals over
the traced window (``trace_reduce.Trace``).  In a serving cell below its
knee the chip also idles because no request is there; read it beside
``batch_wall_p50_s``."""


def read(run):
    return run.trace.idle_share_worst() if run.trace else None
