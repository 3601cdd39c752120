"""Walk driver, set-up: ``sum(trace_s + lower_s)`` over set-up's
``program.build`` lines — Python tracing to a jaxpr and the jaxpr's lowering
to MLIR (a Pallas kernel body is lowered to Mosaic here), before any cache
key exists: paid warm and cold alike, and what fewer, smaller or exported
programs shorten.  An inner ``jit``'s trace lies in its caller's and is
counted once.  ``None`` from a program without the log."""

from benchmark import setup_builds


def read(run):
    return setup_builds.attr_sum(run, "trace_s", "lower_s")
