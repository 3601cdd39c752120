"""Optimizer (``utils/optim.py``) and everything else XLA runs around the
kernels: device self time outside every ``pallas.*`` scope of the
configuration, over the traced window, averaged over the chips."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    outside = run.trace.busy_outside(run.kernel_scopes())
    return outside / (run.trace.window_s * len(run.trace.devices))
