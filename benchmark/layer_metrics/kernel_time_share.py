"""Objective kernel (``ops/pallas_kernels.py``): device self time of the
events under the configuration's kernel scopes (``pallas.css_neg_loglik``,
``pallas.hr_init``, ``pallas.hw_sse`` ...) over the traced window, averaged
over the chips."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    seconds = sum(run.trace.scope(s)["seconds"]
                  for s in run.kernel_scopes())
    return seconds / (run.trace.window_s * len(run.trace.devices))
