"""Objective kernel ``pallas.css_seasonal_neg_loglik`` (the CSS recurrence
over the live lags of a seasonal product polynomial, and its adjoint): share
of the chip's roofline, bytes-bound (``roofline.kernel_roofline``: the bytes
of each event's operands and results over kernel time over the peak of
``peaks.json``).  The events are those of the seasonal fit's named scope
(forward, ``jvp_`` and ``transpose_jvp_``); a plain ARMA fit's events carry
``pallas.css_neg_loglik`` and are not in it.  Where the trace has no such
event — the parent of the PR that added the scope, or a cell of another
family — there is nothing to read and the metric is left out."""

from benchmark import roofline


def read(run):
    return roofline.kernel_roofline(run, "pallas.css_seasonal_neg_loglik")
