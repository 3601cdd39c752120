"""Objective kernel ``pallas.css_neg_loglik`` (ARIMA's CSS recurrence and
its adjoint): share of the chip's roofline, bytes-bound
(``roofline.kernel_roofline``).  The kernel is a serial recurrence, so a
low share is step latency (``kernel_step_ns``), not wasted bandwidth."""

from benchmark import roofline


def read(run):
    return roofline.kernel_roofline(run, "pallas.css_neg_loglik")
