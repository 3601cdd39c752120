"""Objective kernel ``pallas.garch_neg_loglik`` (the GARCH(1,1) variance
recursion with its Gaussian likelihood, and its adjoint): share of the
chip's roofline, bytes-bound (``roofline.kernel_roofline``).  The kernel's
own events only: the chunk is folded once a fit (PR 29), and the
likelihood's cotangent is an XLA pass; both are outside this share and
inside ``optimizer_device_share``."""

from benchmark import roofline


def read(run):
    return roofline.kernel_roofline(run, "pallas.garch_neg_loglik")
