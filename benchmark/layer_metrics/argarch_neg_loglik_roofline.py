"""Objective kernel ``pallas.argarch_neg_loglik`` (the GARCH(1,1) kernel
pair with the AR(1) mean equation formed in its calls — ``r_t = y_t - c -
phi y_{t-1}`` in VMEM — and its adjoint): share of the chip's roofline,
bytes-bound (``roofline.kernel_roofline``).  The events under the scope: the
two calls, and the ``[rows]``-sized folds of the parameters and the seed
around them.  A program without the scope (the composition that builds a
returns panel in XLA and calls ``pallas.garch_neg_loglik``) gives nothing to
read."""

from benchmark import roofline


def read(run):
    return roofline.kernel_roofline(run, "pallas.argarch_neg_loglik")
