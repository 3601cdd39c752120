"""Objective kernel ``pallas.hw_sse`` (the Holt-Winters recursion and its
adjoint): share of the chip's roofline, bytes-bound
(``roofline.kernel_roofline``)."""

from benchmark import roofline


def read(run):
    return roofline.kernel_roofline(run, "pallas.hw_sse")
