"""Objective kernel ``pallas.css_grid_neg_loglik`` (the CSS recurrence of
EVERY order of a grid over one folded panel, and its adjoint): share of the
chip's roofline, bytes-bound (``roofline.kernel_roofline``: the bytes of each
event's operands and results over kernel time over the peak of
``peaks.json``).  The events are those of the order search's named scope
(forward, ``jvp_`` and ``transpose_jvp_``); a single order's events carry
``pallas.css_neg_loglik`` or ``pallas.css_seasonal_neg_loglik`` and are not
in it.  The panel is an operand ONCE whatever the number of orders, so a
value-only call moves few bytes for its work and the share is low by
design: what bounds the step is its bundles, not the HBM.  Where the trace
has no such event — the parent of the PR that added the scope, or a cell of
another family — there is nothing to read and the metric is left out."""

from benchmark import roofline


def read(run):
    return roofline.kernel_roofline(run, "pallas.css_grid_neg_loglik")
