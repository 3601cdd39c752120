"""Optimizer (``utils/optim.py``): the share of the traced window the device
spent on the value-and-gradient kernel events of stage 1's lockstep loops
(the ``jvp_`` / ``transpose_jvp_`` pair an iteration;
``benchmark/device_phases.py``)."""

from benchmark import device_phases


def read(run):
    return device_phases.share(run, "stage1_gradient")
