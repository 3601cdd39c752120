"""Optimizer (``utils/optim.py``): the share of the traced window the device
spent on the XLA ops directly in stage 1's lockstep loops — the two-loop
recursion, the history, the accept and stopping tests
(``benchmark/device_phases.py``)."""

from benchmark import device_phases


def read(run):
    return device_phases.share(run, "stage1_update")
