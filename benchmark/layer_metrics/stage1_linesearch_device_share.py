"""Optimizer (``utils/optim.py``): the share of the traced window the device
spent inside stage 1's line searches — the value-only objective calls and
the XLA ops between them (``benchmark/device_phases.py``);
``stage1_trials_per_iter`` is their count."""

from benchmark import device_phases


def read(run):
    return device_phases.share(run, "stage1_linesearch")
