"""Optimizer (``models/lockstep.py``): the share of the traced window the
device spent in the stage-1 program OUTSIDE its lockstep loops — the
family's probe ahead of the driver, differencing, the fold, the start's
kernels, the initial value-and-gradient, the compaction's gather,
``finalize`` (``benchmark/device_phases.py``)."""

from benchmark import device_phases


def read(run):
    return device_phases.share(run, "stage1_prep")
