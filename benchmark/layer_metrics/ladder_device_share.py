"""Retry ladder (``reliability/runner.py``): the share of the traced window
the device spent on the ladder's rungs — events from a ``fit.rung.*`` span's
open to the close of its chunk (``benchmark/device_phases.py``); 0 where no
rung ran."""

from benchmark import device_phases


def read(run):
    return device_phases.share(run, "ladder")
