"""Optimizer (``models/lockstep.py``): the share of the traced window the
device spent on stage 2 — from a ``fit.stage2`` span's open to the close of
the ``fit.readback`` that follows (``benchmark/device_phases.py``); 0 where
none was dispatched."""

from benchmark import device_phases


def read(run):
    return device_phases.share(run, *device_phases.STAGE2)
