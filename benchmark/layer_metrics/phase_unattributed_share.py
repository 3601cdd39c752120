"""Device, walk cells: the share of the traced window the device spent on
events ``benchmark/device_phases.py`` cannot give to a program or a phase —
under ``chunk`` itself, the hand-over, no span, the inline path, or a bracket
whose loops match no anchor.  What ``idle_unnamed_share`` is for idle time."""

from benchmark import device_phases


def read(run):
    return device_phases.share(run, "unattributed")
