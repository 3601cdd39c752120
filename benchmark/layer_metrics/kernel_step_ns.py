"""Objective kernel (``ops/pallas_kernels.py``): nanoseconds one time step
of the recurrence takes in an objective-kernel event at the cell's chunk
size — kernel time over (events x padded time steps).  The recurrence is
serial in time; an event walks the chunk's 1,024-series blocks one after
another, each step a handful of vector operations on a tiny carry, so this
is the latency floor VERDICT r5 asked for, not a bandwidth figure."""

from benchmark import roofline


def read(run):
    if run.trace is None:
        return None
    obj = run.cell.config["objective"]
    got = run.trace.scope(obj["kernel"])
    if not got["events"]:
        return None
    steps = roofline.padded_time_steps(int(obj["time_steps"]))
    return got["seconds"] * 1e9 / (got["events"] * steps)
