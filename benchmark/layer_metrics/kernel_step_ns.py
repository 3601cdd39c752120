"""Objective kernel (``ops/pallas_kernels.py``): kernel time over (events x
padded time steps), in nanoseconds — the mean time step of an
objective-kernel event, over ALL the events of the traced window.  The
recurrence is serial in time; an event walks the batch in grid steps of
R x 1,024 series (R = 1, 2 or 4 vector registers a time step, the kernel
file's rule), each time step a handful of vector operations on a tiny
carry.  Stage 2's events run on the compacted stragglers, an eighth of the
chunk's rows, and are counted like stage 1's full-chunk events, so this is
NOT one event's time over T (a stage-1 value-only event of HW took 2.58 ms
where this metric times T gave 1.74; PR 31): it moves with the kernels'
pace and with the mix of the two stages.  A latency figure, not a
bandwidth one; the ``*_roofline`` metrics are those."""

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
