"""Walk driver, set-up: the share of ``setup_s`` under a build — the union,
over every thread, of set-up's ``program.build`` intervals (``t0`` to
``t0 + wall_s``: trace, lowering and the backend's read or compile), clipped
to set-up, over ``setup_s``.  The complement is the panel's generation, the
warm-up walk's real work (its fits, its journal) and what nobody has named.
In (0, 1]; ``None`` from a program without the log."""

from benchmark import setup_builds


def read(run):
    built = setup_builds.setup_builds(run)
    if built is None or not run.setup_s:
        return None
    lo, hi = run.device_mark_t, run.device_mark_t + run.setup_s
    return setup_builds.union_s(
        (max(s["t0"], lo), min(s["t0"] + s["wall_s"], hi))
        for s in built) / run.setup_s
