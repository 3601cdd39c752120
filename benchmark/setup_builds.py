"""Set-up split by what the process BUILT in it (ISSUE 54).

``spark_timeseries_tpu.utils.compile_cache`` keeps one record per executable
the process builds or loads, and ``obs.enable`` writes those it already
holds as ``program.build`` span lines with their true ``t0`` — so the stream
of a traced run, enabled after the warm-up, still holds set-up's builds.
The six ``setup_*`` readers beside ``setup_first_chunk_s`` take the lines
whose ``t0`` lies in ``[run.device_mark_t, run.device_mark_t + run.setup_s]``
(both ``time.time()``).  A program whose stream has no such line (a commit
before the log) gives them nothing to read."""

from benchmark import trace_reduce

BUILD_SPAN = "program.build"


def setup_builds(run):
    """The ``program.build`` lines that started inside set-up, or ``None``
    where the stream has no such line at all."""
    lines = [s for s in run.spans or () if s.get("name") == BUILD_SPAN]
    if not lines or run.setup_s is None:
        return None
    lo, hi = run.device_mark_t, run.device_mark_t + run.setup_s
    return [s for s in lines if lo <= s["t0"] <= hi]


def attr_sum(run, *names):
    """``sum`` of the attributes ``names`` over set-up's builds (a null
    attribute, a miss's ``retrieval_s``, counts 0), or ``None``."""
    built = setup_builds(run)
    if built is None:
        return None
    return sum(s["attrs"].get(n) or 0.0 for s in built for n in names)


def union_s(intervals) -> float:
    """Seconds covered by the ``(start, end)`` pairs, overlaps once."""
    return sum(e - s for s, e in trace_reduce._union(intervals))
