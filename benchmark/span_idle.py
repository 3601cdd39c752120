"""Device-idle time of a one-lane walk, split by what the driver was doing.

``Trace.idle_gaps`` names a gap by the spans open at its MIDPOINT, so a
17 ms gap that straddles ``fit.readback``, ``chunk.submit`` and the next
``chunk.plan`` reads as one name.  This helper splits by OVERLAP instead:
every nanosecond in which the chip ran no operation, inside the traced
window, goes to the innermost program span open at that nanosecond on the
DRIVER thread — the host thread that holds the walk's ``chunk`` spans (the
committer's and the prefetcher's threads work beside the driver; what
holds the chip back is what the driver is in).  The spans are the ``obs``
spans mirrored into the profiler (and the benchmark's own ``bench.walk``),
on the device's clock.

What a reader may ask of it (``benchmark/layer_metrics/*_exposed_s_per_chunk.py``,
``idle_unnamed_share.py``):

- :func:`split` — ``{span name: idle seconds}`` over the window; time under
  no span at all is under :data:`NO_SPAN`.  The values sum to the window's
  idle time (``device_idle_share`` x ``window_s``).  ``None`` when there is
  nothing to split: no trace, no device plane (a CPU rehearsal), no
  ``chunk`` span, or a sharded walk (several chips, or ``chunk`` spans on
  several threads) — every reader then reports nothing.
- :func:`per_chunk` — the idle seconds under some span names, over the
  window's ``chunk`` spans.
- :func:`window_spans` — the ``obs`` span LINES (``run.spans``) of one name
  that started inside the traced window, for readers of span attributes
  (``optimizer_iters_per_chunk``, ``lockstep_useful_share``).

It reads only what ``trace_reduce.Trace`` offers: ``data["host"]``,
``devices[i]["busy"]`` and ``window``.
"""

from __future__ import annotations

import bisect
import functools

from benchmark.trace_reduce import WINDOW_SPAN

NO_SPAN = "no span"
DRIVER_SPAN = "chunk"  # the thread that holds these is the walk's driver
# idle under these is idle the program's tracing does not name
UNNAMED = ("walk", "bench.walk", NO_SPAN)


def _driver_thread(trace):
    """The spans of the one host thread that holds ``chunk`` spans, or
    None (no walk traced, or several lanes)."""
    holders = [th["spans"] for th in trace.data["host"]
               if any(n == DRIVER_SPAN for n, _, _ in th["spans"])]
    return holders[0] if len(holders) == 1 else None


def _segments(spans, w0: int, w1: int) -> list:
    """``[(start, end, innermost span name), ...]`` tiling ``[w0, w1)``:
    spans of one thread nest, so at every instant the innermost is the one
    that started last among those still open."""
    clipped = sorted(((max(s, w0), min(s + d, w1), n) for n, s, d in spans
                      if n != WINDOW_SPAN and s < w1 and s + d > w0),
                     key=lambda c: (c[0], -c[1]))
    out, still_open, at = [], [], w0  # still_open: (end, name), outermost first

    def advance(to: int) -> None:
        nonlocal at
        while still_open and still_open[-1][0] <= to:
            end, name = still_open.pop()
            if end > at:
                out.append((at, end, name))
                at = end
        if to > at:
            out.append((at, to, still_open[-1][1] if still_open else NO_SPAN))
            at = to

    for start, end, name in clipped:
        advance(start)
        still_open.append((end, name))
    advance(w1)
    return out


@functools.lru_cache(maxsize=1)  # seven readers ask about one run's trace
def split(trace):
    """``{span name: device-idle seconds}`` over the traced window (module
    docstring); ``None`` when there is nothing to split."""
    if trace is None or len(trace.devices) != 1:
        return None
    spans = _driver_thread(trace)
    if spans is None:
        return None
    w0, w1 = trace.window
    busy = trace.devices[0]["busy"]  # merged, sorted [start, end)
    starts = [s for s, _ in busy]
    before = [0]  # busy nanoseconds before each interval
    for s, e in busy:
        before.append(before[-1] + e - s)

    def busy_until(t: int) -> int:
        i = bisect.bisect_right(starts, t) - 1
        if i < 0:
            return 0
        return before[i] + min(t, busy[i][1]) - busy[i][0]

    idle = {}
    for a, b, name in _segments(spans, w0, w1):
        ns = (b - a) - (busy_until(b) - busy_until(a))
        idle[name] = idle.get(name, 0) + ns
    return {name: ns / 1e9 for name, ns in idle.items()}


def per_chunk(trace, names):
    """Idle seconds under the spans ``names``, per ``chunk`` span of the
    window; ``None`` when :func:`split` has nothing, or when the program
    that ran has none of these spans (a commit before they existed)."""
    parts = split(trace)
    if parts is None or not any(trace.host_spans(n) for n in names):
        return None
    return sum(parts.get(n, 0.0) for n in names) \
        / len(trace.host_spans(DRIVER_SPAN))


def window_spans(run, name: str) -> list:
    """The ``obs`` span lines of ``name`` that started inside the traced
    window.  The profiler's clock and the lines' ``t0`` (epoch seconds) are
    different clocks, so the window is found in the lines themselves: the
    ``walk`` spans of the walks the kind traced (``result["traced_walks"]``
    are indices into the window's walks, and a ``walk`` line's ``walk`` is
    the run's sequence number, the same order), and every line carries its
    ``walk``."""
    traced = (run.result or {}).get("traced_walks")
    if not traced or not run.spans:
        return []
    roots = sorted(s["walk"] for s in run.spans
                   if s["name"] == "walk" and s.get("walk") is not None)
    if len(roots) <= max(traced):
        return []
    wanted = {roots[i] for i in traced}
    return [s for s in run.spans
            if s["name"] == name and s.get("walk") in wanted]
