"""Device-busy time of a one-lane walk, split by the program that ran and
by the phase of the optimizer inside it.

``span_idle.py`` names the nanoseconds in which the chip ran NOTHING; this
helper names the others.  A v5e trace names an event by its instruction
(``fusion.200``, ``copy.80``, ``while.135``), not by a scope, and only the
Pallas calls keep theirs (``trace_reduce.py``).  Two things the program
does give are enough:

- **the host's spans are on the device's clock, to a millisecond**, and the
  one-lane walk is serial on its driver thread: ``sanitize`` blocks on its probe;
  ``fit.stage1`` runs from the dispatch of stage 1 to the gate's sync, so
  that program executes inside it; stage 2 is dispatched under
  ``fit.stage2`` and waited for under ``fit.readback``; a ladder rung is
  dispatched under ``fit.rung.*``.  So the innermost driver-thread span
  open while a top-level device event runs says which program it belongs
  to (an event nested in a ``while`` belongs where its loop does).  The
  two clocks agree to the millisecond and not below it — in PR 38's
  ``arima111`` trace stage 2's first operation STARTS 0.02-0.48 ms before
  the ``fit.stage2`` span that dispatches it opens, in 47 of 48 chunks —
  so the unit is not the event but the BURST: top-level events with no
  more than :data:`BURST_GAP_NS` of idle time between them (inside a
  program the gaps are under 20 us, between two programs the host's sync
  and dispatch leave over 100), and a burst goes whole to the program
  whose bracket covers most of it;
- **the loops nest as ``utils/optim.py`` is written**, around kernel events
  that do carry a name: a top-level ``while`` with a direct child labelled
  ``jvp_<scope>_`` (``<scope>`` the configuration's ``objective.kernel``) is
  a LOCKSTEP loop, one per start; a ``while`` directly inside it with a
  direct child labelled ``<scope>`` is a LINE SEARCH.

The parts (seconds of self time, disjoint, summing to the window's busy
seconds; every key always there):

``sanitize``
    bursts under the ``sanitize`` span: the sanitizer's probe, with what is
    queued against it (the prefetcher's slice of the next chunk, the
    operation ``chunk`` dispatches just before the span opens).
``ladder``
    events from a ``fit.rung.*`` span's open to the end of its ``chunk``
    (a rung's result is read after its span has closed).
``stage1_prep``, ``stage1_linesearch``, ``stage1_gradient``, ``stage1_update``
    the stage-1 bracket: from ``fit.primary``'s open (a family's own work
    ahead of the driver, ``resolve_align_mode``'s probe, is prep) to the
    close of the ``fit.stage1`` inside it.  Inside a line search:
    ``linesearch`` (the value-only kernel events and the XLA ops between
    them); directly in a lockstep loop the kernel events are ``gradient``
    (the ``jvp_`` / ``transpose_jvp_`` pair) and the rest ``update`` (the
    two-loop recursion, the history, the accept and stopping tests, the
    loop's own self time); everything outside the lockstep loops is
    ``prep`` (differencing, the fold, the start's kernels, the initial
    value-and-gradient, the compaction's gather, ``finalize``).
``stage2_linesearch``, ``stage2_rest``
    the stage-2 bracket: from the first ``fit.stage2`` span's open in a
    ``fit.primary`` to the close of the ``fit.readback`` that follows it.
``unattributed``
    everything else: events under ``chunk`` itself, ``chunk.plan``,
    ``chunk.submit``, ``walk*``, no span, a ``fit.primary`` with no stage
    span (the inline path), a ``fit.readback`` no stage 2 precedes — and
    every bracket in which no loop matches the anchors, whole, so a program
    whose structure changed shows as a number and not as a wrong split.

What a reader may ask of it (``benchmark/layer_metrics/*_device_share.py``,
``phase_unattributed_share.py``):

- :func:`split` — ``{part: seconds}`` of a run's traced window, ``None``
  exactly where ``span_idle.split`` is ``None`` (no trace, no device plane,
  several chips or lanes);
- :func:`share` — some parts over the window;
- :func:`trial_events` — ``{"stage1": n, "stage2": n}``: the value-only
  kernel events the trace holds inside line searches.  A trace can lose
  events, so no metric reads this; ``tests/test_device_phases.py`` holds it
  against the program's own ``trials`` on a recorded chunk.

It reads only what ``trace_reduce.Trace`` offers (``devices[0]["ops"]``,
``["self_ns"]``, ``data["host"]``, ``window``) and ``span_idle``'s driver
thread and its tiling by innermost span.
"""

from __future__ import annotations

import bisect
import functools

from benchmark import span_idle
from benchmark.trace_reduce import _label

PARTS = ("sanitize", "ladder", "stage1_prep", "stage1_linesearch",
         "stage1_gradient", "stage1_update", "stage2_linesearch",
         "stage2_rest", "unattributed")
STAGE2 = ("stage2_linesearch", "stage2_rest")
RUNG = "fit.rung."
BURST_GAP_NS = 50_000  # module docstring: what separates two programs
_FIT_SPANS = ("fit.primary", "fit.stage1", "fit.stage2", "fit.readback")


def _fits(spans) -> list:
    """One entry a ``fit.primary`` span of the driver thread: its interval,
    where the stage-1 bracket ends (the close of the ``fit.stage1`` inside
    it), where the stage-2 bracket starts (the first ``fit.stage2``'s open)
    and the ``fit.readback`` that follows."""
    by = {n: sorted((s, s + d) for name, s, d in spans if name == n)
          for n in _FIT_SPANS}
    primaries = by["fit.primary"]
    fits = []
    for i, (p0, p1) in enumerate(primaries):
        inside = lambda n: [iv for iv in by[n] if p0 <= iv[0] < p1]  # noqa: E731
        upto = primaries[i + 1][0] if i + 1 < len(primaries) else None
        back = [iv for iv in by["fit.readback"]
                if iv[0] >= p1 and (upto is None or iv[0] < upto)]
        s1, s2 = inside("fit.stage1"), inside("fit.stage2")
        fits.append({"span": (p0, p1), "back": back[0] if back else None,
                     "s1_end": max(e for _, e in s1) if s1 else None,
                     "s2_from": s2[0][0] if s2 else None})
    return fits


def _programs(spans, w0: int, w1: int):
    """The window tiled into ``(start, program)``, ``program`` a hashable
    that names one bracket: ``("sanitize",)``, ``("ladder",)``,
    ``("stage1", i)`` / ``("stage2", i)`` of the i-th fit, or ``None``."""
    fits = _fits(spans)
    starts = [f["span"][0] for f in fits]
    chunks = sorted((s, s + d) for n, s, d in spans
                    if n == span_idle.DRIVER_SPAN)
    # a rung's program is dispatched under its span and read after it:
    # the ladder holds the chunk from a rung's open to the chunk's close
    ladder = []
    for s, e in sorted((s, s + d) for n, s, d in spans
                       if n.startswith(RUNG)):
        ends = [c1 for c0, c1 in chunks if c0 <= s < c1]
        ladder.append((s, max([e] + ends)))

    def program(at: int, name: str):
        if any(s <= at < e for s, e in ladder):
            return ("ladder",)
        if name == "sanitize":
            return ("sanitize",)
        if name not in _FIT_SPANS:
            return None
        i = bisect.bisect_right(starts, at) - 1
        if i < 0:
            return None
        f = fits[i]
        if name == "fit.readback":
            under = f["back"] is not None \
                and f["back"][0] <= at < f["back"][1]
            return ("stage2", i) if under and f["s2_from"] is not None \
                else None
        if not f["span"][0] <= at < f["span"][1]:
            return None
        if f["s1_end"] is not None and at < f["s1_end"]:
            return ("stage1", i)
        if f["s2_from"] is not None and at >= f["s2_from"]:
            return ("stage2", i)
        return None

    segments = span_idle._segments(spans, w0, w1)
    seg_starts = [a for a, _, _ in segments]
    # a rung may open, and its chunk close, inside a segment
    edges = sorted(set(seg_starts) | {t for iv in ladder for t in iv
                                      if w0 <= t < w1})
    tiles = []
    for at in edges:
        name = segments[bisect.bisect_right(seg_starts, at) - 1][2]
        p = program(at, name)
        if not tiles or tiles[-1][1] != p:
            tiles.append((at, p))
    return tiles


def _bursts(ops, roots) -> list:
    """The top-level events ``roots`` (by start) in runs ``[start, end,
    [event, ...]]`` that no idle gap over :data:`BURST_GAP_NS` divides."""
    bursts = []
    for r in roots:
        start, end = ops[r][1], ops[r][1] + ops[r][2]
        if bursts and start - bursts[-1][1] <= BURST_GAP_NS:
            bursts[-1][1] = max(bursts[-1][1], end)
            bursts[-1][2].append(r)
        else:
            bursts.append([start, end, [r]])
    return bursts


def _covering(tiles, w1: int, start: int, end: int):
    """The program whose tiles cover most of ``[start, end)``."""
    cover = {}
    i = max(bisect.bisect_right(tiles, start, key=lambda t: t[0]) - 1, 0)
    while i < len(tiles) and tiles[i][0] < end:
        at, p = tiles[i]
        i += 1
        upto = tiles[i][0] if i < len(tiles) else w1
        cover[p] = cover.get(p, 0) + min(end, upto) - max(start, at)
    return max(cover, key=cover.get) if cover else None


def _parents(ops) -> list:
    """Per operation the index of the operation that encloses it (a
    ``while`` spans its body's events), ``None`` at top level."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    parent, stack = [None] * len(ops), []
    for i in order:
        start = ops[i][1]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            parent[i] = stack[-1]
        stack.append(i)
    return parent


@functools.lru_cache(maxsize=1)  # nine readers ask about one run's trace
def _phases(trace, scope: str):
    """``({part: seconds}, {"stage1": n, "stage2": n})`` or ``None``."""
    if trace is None or len(trace.devices) != 1:
        return None
    spans = span_idle._driver_thread(trace)
    if spans is None:
        return None
    dev = trace.devices[0]
    ops, self_ns = dev["ops"], dev["self_ns"]
    tiles = _programs(spans, *trace.window)
    labels = [_label(op[0]) for op in ops]
    parent = _parents(ops)
    children = {}
    for i, p in enumerate(parent):
        children.setdefault(p, []).append(i)

    def loop_over(i: int, child_label: str) -> bool:
        return labels[i].startswith("while") and any(
            labels[c] == child_label for c in children.get(i, ()))

    # a top-level event's program: the one that covers most of its burst
    roots = sorted(children.get(None, []), key=lambda i: ops[i][1])
    program = {}
    for start, end, events in _bursts(ops, roots):
        p = _covering(tiles, trace.window[1], start, end)
        program.update(dict.fromkeys(events, p))
    lockstep = {r for r in roots if program[r] is not None
                and program[r][0] in ("stage1", "stage2")
                and loop_over(r, f"jvp_{scope}_")}
    anchored = {program[r] for r in lockstep}  # the brackets that matched

    parts = dict.fromkeys(PARTS, 0)
    events = {"stage1": 0, "stage2": 0}

    def descend(i: int, part: str, searching=None) -> None:
        # ``searching``: the stage whose line search ``i`` is inside
        parts[part] += self_ns[i]
        if searching and labels[i] == scope and self_ns[i] > 0:
            events[searching] += 1
        for c in children.get(i, ()):
            descend(c, part, searching)

    for r in roots:
        p = program[r]
        if p is None or (p[0] in ("stage1", "stage2") and p not in anchored):
            descend(r, "unattributed")
        elif p[0] in ("sanitize", "ladder"):
            descend(r, p[0])
        elif r not in lockstep:
            descend(r, "stage1_prep" if p[0] == "stage1" else "stage2_rest")
        else:
            stage = p[0]
            rest = "stage2_rest" if stage == "stage2" else None
            parts[rest or "stage1_update"] += self_ns[r]
            for c in children.get(r, ()):
                if loop_over(c, scope):
                    descend(c, f"{stage}_linesearch", stage)
                elif scope in labels[c]:
                    descend(c, rest or "stage1_gradient")
                else:
                    descend(c, rest or "stage1_update")
    return {k: v / 1e9 for k, v in parts.items()}, events


def _of(run):
    trace = getattr(run, "trace", None)
    if trace is None:
        return None
    return _phases(trace, run.cell.config["objective"]["kernel"])


def split(run):
    """``{part: seconds}`` over the traced window of ``run`` (module
    docstring); ``None`` when there is nothing to split."""
    found = _of(run)
    return None if found is None else found[0]


def share(run, *names):
    """The parts ``names`` over the traced window; ``None`` when
    :func:`split` has nothing."""
    parts = split(run)
    if parts is None or run.trace.window_s <= 0:
        return None
    return sum(parts[n] for n in names) / run.trace.window_s


def trial_events(run):
    """``{"stage1": n, "stage2": n}``: value-only kernel events inside line
    searches, as the trace holds them; ``None`` like :func:`split`."""
    found = _of(run)
    return None if found is None else found[1]
