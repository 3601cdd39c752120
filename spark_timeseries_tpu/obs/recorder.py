"""Flight recorder: bounded ring of structured events + JSONL stream.

The Spark event log (``spark.eventLog.enabled``) wrote every job/stage/task
transition to a file the history server replayed after a crash.  The
rebuild's analog is two-layered:

- a **ring buffer** of the last ``ring_size`` events always held in memory
  (cheap enough to leave on for long jobs — old events fall off the back),
  dumped to disk when a fit fails so the post-mortem starts with the tail
  of what the process was doing; and
- an optional **JSONL stream**: when the telemetry plane is enabled with a
  path, every event is also appended (and flushed — a SIGKILL loses at most
  the current line) to a file ``tools/obs_report.py`` renders.

One event = one flat JSON object.  Schema (``SCHEMA_VERSION``, v3):

- every line: ``ts`` (epoch seconds) and ``kind`` in
  ``meta | span | event | metrics``;
- ``meta``: first line of a stream — ``schema``, ``run_id``, ``pid``;
- ``span``: ``name``, ``t0``, ``wall_s``, ``process_s``, ``depth``,
  ``attrs`` (a closed span; emitted at exit), and since v3 its identity
  ``id`` / ``parent`` / ``walk`` (below);
- ``event``: ``name``, ``attrs`` (a point event: journal commit, OOM
  backoff, watchdog timeout, fit failure);
- ``metrics``: a full registry snapshot (``counters`` / ``gauges`` /
  ``histograms``), emitted at the end of an instrumented fit and on
  disable/dump.

Schema v2 (ISSUE 18) adds an OPTIONAL top-level ``trace`` object on
``span`` and ``event`` lines, stamped by :mod:`.core` whenever a
:mod:`.tracing` context is active on the emitting thread:

- ``trace.trace_id``: 16 lowercase hex chars —
  ``sha256("ststpu-trace:" + request_id)[:16]``, identical in every
  process that handles the request (derivation, not propagation);
- ``trace.span_id``: 16 lowercase hex chars —
  ``sha256(trace_id + ":" + site)[:16]`` for the causal segment
  ("client", "server", "server.batch", ...) the line belongs to;
- ``trace.parent_id`` (optional): the caller segment's ``span_id``
  (the wire header carried it across the hop).

v1 streams (no ``trace`` anywhere) remain readable by every consumer;
``tools/obs_report.py --check`` accepts an absent ``trace`` and FAILS a
malformed one (wrong type, bad id shape) instead of letting it vanish.

Schema v3 (ISSUE 25) gives every ``span`` line three top-level fields
(:mod:`.core` sets them; the serving ``trace`` object above is untouched):

- ``id``: a positive integer from one per-run counter, unique within the
  run (the mirrored ``jax.profiler`` annotation carries it as the stat
  ``span_id``);
- ``parent``: the ``id`` of the span that caused this one — the span open
  beneath it on its thread, or the span that handed the work over
  (``commit.overlap`` -> the ``chunk.submit`` that queued it,
  ``stage.overlap`` -> the ``chunk`` that scheduled it, a watchdog
  worker's ``sanitize`` / ``fit.primary`` -> its ``chunk``); ``null`` on a
  root.  A span closes after its children, so a parent's line FOLLOWS
  theirs;
- ``walk`` (present inside a walk only): the run's sequence number of the
  ``fit_chunked`` call, shared by every span of that call, across threads.

The walk path's spans, root to leaf: ``walk`` (attrs ``rows``,
``chunk_rows``, ``lanes``, ``journaled``) > ``walk.open``, then per chunk
``chunk.plan`` (``lo``, ``hi``), ``chunk`` > ``sanitize``, ``fit.primary``
> ``fit.stage1`` (``rows``, ``iters``, ``undone``, and what the lockstep
loop's carry counted over the ``starts``: ``trials``, the line search's
trials, and ``iter_passes``, the sum of the starts' iterations where
``iters`` is their max) and ``fit.stage2`` (``rows``), ``fit.readback``
(``rows``, ``iters_max``, ``iters_sum``, ``failed``, and on the lazy
optimizer path ``stage2_iters`` / ``stage2_trials``: the iterations and
line-search trials of the chunk's stage-2 programs, 0 and 0 where none was
dispatched), the ladder's ``fit.rung.*``, then ``chunk.submit`` (``lo``,
``hi``) > ``commit.overlap`` on the committer thread; ``stage.overlap`` on
the prefetcher thread under its ``chunk``; last ``walk.close``.  A
``chunk`` carries ``phase`` (``compile+execute`` where a build closed on its
thread inside it, else ``execute``), ``builds`` and ``build_s``.

``program.build`` (ISSUE 54) is a span of another making: one per executable
the process builds or loads, written by ``utils.compile_cache``'s build log
through ``obs.closed_span`` once jax has reported the build's end.  ``t0``
is ``time.time()`` at the build's outermost trace (else its first event),
``wall_s`` runs to the backend's end; ``attrs``: ``program`` (jax's name
for it: ``arima._fit_stage1_program``, ``_probe``, ``dynamic_slice``),
``thread``, ``trace_s``, ``lower_s``, ``backend_s``, ``cache`` (``hit`` /
``miss`` / ``off``), ``retrieval_s`` (hits, else null), ``compiled_s``.
``parent`` / ``walk`` are those of the innermost span open on the BUILDING
thread.  It has no ``process_s`` (nobody read that clock at its start) and
no profiler annotation.  ``obs.enable`` first writes the builds the process
made BEFORE the run — ``parent`` null, no ``walk``, and a ``t0`` that
precedes the ``meta`` line's ``ts``, which is legal for this name alone.

v2 lines (no ``id``) stay readable: ``--check`` takes a span line with or
without the identity, and FAILS one whose ``id`` / ``parent`` / ``walk`` is
not a positive integer or whose ``id`` repeats within its run (a stream cut
by SIGKILL ends with children whose parents never closed, so that a
``parent`` resolves is a test's business, not the gate's).
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Optional

__all__ = ["SCHEMA_VERSION", "FlightRecorder"]

SCHEMA_VERSION = 3


class FlightRecorder:
    """Bounded event ring, optionally teeing every event to a JSONL file."""

    # lock-discipline contract (tools/lint lock-map): every instrumented
    # thread emits; ring, counter, and the teed file handle mutate only
    # under _lock (emit downgrades _file to None on a broken stream).
    _protected_by_ = {
        "_ring": "_lock",
        "events_emitted": "_lock",
        "_file": "_lock",
    }

    def __init__(self, run_id: str, ring_size: int = 4096,
                 jsonl_path: Optional[str] = None):
        self.run_id = run_id
        self.jsonl_path = jsonl_path
        self._ring = collections.deque(maxlen=int(ring_size))
        self._lock = threading.Lock()
        self._file = None
        self.events_emitted = 0
        if jsonl_path:
            d = os.path.dirname(os.path.abspath(jsonl_path))
            os.makedirs(d, exist_ok=True)
            self._file = open(jsonl_path, "a", encoding="utf-8")
        self.emit({"kind": "meta", "schema": SCHEMA_VERSION,
                   "run_id": run_id, "pid": os.getpid()})

    def emit(self, ev: dict) -> None:
        """Record one event (adds ``ts`` when absent; never raises — a
        telemetry write failure must not take down the fit it observes)."""
        ev.setdefault("ts", time.time())
        with self._lock:
            self._ring.append(ev)
            self.events_emitted += 1
            if self._file is not None:
                try:
                    self._file.write(json.dumps(ev, default=repr) + "\n")
                    self._file.flush()
                except (OSError, ValueError):
                    # stream broken (disk full, closed fd): keep the ring
                    self._file = None

    def tail(self, n: Optional[int] = None) -> list:
        with self._lock:
            evs = list(self._ring)
        return evs if n is None else evs[-n:]

    def dump(self, path: str, extra_events: Optional[list] = None) -> str:
        """Write the ring tail (plus any closing events) to ``path``."""
        evs = self.tail()
        if extra_events:
            evs = evs + list(extra_events)
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            for ev in evs:
                f.write(json.dumps(ev, default=repr) + "\n")
        return path

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                try:
                    self._file.close()
                except OSError:
                    pass
                self._file = None
