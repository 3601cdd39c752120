"""Background chunk prefetcher: stage the NEXT chunk's device slice while
the current chunk computes.

The pipelined chunk driver (PR 4) hid the journal's *output* side — host
fetch + shard + manifest I/O run on the :class:`~.committer.ChunkCommitter`
while the device computes the next chunk.  The *input* side still stalled
the driver: each walk slice ``yb[lo:hi]`` is a fresh device buffer staged
when the driver reaches the chunk, and for resilient fits (which block on
host-side assembly per chunk) the slice of chunk N+1 could not even
dispatch until chunk N's host work finished.  This module is the input
half of that pipeline — the producer of a training-style input pipeline,
mirroring the committer's design: ONE daemon worker thread that drains a
bounded FIFO of staging requests, and for each

1. dispatches the slice ``panel[lo:hi]`` (the SAME expression the serial
   driver uses, so the compiled slice program and the resulting bytes are
   identical), and
2. blocks until the buffer is materialized on device
   (``jax.block_until_ready``), so a taken slice never re-pays the copy.

``panel`` can also be a lane view over a :class:`~.source.ChunkSource`
(ISSUE 7): the "slice" is then a genuine host→device staging — host read
into a pooled pinned-style buffer plus an H2D copy — and this worker is
what makes the copy ASYNC: chunk N+1's transfer rides here while chunk N
computes, which for host-resident panels is the difference between
walking at device speed and walking at PCIe speed.  The staged buffer is
handed to the driver with no reference retained (slot cleared at take),
so the device allocator recycles chunk N's HBM for chunk N+2 — the
donated-buffer half of the O(chunk)-footprint contract.

With the committer draining finished chunks behind the walk and the
prefetcher staging slices ahead of it, the steady state is the full
three-stage overlap: **stage N+1 ∥ compute N ∥ commit N−1**.

**Prediction, not speculation**: the driver schedules exactly the spans
the walk will visit next (up to ``depth`` consecutive ones, with
committed-grid clamping, torn-shard forced boundaries, and the current
chunk size all applied by the driver before scheduling).  When the walk
deviates anyway — an OOM backoff halves the chunk size, a committer
rollback rewinds the walk, or an idle elastic lane STEALS the tail of
this lane's span (``plan.LaneRunner.try_steal``, ISSUE 11 — every staged
prediction past the split now belongs to the thief) — the driver (or the
thief) **invalidates** the staged slices; a ``take`` that finds no
matching span simply slices inline (a recorded miss), so a stale
prediction can cost at most the work it saved, never correctness: the
staged buffer either IS ``panel[lo:hi]`` for the requested span or it is
not used.

**Bounded depth** (``prefetch_depth``, default 1): at most ``depth``
staged-but-untaken slices exist at any time, bounding the extra device
memory to ``depth`` chunk buffers.  Depth 1 is the classic double buffer
(chunk N computing, chunk N+1 staged).

**Errors** never vanish into the worker: a staging failure (typically an
XLA ``RESOURCE_EXHAUSTED`` — the slice is a fresh HBM allocation) is
delivered at ``take`` for that span, where the chunk driver's normal
fit-time OOM handling rolls it into the backoff ladder.

**Staged grows from "sliced" to "sliced and fitted"** (ISSUE 56): the lane
keeps ONE chunk's fit in flight ahead of the chunk it is finishing.
``fit_ahead(key, values, begin, after)`` runs ``begin(values(), go)`` — take
the staged slice, probe it, call ``go()``, dispatch the fit to its last
program — on a thread of its own (a fit blocks its caller at its stage
gate, and the staging worker above has the slice after next to stage
meanwhile), so that chunk
i + 1's programs are queued on the device behind chunk i's while the driver
reads chunk i back, runs its ladder and hands it over.  ``go()`` is the
order: it returns once ``after`` — the fit ahead of the chunk BEFORE, which
the driver is taking — has dispatched its last program, so this chunk's
stage 1 never lies before that chunk's stage 2 on the device's first-in
first-out queue (two fits that begin together stay together, and both read
back with nothing queued: PERF.md §6, PR 55); the probe, which runs before
``go()``, may lie there and fills the stage gate's gap.  ``go()`` raises
where the fit is not to be dispatched at all: ``after`` failed or BUILT a
program (the build log, ``utils.compile_cache``: a build and a fit in
flight do not share the interpreter), or the slot was dropped meanwhile.
``take_fit(key)`` hands the slot over only if the walk's own decision —
``(lo, hi)``, the chunk size, the align hint — IS the prediction; anything
else is dropped, its thread waited out and its device arrays released
before the caller goes on.  A dropped fit is wasted work, never a different
result.  One ``fit.ahead`` span a fit on its thread (``lo``, ``hi``,
``thread``; at its close ``taken`` and, dropped, ``dropped_for``:
``boundary``, ``oom``, ``rollback``, ``steal``, ``deadline``, ``build``,
``error``, ``close``), parent-linked to the ``chunk`` that launched it.

**Accounting**: the worker records the staging wall per slice; ``take``
records the driver wall spent waiting on an in-flight staging.  Their
difference is the input-staging cost the overlap hid —
``stats().hidden_s`` — published next to the committer's numbers as
``meta["pipeline"]`` input-side fields and the
``input_overlap_efficiency``.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, NamedTuple, Optional

import jax

from .. import obs
from ..utils import compile_cache
from . import watchdog as watchdog_mod

__all__ = ["ChunkPrefetcher", "FitAhead", "PrefetchStats"]

_STOP = object()


class PrefetchStats(NamedTuple):
    """Driver-facing accounting of one prefetcher's lifetime."""

    staged: int  # slices the worker finished staging
    hits: int  # takes served from a staged/in-flight slice
    misses: int  # takes that had to slice inline
    staging_wall_s: float  # total dispatch+materialize wall in the worker
    blocked_s: float  # driver wall spent waiting in take()
    invalidated: int  # staged/pending slices dropped by the driver
    fits_ahead: int = 0  # fits started one chunk ahead of the walk
    fits_ahead_taken: int = 0  # of them, those whose result the walk took

    @property
    def hidden_s(self) -> float:
        """Staging wall the driver never waited for — hidden under the
        previous chunk's compute (and host work)."""
        return max(0.0, self.staging_wall_s - self.blocked_s)


class _Slot:
    """One staged (or in-flight) slice."""

    __slots__ = ("event", "value", "error", "cancelled")

    def __init__(self):
        self.event = threading.Event()
        self.value = None
        self.error = None
        self.cancelled = False


class _NotDispatched(Exception):
    """Raised by a fit ahead's ``go()``: the fit is not to be dispatched."""


class FitAhead:
    """One chunk's fit started ahead of the walk: its prediction, its thread
    and what that left.  ``value`` / ``error`` / ``built`` are written before
    ``done`` is set and read after it; ``dropped_for`` is the prefetcher's
    (first reason wins, under its lock)."""

    __slots__ = ("key", "thread", "acquired", "done", "decided", "value",
                 "error", "built", "taken", "dropped_for")

    def __init__(self, key: tuple):
        self.key = key  # (lo, hi, chunk rows, align hint): the prediction
        self.thread: Optional[threading.Thread] = None
        self.acquired = threading.Event()  # its slice is its own (or never)
        self.done = threading.Event()  # dispatched, failed or given up
        self.decided = threading.Event()  # taken or dropped: the span closes
        self.value = None
        self.error: Optional[BaseException] = None
        self.built: dict = {}  # compile_cache.built_since of its thread
        self.taken = False
        self.dropped_for: Optional[str] = None

    def came_to_nothing(self) -> bool:
        """Whether the fit has ended without a dispatch to take: it failed,
        or its ``go()`` refused."""
        return self.done.is_set() and (
            self.error is not None or self.dropped_for is not None)


class ChunkPrefetcher:
    """Bounded background slice stager for one chunk walk over ``panel``,
    and the owner of the ONE fit the lane keeps in flight ahead of its walk
    (module docstring).

    ``schedule(lo, hi)`` requests staging of ``panel[lo:hi]`` (ignored
    when ``depth`` slices are already staged/in flight, or the span is
    already scheduled); ``take(lo, hi)`` returns the staged buffer when
    the prediction matched (waiting out an in-flight staging) and slices
    inline otherwise; ``invalidate()`` drops every staged/pending slice
    (OOM backoff / rollback re-chunked the walk).  ``close()`` stops the
    worker and returns :class:`PrefetchStats`.
    """

    # lock-discipline contract (tools/lint lock-map): slot map + stats
    # are mutated from both the driver (schedule/take/invalidate) and
    # the staging worker; every site holds _lock.  _closed and the
    # queue handle are driver-only.
    _protected_by_ = {
        "_slots": "_lock",
        "_staged": "_lock",
        "_hits": "_lock",
        "_misses": "_lock",
        "_staging_wall_s": "_lock",
        "_blocked_s": "_lock",
        "_invalidated": "_lock",
        "_ahead": "_lock",
        "_fits_ahead": "_lock",
        "_fits_ahead_taken": "_lock",
    }

    def __init__(self, panel, *, depth: int = 1):
        self._panel = panel
        self.depth = max(1, int(depth))
        self._q: queue.Queue = queue.Queue()
        self._slots: dict = {}  # (lo, hi) -> _Slot
        self._lock = threading.Lock()
        self._staged = 0
        self._hits = 0
        self._misses = 0
        self._staging_wall_s = 0.0
        self._blocked_s = 0.0
        self._invalidated = 0
        self._ahead: Optional[FitAhead] = None  # the untaken fit ahead
        self._fits_ahead = 0
        self._fits_ahead_taken = 0
        self._closed = False
        self._worker = threading.Thread(
            target=self._run, daemon=True, name="chunk-prefetcher")
        self._worker.start()

    # -- worker side --------------------------------------------------------

    def _run(self):
        while True:
            item = self._q.get()
            if item is _STOP:
                return
            lo, hi, slot, link = item
            # drop the tuple's slice reference immediately: the worker
            # blocks in q.get() between requests, and a lingering local
            # would pin the previous staged buffer (= one chunk of HBM)
            # for that whole idle stretch
            item = None
            if slot.cancelled:
                slot.event.set()
                slot = None
                continue
            t0 = time.perf_counter()
            try:
                with obs.span("stage.overlap", parent=link, lo=lo, hi=hi):
                    # the SAME slice expression the serial driver uses:
                    # identical compiled program, identical bytes
                    vals = self._panel[lo:hi]
                    # a taken slice must never re-pay the copy:
                    # lint: host-sync(deliberate staging barrier)
                    jax.block_until_ready(vals)
                slot.value = vals
                vals = None
            except BaseException as e:  # noqa: BLE001 - re-raised at take()
                slot.error = e
            wall = time.perf_counter() - t0
            with self._lock:
                self._staging_wall_s += wall
                if slot.error is None and not slot.cancelled:
                    self._staged += 1
                cancelled = slot.cancelled
            if cancelled:
                # invalidated mid-staging: free the buffer BEFORE signaling
                # — invalidate() waits on this event precisely so the HBM is
                # back when its caller (the OOM-backoff retry) dispatches
                slot.value = None
            obs.counter("prefetch.staged").inc()
            slot.event.set()
            slot = None

    # -- driver side --------------------------------------------------------

    def schedule(self, lo: int, hi: int) -> None:
        """Request staging of ``panel[lo:hi]`` (bounded, idempotent)."""
        if self._closed:
            return
        lo, hi = int(lo), int(hi)
        with self._lock:
            if (lo, hi) in self._slots or len(self._slots) >= self.depth:
                return
            slot = _Slot()
            self._slots[(lo, hi)] = slot
        # the scheduling span's link rides along, so stage.overlap names
        # the chunk that asked for the slice
        self._q.put((lo, hi, slot, obs.span_link()))
        obs.gauge("prefetch.queue_depth").set(len(self._slots))

    def take(self, lo: int, hi: int):
        """The slice for ``[lo, hi)`` — staged when predicted, inline
        otherwise.  Also drops staged slices the walk has passed (their
        ``lo`` is behind the requested one), so a resume-skipped span
        cannot pin a depth slot forever.  Re-raises a staging-time error
        (e.g. RESOURCE_EXHAUSTED) in the driver."""
        lo, hi = int(lo), int(hi)
        with self._lock:
            slot = self._slots.pop((lo, hi), None)
            stale = [k for k in self._slots if k[0] < hi]
            for k in stale:
                self._slots.pop(k).cancelled = True
            self._invalidated += len(stale)
        if slot is None:
            with self._lock:
                self._misses += 1
            obs.counter("prefetch.misses").inc()
            return self._panel[lo:hi]
        t0 = time.perf_counter()
        slot.event.wait()
        blocked = time.perf_counter() - t0
        with self._lock:
            self._blocked_s += blocked
            if slot.error is None:
                self._hits += 1
        if slot.error is not None:
            raise slot.error
        obs.counter("prefetch.hits").inc()
        return slot.value

    def invalidate(self) -> None:
        """Drop every staged/pending slice — the walk re-chunked (OOM
        backoff halved the boundary, or a committer rollback rewound it),
        so every prediction is now wrong.  Blocks until any IN-FLIGHT
        staging has finished and its buffer is released: the caller is
        typically the OOM-backoff path, and a freed staged slice is
        exactly the HBM the halved retry needs — returning while the
        worker still holds the doomed buffer would make the retry re-OOM
        and burn a backoff level for nothing.  The wait is bounded: the
        worker sets every slot's event, including on a staging-time error
        and for cancelled-before-start requests."""
        with self._lock:
            dropped = list(self._slots.values())
            for slot in dropped:
                slot.cancelled = True
            self._invalidated += len(dropped)
            self._slots.clear()
        for slot in dropped:
            slot.event.wait()
            slot.value = None
        obs.gauge("prefetch.queue_depth").set(0)

    # -- the fit ahead ------------------------------------------------------

    def fit_ahead(self, key: tuple, values: Callable, begin: Callable,
                  after: Optional[FitAhead] = None) -> None:
        """Start ``begin(values(), go)`` on a thread of its own as the fit
        ahead for ``key`` (one untaken fit at a time: ignored while there
        is one).  ``values()`` takes the chunk's staged slice, after
        ``after`` has taken its own (a :meth:`take` drops what lies behind
        it); ``go()`` returns when ``after`` has dispatched its last
        program, and raises where this fit is not to be dispatched (module
        docstring)."""
        if self._closed:
            return
        slot = FitAhead(key)
        with self._lock:
            if self._ahead is not None:
                return
            self._ahead = slot
            self._fits_ahead += 1
        # what the watchdog's worker carries over its hop, for the same
        # reasons: the lane and request tags (fault injection, accounting),
        # the trace context, and the span that asked for the fit
        ctx = (watchdog_mod.current_lane(), watchdog_mod.current_request(),
               obs.current_trace(), obs.span_link())
        slot.thread = threading.Thread(
            target=self._fit, args=(slot, values, begin, after, ctx),
            daemon=True,
            name=f"fit-ahead:[{key[0]}, {key[1]})")
        slot.thread.start()

    def _fit(self, slot: FitAhead, values: Callable, begin: Callable,
             after: Optional[FitAhead], ctx: tuple) -> None:
        lane, req, tctx, link = ctx

        def go():
            if after is not None:
                after.done.wait()
                if after.error is not None or after.dropped_for is not None:
                    # nothing is fitted beside a chunk whose own fit ahead
                    # came to nothing: its turn fits it alone
                    raise _NotDispatched(after.dropped_for or "error")
                if after.built.get("builds"):
                    raise _NotDispatched("build")
            if slot.dropped_for is not None:
                raise _NotDispatched(slot.dropped_for)

        with watchdog_mod.lane_context(lane), \
                watchdog_mod.request_context(req), obs.trace_scope(tctx), \
                obs.span("fit.ahead", parent=link, lo=slot.key[0],
                         hi=slot.key[1],
                         thread=threading.current_thread().name) as sp:
            mark, t0 = compile_cache.thread_builds(), time.perf_counter()
            try:
                if after is not None:
                    after.acquired.wait()
                vals = values()
                slot.acquired.set()
                slot.value = begin(vals, go)
            except _NotDispatched as e:
                self._mark(slot, str(e))
            except BaseException as e:  # noqa: BLE001 - the walk's to judge
                slot.error = e
            vals = None  # the chunk's slice is the fit's alone again
            slot.acquired.set()
            slot.built = compile_cache.built_since(mark)
            # the fit's own wall, to its last dispatch: the span stays open
            sp.set(dispatched_s=round(time.perf_counter() - t0, 6))
            slot.done.set()
            # the span closes once the walk has decided
            slot.decided.wait()
            sp.set(taken=slot.taken,
                   **({} if slot.taken else
                      {"dropped_for": slot.dropped_for}))

    def _mark(self, slot: FitAhead, reason: str) -> None:
        """Name why ``slot`` is dropped; the first reason stands."""
        with self._lock:
            if slot.dropped_for is None and not slot.taken:
                slot.dropped_for = reason

    def _decide(self, slot: FitAhead) -> None:
        """``slot`` is taken or dropped for good: release what a dropped
        one holds (device arrays; a held exception's traceback keeps its
        fit's frames, and those their arrays) and end its thread."""
        slot.done.wait()
        if not slot.taken:
            slot.value = slot.error = None
        slot.decided.set()
        slot.thread.join()

    def take_fit(self, key: tuple) -> Optional[FitAhead]:
        """The fit ahead for the walk's decision ``key`` — still running,
        perhaps: :meth:`wait_fit` — or None: nothing was started, or what
        was started is for another decision and is dropped here
        (``boundary`` where nobody named a reason before), waited out."""
        with self._lock:
            slot, self._ahead = self._ahead, None
        if slot is None:
            return None
        if slot.key != tuple(key) or slot.dropped_for is not None:
            self._mark(slot, "boundary")
            self._decide(slot)
            return None
        return slot

    def wait_fit(self, slot: FitAhead) -> tuple:
        """``(value, error)`` of a fit ahead :meth:`take_fit` handed over,
        waiting for its last dispatch: what it dispatched (``slot.taken``
        then), or ``None`` where it came to nothing — dropped (its ``go()``
        refused: ``slot.dropped_for``), or failed, its exception the second
        item, for the walk to judge."""
        try:
            slot.done.wait()
            with self._lock:
                slot.taken = slot.error is None and slot.dropped_for is None
                if slot.taken:
                    self._fits_ahead_taken += 1
            out = slot.value if slot.taken else None, slot.error
            slot.value = None
        finally:
            self._decide(slot)  # whatever interrupts the wait: its thread ends
        return out

    def cancel_fit(self, reason: str) -> None:
        """Mark the untaken fit ahead as dropped without waiting (a thief's
        thread: the walk's next ``take_fit`` waits it out)."""
        with self._lock:
            slot = self._ahead
        if slot is not None:
            self._mark(slot, reason)

    def drop_fit(self, reason: str) -> None:
        """Drop the untaken fit ahead, waiting it out: nothing of it holds
        device memory when the caller goes on."""
        with self._lock:
            slot, self._ahead = self._ahead, None
        if slot is not None:
            self._mark(slot, reason)
            self._decide(slot)

    def fit_dispatched(self) -> None:
        """Wait until the untaken fit ahead, if any, has made its last
        dispatch (or has come to nothing)."""
        with self._lock:
            slot = self._ahead
        if slot is not None:
            slot.done.wait()

    def close(self) -> PrefetchStats:
        """Stop the worker, drop staged slices and the fit ahead, and return
        lifetime stats."""
        if not self._closed:
            self._closed = True
            self.drop_fit("close")
            self.invalidate()
            self._q.put(_STOP)
            self._worker.join(timeout=30.0)
        return self.stats()

    def stats(self) -> PrefetchStats:
        with self._lock:
            return PrefetchStats(self._staged, self._hits, self._misses,
                                 self._staging_wall_s, self._blocked_s,
                                 self._invalidated, self._fits_ahead,
                                 self._fits_ahead_taken)
