"""Telemetry plane state: spans, the registry, the recorder, failure dumps.

One process-global plane, **off by default**: every entry point checks a
single boolean and returns a shared no-op object when disabled, so the
instrumented hot paths (chunk dispatch, ladder rungs, journal commits —
never per-row work) pay one attribute load and one truthiness test.  There
is deliberately no ambient "maybe enabled" middle state: ``enable()``
builds a fresh registry + recorder under a new run id, ``disable()`` emits
a final metrics snapshot and tears both down, and nothing instrumented can
alter what a fit computes — telemetry observes timings and counts, never
arrays (the bitwise-invariance contract ``tests/test_obs.py`` enforces).

Spans nest per thread (the watchdog dispatches fits on worker threads, and
a worker's spans must not splice into the driver thread's stack) and
measure wall clock plus process CPU time.  In JAX the first call of a shape
pays trace + lower + compile (or the persistent cache's read), steady-state
calls pay execute only, and conflating the two is the classic way to misread
a cold chunk as a regression.  So every executable the process builds is a
``program.build`` span (:func:`closed_span`, written by
``utils.compile_cache``'s build log when the build closes: which program,
traced / lowered / read from the cache / compiled, for how long; its
``parent`` is the innermost span open on the BUILDING thread), and the chunk
driver labels a ``chunk`` ``compile+execute`` where a build closed on its
thread inside it, with ``builds`` and ``build_s``.  The log is kept with the
plane off too, and :func:`enable` first writes what it already holds, with
each build's true ``t0`` and no parent: a stream enabled in a resident
process still says what the process built and when.

Every span has an identity (schema v3): ``id``, an integer from one
per-run counter; ``parent``, the id of the span open beneath it on the same
thread, or the link handed over with work that crosses threads
(:func:`span_link` at the hand-over, then ``span(..., parent=link)`` for one
span or :func:`span_scope` around code that opens its own); and ``walk``,
the sequence number every span of one ``fit_chunked`` call shares
(:func:`walk_span` opens the root and draws it; children and links inherit
it).  So a ``commit.overlap`` line on the committer thread names the chunk
that submitted it, and two walks in one run stay apart.

``profile=True`` additionally wraps every span in a
``jax.profiler.TraceAnnotation`` of the same name (with ``span_id=`` the
span's id among the event's stats), so a ``jax.profiler.trace(...)``
capture shows the exact spans the JSONL reports — one vocabulary and one
clock across both tools.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import os
import tempfile
import threading
import time
import uuid
from typing import Optional

from . import tracing
from .memory import peak_memory
from .metrics import NULL_METRIC, MetricsRegistry
from .recorder import SCHEMA_VERSION, FlightRecorder

__all__ = [
    "Span",
    "closed_span",
    "counter",
    "current_span",
    "defer",
    "disable",
    "dump_failure",
    "dump_on_failure",
    "emit_metrics",
    "enable",
    "enable_from_env",
    "enabled",
    "event",
    "gauge",
    "histogram",
    "last_crash_dump",
    "settle",
    "snapshot",
    "span",
    "span_link",
    "span_scope",
    "stream_path",
    "summary",
    "walk_span",
]


class _State:
    __slots__ = ("enabled", "run_id", "metrics", "recorder", "annotation",
                 "crash_dump_dir", "last_crash",
                 "crash_seq", "last_dumped_error", "span_ids", "walk_ids")

    def __init__(self):
        self.enabled = False
        self.run_id = None
        self.metrics = MetricsRegistry()
        self.recorder: Optional[FlightRecorder] = None
        # jax.profiler.TraceAnnotation while enable(profile=True), else None
        self.annotation = None
        self.crash_dump_dir = None
        self.last_crash = None
        self.crash_seq = 0
        self.last_dumped_error = None
        # per-run counters (next() on a count is atomic under the GIL):
        # span ids and walk sequence numbers, deterministic within a run
        self.span_ids = itertools.count(1)
        self.walk_ids = itertools.count(1)


_STATE = _State()
_LOCK = threading.RLock()
_TLS = threading.local()


# -- lifecycle ---------------------------------------------------------------


def enabled() -> bool:
    return _STATE.enabled


def enable(jsonl_path: Optional[str] = None, *, ring_size: int = 4096,
           profile: bool = False, crash_dump_dir: Optional[str] = None) -> str:
    """Turn the telemetry plane on under a fresh run id (returned).

    ``jsonl_path``: tee every event to this JSONL file (appended, flushed
    per event) in addition to the in-memory ring; ``ring_size`` bounds the
    ring; ``profile=True`` mirrors spans into ``jax.profiler``
    annotations; ``crash_dump_dir`` overrides where failure dumps land
    (default: the JSONL's directory, else the system temp dir).  Calling
    while already enabled finalizes the previous run first — metrics never
    bleed across runs.
    """
    with _LOCK:
        if _STATE.enabled:
            disable()
        _STATE.run_id = uuid.uuid4().hex[:12]
        _STATE.metrics = MetricsRegistry()
        _STATE.recorder = FlightRecorder(_STATE.run_id, ring_size=ring_size,
                                         jsonl_path=jsonl_path)
        _STATE.annotation = _trace_annotation() if profile else None
        _STATE.span_ids = itertools.count(1)
        _STATE.walk_ids = itertools.count(1)
        _STATE.crash_dump_dir = crash_dump_dir
        _STATE.crash_seq = 0
        _STATE.last_crash = None
        _STATE.last_dumped_error = None
        from ..utils import compile_cache

        # what the process built before this run, then the plane on, with
        # the log held: a build that closes meanwhile waits and is written
        # live, so each is written once
        with compile_cache.builds_held() as built:
            for b in built:
                attrs = {k: v for k, v in b.items()
                         if k not in ("t0", "wall_s")}
                _emit_closed(_STATE.recorder, "program.build", b["t0"],
                             b["wall_s"], None, 0, attrs)
            _STATE.enabled = True
        tracing.set_plane(True)
        return _STATE.run_id


def disable() -> None:
    """Finalize the run: emit a closing metrics snapshot, close the
    stream, and return every entry point to its no-op fast path.
    Idempotent — disabling a disabled plane does nothing."""
    with _LOCK:
        if not _STATE.enabled:
            return
        rec = _STATE.recorder
        _STATE.enabled = False  # stop new events before the final snapshot
        tracing.set_plane(False)
        if rec is not None:
            rec.emit({"kind": "metrics", **_STATE.metrics.snapshot()})
            rec.close()
        _STATE.recorder = None
        _STATE.annotation = None


def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, imported once per ``enable`` (not
    in every span's ``__enter__``); None where profiling cannot work."""
    try:
        from jax.profiler import TraceAnnotation
    except Exception:  # noqa: BLE001 - profiling is best-effort
        return None
    return TraceAnnotation


def enable_from_env() -> None:
    """Honor ``STSTPU_OBS=1`` (+ ``STSTPU_OBS_JSONL=path``,
    ``STSTPU_OBS_PROFILE=1``) so bench/CI runs opt in without code.

    Runs at package import, so it must never raise: an unusable JSONL
    path (read-only dir, bad mount) degrades to a warning with telemetry
    off rather than breaking ``import spark_timeseries_tpu`` for a
    program that never touches the plane.
    """
    if os.environ.get("STSTPU_OBS", "").lower() not in ("1", "true", "on",
                                                        "yes"):
        return
    try:
        enable(os.environ.get("STSTPU_OBS_JSONL") or None,
               profile=os.environ.get("STSTPU_OBS_PROFILE", "") == "1")
    except Exception as e:  # noqa: BLE001 - telemetry must not break import
        import warnings

        _STATE.enabled = False
        tracing.set_plane(False)
        warnings.warn(f"STSTPU_OBS=1 but enabling telemetry failed "
                      f"({type(e).__name__}: {e}); continuing with the "
                      "plane disabled", stacklevel=2)


# -- metrics / events --------------------------------------------------------


def counter(name: str):
    st = _STATE
    return st.metrics.counter(name) if st.enabled else NULL_METRIC


def gauge(name: str):
    st = _STATE
    return st.metrics.gauge(name) if st.enabled else NULL_METRIC


def histogram(name: str):
    st = _STATE
    return st.metrics.histogram(name) if st.enabled else NULL_METRIC


def event(name: str, **attrs) -> None:
    """Record a point event in the ring (and JSONL stream when configured)."""
    st = _STATE
    rec = st.recorder  # local capture: a concurrent disable() nulls the
    if st.enabled and rec is not None:  # attribute between check and use
        ev = {"kind": "event", "name": name}
        if attrs:
            ev["attrs"] = attrs
        ctx = tracing.current()
        if ctx is not None:
            ev["trace"] = ctx.to_dict()
        rec.emit(ev)


def snapshot() -> Optional[dict]:
    """Current metrics snapshot, or None when disabled."""
    st = _STATE
    return st.metrics.snapshot() if st.enabled else None


def emit_metrics() -> None:
    """Append a metrics-snapshot line to the event stream (end of a fit)."""
    st = _STATE
    rec = st.recorder
    if st.enabled and rec is not None:
        rec.emit({"kind": "metrics", **st.metrics.snapshot()})


def stream_path() -> Optional[str]:
    """The enabled run's JSONL stream path (None when disabled or when
    the recorder is ring-only) — sidecar artifacts (the client's clock
    journal) land NEXT TO the stream, and this is how they find it."""
    st = _STATE
    rec = st.recorder  # local capture vs a concurrent disable()
    if not st.enabled or rec is None:
        return None
    return rec.jsonl_path


# -- spans -------------------------------------------------------------------


class Span:
    """A closed wall/process-time measurement, recorded at ``__exit__``.

    After the block, ``wall_s`` / ``process_s`` hold the measured times —
    instrumented drivers read them to embed per-chunk numbers in result
    metadata without re-measuring.  ``id`` / ``parent`` / ``walk`` are the
    span's identity (module docstring), fixed at ``__enter__``.
    """

    __slots__ = ("name", "attrs", "t0", "wall_s", "process_s", "depth",
                 "id", "parent", "walk", "_link", "_p0", "_ts0", "_ann")

    def __init__(self, name: str, attrs: dict, link: Optional[tuple] = None,
                 walk: Optional[int] = None):
        self.name = name
        self.attrs = attrs
        self.wall_s = None
        self.process_s = None
        self.depth = 0
        self.id = None
        self.parent = None
        self.walk = walk  # set only on a walk root (walk_span)
        self._link = link  # explicit (parent id, walk) from a hand-over
        self._ann = None

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "Span":
        stack = getattr(_TLS, "stack", None)
        if stack is None:
            stack = _TLS.stack = []
        self.depth = len(stack)
        link = self._link or _inherited_link()
        if link is not None:
            self.parent = link[0]
            if self.walk is None:
                self.walk = link[1]
        self.id = next(_STATE.span_ids)
        stack.append(self)
        annotation = _STATE.annotation
        if annotation is not None:
            try:
                self._ann = annotation(self.name, span_id=self.id)
                self._ann.__enter__()
            except Exception:  # noqa: BLE001 - profiling is best-effort
                self._ann = None
        self._ts0 = time.time()
        self._p0 = time.process_time()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        stack = getattr(_TLS, "stack", None)
        if stack and stack[-1] is not self and self in stack:
            # spans entered by hand (fit_chunked's walk.open / walk.close)
            # and left open by an exception: close them innermost first, so
            # their lines are written and the stack is this span's again
            while stack[-1] is not self:
                stack[-1].__exit__(exc_type, exc, tb)
        self.wall_s = time.perf_counter() - self.t0
        self.process_s = time.process_time() - self._p0
        if self._ann is not None:
            try:
                self._ann.__exit__(exc_type, exc, tb)
            except Exception:  # noqa: BLE001
                pass
        if stack and stack[-1] is self:
            stack.pop()
        st = _STATE
        rec = st.recorder  # local capture: disable() may null it between
        if st.enabled and rec is not None:  # the check and the emit
            ev = {"kind": "span", "name": self.name, "t0": self._ts0,
                  "wall_s": round(self.wall_s, 6),
                  "process_s": round(self.process_s, 6), "depth": self.depth,
                  "id": self.id, "parent": self.parent}
            if self.walk is not None:
                ev["walk"] = self.walk
            if self.attrs:
                ev["attrs"] = self.attrs
            if exc_type is not None:
                ev["error"] = exc_type.__name__
            ctx = tracing.current()
            if ctx is not None:
                ev["trace"] = ctx.to_dict()
            rec.emit(ev)
            st.metrics.histogram(f"span.{self.name}").observe(self.wall_s)
        return False


class _NullSpan:
    """Disabled-path span: one shared instance, every method a no-op."""

    __slots__ = ()
    wall_s = None
    process_s = None
    depth = 0
    id = None
    parent = None
    walk = None

    def set(self, **attrs) -> "_NullSpan":
        return self

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


def _inherited_link() -> Optional[tuple]:
    """``(id, walk)`` of the innermost span open on this thread, else the
    link a :func:`span_scope` adopted for it, else None."""
    stack = getattr(_TLS, "stack", None)
    if stack:
        return stack[-1].id, stack[-1].walk
    return getattr(_TLS, "base", None)


def span(name: str, parent: Optional[tuple] = None, **attrs):
    """Open a nested timing span: ``with obs.span("chunk", lo=0): ...``.

    ``parent=`` is a :func:`span_link` taken where the work was handed to
    this thread; without it the parent is the span open beneath this one.
    Disabled plane -> the shared no-op span (no allocation beyond the
    kwargs dict at the call site)."""
    if not _STATE.enabled:
        return NULL_SPAN
    return Span(name, attrs, link=parent)


def closed_span(name: str, t0: float, wall_s: float, **attrs) -> None:
    """Write a span that has ALREADY closed: ``t0`` (``time.time()`` at its
    start) and ``wall_s`` are the caller's measurement, taken after the
    fact (``utils.compile_cache`` learns of a build when jax reports its
    end).  ``parent`` / ``walk`` are those of the innermost span open on the
    CALLING thread, which for a build is the building thread.  No process
    time (nobody read the clock at its start) and no profiler annotation (an
    annotation cannot be opened in the past; the parent's places it on the
    device trace's clock).  Disabled plane -> returns at once."""
    st = _STATE
    rec = st.recorder  # local capture vs a concurrent disable()
    if not st.enabled or rec is None:
        return
    stack = getattr(_TLS, "stack", None)
    _emit_closed(rec, name, t0, wall_s, _inherited_link(),
                 len(stack) if stack else 0, attrs)


def _emit_closed(rec, name, t0, wall_s, link, depth, attrs) -> None:
    ev = {"kind": "span", "name": name, "t0": t0, "wall_s": wall_s,
          "depth": depth, "id": next(_STATE.span_ids),
          "parent": link[0] if link else None}
    if link and link[1] is not None:
        ev["walk"] = link[1]
    if attrs:
        ev["attrs"] = attrs
    rec.emit(ev)
    _STATE.metrics.histogram(f"span.{name}").observe(wall_s)


def walk_span(**attrs):
    """The root span of one ``fit_chunked`` call, named ``walk``: it draws
    the run's next walk sequence number, and every span opened beneath it
    (on this thread, or on another through a link) carries that number as
    ``walk``."""
    if not _STATE.enabled:
        return NULL_SPAN
    return Span("walk", attrs, walk=next(_STATE.walk_ids))


def current_span():
    """The innermost span open on this thread (the shared no-op span when
    disabled or outside every span): how ``fit_chunked``'s body reaches
    the ``walk`` root its decorator opened, to set its attributes."""
    if not _STATE.enabled:
        return NULL_SPAN
    stack = getattr(_TLS, "stack", None)
    return stack[-1] if stack else NULL_SPAN


def span_link() -> Optional[tuple]:
    """``(id, walk)`` of the innermost span open on this thread — what a
    hand-over to another thread carries (the committer's item, the
    prefetcher's slot, the watchdog's worker) so the span opened there
    names its cause.  None when disabled or outside every span."""
    return _inherited_link() if _STATE.enabled else None


@contextlib.contextmanager
def span_scope(link: Optional[tuple]):
    """Adopt ``link`` (a :func:`span_link`) as the parent of the outermost
    spans this thread opens inside the block: the watchdog's worker runs a
    chunk's fit for the driver's ``chunk`` span, and its ``sanitize`` /
    ``fit.primary`` must name that span, not start a new root."""
    prev = getattr(_TLS, "base", None)
    _TLS.base = link
    try:
        yield
    finally:
        _TLS.base = prev


# -- deferred device scalars -------------------------------------------------


_DEFERRED_MAX = 32  # handles a name keeps unread before they are folded


def defer(**device_scalars) -> None:
    """Keep device scalars for a span that closes later on THIS thread
    (``lockstep.fit`` does not wait for stage 2; ``fit.readback`` does):
    :func:`settle` turns them into host integers.  Repeated names sum.  A
    handle that can (a ``jax.Array``) starts its copy to the host here,
    behind its program and without waiting.
    Disabled plane -> returns at once, its arguments untouched."""
    st = _STATE
    if not st.enabled:
        return
    pending = getattr(_TLS, "deferred", None)
    if pending is None or pending[0] != st.run_id:
        pending = _TLS.deferred = (st.run_id, {})
    for name, handle in device_scalars.items():
        # the copy starts now and rides behind the program: settling, after
        # the caller's own blocking reads, then waits for nothing
        start = getattr(handle, "copy_to_host_async", None)
        if start is not None:
            start()
        handles = pending[1].setdefault(name, [])
        handles.append(handle)
        if len(handles) > _DEFERRED_MAX:
            # nobody settles (a fit outside ``resilient_fit``): fold what
            # has piled up, programs long finished, so that nothing grows
            handles[:] = [sum(int(h) for h in handles)]


def take_deferred():
    """What this thread has deferred since its last :func:`settle`, taken
    off the thread UNREAD (``None`` where there is nothing): the handles of
    a fit whose read-back runs on another thread (a chunk fitted ahead of
    its walk, ``reliability/plan.py``), for that thread's ``settle(taken)``."""
    pending = getattr(_TLS, "deferred", None)
    _TLS.deferred = None
    return pending


def settle(taken=None) -> dict:
    """``{name: int}`` of what this thread deferred since the last call
    (or of ``taken``, another thread's :func:`take_deferred`), then nothing
    pending: a read waits for its program and its copy, so call it where
    the thread has waited anyway.  A caller
    that only wants nothing left over from a fit that raised drops the
    result.  Empty when nothing was deferred; handles of a run that has
    been disabled since are dropped unread."""
    pending = taken if taken is not None else getattr(_TLS, "deferred", None)
    _TLS.deferred = None
    if pending is None:
        return {}
    st = _STATE
    if not st.enabled or pending[0] != st.run_id:
        return {}
    out = {}
    for name, handles in pending[1].items():
        try:
            out[name] = sum(int(h) for h in handles)
        except Exception:  # noqa: BLE001 - a failed program's scalar
            pass
    return out


# -- run summary / failure dumps --------------------------------------------


def summary(counters_since: Optional[dict] = None, **extra) -> Optional[dict]:
    """The per-fit telemetry block embedded in journal manifests and
    ``ResilientFitResult.meta["telemetry"]``; None when disabled.

    Always carries a non-null ``peak_memory`` on any working interpreter
    (device HBM when the backend reports it, host peak RSS otherwise —
    ``obs.memory.peak_memory``), the metric snapshot, and whatever
    driver-level ``extra`` the instrumented caller adds (per-chunk span
    rows, resume accounting).  ``counters_since`` is a counter baseline
    (a prior snapshot's ``counters`` map): counters are then reported as
    DELTAS from it, so one ``enable()`` spanning several fits yields
    per-fit counts instead of attributing fit A's failures to fit B's
    manifest.  Gauges and histograms stay run-cumulative (a peak or a
    latency distribution has no meaningful subtraction).
    """
    st = _STATE
    if not st.enabled:
        return None
    pm = peak_memory()
    if pm.bytes is not None:
        st.metrics.gauge("memory.peak_bytes").max(pm.bytes)
        st.metrics.gauge("memory.source").set(pm.source)
    snap = st.metrics.snapshot()
    if counters_since:
        snap["counters"] = {k: v - counters_since.get(k, 0)
                            for k, v in snap["counters"].items()}
    rec = st.recorder  # local capture vs a concurrent disable()
    out = {
        "schema": SCHEMA_VERSION,
        "run_id": st.run_id,
        "jsonl_path": rec.jsonl_path if rec else None,
        "events_recorded": rec.events_emitted if rec else 0,
        "peak_memory": {"bytes": pm.bytes, "source": pm.source,
                        # present only when a host-resident walk staged
                        # through a pool — the disabled-path/no-pool
                        # summary stays byte-identical to pre-ISSUE-7
                        **({"staging_pool_bytes": pm.staging_pool_bytes}
                           if pm.staging_pool_bytes is not None else {})},
        **snap,
    }
    out.update(extra)
    return out


def dump_failure(context: str, error: Optional[BaseException] = None
                 ) -> Optional[str]:
    """Dump the flight-recorder tail for a failed fit; returns the path.

    Best-effort by contract: any internal failure is swallowed (the
    original fit exception must propagate undisturbed), and the same
    exception object is dumped at most once even when several instrumented
    layers (resilient_fit inside fit_chunked inside panel.fit) unwind
    through their own dump hooks.
    """
    st = _STATE
    rec = st.recorder  # local capture vs a concurrent disable()
    if not st.enabled or rec is None:
        return None
    try:
        with _LOCK:
            if error is not None and st.last_dumped_error is not None \
                    and st.last_dumped_error() is error:
                return st.last_crash
            st.crash_seq += 1
            seq = st.crash_seq
        d = st.crash_dump_dir
        if d is None and rec.jsonl_path:
            d = os.path.dirname(os.path.abspath(rec.jsonl_path))
        if d is None:
            d = tempfile.gettempdir()
        path = os.path.join(d, f"obs-crash-{st.run_id}-{seq:02d}.jsonl")
        closing = [
            {"kind": "event", "name": "fit.failure", "ts": time.time(),
             "attrs": {"context": context,
                       "error": (f"{type(error).__name__}: {error}"[:300]
                                 if error is not None else None)}},
            {"kind": "metrics", "ts": time.time(), **st.metrics.snapshot()},
        ]
        rec.emit(closing[0])
        rec.dump(path, extra_events=closing[1:])
        with _LOCK:
            st.last_crash = path
            if error is not None:
                import weakref

                try:
                    st.last_dumped_error = weakref.ref(error)
                except TypeError:  # some exceptions are not weakref-able
                    st.last_dumped_error = None
        return path
    except Exception:  # noqa: BLE001 - telemetry must never mask the fit error
        return None


def last_crash_dump() -> Optional[str]:
    """Path of the most recent failure dump this run, or None."""
    return _STATE.last_crash


def dump_on_failure(context: str, unless=None):
    """Decorator: dump the recorder tail when the wrapped fit raises.

    Zero-cost when disabled (the enabled check runs before any try frame
    matters); the exception always re-raises unchanged.  ``unless`` is a
    predicate on the exception that SKIPS the dump — a caller above may
    treat the error as recoverable (``resilient_fit`` passes the
    RESOURCE_EXHAUSTED check: the chunk driver's backoff handles those,
    and a successful run must not leave crash dumps behind).
    """

    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            if not _STATE.enabled:
                return fn(*args, **kwargs)
            try:
                return fn(*args, **kwargs)
            except Exception as e:
                if unless is None or not unless(e):
                    dump_failure(context, e)
                raise

        return wrapped

    return deco
