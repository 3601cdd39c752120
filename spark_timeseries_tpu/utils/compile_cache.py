"""The compile path's three records: where jax's persistent compilation
cache lives, program-reuse counts, and the log of every executable built.

Compiling is not small here: the production ARIMA(1,1,1) fit program for a
v5e at ``[131072, 1000]`` takes tens of seconds to build, and a restarted
process re-pays it before its first chunk.  Three records, each its own:

1. **jax's persistent cache** (serialized executables keyed by HLO, compile
   options AND the cache path) turns a compile into a disk read.
   :func:`configure` is the ONE place the directory is decided, called
   before first backend use by ``chip_smoke.py``, ``bench.py``,
   ``tests/conftest.py`` and the test workers:

   - ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it natively; nothing is
     set in code, so whoever launches the process places the cache.
   - unset: ``<checkout>/.jax_cache`` — a fixed path derived from this
     package's location (never a temp name, pid or time: the path is part
     of the cache key, so a directory that moves never hits).

2. **The PROGRAM-reuse counters** (``compile_cache.hit`` /
   ``compile_cache.miss`` in the obs registry, fed by ``models.base.
   jit_program``; :func:`program_cache_stats`): whether a LOOKUP found an
   already-jitted wrapper in this process.  The auto-fit order search
   (ISSUE 9) promises one program per order shape reused across chunks, and
   the hit rate is how that promise is measured (``bench.py``
   ``telemetry_summary``).  They say nothing of XLA: a miss here builds a
   wrapper, and the executable is built at its first dispatch.

3. **The build log** (:func:`builds`; ISSUE 54): one record per EXECUTABLE
   the process built or loaded — ``jit_program``'s, a module-level jit's, an
   eagerly dispatched ``jnp`` operation's alike — assembled from the events
   jax reports through ``jax.monitoring`` on the building thread.  The unit
   is a ``backend_compile_duration`` event; the record says which
   ``program`` it was (jax's ``fun_name``: ``jit_program`` hands a built
   function its builder's qualified name, ``arima._fit_stage1_program``),
   on which ``thread``, when (``t0``: ``time.time()`` at its trace's start,
   else at its first event), for how long (``wall_s``, to the backend
   event's end) and of what: ``trace_s`` (Python to a jaxpr), ``lower_s``
   (jaxpr to MLIR; a Pallas kernel body is lowered to Mosaic here),
   ``backend_s`` (the key, then the persistent cache's read, deserialise
   and load, or XLA's compile), ``cache`` (``"hit"`` / ``"miss"`` / ``"off"``
   where no directory was asked for), ``retrieval_s`` (on a hit),
   ``compiled_s`` (what compiling it cost: ``compile_time_saved_sec +
   retrieval_s`` on a hit, ``backend_s`` otherwise).  A trace or lowering
   interval inside another on the same thread (an inner ``jit``) belongs to
   the outer and is counted once; a trace that no build followed (an
   ``eval_shape``) is told from the build's own by its name and left out.
   Kept with the obs plane off too, as the mirrors of (2) are: bounded (the
   last 512), under a lock (lane, committer and prefetcher threads build
   too).  With the plane on each record is also a ``program.build`` span
   line, written when it closes; ``obs.enable`` writes first what the log
   already holds.  The events fire only where something is built: a walk
   that compiles nothing calls no listener.
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading as _threading

__all__ = ["before_build", "builds", "builds_held", "built_since",
           "configure", "listen", "note_hit", "note_miss",
           "program_cache_stats", "thread_builds"]

_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

# -- program-reuse accounting (ISSUE 9 satellite) ----------------------------
#
# The auto-fit order search compiles ONE program per (order, chunk shape)
# and reuses it across every chunk of that order's walk — the whole perf
# argument for riding the grid through the chunk driver.  These counters
# make that reuse a MEASURED number instead of a belief: `models.base.
# jit_program` (the per-static-config program cache every model fit goes
# through) reports each lookup here, the obs registry carries them as
# `compile_cache.hit` / `compile_cache.miss`, and `bench.py` surfaces the
# hit rate in its `telemetry_summary` regression-gate line.  Process-local
# mirrors ride along so the rate is readable even with the obs plane off
# (the obs counters stay authoritative for per-run deltas).

_hits = 0
_misses = 0
# concurrent lane threads (sharded walks) report through here; the obs
# counters carry their own locks, but these process-local mirrors would
# otherwise lose increments to the non-atomic load/add/store
_stats_lock = _threading.Lock()

# -- the build log (ISSUE 54) ------------------------------------------------

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_SAVED = "/jax/compilation_cache/compile_time_saved_sec"

_builds = collections.deque(maxlen=512)
_builds_lock = _threading.Lock()
_listening = False
# what the building thread has seen since its previous build (``spans``: the
# outermost trace / lowering intervals, ``depth`` how many are open; the
# cache's events), and what it has built in all (``n``, ``s``): each thread
# its own, so nothing is shared
_pending = _threading.local()
_PENDING_MAX = 16  # intervals kept for a thread that traces and never builds

# lock-discipline contract (tools/lint lock-map, module-level form):
# sharded lane threads report hits/misses concurrently, and every thread
# that dispatches (lanes, committer, prefetcher) closes builds.
_PROTECTED_BY_ = {"_hits": "_stats_lock", "_misses": "_stats_lock",
                  "_builds": "_builds_lock", "_listening": "_builds_lock"}


def note_hit() -> None:
    """Record a program-cache hit (an already-built jitted program reused)."""
    global _hits
    with _stats_lock:
        _hits += 1
    from .. import obs

    obs.counter("compile_cache.hit").inc()


def note_miss() -> None:
    """Record a program-cache miss (a new program built — trace + compile
    will be paid at its first dispatch)."""
    global _misses
    with _stats_lock:
        _misses += 1
    from .. import obs

    obs.counter("compile_cache.miss").inc()


@contextlib.contextmanager
def before_build(first):
    """Inside the block, ``first()`` runs on THIS thread each time it is
    about to build a program — at the start of its outermost trace, the
    moment jax reports it (:func:`_on_scalar`), before any of the seconds of
    Python that hold the interpreter.  A lane that fits one chunk ahead lets
    the fit in flight reach its last dispatch there
    (``reliability/plan.py``): a fit's thread and a build share one
    interpreter badly (PERF.md §6, PR 56).  A dispatch that finds its
    executable built traces nothing and calls nothing."""
    prev = getattr(_pending, "before_build", None)
    _pending.before_build = first
    try:
        yield
    finally:
        _pending.before_build = prev


def program_cache_stats() -> dict:
    """Process-lifetime program-cache accounting: ``{hits, misses,
    hit_rate}`` (hit_rate None before the first lookup)."""
    with _stats_lock:
        hits, misses = _hits, _misses
    total = hits + misses
    return {
        "hits": hits,
        "misses": misses,
        "hit_rate": round(hits / total, 4) if total else None,
    }


def configure() -> str:
    """Place jax's persistent compilation cache; returns the directory.

    Call before the first backend use (jax latches the cache decision at
    the first compile).  See the module docstring for the rule.
    """
    import jax

    listen()
    if not os.environ.get(_ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    return jax.config.jax_compilation_cache_dir


def listen() -> None:
    """Register the build log's ``jax.monitoring`` listeners, once a
    process: at the first of :func:`configure` or a ``jit_program`` lookup."""
    global _listening
    if _listening:
        return
    import jax

    with _builds_lock:
        if _listening:
            return
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_time_span_listener(_on_time_span)
        jax.monitoring.register_scalar_listener(_on_scalar)
        _listening = True


def _unlisten() -> None:
    """Take the listeners off again (a test's: the log stays)."""
    global _listening
    import jax

    with _builds_lock:
        if _listening:
            jax.monitoring.unregister_event_listener(_on_event)
            jax.monitoring.unregister_event_duration_listener(_on_duration)
            jax.monitoring.unregister_event_time_span_listener(_on_time_span)
            jax.monitoring.unregister_scalar_listener(_on_scalar)
            _listening = False


def _on_event(event, **_) -> None:
    if event == _CACHE_ASKED:
        _pending.asked = True
    elif event == _CACHE_HIT:
        _pending.hit = True


def _on_duration(event, duration_secs, **_) -> None:
    if event == _RETRIEVAL:
        _pending.retrieval_s = duration_secs
    elif event == _SAVED:
        _pending.saved_s = duration_secs


def _on_scalar(event, value, **_) -> None:
    # jax reports an interval's START as a scalar: how deep this thread is
    # in traces and lowerings (an inner jit is traced inside its caller's)
    if event == _TRACE or event == _LOWER:
        depth = getattr(_pending, "depth", 0)
        if depth == 0 and event == _TRACE:
            first = getattr(_pending, "before_build", None)
            if first is not None:
                first()  # an outermost trace is about to start: before_build
        _pending.depth = depth + 1


def _on_time_span(event, start, end, fun_name="", **_) -> None:
    if event == _BACKEND:
        try:
            _close_build(start, end, fun_name)
        except Exception as e:  # noqa: BLE001 - the log must not break a compile
            import warnings

            warnings.warn(f"compile_cache: no build record for {fun_name!r} "
                          f"({type(e).__name__}: {e})", RuntimeWarning,
                          stacklevel=2)
    elif event == _TRACE or event == _LOWER:
        seen = _pending.__dict__
        depth = seen["depth"] = max(seen.get("depth", 1) - 1, 0)
        if depth == 0:
            # an outermost interval: what lay inside it is counted in it
            spans = seen.setdefault("spans", [])
            spans.append((event, start, end, fun_name))
            del spans[:-_PENDING_MAX]


def _close_build(start: float, end: float, fun_name: str) -> None:
    """One record, from what this thread saw since its previous build."""
    seen = _pending.__dict__
    spans = seen.pop("spans", ())
    # the build's own lowering and trace, by name (``jit(f)`` / ``f``): a
    # trace nothing was built from (``eval_shape``) is another program's
    lower = next((s for s in reversed(spans)
                  if s[0] == _LOWER and s[3] == fun_name), None)
    trace = next((s for s in reversed(spans)
                  if s[0] == _TRACE and fun_name.endswith(f"({s[3]})")), None)
    t0 = min([start] + [s[1] for s in (trace, lower) if s])
    hit = seen.pop("hit", False)
    asked = seen.pop("asked", False)
    retrieval_s = seen.pop("retrieval_s", None)
    saved_s = seen.pop("saved_s", 0.0)
    if hit:
        cache = "hit"
    else:
        import jax

        # jax asks its cache of every compile, with or without a directory
        cache = "miss" if asked and jax.config.jax_compilation_cache_dir \
            else "off"
    backend_s = end - start
    program = fun_name[4:-1] if fun_name.startswith("jit(") else fun_name
    attrs = {
        "program": program,
        "thread": _threading.current_thread().name,
        "trace_s": round(trace[2] - trace[1], 6) if trace else 0.0,
        "lower_s": round(lower[2] - lower[1], 6) if lower else 0.0,
        "backend_s": round(backend_s, 6),
        "cache": cache,
        "retrieval_s": round(retrieval_s, 6) if hit else None,
        "compiled_s": round(saved_s + retrieval_s if hit else backend_s, 6),
    }
    wall_s = round(end - t0, 6)
    seen["n"] = seen.get("n", 0) + 1
    seen["s"] = seen.get("s", 0.0) + wall_s
    from .. import obs

    with _builds_lock:
        _builds.append({"t0": t0, "wall_s": wall_s, **attrs})
        # inside the lock: obs.enable replays the log and turns the plane on
        # under it (builds_held), so a build is written once, there or here
        obs.closed_span("program.build", t0, wall_s, **attrs)


def builds() -> list:
    """A copy of the build log, oldest first (module docstring, 3)."""
    with builds_held() as held:
        return held


@contextlib.contextmanager
def builds_held():
    """The log's copy with the log HELD: no build closes inside the block.
    ``obs.enable`` writes the backlog and turns the plane on in here."""
    with _builds_lock:
        yield [dict(b) for b in _builds]


def thread_builds() -> tuple:
    """``(count, wall seconds)`` of the builds that have closed on the
    CALLING thread so far: a mark for :func:`built_since`."""
    seen = _pending.__dict__
    return seen.get("n", 0), seen.get("s", 0.0)


def built_since(mark: tuple) -> dict:
    """What the calling thread built since ``mark = thread_builds()``, as
    the attributes of the span around that stretch (``chunk``, ``sp_fit``):
    ``phase`` ``"compile+execute"`` where a build closed inside it, else
    ``"execute"``, with ``builds`` and ``build_s``."""
    n, s = thread_builds()
    return {"phase": "compile+execute" if n > mark[0] else "execute",
            "builds": n - mark[0], "build_s": round(s - mark[1], 6)}
