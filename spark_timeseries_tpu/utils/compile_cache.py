"""Where jax's persistent compilation cache lives, and program-reuse counts.

Compiling is not small here: the production ARIMA(1,1,1) fit program for a
v5e at ``[131072, 1000]`` takes tens of seconds to build, and a restarted
process re-pays it before its first chunk.  JAX ships a persistent cache
(serialized executables keyed by HLO, compile options AND the cache path)
that turns that into a disk read.  :func:`configure` is the ONE place the
directory is decided, called before first backend use by ``chip_smoke.py``,
``bench.py``, ``tests/conftest.py`` and the test workers:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it natively; nothing is set
  in code, so whoever launches the process places the cache.
- unset: ``<checkout>/.jax_cache`` — a fixed path derived from this
  package's location (never a temp name, pid or time: the path is part of
  the cache key, so a directory that moves never hits).

This module also owns the PROGRAM-reuse counters (``compile_cache.hit`` /
``compile_cache.miss`` in the obs registry, fed by ``models.base.
jit_program``): the auto-fit order search (ISSUE 9) promises one compiled
program per order shape reused across chunks, and the hit rate is how
that promise is measured (``bench.py`` ``telemetry_summary``).
"""

from __future__ import annotations

import os
import threading as _threading

__all__ = ["configure", "note_hit", "note_miss", "program_cache_stats"]

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

# lock-discipline contract (tools/lint lock-map, module-level form):
# sharded lane threads report hits/misses concurrently.
_PROTECTED_BY_ = {"_hits": "_stats_lock", "_misses": "_stats_lock"}


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

    if not os.environ.get(_ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    return jax.config.jax_compilation_cache_dir
