"""Runtime telemetry plane (ISSUE 3): spans, metrics, flight recorder.

The reference got its observability for free from Spark — per-task
metrics, the event log, and a web UI made every job's stragglers, retries,
and memory pressure visible.  The TPU rebuild re-created Spark's
*resilience* (retry ladder, chunk journal, watchdog) but a million-series
fit was still a black box between "started" and the final status counts.
This package is the missing plane, zero-dependency and **off by default**
— when disabled every call returns a shared no-op object, adds no events,
and leaves fit results bitwise-identical to the uninstrumented code:

- :mod:`.core` — nested wall/process-time **spans**
  (``obs.span("chunk", lo=...)``), the ``program.build`` span that
  separates trace + lower + compile (or cache-read) time from steady-state
  execute time (``obs.closed_span``, fed by ``utils.compile_cache``'s build
  log, ISSUE 54), run summaries, and failure dumps; ``profile=True``
  mirrors spans into ``jax.profiler`` annotations.  Every span line carries its identity (schema v3, ISSUE
  25): ``id`` (one per-run counter), ``parent`` (the span that caused it,
  across threads too: ``span_link()`` at a hand-over, ``span(...,
  parent=link)`` or ``span_scope(link)`` on the other side) and ``walk``
  (the sequence number all spans of one ``fit_chunked`` call share;
  ``walk_span()`` opens the root).
- :mod:`.metrics` — the **registry** of counters / gauges / histograms
  the instrumented paths feed: ladder-rung counts per ``FitStatus``,
  sanitizer actions, OOM backoff halvings, watchdog timeouts, journal
  commit latency, ``map_series`` compiled-kernel cache hits/misses,
  peak-memory gauges.
- :mod:`.recorder` — the bounded ring-buffer **flight recorder**: every
  span/event lands in a ring (and, when enabled with a path, a flushed
  JSONL stream ``tools/obs_report.py`` renders), and any fit failure
  dumps the tail for post-mortems.
- :mod:`.memory` — peak-memory probe: device ``memory_stats()`` with a
  host peak-RSS fallback, so the reading is never null on CPU.
- :mod:`.tracing` — fleet-wide distributed tracing (ISSUE 18): trace
  contexts derived DETERMINISTICALLY from content-derived request ids
  (never uuid4), carried on a thread-local, ridden across the wire in
  the serving header, and stamped onto every recorder line as a
  top-level ``trace`` object (schema v2) so
  ``tools/obs_report.py --fleet/--trace`` reassembles one causal
  timeline per request across replicas, retries, and failovers.
- :mod:`.promsink` — streaming Prometheus-textfile sink (ISSUE 12): the
  registry snapshot (+ caller gauges) rendered to the node-exporter
  textfile-collector format with atomic replace, so a RESIDENT serving
  process (``serving.FitServer(prom_path=...)``) is scrapeable mid-run;
  ``validate_textfile`` is the ``obs_report --check --prom`` gate that
  keeps renamed metrics from silently vanishing off dashboards.

Usage::

    from spark_timeseries_tpu import obs
    obs.enable("run.jsonl")           # or STSTPU_OBS=1 in the environment
    res = panel.fit("arima", order=(1, 1, 1), chunk_rows=131_072,
                    checkpoint_dir="/ckpt/job42")
    res.meta["telemetry"]             # per-chunk spans, counters, peak mem
    obs.disable()                     # final metrics snapshot -> JSONL

The walk path, root to leaf (``obs.recorder`` lists the attributes):
``walk`` > ``walk.open``, then per chunk ``chunk.plan``, ``chunk`` >
``sanitize``, ``fit.primary`` > ``fit.stage1`` / ``fit.stage2`` (the lazy
optimizer's stage gate, with the carry's ``iters`` and ``undone`` and what
its loop counted: ``trials``, the line search's trials, and
``iter_passes``, both summed over the ``starts``), ``fit.readback`` (with
the rows' ``iters_max`` / ``iters_sum``, and stage 2's ``stage2_iters`` /
``stage2_trials``: the stage-2 program is not waited for at its dispatch,
so ``lockstep.fit`` hands the two device scalars to ``obs.defer`` and the
read-back, which waits anyway, writes what ``obs.settle`` reads), the
ladder's ``fit.rung.*``, then ``chunk.submit`` > ``commit.overlap`` on the
committer thread, ``stage.overlap`` on the prefetcher thread; last
``walk.close``.  ``benchmark/span_idle.py`` splits the device's idle time
by these names, ``benchmark/device_phases.py`` its busy time.

Beside the tree, wherever an executable is built: ``program.build``
(``program``, ``thread``, ``trace_s``, ``lower_s``, ``backend_s``,
``cache``, ``retrieval_s``, ``compiled_s``), written when the build closes
with the innermost span open on the BUILDING thread as ``parent`` —
``fit.stage1``, ``fit.stage2``, ``fit.rung.retry``, ``sanitize``,
``stage.overlap``, ``commit.overlap`` — so the idle time that span is given
has a line saying why; the ``chunk`` around it reads ``phase``
``compile+execute`` with ``builds`` / ``build_s``.  ``obs.enable`` first
writes the builds the process made before it (true ``t0``, no parent):
``benchmark/setup_builds.py`` splits ``setup_s`` by them.

Instrumented surfaces: ``reliability.fit_chunked`` / ``resilient_fit`` /
``sanitize`` / ``journal`` / ``watchdog`` / the pipelined ``committer``
(queue-depth gauge, per-commit ``commit.overlap`` spans, hidden-commit
counter), ``TimeSeriesPanel.fit`` / ``map_series``, the compat
``fit_model`` wrappers, ``utils.optim``'s straggler-compaction stage, the
time-sharded ``ops.seqparallel`` ``sp_*_fit`` entry points (``sp_fit``
spans tagged ``compile+execute`` / ``execute`` from the build log), and
``parallel.mesh.shard_series``.

Elastic lane supervision (ISSUE 11, ``reliability.plan.LaneSupervisor``)
reports its whole lifecycle here: a per-lane health gauge
``lane.state.<shard>`` (``active`` / ``idle`` / ``retrying`` /
``quarantined`` / ``done`` / ``stopped``), counters ``lane.retry`` /
``lane.quarantine`` / ``lane.steal`` / ``lane.rebalance`` (spans moved
between lanes), and shard-tagged events ``lane.retry`` /
``lane.quarantine`` / ``lane.steal`` that ``tools/obs_report.py`` renders
inside each lane's timeline row (with a degraded-run total in the
header).
"""

from . import core, memory, metrics, promsink, recorder, tracing
from .core import (NULL_SPAN, Span, closed_span, counter, current_span, defer,
                   disable, dump_failure, dump_on_failure, emit_metrics,
                   enable, enable_from_env, enabled, event, gauge, histogram,
                   last_crash_dump, settle, snapshot, span, span_link,
                   span_scope, stream_path, summary, take_deferred,
                   walk_span)
from .memory import PeakMemory, peak_memory, register_staging_pool
from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .promsink import PromTextfileSink
from .recorder import SCHEMA_VERSION, FlightRecorder
from .tracing import (TraceContext, trace_for_request, trace_from_wire,
                      trace_scope, trace_to_wire)
from .tracing import current as current_trace

__all__ = [
    "Counter",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NULL_SPAN",
    "PeakMemory",
    "PromTextfileSink",
    "SCHEMA_VERSION",
    "Span",
    "TraceContext",
    "closed_span",
    "core",
    "counter",
    "current_span",
    "current_trace",
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
    "memory",
    "metrics",
    "peak_memory",
    "promsink",
    "recorder",
    "register_staging_pool",
    "settle",
    "snapshot",
    "span",
    "span_link",
    "span_scope",
    "stream_path",
    "summary",
    "take_deferred",
    "trace_for_request",
    "trace_from_wire",
    "trace_scope",
    "trace_to_wire",
    "tracing",
    "walk_span",
]

# bench / CI opt-in without code changes (no-op unless STSTPU_OBS=1)
enable_from_env()
