"""Chunked fit execution: pipelined commits, OOM backoff, journal, watchdog.

The north-star workload (ROADMAP: 1M series x 1k obs) cannot always fit one
monolithic batch in HBM — and the right chunk size depends on the model,
the dtype, and what else is resident on the chip.  Rather than making the
caller guess, :func:`fit_chunked` walks the panel in row chunks and treats
``RESOURCE_EXHAUSTED`` as a recoverable signal: the chunk size is halved
(bounded retries) and the degradation is recorded in the result metadata,
the batch analog of Spark re-running a too-big task after an executor OOM.

Only allocation failures trigger backoff; every other error propagates
unchanged (halving a chunk cannot fix a shape bug, and silently retrying
would bury it).

Above the backoff sit the two *job-level* durability layers Spark provided
for free and a single Python process does not:

- ``checkpoint_dir=`` attaches a write-ahead **chunk journal**
  (:mod:`.journal`): every finished chunk is committed as an npz shard
  plus an atomically updated manifest, and a restarted run SKIPS committed
  chunks, producing results bitwise-identical to an uninterrupted run.
- ``chunk_budget_s=`` / ``job_budget_s=`` arm the **deadline watchdog**
  (:mod:`.watchdog`): a chunk that overruns its wall-clock budget is
  marked ``FitStatus.TIMEOUT`` (rows NaN, journal entry ``TIMEOUT``) and
  the walk continues; once the job budget is spent, remaining chunks are
  marked TIMEOUT without dispatch.  The job always terminates with exact
  per-row status counts instead of hanging past its SLO, and a later
  resume retries only the TIMEOUT/pending chunks.

**Pipelined execution** (``pipeline=True``, the default): finished chunks
are handed to a bounded background committer
(:class:`~.committer.ChunkCommitter`) that preserves the journal's
single-writer, shard-before-manifest, in-order protocol while the driver
thread is already slicing and dispatching the next chunk; a background
:class:`~.prefetcher.ChunkPrefetcher` stages chunk N+1's device slice
while chunk N computes, under a static align-mode plan computed once per
walk, and keeps ONE chunk's fit in flight ahead of the chunk the driver is
finishing.  The steady state is stage N+2 ∥ fit N+1 ∥ read back N ∥
commit N−1, results are bitwise-identical to ``pipeline=False``, and
``meta["pipeline"]`` reports how much commit and staging wall the overlap
hid and how many chunks came from a fit ahead.

**Host-resident panels** (ISSUE 7): everything above assumed the panel
resident in device memory before the walk began.  Passing a
:class:`~.source.ChunkSource` instead of an array (host ``np.ndarray``
via ``HostChunkSource``, an npz shard directory via ``NpzShardSource``,
or anything ``as_source`` coerces) walks a panel that NEVER fully
resides on device: each chunk is staged H2D through the source's pool of
reusable host buffers — prefetched ahead of the walk by the same
:class:`~.prefetcher.ChunkPrefetcher` — and the staged device buffer is
donated back to the allocator the moment its chunk's fit has consumed
it, so steady-state device footprint is O(chunk), not O(panel).  The
staged bytes are exactly ``panel[lo:hi]``, so the host-resident walk is
bitwise-identical to the in-HBM walk and journals cross-resume between
residencies.

**Sharded execution** (ISSUE 6): everything above ran on ONE device.  With
``shard=True`` (or an explicit ``mesh=``) the walk's configuration is
compiled into an :class:`~.plan.ExecutionPlan` whose lanes partition the
CHUNK GRID contiguously across the mesh's series-axis devices, and one
:class:`~.plan.LaneRunner` per shard — each with its own journal
namespace, committer, and prefetcher — walks its span concurrently while
the job deadline and the obs registry stay shared.  Shard boundaries
always land on the single-device walk's chunk boundaries, so the sharded
result is bitwise-identical to the single-device walk on the same panel;
shard/process 0 merges the per-shard manifests into ONE job manifest
(``journal.merge_job_manifest``) and ONE shard-tagged telemetry timeline,
and a crash/preemption resume replays only the shards/chunks that did not
commit.  Under ``jax.distributed`` each process runs the lanes of its own
addressable shards (build the global panel with
``parallel.mesh.distribute_panel``) and returns its local rows.
"""

from __future__ import annotations

import functools
import os
import threading
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from . import delta as delta_mod
from . import journal as journal_mod
from . import plan as plan_mod
from . import sink as sink_mod
from . import source as source_mod
from . import watchdog as watchdog_mod
from .plan import (ExecutionPlan, LaneRunner, LaneSpec, OOMBackoffExceeded,
                   _TimeoutChunk, _piece_status, is_resource_exhausted)
from .runner import ResilientFitResult, _accepted_kwargs
from .status import STATUS_DTYPE, FitStatus, status_counts

__all__ = ["OOMBackoffExceeded", "is_resource_exhausted", "fit_chunked"]


def _under_walk_span(fn):
    """Run ``fn`` (``fit_chunked``) under the ``walk`` root span: the owner
    of the walk id every span of the call shares.  Leaving the span also
    closes ``walk.open`` / ``walk.close`` if an exception left one open."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        if not obs.enabled():
            return fn(*args, **kwargs)
        with obs.walk_span():
            return fn(*args, **kwargs)

    return wrapped


@obs.dump_on_failure("fit_chunked")
@_under_walk_span
def fit_chunked(
    fit_fn: Callable,
    y,
    *,
    chunk_rows: Optional[int] = None,
    min_chunk_rows: int = 256,
    max_backoffs: int = 8,
    resilient: bool = True,
    policy: str = "impute",
    ladder=None,
    checkpoint_dir: Optional[str] = None,
    resume: str = "auto",
    chunk_budget_s: Optional[float] = None,
    job_budget_s: Optional[float] = None,
    pipeline: bool = True,
    pipeline_depth: int = 2,
    prefetch_depth: int = 1,
    align_mode: Optional[str] = None,
    mesh=None,
    shard: bool = False,
    lane_retries: int = 1,
    lane_retry_backoff_s: float = 0.1,
    rebalance_threshold: float = 4.0,
    process_index: Optional[int] = None,
    grid: Optional[tuple] = None,
    delta_from: Optional[str] = None,
    delta_warmstart: bool = True,
    sink=None,
    journal_extra: Optional[dict] = None,
    _journal_commit_hook=None,
    **fit_kwargs,
) -> ResilientFitResult:
    """Fit ``y [B, T]`` in row chunks of at most ``chunk_rows``.

    ``y`` is a device-placeable array — or a :class:`~.source.ChunkSource`
    for panels that must NOT fully reside on device (host RAM, npz shard
    directories): the walk then stages each chunk H2D through the
    source's staging pool as it arrives, at the same chunk boundaries,
    producing bitwise-identical results (see the module docstring's
    host-resident section; ``meta["source"]`` and
    ``meta["pipeline"]["staging_pool"]`` carry the staging accounting,
    and sources without an explicit ``chunk_rows`` default to the
    source's natural chunking, e.g. npz shard size).

    Each chunk runs through :func:`~.runner.resilient_fit` (sanitize +
    retry ladder) unless ``resilient=False``, in which case ``fit_fn`` is
    called directly and per-row status comes from the model's own status
    output.  On a ``RESOURCE_EXHAUSTED`` failure the chunk size halves
    (never below ``min_chunk_rows``) and the chunk is retried, at most
    ``max_backoffs`` times per lane; exhausting the budget (or OOMing at
    the floor) raises :class:`OOMBackoffExceeded`.

    **Durability** (``checkpoint_dir=``): finished chunks are committed to
    a write-ahead journal (:class:`~.journal.ChunkJournal`) — npz shard
    first, then an atomic manifest update recording the row range, per-row
    ``FitStatus`` counts, wall time, peak device memory, and the run's
    config hash / panel fingerprint.  A restarted call with the same panel
    and config (``resume="auto"``, the default) loads committed chunks
    from their shards and recomputes only what is missing, so the final
    result is bitwise-identical to an uninterrupted run; a journal written
    under a different panel or config is rejected
    (:class:`~.journal.StaleJournalError`), as is a torn manifest
    (:class:`~.journal.TornManifestError`) — under EVERY resume mode: a
    journal directory belongs to one (panel, config) job for its lifetime,
    and a different job must claim a fresh directory (or the operator
    removes the old one explicitly).  ``resume="never"`` reruns the same
    job from scratch, ignoring its committed chunks; ``"require"`` demands
    a resumable manifest.  Under ``jax.distributed`` every process
    journals into its own namespace and only process 0 commits the
    job-level ``manifest.json`` (``process_index`` defaults to
    ``jax.process_index()``).

    **Pipelining** (``pipeline=True``, default): with a journal attached,
    the host fetch + shard write + manifest update of a finished chunk run
    on a background committer thread (at most ``pipeline_depth`` commits
    in flight, in order) while the driver dispatches the next chunk, so
    the device no longer idles for the commit latency.  The pipeline
    changes WHERE the commit I/O happens, never what is computed: results
    are bitwise-identical to ``pipeline=False``, the journal's
    single-writer / shard-before-manifest / in-order protocol is
    preserved, and a crash with commits in flight resumes exactly as a
    serial crash would (uncommitted chunks recompute).  The pipeline
    knobs are deliberately EXCLUDED from the journal's config hash — a
    serial journal resumes under a pipelined run and vice versa.
    ``pipeline=False`` restores the fully serial walk.
    ``meta["pipeline"]`` reports the commit wall time, how much of it the
    driver never waited for (``hidden_commit_s``), and the resulting
    ``overlap_efficiency``.

    **Input staging** (the other half of the pipeline): while chunk N
    computes, a background :class:`~.prefetcher.ChunkPrefetcher` stages
    chunk N+1's device slice (at most ``prefetch_depth`` slices ahead,
    default 1 — the classic double buffer), so in steady state the walk
    runs stage N+1 ∥ compute N ∥ commit N−1.  The staged buffer is the
    SAME ``yb[lo:hi]`` the serial driver slices (identical bytes); the
    driver predicts the next span on the committed grid (resume clamping
    and torn-shard boundaries included) and invalidates staged slices
    whenever OOM backoff or a committer rollback re-chunks the walk, so a
    stale prediction degrades to an inline slice, never a wrong one.
    ``prefetch_depth=0`` (or ``pipeline=False``) disables staging.
    ``meta["pipeline"]`` gains the input-side accounting
    (``staging_wall_s`` / ``hidden_staging_s`` /
    ``input_overlap_efficiency``) and the combined
    ``end_to_end_overlap_efficiency``.

    **The fit, one chunk ahead** (what ``pipeline`` / ``prefetch_depth``
    hold in flight since ISSUE 56: "staged" is "sliced and fitted"): a
    chunk's fit has two halves — the probe and the dispatch of its
    programs (a lazy optimizer's stage gate is waited for there), then
    the read-back and the ladder.  Wherever the walk stages slices, the
    prefetcher's fit-ahead thread runs the FIRST half of chunk N+1 while
    the driver is at chunk N's turn: its probe at once (on the device's
    queue it lies behind chunk N's stage 1), its programs once chunk N's
    last program has been dispatched — never before it, the queue is first
    in, first out — so the device runs chunk N+1's stage 1 while the driver
    reads chunk N back, runs its ladder, hands it to the committer and
    plans the next turn.  At that turn the driver TAKES the dispatched fit
    if the span it decides — ``(lo, hi)``, the chunk size, the align hint —
    is the one the fit assumed, and otherwise drops it (waited out, its
    device arrays released) and fits serially: an OOM backoff, a committer
    rollback, a steal, the job deadline and a boundary the prediction
    missed all do.  Commits stay in walk order, ``chunk_rows_after`` is
    captured at submit as before, and every chunk is bit for bit the
    serial walk's.  Nothing is fitted ahead with ``pipeline=False`` or
    ``prefetch_depth=0``, under ``chunk_budget_s`` (the watchdog's budget
    bounds ONE chunk's compute), of a whole-span chunk, a chunk the journal
    holds or a forced recompute, and behind a chunk whose own dispatch
    BUILT a program (a process's first chunk of a shape); a build on the
    driver's thread during a chunk's second half (a rung's first program)
    first lets the fit in flight make its last dispatch.  A
    ``RESOURCE_EXHAUSTED`` of the fit ahead is no OOM event of the walk:
    the lane fits nothing ahead for the rest of the walk and the chunk is
    fitted at its turn, where the backoff ladder sees what it saw before.
    COST: two chunks' working sets are on the device at once — the next
    chunk's slice, its sanitized copy and its programs' buffers beside
    this chunk's result, about one chunk of the panel more at the peak
    (PERF.md §5: ``peak_hbm_gb``).  ``meta["pipeline"]`` gains
    ``fits_ahead`` (fits started ahead) and ``fits_ahead_taken`` (chunks
    whose result came from one); each is a ``fit.ahead`` span on its
    thread, parent-linked to the ``chunk`` that started it.

    **Static align-mode plan**: when ``fit_fn`` accepts the ``align_mode``
    hint (every bundled model fit does — ``models.base.resolve_align_mode``),
    a sliced walk computes the panel's alignment mode ONCE and threads it
    into every chunk fit as a static argument, eliminating the per-chunk
    NaN-probe host sync and the per-array-identity align-cache misses on
    fresh slice buffers.  The panel-level mode is a row-wise property, so
    it is exact for every row slice.  Pass ``align_mode=`` to skip even
    the one probe (the journal's config hash covers the resolved mode, so
    a resumed run must use the same plan); a hint too strong for the data
    flags the violating rows instead of silently misfitting them (see
    ``resolve_align_mode``).  Resilient walks downgrade the hint to
    ``"general"`` for chunks the sanitizer actually modified
    (``runner.resilient_fit``), keeping the hint sound when repairs
    change a chunk's NaN pattern.  ``meta["align_mode"]`` records the
    plan.

    **Sharded execution** (``shard=True`` or ``mesh=``): the chunk grid is
    partitioned contiguously across the mesh's series-axis devices
    (:func:`~.plan.shard_spans` — every shard owns whole chunks, so shard
    boundaries ARE single-device chunk boundaries) and one
    :class:`~.plan.LaneRunner` per shard walks its span concurrently,
    each with its own prefetch → compute → commit pipeline over its
    device-resident slice (``parallel.mesh.lane_values`` places the
    panel, using one ``NamedSharding(mesh, P("series", None))`` placement
    when the spans are the even split).  The sharded result is
    bitwise-identical to the single-device walk on the same panel.  With
    ``shard=True`` and no ``chunk_rows``, each shard gets one chunk.
    Journaled sharded walks commit into per-shard namespaces
    (``shard_00000/…``) and shard/process 0 merges them into ONE
    ``manifest.json`` (with a ``shards`` block and shard-tagged telemetry
    timeline) after the lanes join; a resume rebuilds the same lanes
    (same mesh/shard count — a changed shard layout is rejected as stale)
    and replays only uncommitted chunks, and the merged manifest can even
    be adopted by a later SINGLE-device walk of the same job (plan knobs
    are excluded from the config hash).  ``meta["shards"]`` records the
    lane layout; ``meta["pipeline"]`` aggregates the lanes and reports
    per-shard overlap in ``meta["pipeline"]["shards"]``.  Under
    ``jax.distributed`` each process runs the lanes of its addressable
    shards and returns its LOCAL rows (build the global panel with
    ``parallel.mesh.distribute_panel``).

    **Deadlines**: ``chunk_budget_s`` bounds each chunk's fit (overrun ->
    rows flagged ``TIMEOUT``, walk continues — the compiled computation is
    abandoned, not cancelled; with the budget armed, non-resilient fits
    block on device completion inside the watchdog window so the budget
    covers compute, not just async dispatch); ``job_budget_s`` bounds the
    whole walk (once spent, remaining chunks are marked TIMEOUT without
    dispatch — the deadline is shared by every lane).  Both paths drain
    the commit queue before touching the journal, so the TIMEOUT mark
    always lands after every earlier commit.  Partial results always
    carry exact status counts, and TIMEOUT chunks are retried on a
    journaled resume.

    ``meta`` records ``chunk_rows_initial`` / ``chunk_rows_final``, every
    backoff and timeout event, ``degraded=True`` whenever a backoff or
    timeout happened, and — when journaled — the journal accounting
    (``meta["journal"]``: run id, chunks committed/resumed/timeout).

    **Elastic lanes** (ISSUE 11, single-process sharded walks): lane
    failures no longer fail the job.  Lanes pull grid-aligned spans from
    a shared work queue (seeded with the static partition, so a healthy
    walk is layout-identical to PR 6); a lane whose walk raises is
    retried up to ``lane_retries`` times with exponential backoff
    (``lane_retry_backoff_s``), then QUARANTINED — its device leaves the
    active set, its uncommitted chunks are re-staged to survivors'
    devices and recomputed, and chunks it already committed are ADOPTED
    from its journal namespace (chunk entries carry an ``owner`` lane
    tag; the merged manifest reconciles reassigned chunks and records a
    ``rebalance`` block).  Idle lanes STEAL the grid-aligned tail of a
    straggler's remaining span once its projected finish exceeds
    ``rebalance_threshold`` mean chunk walls.  Results stay
    bitwise-identical to the uninterrupted single-device walk regardless
    of which lane computed which chunk; SIGKILL-resume composes (a
    resumed job re-admits previously quarantined devices and replays
    only truly-uncommitted work); a job that loses ALL lanes still fails
    with the original error.  ``meta["shards"]["elastic"]`` records
    quarantines/steals/retries.  Under ``jax.distributed`` (host RAM is
    process-local, so a process cannot re-stage another process's rows)
    the static fail-fast layout is kept.

    **Delta walks** (``delta_from=PRIOR_ROOT``, ISSUE 15): refit only
    what changed.  The planner (:mod:`.delta`) diffs this panel against
    the committed journal at ``PRIOR_ROOT`` using the per-chunk content
    fingerprints every version-2 manifest records: unchanged chunks
    (**clean**) are spliced into this walk's NEW journal namespace as
    ordinary commits up front — zero compute, provenance recorded in the
    manifest's ``extra.delta`` block and the entries' ``delta.class`` —
    so the resume machinery skips them; chunks whose history GREW with a
    byte-identical prefix (**warm**) refit warm-started from the
    journaled params via augmented init-param columns
    (:class:`~.delta.WarmstartFit`; requires ``resilient=False`` and a
    fit with ``init_params=``, e.g. the arima family); revised/new
    chunks refit in full.  The delta result is bitwise-identical to a
    from-scratch refit of the new panel on the same chunk grid — a
    same-length delta (clean + dirty) against the COLD walk
    (determinism: identical rows + identical config + aligned grid
    reproduce identical bytes; off-grid prior boundaries are refused
    adoption and recomputed), a grown (warm) delta against a
    warm-started full walk of the same augmented panel (EVERY computed
    chunk rides the warm wrapper there — dirty/new rows start from
    zeroed inits rather than the model's own cold init);
    ``delta_warmstart=False`` (exact mode) refits everything cold,
    pinning the WHOLE result bitwise against the cold walk — prefer it
    when the delta is mostly new/revised rows rather than appended
    ticks.  A prior journal without chunk fingerprints (journal
    version 1 — still resumable), with shrunk rows/time, or fitted
    under a different config is rejected loudly
    (:class:`~.delta.StalePriorError`).  Requires ``checkpoint_dir=``;
    a SIGKILLed delta walk resumes bitwise and never recomputes an
    adopted chunk.  ``meta["delta"]`` reports the class counts.

    **Grid coordinate** (``grid=(index, total)`` or
    ``(index, total, members)``): an auto-fit order search
    (``models.auto``) runs one ordinary walk per candidate order — or,
    fused, one walk per same-``d`` fusion group, whose member grid
    indices ride in ``members`` (leading with the walk's own index); the
    coordinate places this walk's plan on that grid — chunk
    spans/events/telemetry rows carry a ``grid`` tag (one
    ``tools/obs_report.py`` timeline lane per walk), the manifest
    records ``extra.grid`` (with ``fused`` for a group walk), and
    ``meta["grid"]`` echoes it.  Like the pipeline/shard knobs it is NOT
    part of the journal config hash: the orders themselves ride in the
    hashed fit kwargs; the coordinate only labels where in the search
    the work happened.

    **Telemetry** (``obs.enable()``): the call is one ``walk`` span (the
    root of the span tree and owner of the walk id) over ``walk.open``
    (everything up to the lanes' start), the lanes' chunks and
    ``walk.close`` (merge, assembly, manifest); each chunk dispatch runs
    under an
    ``obs.span("chunk")`` tagged ``compile+execute`` where an executable
    was built on its thread inside it (JAX pays trace + lower + compile, or
    the persistent cache's read, there: ``builds`` / ``build_s``, each build
    a ``program.build`` span) and ``execute`` otherwise; backoffs, timeouts, and per-row status totals feed the
    metrics registry; the committer reports a ``committer.queue_depth``
    gauge, per-commit ``commit.overlap`` spans, and a
    ``committer.hidden_commit_ms`` counter; and the per-run summary —
    per-chunk span times (shard-tagged under a sharded plan), counters,
    peak memory (never null: host-RSS fallback) — lands in
    ``meta["telemetry"]`` and, when journaled, the manifest's
    ``telemetry`` block.  Disabled (the default), none of this runs and
    the result is bitwise-identical to the uninstrumented driver.
    """
    # -- chunk source (ISSUE 7) ----------------------------------------------
    # `y` may be a ChunkSource instead of an array: the panel then lives
    # wherever the source says (host RAM, an npz shard directory) and every
    # chunk is staged H2D through the source's pinned-style staging pool as
    # the walk reaches it — the panel NEVER fully resides on device.  A
    # DeviceChunkSource unwraps to the resident-array walk, byte-identical
    # to passing the array itself.
    walk_sp = obs.current_span()  # the `walk` root (_under_walk_span)
    # entered by hand, not `with`: walk.open and walk.close cover the two
    # long halves of this body around the lanes; an exception that leaves
    # one open is closed by the walk root's exit
    open_sp = obs.span("walk.open").__enter__()
    src = None
    chunk_rows_from_source = False
    if isinstance(y, source_mod.ChunkSource):
        if isinstance(y, source_mod.DeviceChunkSource):
            yb = y.array
        else:
            src = y
            yb = None
            if chunk_rows is None and src.default_chunk_rows:
                chunk_rows_from_source = True
                # sources know their natural chunking — shard size for
                # npz dirs, a bounded slice for host arrays — and the
                # grid lands there unless the caller says otherwise (a
                # whole-panel default chunk would stage the oversubscribed
                # panel in one slice and defeat the point)
                chunk_rows = src.default_chunk_rows
    else:
        yb = jnp.asarray(y)
    if src is not None:
        b, t_len = src.shape
        panel_dtype = src.dtype
        src_stats0 = src.stats()
        # peak_live_device_bytes must be THIS walk's high-water mark (the
        # O(chunk) footprint consumers assert), not a previous walk's
        src.reset_peak_live()
    else:
        if yb.ndim != 2:
            raise ValueError(
                f"fit_chunked expects [batch, time], got {yb.shape}")
        b = yb.shape[0]
        t_len = int(yb.shape[1])
        panel_dtype = np.dtype(str(yb.dtype))

    # -- delta walk (ISSUE 15) -----------------------------------------------
    # delta_from= diffs THIS panel against a committed prior journal
    # (reliability.delta): unchanged chunks are spliced into the new
    # journal as ordinary commits up front (zero compute — the resume
    # machinery then skips them), grown-history chunks refit warm-started
    # from the journaled params via augmented init columns, and only the
    # revised/new remainder refits cold.  Everything after this branch is
    # the ordinary walk: pipelining, prefetch, sources, sharding, elastic
    # lanes, and serving compose with no delta-specific driver code.
    delta_plan = None
    delta_wrapped = False
    data_cols = None
    # grid- and placement-independent identity of the INNER fit (the
    # model + its kwargs, align/driver knobs excluded), recorded in every
    # journaled manifest (`extra.fit`) and checked before a warm delta
    # splices another job's params in as inits: warm-starting
    # arima(1,0,1) from an arima(2,0,1) journal must fail loudly, not as
    # an opaque shape error (or worse, a silent wrong-basin init)
    fit_base = journal_mod.config_hash(
        fit_fn, {k: v for k, v in fit_kwargs.items() if k != "align_mode"})
    _inner = fit_fn
    while isinstance(_inner, functools.partial):
        _inner = _inner.func
    fit_name = (getattr(_inner, "__module__", "?") + "."
                + getattr(_inner, "__qualname__", repr(_inner)))
    if delta_from is not None:
        if checkpoint_dir is None:
            raise ValueError(
                "delta_from= requires checkpoint_dir=: the delta walk "
                "journals adopted + recomputed chunks into a NEW namespace")
        try:
            _n_procs0 = jax.process_count()
        except Exception:  # noqa: BLE001 - no backend yet: single process
            _n_procs0 = 1
        if _n_procs0 > 1:
            raise ValueError(
                "delta walks are single-process (the planner streams the "
                "panel's rows on the host to fingerprint each chunk)")
        # only a CALLER-chosen chunk_rows constrains the delta grid: a
        # source's natural chunking (npz shard size) must not preempt
        # the prior walk's grid, or the documented "omit chunk_rows and
        # the delta defaults to the prior grid" workflow would reject
        # itself whenever the shard size differs from the prior grid
        delta_plan = delta_mod.plan_delta(
            delta_from, src if src is not None else yb,
            chunk_rows=None if chunk_rows_from_source else chunk_rows,
            warmstart=delta_warmstart)
        # the prior walk's grid: delta identity is per-chunk, so the
        # grids must align for adoption to mean anything
        chunk_rows = delta_plan.chunk_rows
        data_cols = t_len  # the new walk's fingerprints cover the raw data
        if delta_plan.counts["warm"] and delta_warmstart:
            pfit = ((delta_plan.manifest.get("extra") or {})
                    .get("fit") or {})
            if pfit.get("base_config") and \
                    pfit["base_config"] != fit_base:
                raise delta_mod.StalePriorError(
                    f"prior journal {delta_plan.prior_dir} fitted "
                    f"{pfit.get('name')} under a different model "
                    "configuration; its params cannot warm-start this "
                    "fit — refit from scratch or point delta_from at a "
                    "journal of the SAME fit/kwargs")
            if resilient:
                raise ValueError(
                    "a warm-started delta walk must run resilient=False "
                    "(the sanitizer would 'repair' the init-param "
                    "columns); pass resilient=False, or "
                    "delta_warmstart=False for an exact cold delta")
            import inspect as _dinspect

            try:
                _fit_params = _dinspect.signature(fit_fn).parameters
            except (TypeError, ValueError):
                _fit_params = {}
            for need in ("init_params", "align_mode"):
                if need not in _fit_params:
                    raise TypeError(
                        "delta_warmstart=True needs a fit_fn with an "
                        f"explicit {need}= parameter (the arima family "
                        "has one); pass delta_warmstart=False for an "
                        "exact cold delta")
            if align_mode is None:
                # resolved on the RAW panel before augmentation: the init
                # columns carry NaN on dirty/new rows, which would
                # otherwise downgrade the plan to "general" for data the
                # fit never sees unaligned
                from ..models import base as _model_base

                align_mode = (src.align_mode() if src is not None
                              else _model_base.align_mode_on_host(yb))
            fit_fn = delta_mod.WarmstartFit(fit_fn, t_len, delta_plan.k)
            aug = delta_mod.warm_panel(src if src is not None else yb,
                                       delta_plan.init)
            delta_wrapped = True
            if isinstance(aug, source_mod.ChunkSource):
                src = aug
                b, t_len = src.shape
                panel_dtype = src.dtype
                src_stats0 = src.stats()
                src.reset_peak_live()
            else:
                yb = aug
                b = yb.shape[0]
                t_len = int(yb.shape[1])

    # -- lane layout (the sharded half of the ExecutionPlan) -----------------
    # resolved BEFORE the align plan and the journal: the shard count can
    # pick the default chunk size, and lane placement is the mesh plane's
    # data distribution step
    use_mesh = mesh
    if use_mesh is not None or shard:
        # lazy: parallel must stay importable without the driver and
        # vice versa, and unsharded walks never pay the import
        from ..parallel import mesh as meshlib
    if use_mesh is None and shard:
        use_mesh = meshlib.default_mesh()
    n_shards = 1
    if use_mesh is not None:
        n_shards = len(meshlib.series_devices(use_mesh))
        if chunk_rows is None and n_shards > 1:
            # shard=True without a chunk size: one chunk per shard — the
            # coarsest layout that still gives every device a lane
            chunk_rows = -(-b // n_shards)
    chunk = int(chunk_rows) if chunk_rows else b
    chunk = max(1, min(chunk, b))
    chunk0 = chunk

    spans = [(0, b)]
    lanes = None  # [(shard_id, lo, hi, device, lane_values), ...]
    if use_mesh is not None and n_shards > 1:
        spans = list(plan_mod.shard_spans(b, chunk0, n_shards))
        if len(spans) > 1:
            if src is not None:
                # source-backed lanes need no device placement up front:
                # each lane stages ONLY its own spans, H2D to its device,
                # as its walk reaches them.  Host RAM is process-local,
                # so a source-backed sharded walk is SINGLE-process —
                # enforced here, before any journal namespace is opened:
                # under jax.distributed every process would otherwise
                # build lanes for ALL spans (duplicate work, concurrent
                # writers on the same shard namespaces) and die at
                # device_put to a non-addressable device.  The multi-host
                # path distributes device arrays (distribute_panel).
                try:
                    n_procs = jax.process_count()
                except Exception:  # noqa: BLE001 - no backend: 1 process
                    n_procs = 1
                if n_procs > 1:
                    raise ValueError(
                        "sharded walks over a ChunkSource are "
                        "single-process (host RAM/disk is process-local); "
                        "under jax.distributed build a global device "
                        "panel with parallel.mesh.distribute_panel "
                        "instead of a source")
                devs = meshlib.series_devices(use_mesh)
                lanes = [(sid, slo, shi, devs[sid],
                          source_mod.SourceLane(src, base=slo,
                                                device=devs[sid]))
                         for sid, (slo, shi) in enumerate(spans)]
            else:
                try:
                    lanes = meshlib.lane_values(yb, use_mesh, spans)
                except BaseException:
                    # lane placement fails per-process (local shard
                    # layout): on a journaled job the OTHER processes will
                    # block in the timeout-less pre-merge barrier — join
                    # it so the error surfaces instead of hanging the
                    # survivors (unjournaled jobs have no barrier: joining
                    # one would hang US)
                    if checkpoint_dir is not None:
                        _distributed_barrier()
                    raise
    sharded = lanes is not None
    if not sharded:
        spans = [(0, b)]
        lanes = [(0, 0, b, None,
                  source_mod.SourceLane(src) if src is not None else yb)]

    # static align-mode plan: resolve the panel's alignment mode ONCE (or
    # take the caller's hint) and thread it into every chunk fit as a
    # static argument — the per-chunk NaN probe (one host sync per sliced
    # chunk) disappears.  The mode is a row-wise property of the panel, so
    # the panel-level answer is exact for every row slice (and for every
    # shard's slice).  Injected BEFORE the journal's config hash is
    # computed: the plan changes which compiled program fits the chunks,
    # so a resume must run the same one.
    from ..models import base as model_base

    import inspect as _inspect

    def _explicit_align_param(fn) -> bool:
        try:
            return "align_mode" in _inspect.signature(fn).parameters
        except (TypeError, ValueError):
            return False

    fit_takes_align = "align_mode" in _accepted_kwargs(
        fit_fn, {"align_mode": None})
    if align_mode is not None:
        # a caller-provided hint is an explicit opt-in: a **kwargs fit_fn
        # is trusted to forward it (the caller asserted it can)
        if not fit_takes_align:
            raise TypeError(
                "align_mode= was given but fit_fn does not accept an "
                "align_mode keyword (the hint would be silently dropped)")
        fit_kwargs = {**fit_kwargs,
                      "align_mode": model_base.resolve_align_mode(
                          yb if src is None else src, align_mode)}
    elif (_explicit_align_param(fit_fn)
          and (src is not None or chunk < b or sharded)
          and "align_mode" not in fit_kwargs):
        # AUTO-injection requires align_mode as an explicitly NAMED
        # parameter — a bare **kwargs does not count (a third-party
        # `def my_fit(y, **opts)` forwarding to a strict solver would
        # blow up on, or silently absorb, a keyword it never asked for).
        # Only sliced walks benefit: a whole-panel chunk hands the
        # caller's array through and the model's own per-array probe
        # cache holds.  A sharded walk always slices (every lane array is
        # a fresh buffer), so it always plans — and a SOURCE walk always
        # stages fresh buffers, so it plans too, probing on the HOST
        # (streamed through the source: the panel never touches the
        # device for the probe).
        fit_kwargs = {**fit_kwargs,
                      "align_mode": (src.align_mode() if src is not None
                                     else model_base.align_mode_on_host(yb))}
    plan_mode = fit_kwargs.get("align_mode") if fit_takes_align else None

    # -- grid coordinate (ISSUE 9 / 10) --------------------------------------
    # an auto-fit order search (models.auto) runs one ordinary walk per
    # candidate order — or, fused (ISSUE 10), one walk per fusion GROUP of
    # same-d orders; grid=(index, total) or (index, total, members) places
    # this walk on that grid so its telemetry rows/events are per-walk
    # lanes and the journal records where in the search the chunks belong
    # (a fused walk's chunks carry the whole group in extra.grid.fused).
    # NOT config-hashed (the orders themselves ride in fit_kwargs, which
    # is) — purely a label.
    if grid is not None:
        gi, gn = (int(grid[0]), int(grid[1]))
        if not (0 <= gi < gn):
            raise ValueError(f"grid index {gi} out of range for total {gn}")
        members = None
        if len(grid) > 2 and grid[2] is not None:
            members = [int(m) for m in grid[2]]
            if any(not (0 <= m < gn) for m in members) or members[0] != gi:
                raise ValueError(
                    f"grid members {members} must sit in [0, {gn}) and "
                    f"lead with the walk's own index {gi}")
        grid = (gi, gn)
        grid_members = members
        gx = {"index": gi, "total": gn}
        if members is not None:
            gx["fused"] = members
        journal_extra = {**(journal_extra or {}), "grid": gx}
    else:
        grid_members = None

    # -- journal(s) ----------------------------------------------------------
    if src is not None:
        # the source spelling rides in the manifest `extra` (NOT the config
        # hash: the bytes are the panel's, not the placement's — an in-HBM
        # journal resumes under a host-RAM walk and vice versa, both
        # fingerprinting sampled VALUES; npz shard dirs fingerprint by
        # shard identity and so journal in their own domain) so
        # post-mortems and the budget advisor can see what the walk read
        # and how big the panel really was
        journal_extra = {**(journal_extra or {}),
                         "source": {"kind": src.kind,
                                    "panel_bytes": int(src.nbytes)}}
    # -- write-back sink (ISSUE 20) ------------------------------------------
    # results stream OUT as durable output shards instead of concatenating
    # in host RAM: every committed chunk's arrays are handed to the sink's
    # background writer (the committer's on_commit hook), the walk keeps
    # boundary-only placeholders, and assembly finalizes the sink instead
    # of materializing the panel-sized result.  The sink moves I/O only —
    # like the pipeline knobs it is NOT part of the journal's config hash,
    # so a sink walk resumes an in-RAM journal and vice versa.
    if sink is not None:
        if checkpoint_dir is None:
            raise ValueError(
                "sink= streams committed chunks out, so it requires a "
                "journaled walk: pass checkpoint_dir= as well")
        if sharded:
            raise ValueError(
                "sink= is not supported with shard=True/mesh=: output "
                "shards are named by global row span and a merged "
                "multi-lane sink is not implemented")
        if isinstance(sink, (str, os.PathLike)):
            sink = sink_mod.WritableChunkSource(sink)
        journal_extra = {**(journal_extra or {}),
                         "sink": {"directory": sink.directory,
                                  "depth": sink.depth}}
    journals = None
    cfg = fp = None
    if checkpoint_dir is not None:
        # EVERY journaled walk records the panel's geometry (extra, not
        # hashed): the budget advisor needs panel bytes from an IN-HBM
        # manifest to say "the next run of this panel should go
        # host-resident" — advice that is moot once a source already ran
        if data_cols is None:
            data_cols = t_len
        journal_extra = {
            **(journal_extra or {}),
            "panel": {"bytes": int(b) * int(t_len) * panel_dtype.itemsize,
                      "time": int(t_len), "dtype": str(panel_dtype)},
            # how many leading DATA columns the per-chunk fingerprints
            # cover (ISSUE 15) — a warm delta walk's init columns are
            # deliberately excluded so tick-feed chains stay delta-eligible
            "chunk_fp_cols": int(data_cols),
            # the INNER fit's identity (warm-wrapped walks record the
            # wrapped model, not the wrapper) — what a later warm delta
            # checks before adopting these params as inits
            "fit": {"name": fit_name, "base_config": fit_base}}
        if delta_plan is not None:
            journal_extra["delta"] = delta_mod.delta_extra(
                delta_plan, warmstart=delta_wrapped, data_cols=data_cols)
        if process_index is None:
            try:
                process_index = jax.process_index()
            except Exception:  # noqa: BLE001 - no backend yet: single process
                process_index = 0
        # pipeline/shard knobs deliberately NOT hashed: they move I/O and
        # compute between threads and devices without changing a byte of
        # the result, so a serial journal resumes under a pipelined run
        # (and vice versa), and a merged sharded manifest is adopted by a
        # later single-device walk.  The reverse direction is NOT adoption:
        # a sharded walk starts fresh shard namespaces and recomputes
        # chunks a root/serial manifest already holds (identical bytes,
        # just repeated work)
        cfg = journal_mod.config_hash(
            fit_fn, fit_kwargs,
            extra={"chunk_rows": chunk0, "min_chunk_rows": min_chunk_rows,
                   "resilient": resilient, "policy": policy,
                   "ladder": "default" if ladder is None else repr(ladder)})
        fp = src.fingerprint() if src is not None else _fingerprint(yb)
        if delta_plan is not None and not delta_plan.grown \
                and delta_plan.prior_config_hash != cfg:
            # clean adoption rests on determinism: identical rows under an
            # IDENTICAL config reproduce identical bytes.  A same-shape
            # prior fitted under a different config cannot donate a single
            # chunk — pointing delta_from at it is operator error, not a
            # silent full refit
            raise delta_mod.StalePriorError(
                f"prior journal {delta_plan.prior_dir} was fitted under a "
                f"different configuration (config_hash "
                f"{delta_plan.prior_config_hash} != {cfg}); its chunks "
                "cannot be adopted into this walk — refit from scratch or "
                "point delta_from at the matching journal")
        # per-chunk content fingerprint sampler (ISSUE 15): every commit
        # records the chunk's own row identity so a LATER delta walk can
        # adopt unchanged chunks.  Multi-process global arrays are not
        # host-sampleable here; their entries simply omit the field.
        chunk_fp = None
        try:
            _addressable = (True if src is not None
                            else getattr(yb, "is_fully_addressable", True))
        except Exception:  # noqa: BLE001 - duck typing over jax versions
            _addressable = False
        if _addressable:
            chunk_fp = delta_mod.chunk_fp_fn(src, yb, data_cols)
        if not sharded:
            journals = [journal_mod.ChunkJournal(
                checkpoint_dir,
                config_hash=cfg,
                panel_fingerprint=fp,
                n_rows=b,
                chunk_rows=chunk0,
                resume=resume,
                process_index=process_index,
                extra=journal_extra,
                commit_hook=_journal_commit_hook,
                chunk_fp=chunk_fp,
            )]
        else:
            # one journal namespace per shard (shard_00000/…): lanes are
            # concurrent writers, and the journal's single-writer rule is
            # per namespace.  The shard layout rides in `extra` so a
            # resume under a DIFFERENT mesh is rejected as stale instead
            # of splicing mismatched spans.
            journals = []
            try:
                # lanes never open the root manifest, so a foreign job's
                # durable state in this dir would survive unnoticed until
                # the merge destroyed it — reject it BEFORE any compute,
                # like the single-device journal does
                journal_mod.check_root_manifest(
                    checkpoint_dir, config_hash=cfg,
                    panel_fingerprint=fp, n_rows=b)
                for (sid, slo, shi, _dev, _vals) in lanes:
                    extra = dict(journal_extra or {})
                    extra.update({"shard_id": sid, "shard_lo": slo,
                                  "shard_hi": shi, "n_shards": len(spans)})
                    journals.append(journal_mod.ChunkJournal(
                        checkpoint_dir,
                        config_hash=cfg,
                        panel_fingerprint=fp,
                        n_rows=b,
                        chunk_rows=chunk0,
                        resume=resume,
                        process_index=process_index,
                        shard_index=sid,
                        extra=extra,
                        commit_hook=_journal_commit_hook,
                        chunk_fp=chunk_fp,
                    ))
            except BaseException:
                # stale/torn LOCAL journal state is asymmetric across
                # processes: peers with clean disks will finish their
                # lanes and block in the timeout-less pre-merge barrier —
                # join it so the error surfaces cluster-wide
                _distributed_barrier()
                raise
        if delta_plan is not None and delta_plan.adopted:
            # splice the clean chunks' committed results into the NEW
            # namespace as ordinary commits BEFORE the walk starts: the
            # resume machinery then skips them like any committed chunk,
            # and a resumed delta walk (committed() already true) never
            # re-adopts — nor recomputes — them
            _delta_adopt(delta_plan, journals,
                         spans if sharded else None, sharded)
    deadline = watchdog_mod.Deadline(job_budget_s)

    # per-chunk telemetry rows for meta["telemetry"] / the manifest block;
    # None (not empty) when disabled so the disabled path allocates nothing
    # and meta stays byte-identical to the uninstrumented driver
    tele = obs.enabled()
    # counter baseline at fit start: the registry is run-wide (one
    # obs.enable() can span many fits), but THIS fit's summary must report
    # its own activity — counters are emitted as deltas from here, so fit
    # B's manifest does not inherit fit A's DIVERGED rows or OOM backoffs.
    # Known limit: a watchdog-ABANDONED worker (timed-out chunk) may still
    # be incrementing counters after its fit returns; those late increments
    # land in whichever delta window is open (XLA dispatch cannot be
    # cancelled, so this is inherent to abandonment, and data-quality only)
    counters0 = (obs.snapshot() or {}).get("counters") if tele else None
    # -- the plan, then its lanes -------------------------------------------
    lane_specs = tuple(LaneSpec(sid, slo, shi, dev)
                       for (sid, slo, shi, dev, _vals) in lanes)
    # elastic supervision (ISSUE 11) applies to SINGLE-PROCESS multi-lane
    # walks: under jax.distributed a process cannot re-stage another
    # process's rows (they are not addressable here), so multi-host jobs
    # keep the static fail-fast layout
    try:
        _n_procs = jax.process_count()
    except Exception:  # noqa: BLE001 - no backend yet: single process
        _n_procs = 1
    elastic = sharded and len(lane_specs) > 1 and _n_procs <= 1
    plan = ExecutionPlan(
        n_rows=b,
        chunk_rows=chunk0,
        min_chunk_rows=min_chunk_rows,
        max_backoffs=max_backoffs,
        resilient=resilient,
        policy=policy,
        ladder=ladder,
        checkpoint_dir=checkpoint_dir,
        resume=resume,
        chunk_budget_s=chunk_budget_s,
        job_budget_s=job_budget_s,
        pipeline=pipeline,
        pipeline_depth=pipeline_depth,
        prefetch_depth=prefetch_depth,
        align_mode=plan_mode,
        lanes=lane_specs,
        process_index=int(process_index or 0),
        n_shards=len(spans) if sharded else 1,
        grid=grid,
        elastic=elastic,
        lane_retries=int(lane_retries),
        lane_retry_backoff_s=float(lane_retry_backoff_s),
        rebalance_threshold=float(rebalance_threshold),
    )
    # journal handles: an elastic lane READS committed state across every
    # shard namespace (adopting a quarantined/stolen-from lane's durable
    # chunks) and WRITES only its own; static walks keep the direct handle
    lane_journals = None
    if journals is not None:
        lane_journals = (
            [journal_mod.ShardJournalView(j, journals) for j in journals]
            if elastic else list(journals))
    # one lane, journaled and pipelined, no sink: the committer fills the
    # result arrays chunk by chunk and walk.close takes them whole
    assembly = (plan_mod.ResultAssembly(b)
                if (lane_journals is not None and pipeline and sink is None
                    and len(lane_specs) == 1 and not elastic) else None)
    runners = [
        LaneRunner(plan, spec, fit_fn, fit_kwargs, vals,
                   journal=(lane_journals[i] if lane_journals is not None
                            else None),
                   deadline=deadline, tele=tele, sink=sink,
                   assembly=assembly)
        for i, (spec, (_sid, _lo, _hi, _dev, vals))
        in enumerate(zip(lane_specs, lanes))
    ] if not elastic else None
    # overlap the root-manifest merge with the last lanes' tails (ISSUE 7
    # satellite, PR-6 follow-on): while slower lanes finish, shard/process 0
    # already READS and parses the shard manifests the committed lanes have
    # written — the merge after the barrier then only re-reads manifests
    # that changed since.  Read-only by construction: the root manifest's
    # single writer is still merge_job_manifest, after the lanes join.
    warmer = None
    if (journals is not None and sharded and len(lane_specs) > 1
            and int(process_index or 0) == 0):
        warmer = journal_mod.MergeWarmer(checkpoint_dir, len(spans))
    elastic_meta = None
    walk_sp.set(rows=int(b), chunk_rows=chunk0, lanes=len(lane_specs),
                journaled=journals is not None)
    open_sp.__exit__(None, None, None)
    try:
        if elastic:
            # elastic supervision (ISSUE 11): lanes pull spans from the
            # shared work queue, failures quarantine instead of failing
            # the job, idle lanes steal from stragglers, and reassigned
            # spans are re-staged to the computing lane's device
            def _restage(rlo, rhi, device):
                if src is not None:
                    return source_mod.SourceLane(src, base=rlo,
                                                 device=device)
                return plan_mod.RestagedPanel(yb, device=device, base=rlo)

            supervisor = plan_mod.LaneSupervisor(
                plan, fit_fn, fit_kwargs,
                [(spec, vals) for spec, (_s, _l, _h, _d, vals)
                 in zip(lane_specs, lanes)],
                journals=lane_journals, deadline=deadline, tele=tele,
                restage=_restage)
            results, elastic_meta = supervisor.run()
        elif len(runners) == 1:
            results = [runners[0].run()]
        else:
            results = [None] * len(runners)
            errors = [None] * len(runners)

            def _drive(i):
                try:
                    results[i] = runners[i].run()
                except BaseException as e:  # noqa: BLE001 - re-raised below
                    errors[i] = e

            threads = [threading.Thread(target=_drive, args=(i,), daemon=True,
                                        name=f"chunk-lane-{r.spec.shard_id}")
                       for i, r in enumerate(runners)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            first = next((e for e in errors if e is not None), None)
            if first is not None:
                # the failing lane already closed its own committer/
                # prefetcher; the OTHER lanes ran to completion (their
                # journals keep their commits — a resume replays only
                # what is missing)
                raise first
            results = [r for r in results if r is not None]
            results.sort(key=lambda r: r.spec.lo)
    except BaseException:
        if warmer is not None:
            warmer.stop()
        # peer processes of a journaled sharded job are (or will be)
        # blocked in the pre-merge barrier, which has no timeout: a
        # process whose lane failed must still JOIN it so the error
        # surfaces cluster-wide instead of hanging the survivors (the
        # barrier is best-effort and a no-op single-process)
        if journals is not None and sharded:
            _distributed_barrier()
        raise

    # -- merge lanes ---------------------------------------------------------
    close_sp = obs.span("walk.close").__enter__()
    # results arrive one per WALKED SPAN (an elastic lane can walk several);
    # spans are disjoint and each result's pieces ascend, so the sort by
    # span lo yields globally ascending pieces either way
    pieces = [p for r in results for p in r.pieces]
    pieces.sort(key=lambda p: p[0])
    oom_events, timeout_events = [], []
    for r in results:
        tag = {"shard": r.spec.shard_id} if sharded else {}
        oom_events.extend({**ev, **tag} for ev in r.oom_events)
        timeout_events.extend({**ev, **tag} for ev in r.timeout_events)
    chunk_final = min((r.chunk_final for r in results), default=chunk0)
    tele_chunks = None
    if tele:
        tele_chunks = [row for r in results for row in (r.tele_chunks or [])]
        tele_chunks.sort(key=lambda c: c["lo"])

    dtype = panel_dtype
    sink_acct = None
    if sink is not None:
        # write-back assembly (ISSUE 20): every computed/resumed chunk
        # already streamed out through the sink — only TIMEOUT spans are
        # materialized here (as the NaN/TIMEOUT rows the in-RAM assembly
        # would synthesize), then the sink verifies its spans tile
        # [0, n_rows) and writes the durable sink manifest.  The result
        # arrays stay None: the caller reads the output shards back at
        # O(chunk) footprint (NpzShardSource over the sink directory).
        sink.barrier()  # every queued write durable; param width known
        k = sink.param_width or 1
        for plo, phi, p in pieces:
            if isinstance(p, _TimeoutChunk):
                n = phi - plo
                sink.write(plo, phi, {
                    "params": np.full((n, k), np.nan, dtype),
                    "nll": np.full(n, np.nan, dtype),
                    "converged": np.zeros(n, bool),
                    "iters": np.zeros(n, np.int32),
                    "status": np.full(n, FitStatus.TIMEOUT, STATUS_DTYPE),
                })
        sink_acct = sink.finalize(b)
        params = nll = conv = iters = status = None
        counts = {m.name: int(sink_acct["status_counts"].get(
            str(m.value), 0)) for m in FitStatus}
    else:
        # parameter width for synthesized TIMEOUT rows comes from any
        # finished chunk; an all-TIMEOUT job degenerates to one NaN column
        k = next((int(np.asarray(p.params).shape[-1]) for _, _, p in pieces
                  if not isinstance(p, _TimeoutChunk)), 1)

        def _mat(p):
            if isinstance(p, _TimeoutChunk):
                n = p.hi - p.lo
                return (np.full((n, k), np.nan, dtype),
                        np.full(n, np.nan, dtype),
                        np.zeros(n, bool),
                        np.zeros(n, np.int32),
                        np.full(n, FitStatus.TIMEOUT, STATUS_DTYPE))
            return (np.asarray(p.params), np.asarray(p.neg_log_likelihood),
                    np.asarray(p.converged), np.asarray(p.iters),
                    _piece_status(p))

        taken = assembly.take(pieces) if assembly is not None else None
        mats = [_mat(p) for _, _, p in pieces] if taken is None else []
        if taken is not None:
            params, nll, conv, iters, status = taken
        elif mats:
            params = np.concatenate([m[0] for m in mats])
            nll = np.concatenate([m[1] for m in mats])
            conv = np.concatenate([m[2] for m in mats])
            iters = np.concatenate([m[3] for m in mats])
            status = np.concatenate([m[4] for m in mats])
        else:
            # a jax.distributed process whose addressable devices own no
            # lane (fewer local spans than mesh devices): its LOCAL result
            # is legitimately empty — it still joins the barrier below
            params = np.zeros((0, k), dtype)
            nll = np.zeros(0, dtype)
            conv = np.zeros(0, bool)
            iters = np.zeros(0, np.int32)
            status = np.zeros(0, STATUS_DTYPE)
        counts = status_counts(status)

    meta = {
        "chunk_rows_initial": chunk0,
        "chunk_rows_final": chunk_final,
        "chunks_run": len(pieces),
        "oom_backoffs": len(oom_events),
        "oom_events": oom_events,
        "timeouts": len(timeout_events),
        "timeout_events": timeout_events,
        "degraded": bool(oom_events or timeout_events),
        "status_counts": counts,
    }
    if sink_acct is not None:
        meta["sink"] = sink_acct
    if sharded:
        meta["shards"] = {
            "n_shards": len(spans),
            "spans": [[int(slo), int(shi)] for slo, shi in spans],
            "lanes_run": len({r.spec.shard_id for r in results}),
            "devices": [str(spec.device) for spec in lane_specs],
        }
        if elastic_meta is not None:
            meta["shards"]["elastic"] = elastic_meta
    if grid is not None:
        meta["grid"] = {"index": grid[0], "total": grid[1]}
        if grid_members is not None:
            meta["grid"]["fused"] = grid_members
    if delta_plan is not None:
        meta["delta"] = {"from": delta_plan.prior_dir,
                         "counts": dict(delta_plan.counts),
                         "warmstart": delta_wrapped}
    if journals is not None and not sharded:
        meta["journal"] = journals[0].accounting()
    if plan_mode is not None:
        meta["align_mode"] = plan_mode
    pipe_meta = _pipeline_meta(results, sharded)
    if src is not None:
        # host-resident accounting (ISSUE 7): the staging pool's
        # hit/reuse counts, the H2D copy wall/bytes, and the
        # donated-buffer high-water mark (peak_live_device_bytes — the
        # O(chunk) steady-state device footprint the oversubscribed bench
        # asserts).  Deltas against the walk's start, so a source shared
        # across walks reports per-walk numbers.
        src_staging = src.stats_delta(src_stats0)
        meta["source"] = {"kind": src.kind,
                          "panel_bytes": int(src.nbytes),
                          "shape": [int(b), int(t_len)],
                          "staging_pool": src_staging}
        if pipe_meta is None:
            pipe_meta = {}  # serial source walks still report staging
        pipe_meta["staging_pool"] = src_staging
    if pipe_meta is not None:
        meta["pipeline"] = pipe_meta
    # ladder/sanitize accounting aggregated across chunks (resilient mode)
    rung_totals: dict = {}
    for _, _, p in pieces:
        for r in (getattr(p, "meta", None) or {}).get("ladder", ()):
            # ``continued`` / ``iters``: rows that entered a rung at the
            # end point of the attempt before it, and the rungs' lockstep
            # iterations (each call's largest), summed over the chunks
            agg = rung_totals.setdefault(
                r["rung"], {"attempted": 0, "rescued": 0, "continued": 0,
                            "iters": 0})
            for key in agg:
                agg[key] += r.get(key, 0)
    if rung_totals:
        meta["ladder_totals"] = rung_totals

    telemetry = None
    if tele:
        for name, v in meta["status_counts"].items():
            if v:
                obs.counter(f"fit_status.{name}").add(v)
        # summary() is None if the plane was disabled mid-run: drop the
        # block entirely rather than crash or journal a null
        extra_tele = {}
        if plan_mode is not None:
            extra_tele["align_mode"] = plan_mode
        if pipe_meta is not None and ("staging_wall_s" in pipe_meta
                                      or "staging_pool" in pipe_meta):
            # the input-staging overlap numbers ride into the manifest so
            # tools/advise_budget.py can suggest prefetch_depth (and the
            # align hint) for the next run of this config; host-resident
            # walks add the staging-pool block (pool reuse, H2D wall,
            # donated-buffer peak) even when the walk ran serially
            extra_tele["input_staging"] = {
                k2: pipe_meta[k2] for k2 in (
                    "prefetch_depth", "chunks_staged", "staged_hits",
                    "staged_misses", "staging_wall_s", "hidden_staging_s",
                    "input_overlap_efficiency", "staging_pool")
                if k2 in pipe_meta}
        if pipe_meta is not None and "shards" in pipe_meta:
            # per-lane commit/staging overlap rides into the merged job
            # manifest so a straggler lane is a journaled fact, not a
            # vanished meta dict (bench gates on it; advise_budget reads it)
            extra_tele["shards_pipeline"] = pipe_meta["shards"]
        telemetry = obs.summary(counters_since=counters0, chunks=tele_chunks,
                                **extra_tele)
        if telemetry is not None:
            meta["telemetry"] = telemetry
            if journals is not None and not sharded:
                journals[0].record_telemetry(telemetry)
            obs.emit_metrics()

    if journals is not None and sharded:
        # shard/process 0 is the single writer of the job-level manifest:
        # merge every shard namespace (chunks re-pathed shard-relative and
        # tagged with their shard id, a `shards` block, the merged
        # telemetry timeline) into ONE manifest.json after the lanes join
        acct = None
        if int(process_index or 0) == 0:
            _distributed_barrier()
            acct = journal_mod.merge_job_manifest(
                checkpoint_dir,
                config_hash=cfg,
                panel_fingerprint=fp,
                n_rows=b,
                chunk_rows=chunk0,
                spans=spans,
                telemetry=telemetry,
                extra=journal_extra,
                cache=warmer.stop() if warmer is not None else None,
                rebalance=elastic_meta,
            )
        else:
            _distributed_barrier()
            # a process may own ZERO local lanes (fewer spans than its
            # addressable devices): journals is then empty, but the job
            # root is just the checkpoint dir
            root = (journals[0].dir.rsplit("/shard_", 1)[0] if journals
                    else os.path.abspath(checkpoint_dir))
            acct = {"dir": root,
                    "manifest": None, "merged_shards": None,
                    "config_hash": cfg,
                    "process_index": int(process_index or 0)}
        acct["chunks_resumed"] = sum(j.resumed_entries for j in journals)
        meta["journal"] = acct
    close_sp.__exit__(None, None, None)
    return ResilientFitResult(params, nll, conv, iters, status, meta)


def _pipeline_meta(results, sharded: bool) -> Optional[dict]:
    """``meta["pipeline"]`` merged across lanes.

    The single-lane block is byte-identical to the pre-plan driver's; a
    sharded plan sums the lanes (total commit/staging wall vs total driver
    blocked wall) and adds a per-shard breakdown so a slow lane is visible
    behind the aggregate.
    """
    pipes = [(r.spec.shard_id, r.pipe_stats, r.committer_depth)
             for r in results if r.pipe_stats is not None]
    pfs = [(r.spec.shard_id, r.pf_stats, r.prefetch_depth)
           for r in results if r.pf_stats is not None]
    if not pipes and not pfs:
        return None
    pipe_meta = {}
    commit_wall = hidden_commit = 0.0
    if pipes:
        commit_wall = sum(s.commit_wall_s for _, s, _ in pipes)
        hidden_commit = sum(s.hidden_s for _, s, _ in pipes)
        pipe_meta.update({
            "depth": pipes[0][2],
            "commits_background": sum(s.commits for _, s, _ in pipes),
            "commit_wall_s": round(commit_wall, 6),
            "driver_blocked_s": round(
                sum(s.blocked_s for _, s, _ in pipes), 6),
            "hidden_commit_s": round(hidden_commit, 6),
            "max_queue_depth": max(s.max_queue_depth for _, s, _ in pipes),
            # fraction of commit wall the driver never waited for — the
            # number the bench's journaled-vs-unjournaled pair publishes
            "overlap_efficiency": (
                round(hidden_commit / commit_wall, 4)
                if commit_wall > 0 else None),
        })
        obs.gauge("committer.hidden_commit_s").set(round(hidden_commit, 6))
        obs.counter("committer.hidden_commit_ms").add(
            int(hidden_commit * 1000))
    staging_wall = hidden_staging = 0.0
    if pfs:
        staging_wall = sum(s.staging_wall_s for _, s, _ in pfs)
        hidden_staging = sum(s.hidden_s for _, s, _ in pfs)
        pipe_meta.update({
            "prefetch_depth": pfs[0][2],
            "chunks_staged": sum(s.staged for _, s, _ in pfs),
            "staged_hits": sum(s.hits for _, s, _ in pfs),
            "staged_misses": sum(s.misses for _, s, _ in pfs),
            "staged_invalidated": sum(s.invalidated for _, s, _ in pfs),
            # the fit kept one chunk ahead of the walk (prefetcher.fit_ahead):
            # fits started ahead, and those whose result the walk took
            "fits_ahead": sum(s.fits_ahead for _, s, _ in pfs),
            "fits_ahead_taken": sum(s.fits_ahead_taken for _, s, _ in pfs),
            "staging_wall_s": round(staging_wall, 6),
            "staging_blocked_s": round(
                sum(s.blocked_s for _, s, _ in pfs), 6),
            "hidden_staging_s": round(hidden_staging, 6),
            # fraction of input-staging wall hidden under compute
            "input_overlap_efficiency": (
                round(hidden_staging / staging_wall, 4)
                if staging_wall > 0 else None),
        })
        obs.counter("prefetch.hidden_staging_ms").add(
            int(hidden_staging * 1000))
    # end-to-end: of ALL the overlap-eligible wall (journal commits +
    # input staging), the fraction the driver never waited for — the
    # single number that says "the walk is dispatch-ahead end to end"
    total_wall = commit_wall + staging_wall
    total_hidden = hidden_commit + hidden_staging
    pipe_meta["end_to_end_overlap_efficiency"] = (
        round(total_hidden / total_wall, 4) if total_wall > 0 else None)
    if sharded:
        # per-shard accumulation: an ELASTIC lane (ISSUE 11) walks several
        # spans — one LaneResult each — and its commit/staging accounting
        # must sum into ONE row per shard, not overwrite
        by_shard: dict = {}
        for sid, s, _d in pipes:
            e = by_shard.setdefault(sid, {"shard": sid})
            cw = e.get("commit_wall_s", 0.0) + s.commit_wall_s
            hc = e.get("hidden_commit_s", 0.0) + s.hidden_s
            e.update({
                "commits_background": e.get("commits_background", 0)
                + s.commits,
                "commit_wall_s": round(cw, 6),
                "hidden_commit_s": round(hc, 6),
                "overlap_efficiency": (round(hc / cw, 4) if cw > 0
                                       else None),
            })
        for sid, s, _d in pfs:
            e = by_shard.setdefault(sid, {"shard": sid})
            sw = e.get("staging_wall_s", 0.0) + s.staging_wall_s
            hs = e.get("hidden_staging_s", 0.0) + s.hidden_s
            e.update({
                "chunks_staged": e.get("chunks_staged", 0) + s.staged,
                "staging_wall_s": round(sw, 6),
                "hidden_staging_s": round(hs, 6),
                "input_overlap_efficiency": (round(hs / sw, 4) if sw > 0
                                             else None),
            })
        pipe_meta["shards"] = [by_shard[sid] for sid in sorted(by_shard)]
    return pipe_meta


def _delta_adopt(plan, journals, spans, sharded: bool) -> None:
    """Commit a delta plan's clean chunks into the new walk's journal(s).

    Adoption is an ordinary ``commit_chunk`` of the prior result arrays
    (zero compute, entry tagged ``delta.class == "adopted"`` with the
    source manifest), routed into the shard namespace whose span holds
    the chunk under a sharded plan — single-writer protocol untouched,
    and the elastic ``ShardJournalView`` sees cross-namespace adoption
    like any reassigned commit.  Already-committed chunks (a resumed
    delta walk) are left exactly as they are: adopted chunks are never
    recomputed OR re-spliced on resume.
    """
    src_manifest = os.path.join(plan.prior_dir, "manifest.json")
    batches: dict = {}  # journal -> [(lo, hi, shard_path, info), ...]
    for entry, shard_path in plan.adopted:
        lo, hi = int(entry["lo"]), int(entry["hi"])
        if sharded:
            sid = next((i for i, (slo, shi) in enumerate(spans)
                        if slo <= lo < shi), 0)
            j = journals[sid]
        else:
            j = journals[0]
        if j.committed(lo) is not None:
            continue
        counts = entry.get("status_counts")
        if counts is None:
            with np.load(shard_path, allow_pickle=False) as z:
                counts = status_counts(np.asarray(z["status"]))
        info = {"wall_s": 0.0, "status_counts": counts,
                "delta": {"class": "adopted",
                          "source_manifest": src_manifest}}
        if entry.get("chunk_fingerprint"):
            # the planner just PROVED the new panel's rows hash to this —
            # recording the prior value verbatim skips a redundant sample
            info["chunk_fingerprint"] = entry["chunk_fingerprint"]
        batches.setdefault(id(j), (j, []))[1].append(
            (lo, hi, shard_path, info))
    for j, items in batches.values():
        adopted = j.adopt_chunks(items)
        obs.counter("delta.chunks_adopted").add(len(adopted))


def _fingerprint(yb) -> str:
    """Panel fingerprint, tolerant of multi-process global arrays (whose
    rows are not all addressable here — sampling them would need a
    collective): those fall back to a shape/dtype/sharding fingerprint,
    which is weaker but consistent across the processes of one job."""
    try:
        addressable = getattr(yb, "is_fully_addressable", True)
    except Exception:  # noqa: BLE001 - duck typing over jax versions
        addressable = True
    if addressable:
        return journal_mod.panel_fingerprint(yb)
    import hashlib

    h = hashlib.sha256(
        f"global:{yb.shape}:{yb.dtype}:{yb.sharding}".encode())
    return h.hexdigest()[:16]


def _distributed_barrier() -> None:
    """Best-effort cross-process barrier before the job-manifest merge:
    process 0 must not merge shard manifests other processes are still
    writing.  No-op (and never fatal) single-process or on backends
    without collectives."""
    try:
        if jax.process_count() <= 1:
            return
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("ststpu-sharded-merge")
    except Exception:  # noqa: BLE001 - barrier is best-effort by design
        import warnings

        warnings.warn(
            "fit_chunked: cross-process barrier before the job-manifest "
            "merge failed; the merged manifest may briefly lag the last "
            "shard commits", stacklevel=2)
