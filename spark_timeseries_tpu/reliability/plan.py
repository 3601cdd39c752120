"""Execution plan + lane scheduler: the chunk walk as data, then as code.

Through PR 5 the durable pipelined walk lived as one hand-wired loop inside
``reliability.chunked.fit_chunked``: prefetcher, committer, watchdog, and
journal were constructed inline and driven by closures, and the whole
arrangement assumed ONE device and ONE lane.  This module is the refactor
ROADMAP called the right first move for scale-out: the walk's
configuration becomes an explicit :class:`ExecutionPlan` (spans, lanes,
budgets as *data*), and the walk itself becomes :class:`LaneRunner` — the
per-lane scheduler that owns exactly one prefetch → compute → commit
pipeline over one contiguous row span.

**One plan, one to N lanes.**  The serial walk, the pipelined walk, and
the sharded walk are the SAME ``ExecutionPlan`` with different knob values
and one-vs-many :class:`LaneSpec` entries.  A single-lane plan reproduces
the PR 1–5 driver bit for bit; a sharded plan (``fit_chunked(shard=True)``
or ``mesh=``) partitions the CHUNK GRID into contiguous per-shard spans —
each mesh device owns a contiguous block of whole chunks, the sharded
twin of the reference's "every partition owns whole series" invariant —
and runs one ``LaneRunner`` per shard concurrently, each dispatching to
its own device.  Because shard boundaries always land on the single-device
walk's chunk boundaries, every chunk is the same rows through the same
compiled program either way, so the sharded result is bitwise-identical
to the single-device walk on the same panel.

**Durability composes unchanged.**  Each lane journals into its own shard
namespace (``shard_00000/…`` — the per-process namespace rule of
:mod:`.journal`, extended down to lanes), and the driver's shard 0 merges
the shard manifests into ONE job manifest after the lanes join.  A
crash/preemption resume rebuilds the same plan, and each lane replays only
its own uncommitted chunks.

Plan knobs (lanes, mesh, pipeline depths) are deliberately EXCLUDED from
the journal's config hash: they move work between threads and devices
without changing a byte of any chunk, so a journal written by the
pre-plan single-device driver resumes under a SINGLE-lane plan, and a
merged sharded job manifest can even be adopted by a later single-device
walk (the merged entries keep their shard-relative paths).  The reverse
is not adoption: a sharded plan's lanes journal into fresh shard
namespaces, so chunks a root/serial manifest already committed are
recomputed (identical bytes, just repeated work), never spliced.

**Elastic lanes** (ISSUE 11).  Through PR 9 a sharded walk inverted the
reference's resilience promise: one lane hitting an unrecoverable fit
exception, an exhausted OOM-backoff ladder, or a dead device failed the
ENTIRE job, and a straggler lane paced every healthy device.  The
:class:`LaneSupervisor` restores the Spark contract at lane granularity:
lanes PULL grid-aligned spans from a shared lock-protected
:class:`WorkQueue` instead of owning a static partition; a lane whose
walk raises is retried up to ``lane_retries`` times with backoff, then
**quarantined** — its device leaves the active set, its *uncommitted*
chunks are re-enqueued and recomputed by survivors (committed shards are
ADOPTED from the dead lane's journal namespace via the cross-namespace
:class:`~.journal.ShardJournalView`, so only truly-uncommitted work
replays), and each idle survivor re-stages reassigned chunks to its own
device (:class:`RestagedPanel` / ``SourceLane``, O(chunk) either way).
Stragglers rebalance the same way: an idle lane STEALS the grid-aligned
tail of the slowest lane's remaining span when that lane's projected
finish exceeds ``rebalance_threshold`` mean chunk walls.  Every steal
boundary stays on the single-device chunk grid (and never splits a
committed chunk), so the walk's results remain bitwise-identical to the
uninterrupted single-device walk regardless of which lane computed which
chunk; a job that loses ALL lanes still fails with the original error.
"""

from __future__ import annotations

import math
import threading
import time
from typing import Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import numpy as np

from .. import obs
from ..utils import compile_cache
from . import committer as committer_mod
from . import prefetcher as prefetcher_mod
from . import source as source_mod
from . import watchdog as watchdog_mod
from .runner import begin_fit, finish_fit
from .status import FitStatus, STATUS_DTYPE, status_counts

__all__ = [
    "ExecutionPlan",
    "LaneRunner",
    "LaneSpec",
    "LaneSupervisor",
    "OOMBackoffExceeded",
    "RestagedPanel",
    "WorkQueue",
    "is_resource_exhausted",
    "shard_spans",
]

# substrings the XLA runtime uses for allocation failure; the simulated OOM
# of reliability.faultinject raises with the same marker so tier-1 CPU tests
# drive this path without a real HBM exhaustion
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory")


class OOMBackoffExceeded(RuntimeError):
    """Raised when the minimum chunk size still exhausts device memory."""


def is_resource_exhausted(e: BaseException) -> bool:
    """True for XLA RESOURCE_EXHAUSTED-style allocation failures.

    ``jaxlib``'s ``XlaRuntimeError`` subclasses ``RuntimeError``, so the
    check is message-based on RuntimeError/MemoryError rather than pinned
    to a jaxlib exception type that moves between releases.
    """
    if isinstance(e, MemoryError):
        return True
    if not isinstance(e, RuntimeError):
        return False
    msg = str(e)
    return any(m in msg for m in _OOM_MARKERS)


class LaneSpec(NamedTuple):
    """One lane of the walk: a contiguous row span and (optionally) the
    device that owns it.  ``device=None`` means "wherever the caller's
    panel lives" — the single-device walk."""

    shard_id: int
    lo: int  # global row offset (inclusive)
    hi: int  # global row offset (exclusive)
    device: Optional[object] = None  # jax.Device for sharded lanes


class ExecutionPlan(NamedTuple):
    """The whole walk as data: spans, lanes, budgets, pipeline knobs.

    Built once per ``fit_chunked`` call (and rebuilt identically on a
    journaled resume — everything that decides a chunk's BYTES is covered
    by the journal config hash; everything here that is not hashed only
    decides WHERE/WHEN work happens).
    """

    n_rows: int
    chunk_rows: int  # initial chunk size (chunk0)
    min_chunk_rows: int
    max_backoffs: int  # per-lane OOM backoff budget
    resilient: bool
    policy: str
    ladder: Optional[tuple]
    checkpoint_dir: Optional[str]
    resume: str
    chunk_budget_s: Optional[float]
    job_budget_s: Optional[float]
    pipeline: bool
    pipeline_depth: int
    prefetch_depth: int
    align_mode: Optional[str]  # resolved static plan mode (None: no hint)
    lanes: Tuple[LaneSpec, ...]  # the lanes THIS process runs
    process_index: int
    # GLOBAL shard count: under jax.distributed a process may run a single
    # lane (or none) of a genuinely sharded walk, and its telemetry/events
    # must still carry shard tags so the merged timeline stays per-lane
    n_shards: int = 1
    # GRID coordinate (ISSUE 9): an auto-fit order search runs one ordinary
    # walk per candidate order; ``(grid_index, grid_total)`` places this
    # walk's plan on that grid so its chunk spans/events/telemetry carry a
    # ``grid`` tag (tools/obs_report.py renders one timeline lane per
    # order).  Like the shard/pipeline knobs it is deliberately EXCLUDED
    # from the journal config hash — the order itself rides in fit_kwargs,
    # which IS hashed; the coordinate only labels where work happened.
    grid: Optional[Tuple[int, int]] = None
    # ELASTIC knobs (ISSUE 11) — like every other plan knob they move work
    # between lanes without changing a byte, so none are config-hashed.
    # ``elastic`` is resolved by the driver: True for single-process
    # multi-lane walks (under jax.distributed a process cannot re-stage
    # another process's rows, so those keep the fail-fast static layout).
    elastic: bool = False
    lane_retries: int = 1  # failed-lane retries before quarantine
    lane_retry_backoff_s: float = 0.1  # first retry's backoff (doubles)
    rebalance_threshold: float = 4.0  # steal when a lane's projected
    # remaining wall exceeds this many mean chunk walls

    @property
    def sharded(self) -> bool:
        return self.n_shards > 1


def shard_spans(n_rows: int, chunk_rows: int,
                n_shards: int) -> Sequence[Tuple[int, int]]:
    """Partition the chunk grid into at most ``n_shards`` contiguous spans.

    The unit of distribution is the CHUNK, not the row: every span is a
    whole number of ``chunk_rows`` chunks (the last span absorbs the
    ragged tail), so a sharded walk visits exactly the chunk boundaries
    the single-device walk would — the invariant the bitwise-identity
    contract rests on.  Shards are balanced to within one chunk; when
    there are fewer chunks than shards, the extra shards get no lane.
    """
    n_rows = int(n_rows)
    chunk_rows = max(1, int(chunk_rows))
    n_chunks = -(-n_rows // chunk_rows)
    n_lanes = max(1, min(int(n_shards), n_chunks))
    q, r = divmod(n_chunks, n_lanes)
    spans, start = [], 0
    for i in range(n_lanes):
        take = q + (1 if i < r else 0)
        lo = start * chunk_rows
        start += take
        hi = min(start * chunk_rows, n_rows)
        spans.append((lo, hi))
    return spans


def _span_times(sp) -> dict:
    """Wall/process times of a closed chunk span, or ``{}`` when the plane
    was disabled mid-run (the span degraded to the shared no-op whose
    times are None — telemetry may lose a row's timings but must never
    crash the fit it observes)."""
    if sp.wall_s is None:
        return {}
    out = {"wall_s": round(sp.wall_s, 6)}
    if sp.process_s is not None:
        out["process_s"] = round(sp.process_s, 6)
    return out


class _TimeoutChunk:
    """Placeholder for a chunk whose fit never finished; materialized into
    NaN-param / ``TIMEOUT``-status rows once the parameter width is known
    (from any finished chunk) at assembly time."""

    __slots__ = ("lo", "hi")

    def __init__(self, lo: int, hi: int):
        self.lo, self.hi = lo, hi


class _SunkChunk:
    """Placeholder for a chunk whose result already streamed out through
    the write-back sink (ISSUE 20): the walk keeps only its boundaries,
    so a sink-mode walk's host footprint stays O(chunk) instead of
    accumulating every chunk's arrays for the final concatenate."""

    __slots__ = ("lo", "hi")

    def __init__(self, lo: int, hi: int):
        self.lo, self.hi = lo, hi


def _piece_status(p) -> np.ndarray:
    """Status of one chunk result; synthesized when the fit has none."""
    status = getattr(p, "status", None)
    conv = np.asarray(p.converged)
    if status is None:
        finite = np.isfinite(np.asarray(p.params)).all(axis=-1)
        return np.where(conv & finite, FitStatus.OK,
                        FitStatus.DIVERGED).astype(STATUS_DTYPE)
    return np.asarray(status).astype(STATUS_DTYPE)


def _commit_arrays(piece) -> dict:
    """Host-side arrays of one finished chunk, in the journal shard schema.

    Under the pipelined driver this runs on the committer thread, so for
    non-resilient fits the device->host fetch itself overlaps the next
    chunk's device compute."""
    return {
        "params": np.asarray(piece.params),
        "nll": np.asarray(piece.neg_log_likelihood),
        "converged": np.asarray(piece.converged),
        "iters": np.asarray(piece.iters),
        "status": _piece_status(piece),
    }


class ResultAssembly:
    """The walk's result arrays, filled a chunk at a time on the committer
    thread, under the next chunk's device compute.

    The assembly at ``walk.close`` was one ``np.concatenate`` a result
    array on the driver's thread after the last chunk: fresh pages for the
    whole result, 135 ms a walk of ``[1,048,576, 33]`` f32 params with the
    device idle (my chip run 4, PR 49).  ``place`` is the committer's
    ``on_fetch`` hook: it copies the chunk's host arrays — the very arrays
    the journal shard is written from — into rows ``[lo, hi)``.  ``take``
    hands the arrays over only if the placed spans are EXACTLY the walk's
    pieces (a resumed, timed-out or rolled-back walk placed other spans, or
    fewer): anything else returns ``None`` and the walk concatenates as
    before, so the result's bytes are the concatenation's either way."""

    _FIELDS = ("params", "nll", "converged", "iters", "status")

    def __init__(self, n_rows: int):
        self.n_rows = int(n_rows)
        self._arrays: Optional[dict] = None
        self._placed: dict = {}
        self._sound = True

    def place(self, lo: int, hi: int, arrays: dict) -> None:
        if self._arrays is None:
            self._arrays = {
                k: np.empty((self.n_rows,) + arrays[k].shape[1:],
                            arrays[k].dtype) for k in self._FIELDS}
        # the next chunk's rows, if the walk has not been there yet, are
        # touched now: their fresh pages (2 ms a MB on a chip machine) fault
        # under this chunk's successor, and the walk's LAST chunk, whose
        # commit the driver waits for, copies into warm ones
        ahead = min(2 * hi - lo, self.n_rows) \
            if hi >= max(self._placed.values(), default=0) else hi
        for k in self._FIELDS:
            out, a = self._arrays[k], arrays[k]
            if a.dtype != out.dtype or a.shape != (hi - lo,) + out.shape[1:]:
                self._sound = False  # what concatenate would promote or refuse
                return
            out[lo:hi] = a
            out[hi:ahead] = 0
        self._placed[int(lo)] = int(hi)

    def take(self, pieces: list) -> Optional[tuple]:
        """``(params, nll, converged, iters, status)`` if every piece of
        the finished walk was placed and nothing else was, else ``None``."""
        spans = {int(lo): int(hi) for lo, hi, _ in pieces}
        tiled = sum(hi - lo for lo, hi in spans.items()) == self.n_rows
        if not (self._sound and tiled and len(spans) == len(pieces)
                and spans == self._placed):
            return None
        return tuple(self._arrays[k] for k in self._FIELDS)


class _LaneView:
    """Offset view over a lane's device-local panel: translates the walk's
    GLOBAL row spans into the lane array's local rows, so the prefetcher
    and the inline slice path share one expression (and the staged bytes
    are exactly the bytes the inline slice would produce)."""

    __slots__ = ("arr", "base")

    def __init__(self, arr, base: int):
        self.arr = arr
        self.base = int(base)

    def __getitem__(self, s: slice):
        return self.arr[s.start - self.base:s.stop - self.base]


class RestagedPanel:
    """Device-staging view over the driver's resident panel, for a lane
    walking a REASSIGNED span (quarantine hand-off or a straggler steal —
    ISSUE 11): the lane's device never held those rows, so each chunk's
    slice is staged to it on demand — ``device_put(panel[lo:hi], device)``,
    the same bytes the original lane's resident slice held, at O(chunk)
    device footprint (the SourceLane pattern, for in-HBM panels).

    Local coordinates: row 0 is global row ``base`` (the reassigned span's
    lo), matching the lane-array convention ``LaneRunner`` slices with.
    """

    __slots__ = ("arr", "device", "base")

    def __init__(self, arr, device=None, base: int = 0):
        self.arr = arr
        self.device = device
        self.base = int(base)

    def __getitem__(self, s: slice):
        vals = self.arr[s.start + self.base:s.stop + self.base]
        return (jax.device_put(vals, self.device)
                if self.device is not None else jax.numpy.asarray(vals))


class LaneResult(NamedTuple):
    """Everything one lane hands back to the driver for merging."""

    spec: LaneSpec
    pieces: list  # (lo, hi, piece) in walk order; piece may be _TimeoutChunk
    oom_events: list
    timeout_events: list
    tele_chunks: Optional[list]
    pipe_stats: Optional[committer_mod.CommitterStats]
    pf_stats: Optional[prefetcher_mod.PrefetchStats]
    chunk_final: int
    committer_depth: Optional[int]
    prefetch_depth: Optional[int]


class LaneRunner:
    """One prefetch → compute → commit lane over one contiguous row span,
    ONE chunk's fit kept in flight ahead of the chunk being finished where
    the walk stages slices (:meth:`_fit_ahead`, :meth:`_take_ahead`).

    This IS the former ``fit_chunked`` loop, verbatim in behavior: the
    single-lane plan reproduces the PR 1–5 driver (same chunk boundaries,
    same journal protocol, same backoff/timeout/rollback semantics, same
    bytes).  A sharded plan runs several of these concurrently, one per
    mesh device, each against its own journal namespace and its own
    committer/prefetcher pair; the shared pieces of state are the job
    :class:`~.watchdog.Deadline` (wall clock is global) and the obs
    metrics registry (counters are merged accounting by design).

    ``values`` is the lane's device-local panel whose row 0 is global row
    ``spec.lo``; the walk itself runs in GLOBAL row coordinates so journal
    entries, telemetry rows, and result assembly agree across lanes.
    """

    # lock-discipline contract (tools/lint lock-map): the elastic span
    # state is mutated by this lane's thread AND by thieves calling
    # try_steal from supervisor threads — every site holds the span
    # lock.  _t0 is written once by the lane thread at run() entry
    # (single writer; readers take the lock) and stays undeclared.
    _protected_by_ = {
        "_hi": "_mu",
        "_busy_hi": "_mu",
        "_steal_closed": "_mu",
        "_rows_done": "_mu",
    }

    def __init__(self, plan: ExecutionPlan, spec: LaneSpec, fit_fn: Callable,
                 fit_kwargs: dict, values, *, journal=None, deadline=None,
                 tele: bool = False, sink=None,
                 assembly: Optional[ResultAssembly] = None):
        self.plan = plan
        self.spec = spec
        self.fit_fn = fit_fn
        self.fit_kwargs = fit_kwargs
        self.values = values
        self.journal = journal
        # write-back sink (ISSUE 20): every committed chunk's host arrays
        # stream out through it, and the pieces list keeps boundary-only
        # placeholders — the walk never accumulates result arrays
        self.sink = sink
        self.deadline = deadline or watchdog_mod.Deadline(plan.job_budget_s)
        self.tele = tele
        # obs attrs tagged with the shard id ONLY for sharded plans: the
        # single-lane walk's spans/events/meta stay byte-identical to the
        # pre-plan driver.  A grid-placed plan (auto-fit order search)
        # additionally tags every span/event with its order's grid index
        self.tag = {"shard": spec.shard_id} if plan.sharded else {}
        if plan.grid is not None:
            self.tag = {**self.tag, "grid": int(plan.grid[0])}
        # sharded journal entries — commits AND timeout marks — record the
        # lane that produced them (ISSUE 11): under elastic reassignment
        # either kind can land in a namespace whose nominal span does not
        # contain it, and the merge/validators reconcile by this tag.
        # Single-device manifests stay byte-identical (no tag).
        self._owner = {"owner": spec.shard_id} if plan.sharded else {}
        # source-backed lanes (ISSUE 7): `values` is a SourceLane over a
        # host-resident ChunkSource — every chunk, including a whole-span
        # one, must be STAGED (there is no resident device array to hand
        # through), and the staged buffer is donated back to the allocator
        # the moment the chunk's fit drops it.  RestagedPanel (ISSUE 11)
        # is the in-HBM twin for reassigned spans: same rule.
        self._from_source = isinstance(
            values, (source_mod.SourceLane, RestagedPanel))
        # elastic-steal state (ISSUE 11): the span's END is mutable — an
        # idle lane may steal the grid-aligned tail of the remaining span
        # (try_steal, called from ANOTHER thread) — so every read of the
        # span end and every dispatch-boundary decision happens under one
        # lock, and nothing at/before _busy_hi can ever be stolen
        self._mu = threading.Lock()
        self._hi = spec.hi
        self._busy_hi = spec.lo
        self._steal_closed = False
        self._rows_done = 0  # rows COMPUTED by this runner (not resumed)
        self._t0: Optional[float] = None

        span_rows = spec.hi - spec.lo
        self.chunk = max(1, min(plan.chunk_rows, span_rows))
        self.committer = None
        if journal is not None and plan.pipeline:
            self.committer = committer_mod.ChunkCommitter(
                journal, _commit_arrays, depth=plan.pipeline_depth,
                probe=obs.peak_memory, status_counts=status_counts,
                on_commit=(sink.write if sink is not None else None),
                on_fetch=(assembly.place if assembly is not None else None))
        # input-side pipeline: stage chunk N+1's slice while chunk N
        # computes.  Only sliced walks stage (a whole-span chunk has no
        # next slice), and pipeline=False stays the fully serial escape
        # hatch for BOTH halves
        self.prefetcher = None
        if plan.pipeline and plan.prefetch_depth and self.chunk < span_rows:
            panel = values if spec.lo == 0 else _LaneView(values, spec.lo)
            self.prefetcher = prefetcher_mod.ChunkPrefetcher(
                panel, depth=plan.prefetch_depth)

        # the fit kept in flight one chunk ahead (prefetcher.fit_ahead): off
        # for the rest of the walk once a fit ahead met RESOURCE_EXHAUSTED —
        # two chunks' working sets do not fit, and that is no backoff
        self._ahead_off = False
        # this turn: a fit ahead was taken, or is in flight beside the chunk
        self._beside = False

        self.pieces: list = []
        self.oom_events: list = []
        self.timeout_events: list = []
        self.tele_chunks: Optional[list] = [] if tele else None
        # boundaries of committed-but-unloadable (torn-shard) chunks: the
        # recompute must cover the EXACT recorded [lo, hi) — deriving hi
        # from the current chunk size could overlap a later committed chunk
        # and break the bitwise-identical-boundaries contract
        self.lost_boundaries: dict = {}

    # -- slicing -------------------------------------------------------------

    def _slice(self, lo: int, hi: int):
        base = self.spec.lo
        return self.values[lo - base:hi - base]

    # -- elastic span (ISSUE 11) ---------------------------------------------

    @property
    def hi(self) -> int:
        """The span's CURRENT end — shrinks when an idle lane steals the
        tail (``try_steal``)."""
        with self._mu:
            return self._hi

    def progress(self) -> dict:
        """Live walk telemetry for the supervisor's rebalance decision."""
        with self._mu:
            return {
                "rows_done": self._rows_done,
                "rows_remaining": max(0, self._hi - self._busy_hi),
                "elapsed_s": (time.perf_counter() - self._t0
                              if self._t0 is not None else 0.0),
            }

    def try_steal(self) -> Optional[Tuple[int, int]]:
        """Give up the grid-aligned tail of this lane's remaining span to
        an idle lane; returns the stolen ``(lo, hi)`` or None.

        The split lands on the single-device chunk grid (multiples of the
        plan's ``chunk_rows`` — the invariant the bitwise contract rests
        on), strictly beyond everything this lane has dispatched or
        resumed (``_busy_hi``), keeps the victim at least half the
        remaining whole chunks, and never lands strictly inside a chunk
        some namespace already committed (a previous run's OOM backoff
        can leave off-grid committed boundaries; splitting one would make
        thief and victim double-compute its rows).  Staged slices are
        invalidated — every prediction past the split is now wrong.
        """
        chunk0 = max(1, int(self.plan.chunk_rows))
        with self._mu:
            if self._steal_closed:
                return None
            hi = self._hi
            base = max(self._busy_hi, self.spec.lo)
            g0 = -(-base // chunk0) * chunk0
            if g0 >= hi:
                return None
            n_rem = -(-(hi - g0) // chunk0)  # whole grid chunks left
            if n_rem < 2:
                return None
            split = g0 + ((n_rem + 1) // 2) * chunk0  # victim keeps ceil
            if self.journal is not None:
                for _ in range(n_rem):
                    x = self.journal.committed_crossing(split)
                    if x is None:
                        break
                    split = int(x)
            if split <= base or split >= hi:
                return None
            self._hi = split
        if self.prefetcher is not None:
            # staged predictions past the split belong to the thief now;
            # dropping ALL staged slices is conservative but safe (a kept
            # span degrades to an inline slice — a miss, never a wrong one);
            # the fit ahead too: the walk's next turn waits it out
            self.prefetcher.cancel_fit("steal")
            self.prefetcher.invalidate()
        return split, hi

    def close_steals(self) -> int:
        """Atomically close the span to further steals and return its
        FINAL end.  The supervisor calls this the moment a runner's walk
        fails: the retry/quarantine hand-off re-walks ``[lo, hi)``, and a
        steal landing between the failure and that hand-off would make
        the stolen tail both the thief's work and the retry's — duplicate
        rows in the assembled result.  Steals that completed before the
        close already shrank ``_hi``, so the returned end excludes them.
        """
        with self._mu:
            self._steal_closed = True
            return self._hi

    def _note_busy(self, row: int) -> None:
        with self._mu:
            if row > self._busy_hi:
                self._busy_hi = row

    # -- backoff / rollback --------------------------------------------------

    def _record_oom(self, at_row: int, rows: int, e: BaseException) -> int:
        """Shared backoff bookkeeping for fit-time, staging-time, and
        commit-time OOMs; returns the halved chunk size (or raises when
        the budget/floor is spent).  Every staged slice is invalidated
        first: the halved boundary makes every prefetch prediction wrong,
        and a freed staged buffer is exactly the HBM the retry needs."""
        plan = self.plan
        if self.prefetcher is not None:
            self.prefetcher.drop_fit("oom")
            self.prefetcher.invalidate()
        self.oom_events.append({
            "at_row": at_row, "chunk_rows": rows,
            "error": f"{type(e).__name__}: {e}"[:200],
        })
        obs.counter("chunked.oom_backoffs").inc()
        obs.event("chunk.oom_backoff", at_row=at_row, chunk_rows=rows,
                  **self.tag)
        if rows <= plan.min_chunk_rows or len(self.oom_events) > plan.max_backoffs:
            raise OOMBackoffExceeded(
                f"chunk of {rows} rows still RESOURCE_EXHAUSTED after "
                f"{len(self.oom_events)} backoffs (floor {plan.min_chunk_rows})"
            ) from e
        return max(plan.min_chunk_rows, rows // 2)

    def _rollback(self, err):
        """Handle a committer-detected failure (the fetch/commit of an
        async-dispatched chunk raised on the worker thread).

        Non-OOM errors re-raise unchanged.  An OOM rolls the walk back to
        the failed chunk: everything at/after it is uncommitted (in-order
        queue), so its pieces are dropped, the chunk size halves, and the
        walk re-enters at the failed row — the pipelined twin of the
        fit-time backoff.  Returns the (lo, chunk) to continue from."""
        e, flo, fhi = err
        if self.prefetcher is not None:
            # the walk rewinds: what was fitted ahead of it is of a walk
            # that no longer is (waited out, its arrays released)
            self.prefetcher.drop_fit("rollback")
        if not is_resource_exhausted(e):
            raise e
        new_chunk = self._record_oom(flo, fhi - flo, e)
        self.pieces[:] = [p for p in self.pieces if p[0] < flo]
        if self.sink is not None:
            # defensive: in-order commits mean spans >= flo never reached
            # the sink, but the rolled-back grid must not leave any behind
            self.sink.discard_from(flo)
        if self.tele:
            self.tele_chunks[:] = [r for r in self.tele_chunks
                                   if r["lo"] < flo]
        return flo, new_chunk

    def _next_span(self, nlo: int, cur_chunk: int):
        """The span the walk will visit after the current chunk — the
        prefetcher's prediction.  Mirrors the walk's own boundary logic
        exactly: torn-shard forced boundaries, then the committed-grid
        clamp (a staged slice must never sail past a committed chunk's
        ``lo``).  Returns None at the lane end or when the next span is
        already committed (the resume path loads it from its shard — no
        device slice needed)."""
        span_hi = self.hi
        if nlo >= span_hi:
            return None
        journal = self.journal
        if journal is not None and journal.committed(nlo) is not None:
            return None
        forced = self.lost_boundaries.get(nlo)
        if forced:
            return nlo, forced[0]
        nhi = min(nlo + cur_chunk, span_hi)
        if journal is not None:
            nxt = journal.next_committed_lo(nlo)
            if nxt is not None and nxt < nhi:
                nhi = nxt
        return nlo, nhi

    def _drain_for_journal_write(self):
        """Synchronize with the committer before the driver itself writes
        the journal (TIMEOUT marks, forced torn-shard recommits): after
        this, every earlier commit is durable and the driver is the only
        writer.  Returns a pending error tuple instead of raising so the
        caller can roll back."""
        if self.committer is None:
            return None
        return self.committer.drain(raise_pending=False)

    # -- the chunk body: values, first half, second half ----------------------

    def _values(self, lo: int, hi: int, chunk: int):
        """This chunk's input values, and the next spans' staging scheduled.

        The whole-span chunk hands the lane's array through untouched (a
        slice would be a fresh device buffer — an extra HBM copy, and a
        miss in the per-array-identity align-mode cache callers pre-warm);
        sliced chunks come from the prefetcher when the staged prediction
        matched.  A staged slice can be queued behind an ABANDONED
        (timed-out) computation, so the wait on it must be bounded by the
        same budget as the compute it feeds — and a staging-time
        RESOURCE_EXHAUSTED surfaces here, through the watchdog, into the
        same backoff ladder as a fit-time one.  A source-backed lane never
        hands ``values`` through: a whole-span chunk still stages H2D (the
        panel lives in host RAM/disk, not on device)."""
        spec = self.spec
        if lo == spec.lo and hi == spec.hi and not self._from_source:
            vals = self.values
        elif self.prefetcher is not None:
            vals = self.prefetcher.take(lo, hi)
        else:
            vals = self._slice(lo, hi)
        if self.prefetcher is not None:
            # stage the next spans now (up to depth ahead — take() just
            # freed this chunk's slot), so they materialize while this
            # chunk computes (and, for resilient fits, while the ladder
            # blocks on host work)
            nlo = hi
            for _ in range(self.prefetcher.depth):
                nxt = self._next_span(nlo, chunk)
                if nxt is None:
                    break
                self.prefetcher.schedule(*nxt)
                nlo = nxt[1]
        return vals

    def _begin(self, vals, not_before=None):
        """The chunk's fit to its last dispatch, on whichever thread runs
        it: the probe and the primary fit of a resilient plan
        (``runner.begin_fit``), the fit itself of a plain one."""
        plan = self.plan
        if plan.resilient:
            return begin_fit(self.fit_fn, vals, policy=plan.policy,
                             not_before=not_before, **self.fit_kwargs)
        if not_before is not None:
            not_before()
        return self.fit_fn(vals, **self.fit_kwargs)

    def _finish(self, begun):
        """The rest of the chunk's fit, on the thread that runs the chunk:
        the read-back and the ladder of a resilient plan."""
        plan = self.plan
        if plan.resilient:
            # a build and a fit in flight do not share the interpreter: a
            # rung that has to BUILD a program lets the fit ahead make its
            # last dispatch before it traces (one chunk's wall, in the one
            # chunk of a process that builds a rung)
            pf = self.prefetcher
            with compile_cache.before_build(
                    pf.fit_dispatched if pf is not None else None):
                return finish_fit(self.fit_fn, begun, ladder=plan.ladder)
        if plan.chunk_budget_s is not None:
            # with a deadline armed the budget must cover the device
            # computation, not just its async dispatch — block here,
            # INSIDE the watchdog window
            # lint: host-sync(deliberate watchdog barrier)
            jax.block_until_ready(begun)
        return begun

    # -- one chunk's fit in flight ahead of the walk (ISSUE 56) ---------------

    def _ahead_key(self, lo: int, hi: int, chunk: int) -> tuple:
        """What a fit ahead assumed of the walk, and what the walk's turn
        compares: the span, the chunk size and the align hint."""
        return lo, hi, chunk, self.fit_kwargs.get("align_mode")

    def _fit_ahead(self, nlo: int, chunk: int, after=None) -> None:
        """Start the fit of the span the walk visits after ``nlo`` on the
        prefetcher's fit-ahead thread, behind ``after`` (the fit ahead this
        turn is taking, if it is still running).  What decides it is what
        the walk knows: a staging prefetcher (``pipeline``,
        ``prefetch_depth``, a sliced walk), no chunk budget (the watchdog's
        budget bounds ONE chunk's compute, and a fit queued behind another
        would be charged for it), no RESOURCE_EXHAUSTED of a fit ahead in
        this walk, and a next span the prediction names (not the lane's end,
        not a chunk the journal holds, no forced recompute)."""
        if (self.prefetcher is None or self._ahead_off
                or self.plan.chunk_budget_s is not None):
            return
        nxt = self._next_span(nlo, chunk)
        if nxt is None or nlo in self.lost_boundaries:
            return
        lo, hi = nxt

        self.prefetcher.fit_ahead(
            self._ahead_key(lo, hi, chunk),
            lambda: self._values(lo, hi, chunk), self._begin, after)
        self._beside = True

    def _take_ahead(self, lo: int, hi: int, chunk: int, built: dict):
        """This turn's chunk as its fit ahead dispatched it, or None: there
        is none, the walk's decision is not the prediction, or it came to
        nothing.  While the driver waits for it the NEXT span's fit is
        already started behind it: its probe fills this chunk's stage gate,
        its stage 1 follows this chunk's stage 2.  A RESOURCE_EXHAUSTED of
        the fit ahead is not an OOM event of the walk — two chunks' working
        sets were in flight: the lane fits nothing ahead from here on and
        the chunk is fitted at its turn; any other exception is the chunk's
        own and is raised here, at its turn."""
        pf = self.prefetcher
        slot = pf.take_fit(self._ahead_key(lo, hi, chunk)) \
            if pf is not None else None
        if slot is None:
            return None
        self._beside = True
        if not slot.came_to_nothing():  # else this turn fits the chunk alone
            self._fit_ahead(hi, chunk, after=slot)
        begun, err = pf.wait_fit(slot)
        if slot.taken:
            if slot.built.get("builds"):  # built on ITS thread, in this chunk
                built.update(slot.built)
            return begun
        # the fit started behind it has given up with it
        pf.drop_fit(slot.dropped_for or "error")
        if err is not None:
            if not is_resource_exhausted(err):
                raise err
            self._ahead_off = True
            pf.invalidate()
        return None

    # -- the walk ------------------------------------------------------------

    def run(self) -> LaneResult:
        self._t0 = time.perf_counter()
        try:
            # sharded lanes tag their thread (and, via the watchdog, their
            # budgeted workers) with the shard id: lane-targeted fault
            # injection and per-lane accounting key on it.  Single-lane
            # walks stay untagged — byte-identical to the pre-plan driver.
            with watchdog_mod.lane_context(
                    self.spec.shard_id if self.plan.sharded else None):
                self._walk()
        except BaseException:
            if self.committer is not None:
                # the walk is failing: stop the worker without letting a
                # second (pending) commit error mask the original exception
                self.committer.close(raise_pending=False)
            if self.prefetcher is not None:
                self.prefetcher.close()
            raise
        pipe_stats = (self.committer.close()
                      if self.committer is not None else None)
        pf_stats = (self.prefetcher.close()
                    if self.prefetcher is not None else None)
        return LaneResult(
            self.spec, self.pieces, self.oom_events, self.timeout_events,
            self.tele_chunks, pipe_stats, pf_stats, self.chunk,
            self.committer.depth if self.committer is not None else None,
            self.prefetcher.depth if self.prefetcher is not None else None)

    def _walk(self) -> None:
        plan, spec = self.plan, self.spec
        journal, deadline = self.journal, self.deadline
        tele = self.tele
        fit_fn, fit_kwargs = self.fit_fn, self.fit_kwargs
        lo = spec.lo
        while True:
            # chunk.plan: the driver's work before a dispatch (committer
            # error poll, resume lookup, boundary decision, deadline check);
            # the walk's last turn, the final drain, has no `hi`
            with obs.span("chunk.plan", lo=lo, **self.tag) as plan_sp:
                if self.committer is not None:
                    err = self.committer.take_error()
                    if err is not None:
                        lo, self.chunk = self._rollback(err)
                        continue
                if lo >= self.hi:
                    # final drain: a commit of one of the last chunks may
                    # still fail (or OOM at fetch) — that must surface (or
                    # roll the walk back) BEFORE assembly reads the pieces
                    err = self._drain_for_journal_write()
                    if err is not None:
                        lo, self.chunk = self._rollback(err)
                        continue
                    break
                if journal is not None:
                    entry = journal.committed(lo)
                    if entry is not None:
                        piece = journal.load_chunk(entry)
                        if piece is not None:
                            # not stealable:
                            self._note_busy(int(entry["hi"]))
                            if self.sink is not None:
                                # resume re-emits the chunk through the
                                # sink: the durable re-write replaces any
                                # torn or missing output shard with the same
                                # bytes, which is what makes a killed-and-
                                # resumed sink directory finalize
                                # bitwise-identical
                                self.sink.write(lo, int(entry["hi"]),
                                                _commit_arrays(piece))
                                piece = _SunkChunk(lo, int(entry["hi"]))
                            self.pieces.append(
                                (lo, int(entry["hi"]), piece))
                            if tele:
                                self.tele_chunks.append(
                                    {"lo": lo, "hi": int(entry["hi"]),
                                     "phase": "resumed", **self.tag})
                            lo = entry["hi"]
                            # replay the backoff state in effect when the
                            # chunk committed, so the resumed walk visits the
                            # SAME boundaries the uninterrupted run would have
                            self.chunk = int(entry.get("chunk_rows_after",
                                                       self.chunk))
                            continue
                        self.lost_boundaries[lo] = (
                            int(entry["hi"]),
                            int(entry.get("chunk_rows_after", self.chunk)))
                forced = self.lost_boundaries.get(lo)
                # the chunk boundary is decided and PUBLISHED (as _busy_hi)
                # under the span lock, so a concurrent try_steal can never
                # split inside a chunk this iteration is about to dispatch
                with self._mu:
                    hi = (forced[0] if forced
                          else min(lo + self.chunk, self._hi))
                    if journal is not None and not forced:
                        # keep the walk on the committed grid: after an OOM
                        # backoff whose halving does not divide the original
                        # chunk size, a free-running hi would sail past the
                        # next committed chunk's lo, orphaning it (never
                        # matched again) and double-counting its rows in the
                        # manifest — clamp to the boundary instead
                        nxt = journal.next_committed_lo(lo)
                        if nxt is not None and nxt < hi:
                            hi = nxt
                    if hi > self._busy_hi:
                        self._busy_hi = hi
                if deadline.exceeded():
                    if self.prefetcher is not None:
                        self.prefetcher.drop_fit("deadline")
                    err = self._drain_for_journal_write()
                    if err is not None:
                        lo, self.chunk = self._rollback(err)
                        continue
                    if forced:
                        self.chunk = forced[1]
                        self.lost_boundaries.pop(lo, None)
                    self.timeout_events.append({
                        "at_row": lo, "chunk_rows": hi - lo,
                        "dispatched": False,
                        "budget_s": deadline.budget_s, "scope": "job"})
                    obs.counter("chunked.timeouts.job").inc()
                    obs.event("chunk.timeout", lo=lo, hi=hi, scope="job",
                              dispatched=False, **self.tag)
                    if tele:
                        self.tele_chunks.append({"lo": lo, "hi": hi,
                                                 "phase": "timeout",
                                                 "scope": "job", **self.tag})
                    self.pieces.append((lo, hi, _TimeoutChunk(lo, hi)))
                    if journal is not None:
                        journal.mark_timeout(lo, hi, scope="job",
                                             budget_s=deadline.budget_s,
                                             chunk_rows_after=self.chunk,
                                             **self._owner)
                    lo = hi
                    continue
                plan_sp.set(hi=hi)

            phase, built = None, {}

            def run_chunk(lo=lo, hi=hi, chunk=self.chunk):
                # lo/hi/chunk are DEFAULT-ARG SNAPSHOTS, not closure reads:
                # a watchdog-abandoned thread keeps running after the driver
                # has mutated the loop variables, and it must keep operating
                # on ITS chunk's span — never take() the live chunk's staged
                # slice or slice a torn lo/hi pair mid-update.  The chunk's
                # values are acquired INSIDE the watchdog window (_values).
                # What the thread that RUNS the chunk (this one, or the
                # watchdog's worker under a budget) builds inside it comes
                # from the build log: a first dispatch pays trace + lower +
                # compile or the cache's read, a later one of the same shape
                # executes a loaded program (a backoff-halved chunk is a NEW
                # shape, and every lane's device has executables of its own)
                mark = compile_cache.thread_builds()
                self._beside = False
                try:
                    begun = self._take_ahead(lo, hi, chunk, built)
                    if begun is None:
                        begun = self._begin(self._values(lo, hi, chunk))
                        # the lane fits ahead of a chunk only if that
                        # chunk's own dispatch built nothing: chunk 0 of a
                        # process's first walk builds the stage programs,
                        # and a second thread calling the same un-built jit
                        # would trace and lower them again
                        if not compile_cache.built_since(mark)["builds"]:
                            self._fit_ahead(hi, chunk)
                    return self._finish(begun)
                except Exception as e:  # noqa: BLE001 - filtered just below
                    if not (self._beside and is_resource_exhausted(e)):
                        raise
                finally:
                    mine = compile_cache.built_since(mark)
                    if built:  # a taken fit's builds, on its own thread
                        mine.update(
                            phase="compile+execute",
                            builds=mine["builds"] + built["builds"],
                            build_s=round(
                                mine["build_s"] + built["build_s"], 6))
                    built.update(mine)
                # RESOURCE_EXHAUSTED with a second chunk's working set in
                # flight is no OOM event of the walk: that fit is waited
                # out and dropped, the lane fits nothing ahead from here
                # on, and the chunk is fitted once more, alone — outside
                # the handler, so the exception, its traceback and the
                # frames (device arrays) they hold are gone before it
                begun = None
                self._ahead_off = True
                self.prefetcher.drop_fit("oom")
                return self._finish(
                    self._begin(self._values(lo, hi, chunk)))

            sp = obs.span("chunk", lo=lo, hi=hi, **self.tag)
            t0 = time.perf_counter()
            try:
                with sp:
                    try:
                        piece = watchdog_mod.call_with_deadline(
                            run_chunk, plan.chunk_budget_s,
                            label=f"chunk rows [{lo}, {hi})")
                    finally:
                        if built:  # not of a worker the watchdog abandoned
                            phase = built["phase"]
                            sp.set(**built)
            except watchdog_mod.DeadlineExceeded:
                err = self._drain_for_journal_write()
                if err is not None:
                    lo, self.chunk = self._rollback(err)
                    continue
                if forced:
                    self.chunk = forced[1]
                    self.lost_boundaries.pop(lo, None)
                self.timeout_events.append({
                    "at_row": lo, "chunk_rows": hi - lo, "dispatched": True,
                    "budget_s": plan.chunk_budget_s, "scope": "chunk"})
                obs.counter("chunked.timeouts.chunk").inc()
                obs.event("chunk.timeout", lo=lo, hi=hi, scope="chunk",
                          dispatched=True, budget_s=plan.chunk_budget_s,
                          **self.tag)
                if tele:
                    self.tele_chunks.append(
                        {"lo": lo, "hi": hi, "phase": "timeout",
                         "scope": "chunk", **self.tag, **_span_times(sp)})
                self.pieces.append((lo, hi, _TimeoutChunk(lo, hi)))
                if journal is not None:
                    journal.mark_timeout(lo, hi, scope="chunk",
                                         budget_s=plan.chunk_budget_s,
                                         chunk_rows_after=self.chunk,
                                         **self._owner)
                lo = hi
                continue
            except Exception as e:  # noqa: BLE001 - filtered just below
                if not is_resource_exhausted(e):
                    raise
                # drain before re-entering backoff: the journal state is
                # then deterministic at every backoff decision, and a
                # failed commit of an EARLIER chunk takes precedence over
                # this chunk's fit-time OOM (it is earlier in the walk)
                err = self._drain_for_journal_write()
                if err is not None:
                    lo, self.chunk = self._rollback(err)
                    continue
                if forced:
                    # a torn-shard recompute is pinned to the committed
                    # [lo, hi): halving `chunk` would not shrink the
                    # dispatch (hi stays forced), so retrying is futile —
                    # fail with the actionable cause instead of burning the
                    # backoff budget
                    raise OOMBackoffExceeded(
                        f"recompute of torn-shard chunk [{lo}, {hi}) hit "
                        "RESOURCE_EXHAUSTED; its boundaries are fixed by the "
                        "journal, so backoff cannot help. Free device "
                        "memory, or restart the job under a fresh "
                        "checkpoint_dir (or remove this journal explicitly) "
                        "to let the walk re-chunk."
                    ) from e
                self.chunk = self._record_oom(lo, self.chunk, e)
                continue
            # chunk.submit: from the chunk span's end to the next turn —
            # the telemetry row, the hand-over to the committer (its
            # backpressure included) or the synchronous commit
            with obs.span("chunk.submit", lo=lo, hi=hi, **self.tag):
                if forced:
                    # torn-shard recompute done: restore the recorded walk
                    self.chunk = forced[1]
                    self.lost_boundaries.pop(lo, None)
                if tele:
                    self.tele_chunks.append(
                        {"lo": lo, "hi": hi, "phase": phase, **self.tag,
                         **_span_times(sp)})
                if journal is not None:
                    wall_s = round(time.perf_counter() - t0, 4)
                    owner = self._owner
                    if self.committer is not None and not forced:
                        # background commit: the fetch + shard + manifest
                        # update overlap the next chunk's dispatch/compute.
                        # chunk_rows_after is captured NOW (not at commit
                        # time) so the recorded backoff state matches the
                        # serial walk exactly
                        try:
                            self.committer.submit(lo, hi, piece, wall_s=wall_s,
                                                  chunk_rows_after=self.chunk,
                                                  **owner)
                        except BaseException as se:
                            err = self.committer.take_error()
                            # only the worker's OWN re-raised error enters the
                            # rollback path: an unrelated exception (e.g. a
                            # Ctrl-C landing while submit blocked) must abort,
                            # not be converted into an OOM retry
                            if err is None or err[0] is not se:
                                raise
                            lo, self.chunk = self._rollback(err)
                            continue
                    else:
                        # forced torn-shard recommits stay synchronous: they
                        # are rare, their boundaries are pinned by the
                        # journal, and the serial path keeps their edge
                        # semantics exact
                        err = self._drain_for_journal_write()
                        if err is not None:
                            lo, self.chunk = self._rollback(err)
                            continue
                        arrays = _commit_arrays(piece)
                        pm = obs.peak_memory()
                        journal.commit_chunk(
                            lo, hi, arrays,
                            wall_s=wall_s,
                            peak_hbm_bytes=pm.bytes,
                            peak_hbm_source=pm.source,
                            chunk_rows_after=self.chunk,
                            status_counts=status_counts(arrays["status"]),
                            # host-resident walks: the staging RAM behind the
                            # device peak, so oversubscribed post-mortems see
                            # the job's whole footprint (obs.memory)
                            **({"peak_staging_pool_bytes":
                                pm.staging_pool_bytes}
                               if pm.staging_pool_bytes is not None else {}),
                            **owner,
                        )
                        if self.sink is not None:
                            self.sink.write(lo, hi, arrays)
                if self.sink is not None:
                    # the committer (or the serial path above) owns the real
                    # piece until its arrays are durable in the sink; the walk
                    # keeps only the boundaries
                    self.pieces.append((lo, hi, _SunkChunk(lo, hi)))
                else:
                    self.pieces.append((lo, hi, piece))
                with self._mu:
                    self._rows_done += hi - lo
            lo = hi


# ---------------------------------------------------------------------------
# elastic lane scheduling (ISSUE 11): work queue, supervision, rebalance
# ---------------------------------------------------------------------------


class WorkQueue:
    """Lock-protected queue of chunk-grid spans the elastic lanes pull.

    Seeded with the static shard partition, each span PREFERRED by its
    nominal lane — so a healthy walk pulls exactly the spans the static
    layout would have assigned and stays namespace- and byte-identical to
    it.  A quarantined lane's span re-enters unpreferred and is picked up
    by whichever survivor goes idle first.  ``cond`` is the one condition
    variable the whole supervisor synchronizes on (push, pull, lane
    completion, fatal errors): lanes never take it while holding a
    runner/journal lock, so the lock order cond → runner → journal is
    acyclic.
    """

    # lock-discipline contract (tools/lint lock-map): every lane thread
    # pushes/pulls spans; the ``*_locked`` helpers are called with the
    # condition held (the codebase convention the linter honors).
    _protected_by_ = {"_spans": "cond"}

    def __init__(self):
        self.cond = threading.Condition()
        self._spans: list = []  # (lo, hi, preferred_sid-or-None)

    def push(self, lo: int, hi: int, preferred: Optional[int] = None) -> None:
        with self.cond:
            self._push_locked(lo, hi, preferred)
            self.cond.notify_all()

    def _push_locked(self, lo: int, hi: int,
                     preferred: Optional[int] = None) -> None:
        self._spans.append((int(lo), int(hi), preferred))
        self._spans.sort(key=lambda s: s[0])

    def _pull_locked(self, sid: int) -> Optional[Tuple[int, int]]:
        """Lowest-lo span preferred by ``sid``, else lowest-lo UNPREFERRED
        span.  A span preferred by ANOTHER lane is never poached: its lane
        is alive and will pull it (at thread-startup a fast lane could
        otherwise grab a peer's span before that peer's thread is even
        scheduled — work the peer's device should do, and the surface
        lane-targeted fault injection and per-lane accounting key on);
        quarantine strips the dead lane's preference first
        (:meth:`release_preference`), so nothing is ever stranded."""
        pick = None
        for i, (_lo, _hi, pref) in enumerate(self._spans):
            if pref == sid:
                pick = i
                break
            if pref is None and pick is None:
                pick = i
        if pick is None:
            return None
        lo, hi, _ = self._spans.pop(pick)
        return lo, hi

    def _release_preference_locked(self, sid: int) -> None:
        self._spans = [(lo, hi, None if pref == sid else pref)
                       for lo, hi, pref in self._spans]

    def pending(self) -> list:
        with self.cond:
            return [(lo, hi) for lo, hi, _ in self._spans]


class LaneSupervisor:
    """Elastic scheduler for a multi-lane sharded walk (ISSUE 11).

    One supervisor thread per lane device, each looping pull → walk →
    pull over the shared :class:`WorkQueue`.  Failure containment per the
    module docstring: an ``Exception`` escaping a lane's walk (fit bug,
    exhausted OOM ladder, dead device) is retried up to
    ``plan.lane_retries`` times with exponential backoff, then the lane is
    QUARANTINED — its span re-enqueued for survivors, who re-stage the
    rows to their own devices (``restage``) and adopt whatever chunks the
    dead lane already committed (the per-lane journal handle is a
    cross-namespace :class:`~.journal.ShardJournalView`).  A
    ``BaseException`` (KeyboardInterrupt, the fault harness's
    ``SimulatedCrash`` standing in for SIGKILL) is FATAL: no quarantine,
    no reassignment — it re-raises from :meth:`run` exactly as the static
    layout would, so crash-resume semantics are unchanged.  Idle lanes
    STEAL from stragglers via ``LaneRunner.try_steal`` once the victim's
    projected remaining wall exceeds ``plan.rebalance_threshold`` mean
    chunk walls.  If every lane is quarantined with work remaining, the
    FIRST lane's original error re-raises — a job that loses all lanes
    still fails loudly.
    """

    # lock-discipline contract (tools/lint lock-map): supervisor state
    # is mutated from every lane thread; the ONE condition variable the
    # whole supervisor synchronizes on (queue.cond) guards it all —
    # keeping the lock order cond -> runner -> journal acyclic.
    _protected_by_ = {
        "results": "queue.cond",
        "_active": "queue.cond",
        "_busy": "queue.cond",
        "_fatal": "queue.cond",
        "_quarantined": "queue.cond",
        "_errors": "queue.cond",
        "_steals": "queue.cond",
        "_retries": "queue.cond",
        "_global_walls": "queue.cond",
        "_lane_mean_wall": "queue.cond",
    }

    def __init__(self, plan: ExecutionPlan, fit_fn: Callable,
                 fit_kwargs: dict, lanes: Sequence[tuple], *,
                 journals: Optional[Sequence] = None, deadline=None,
                 tele: bool = False,
                 restage: Optional[Callable] = None):
        self.plan = plan
        self.fit_fn = fit_fn
        self.fit_kwargs = fit_kwargs
        self.lanes = list(lanes)  # [(LaneSpec, values), ...]
        self.journals = list(journals) if journals is not None else None
        self.deadline = deadline or watchdog_mod.Deadline(plan.job_budget_s)
        self.tele = tele
        self.restage = restage

        self.queue = WorkQueue()
        self.results: list = []
        self._active: dict = {}  # sid -> live LaneRunner (steal victims)
        self._busy: set = set()  # sids mid-span (walking or retry backoff)
        self._journal_by_sid = {}
        if self.journals is not None:
            for (spec, _v), j in zip(self.lanes, self.journals):
                self._journal_by_sid[spec.shard_id] = j
        self._lane_mean_wall: dict = {}  # sid -> mean computed-chunk wall
        self._global_walls: list = []  # (n_chunks, wall_s) per finished span
        self._quarantined: list = []
        self._errors: list = []
        self._fatal: Optional[BaseException] = None
        self._steals = 0
        self._retries = 0

    # -- scheduling ---------------------------------------------------------

    def _state(self, sid: int, state: str) -> None:
        obs.gauge(f"lane.state.{sid}").set(state)

    def _mean_chunk_wall(self, sid: int) -> Optional[float]:
        ref = self._lane_mean_wall.get(sid)
        if ref:
            return ref
        n = sum(c for c, _w in self._global_walls)
        w = sum(w for _c, w in self._global_walls)
        return (w / n) if n else None

    def _pick_victim_locked(self, thief_sid: int):
        """The active lane worth stealing from, or None.  Called under the
        queue cond; reads each runner's live progress (runner lock)."""
        ref = self._mean_chunk_wall(thief_sid)
        if ref is None:
            return None  # no completed chunk anywhere yet: too early
        best, best_proj = None, 0.0
        for vsid, runner in self._active.items():
            if vsid == thief_sid:
                continue
            p = runner.progress()
            if p["rows_remaining"] <= 0:
                continue
            if p["rows_done"] > 0:
                proj = p["rows_remaining"] * p["elapsed_s"] / p["rows_done"]
            elif p["elapsed_s"] > 2.0 * ref:
                proj = math.inf  # no chunk done yet and already overdue
            else:
                continue
            if proj > best_proj:
                best, best_proj = runner, proj
        if best is None:
            return None
        if best_proj <= self.plan.rebalance_threshold * ref:
            return None
        return best

    def _next_work(self, sid: int):
        """Block until there is a span for this lane: from the queue, or
        stolen from a straggler.  None = no work will ever come (all spans
        done, or a fatal error is propagating)."""
        cond = self.queue.cond
        while True:
            with cond:
                if self._fatal is not None:
                    return None
                span = self.queue._pull_locked(sid)
                if span is not None:
                    self._busy.add(sid)
                    return span
                if not self._busy and not self.queue._spans:
                    cond.notify_all()  # release peers blocked in wait()
                    return None
                victim = self._pick_victim_locked(sid)
            if victim is not None:
                stolen = victim.try_steal()
                if stolen is not None:
                    with cond:
                        self._busy.add(sid)
                        self._steals += 1
                    obs.counter("lane.steal").inc()
                    obs.counter("lane.rebalance").inc()
                    obs.event("lane.steal", shard=sid,
                              victim=victim.spec.shard_id,
                              lo=stolen[0], hi=stolen[1])
                    return stolen
            with cond:
                # spans preferred by not-yet-started peers also park us
                # here: their own lanes will pull them (or a quarantine
                # will release them to everyone)
                if self._fatal is None and (self._busy
                                            or self.queue._spans):
                    cond.wait(timeout=0.05)

    def _values_for(self, spec0: LaneSpec, values0, lo: int, hi: int):
        """The values a lane walks for span ``[lo, hi)``: its own resident
        array when that IS its nominal span, else a re-staged O(chunk)
        view onto the driver's panel/source."""
        if (lo, hi) == (spec0.lo, spec0.hi):
            return values0
        if self.restage is None:
            raise RuntimeError(
                "elastic reassignment needs a restage callback")
        return self.restage(lo, hi, spec0.device)

    def _quarantine(self, sid: int, e: Exception, attempts: int,
                    lo: int, hi: int) -> None:
        cause = f"{type(e).__name__}: {e}"[:200]
        rec = {"shard_id": int(sid), "cause": cause,
               "retries": int(attempts - 1), "span": [int(lo), int(hi)]}
        with self.queue.cond:
            self._quarantined.append(rec)
            self._errors.append(e)
            self._busy.discard(sid)
            self._push_remainder_locked(sid, lo, hi)
            # any span still reserved for this lane is up for grabs now
            self.queue._release_preference_locked(sid)
            self.queue.cond.notify_all()
        obs.counter("lane.quarantine").inc()
        obs.counter("lane.rebalance").inc()
        obs.event("lane.quarantine", shard=sid, cause=cause,
                  retries=attempts - 1, lo=lo, hi=hi)
        self._state(sid, "quarantined")

    def _push_remainder_locked(self, sid: int, lo: int, hi: int) -> None:
        self.queue._push_locked(lo, hi, preferred=None)

    # -- the lane loop ------------------------------------------------------

    def _drive(self, idx: int) -> None:
        plan = self.plan
        spec0, values0 = self.lanes[idx]
        sid = spec0.shard_id
        cond = self.queue.cond
        jour = self._journal_by_sid.get(sid)
        self._state(sid, "active")
        while True:
            work = self._next_work(sid)
            if work is None:
                self._state(sid,
                            "done" if self._fatal is None else "stopped")
                return
            lo, hi = work
            try:
                vals = self._values_for(spec0, values0, lo, hi)
            except Exception as e:  # noqa: BLE001 - a restage failure is a
                # lane failure (the device may be gone): quarantine, do not
                # kill the job
                self._quarantine(sid, e, 1, lo, hi)
                return
            failures = 0
            span_hi = hi
            while True:  # attempt loop over this span
                spec = LaneSpec(sid, lo, span_hi, spec0.device)
                if span_hi != hi and not isinstance(
                        vals, (source_mod.SourceLane, RestagedPanel)):
                    # a steal landed during a failed attempt: shrink the
                    # resident values to the kept span so the whole-span
                    # hand-through can never pass extra rows to the fit
                    vals = vals[:span_hi - lo]
                    hi = span_hi
                runner = LaneRunner(plan, spec, self.fit_fn,
                                    self.fit_kwargs, vals, journal=jour,
                                    deadline=self.deadline, tele=self.tele)
                with cond:
                    self._active[sid] = runner
                self._state(sid, "active")
                t0 = time.perf_counter()
                try:
                    result = runner.run()
                except Exception as e:  # noqa: BLE001 - lane containment
                    with cond:
                        self._active.pop(sid, None)
                    # close the failed span to steals BEFORE reading its
                    # end: a thief holding this runner could otherwise
                    # still shrink it after we decide what to retry/
                    # re-enqueue, and the stolen tail would be walked by
                    # both sides (duplicate rows in assembly)
                    span_hi = runner.close_steals()
                    failures += 1
                    if failures <= plan.lane_retries:
                        # concurrent peers retry too: the counter is
                        # cond-guarded like the rest of the supervisor
                        # state (a bare += here dropped increments)
                        with cond:
                            self._retries += 1
                        self._state(sid, "retrying")
                        obs.counter("lane.retry").inc()
                        obs.event("lane.retry", shard=sid, attempt=failures,
                                  lo=lo, hi=span_hi,
                                  error=f"{type(e).__name__}: {e}"[:160])
                        time.sleep(plan.lane_retry_backoff_s
                                   * (2 ** (failures - 1)))
                        continue
                    self._quarantine(sid, e, failures, lo, span_hi)
                    return
                except BaseException as e:  # crash/interrupt: fatal, no
                    # containment — resume semantics must match the static
                    # layout (the journal, not a survivor, is the recovery)
                    with cond:
                        self._active.pop(sid, None)
                        self._busy.discard(sid)
                        if self._fatal is None:
                            self._fatal = e
                        cond.notify_all()
                    raise
                break
            span_wall = time.perf_counter() - t0
            n_comp = 0
            for _plo, _phi, p in result.pieces:
                if isinstance(p, _TimeoutChunk):
                    continue
                pm = getattr(p, "meta", None)
                if isinstance(pm, dict) and pm.get("resumed_from_journal"):
                    continue
                n_comp += 1
            with cond:
                self._active.pop(sid, None)
                self._busy.discard(sid)
                self.results.append(result)
                if n_comp:
                    self._global_walls.append((n_comp, span_wall))
                    prev = self._lane_mean_wall.get(sid)
                    mean = span_wall / n_comp
                    self._lane_mean_wall[sid] = (
                        mean if prev is None else 0.5 * (prev + mean))
                cond.notify_all()
            self._state(sid, "idle")

    # -- entry point --------------------------------------------------------

    def run(self) -> Tuple[list, dict]:
        """Run the elastic walk; returns ``(results, elastic_meta)``.

        Raises the fatal error (crash/interrupt) unchanged, or — when
        every lane was quarantined with spans still unprocessed — the
        FIRST quarantined lane's original error.
        """
        for spec, _vals in self.lanes:
            self.queue.push(spec.lo, spec.hi, preferred=spec.shard_id)
        threads = [
            threading.Thread(target=self._drive_safe, args=(i,), daemon=True,
                             name=f"chunk-lane-{spec.shard_id}")
            for i, (spec, _v) in enumerate(self.lanes)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        if self._fatal is not None:
            raise self._fatal
        undone = self.queue.pending()
        if undone:
            # every lane is gone and work remains: the job is lost — fail
            # with the FIRST lane's original error (invariant 3 of the
            # tentpole), the quarantine record riding its __notes__-free
            # message via the exception chain below
            first = self._errors[0] if self._errors else RuntimeError(
                f"elastic walk stalled with spans pending: {undone}")
            raise first
        with self.queue.cond:
            # every lane joined, but the declared discipline (results is
            # cond-guarded) holds uniformly — uncontended here
            self.results.sort(key=lambda r: r.spec.lo)
        return self.results, self.elastic_meta()

    def _drive_safe(self, idx: int) -> None:
        sid = self.lanes[idx][0].shard_id
        try:
            self._drive(idx)
        except BaseException as e:  # noqa: BLE001 - re-raised after join
            # ANY error escaping the lane loop — including supervisor-level
            # failures outside the runner.run() handlers (LaneRunner
            # construction, the retry-path values slice) — is recorded as
            # fatal and the lane's busy state released, so peers stop
            # polling and the job FAILS LOUDLY instead of hanging with a
            # silently dead lane still marked busy
            with self.queue.cond:
                self._active.pop(sid, None)
                self._busy.discard(sid)
                if self._fatal is None:
                    self._fatal = e
                self.queue.cond.notify_all()

    def elastic_meta(self) -> dict:
        return {
            "quarantined": list(self._quarantined),
            "steals": int(self._steals),
            "lane_retries_used": int(self._retries),
            "reassigned_spans": len(self._quarantined) + int(self._steals),
        }
