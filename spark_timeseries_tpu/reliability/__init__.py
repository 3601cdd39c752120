"""Resilient fit execution (L4.5): the batch analog of Spark task retry.

The reference inherited robustness from its substrate — a NaN-poisoned or
OOM-killed executor task was re-run elsewhere by Spark.  The TPU rebuild's
substrate is one monolithic vmapped program, so this package rebuilds the
same guarantees at row granularity:

- :mod:`.status` — the per-row :class:`FitStatus` vocabulary every public
  ``fit`` now reports.
- :mod:`.sanitize` — input repair/rejection (NaN/Inf/constant/all-NaN)
  with an impute / exclude / raise policy.
- :mod:`.runner` — :func:`resilient_fit`: sanitize, fit, then a retry ->
  fallback ladder over the failed subset (perturbed inits, portable
  backend) before any row is marked ``DIVERGED``.
- :mod:`.chunked` — :func:`fit_chunked`: chunked execution with bounded
  ``RESOURCE_EXHAUSTED`` backoff and degradation recorded in metadata.
- :mod:`.plan` — :class:`ExecutionPlan` / :class:`LaneRunner`: the walk's
  configuration as data (spans, lanes, budgets) and the per-lane
  scheduler that owns one prefetch → compute → commit pipeline; the
  serial, pipelined, and mesh-sharded walks are all the same plan with
  one-vs-many lanes (``fit_chunked(shard=True)`` runs one lane per mesh
  device, bitwise-identical to the single-device walk).  Sharded walks
  are ELASTIC (:class:`~.plan.LaneSupervisor` + :class:`~.plan.WorkQueue`):
  a failing lane is retried then quarantined — survivors adopt its
  committed chunks and recompute the rest — and idle lanes steal
  grid-aligned spans from stragglers, still bitwise-identical to the
  uninterrupted single-device walk.
- :mod:`.committer` — :class:`ChunkCommitter`: the pipelined driver's
  bounded background commit thread — journal commits and host I/O overlap
  the next chunk's device compute while preserving the journal's
  single-writer, in-order commit protocol.
- :mod:`.prefetcher` — :class:`ChunkPrefetcher`: the input half of the
  pipeline — a bounded background stager that materializes chunk N+1's
  device slice while chunk N computes (stage ∥ compute ∥ commit), with
  driver-controlled invalidation on OOM backoff and rollback; and the
  owner of the ONE fit a lane keeps in flight ahead of its walk (chunk
  N+1's probe and programs queued behind chunk N's while the driver reads
  chunk N back; taken at its turn if the walk's decision is the
  prediction, dropped otherwise).
- :mod:`.source` — :class:`ChunkSource`: where the panel's rows live —
  device array (today's path), host ``np.ndarray``, or an npz shard
  directory — so ``fit_chunked(fit_fn, as_source(...))`` walks panels
  that NEVER fully reside on device: chunks are staged H2D through a
  pool of reusable host buffers and donated back to the allocator as the
  walk passes, bounding steady-state device footprint at O(chunk).
- :mod:`.journal` — :class:`ChunkJournal`: write-ahead per-chunk npz
  shards + an atomic JSON manifest, so a journaled multi-chunk fit
  (``fit_chunked(..., checkpoint_dir=...)``) survives process death and
  resumes bitwise-identical, skipping committed chunks.
- :mod:`.watchdog` — wall-clock deadlines for fit dispatch: overrunning
  chunks are flagged ``TIMEOUT`` and the job degrades gracefully instead
  of hanging past its SLO.
- :mod:`.faultinject` — deterministic data, behavioral, and process
  faults (forced non-convergence, simulated OOM, SIGKILL-after-commit,
  torn manifests, disk EIO/ENOSPC/torn-write schedules) so every
  recovery path runs in tier-1 CPU tests.
- :mod:`.chaos` — seeded chaos scenarios (ISSUE 17): timed schedules
  composing the fault primitives against a live fleet, the invariant
  checker (conservation, bitwise re-answers, monotonic fencing, bounded
  unavailability), and the durable ``chaos_manifest.json`` record.
"""

from . import (chaos, chunked, committer, delta, faultinject, journal, plan,
               prefetcher, runner, sanitize, source, status, watchdog)
from .chaos import (ChaosEvent, ChaosRunner, InvariantViolation,
                    chaos_schedule, check_invariants, load_chaos_manifest,
                    unavailability_windows, write_chaos_manifest)
from .chunked import OOMBackoffExceeded, fit_chunked, is_resource_exhausted
from .delta import (DeltaError, DeltaPlan, StalePriorError, WarmstartFit,
                    plan_delta)
from .committer import ChunkCommitter, CommitterStats
from .plan import (ExecutionPlan, LaneRunner, LaneSpec, LaneSupervisor,
                   RestagedPanel, WorkQueue, shard_spans)
from .prefetcher import ChunkPrefetcher, PrefetchStats
from .journal import (ChunkJournal, FencedError, JournalError, Lease,
                      LeaseError, MergeWarmer, ShardJournalView,
                      StaleJournalError, TornManifestError, acquire_lease,
                      config_hash, merge_job_manifest, panel_fingerprint,
                      read_lease)
from .source import (ChunkSource, DeviceChunkSource, HostChunkSource,
                     NpzShardSource, SourceError, StagingPool, as_source,
                     write_npz_shards)
from .runner import (ResilientFitResult, RetryRung, default_ladder,
                     resilient_fit)
from .sanitize import SanitizeReport, sanitize
from .status import FitStatus, merge_status, status_counts
from .watchdog import Deadline, DeadlineExceeded, call_with_deadline

__all__ = [
    "ChaosEvent",
    "ChaosRunner",
    "ChunkCommitter",
    "ChunkJournal",
    "ChunkPrefetcher",
    "ChunkSource",
    "CommitterStats",
    "DeviceChunkSource",
    "HostChunkSource",
    "MergeWarmer",
    "NpzShardSource",
    "PrefetchStats",
    "SourceError",
    "StagingPool",
    "as_source",
    "write_npz_shards",
    "Deadline",
    "DeadlineExceeded",
    "ExecutionPlan",
    "FencedError",
    "FitStatus",
    "JournalError",
    "Lease",
    "LeaseError",
    "acquire_lease",
    "read_lease",
    "LaneRunner",
    "LaneSpec",
    "LaneSupervisor",
    "OOMBackoffExceeded",
    "RestagedPanel",
    "ShardJournalView",
    "WorkQueue",
    "ResilientFitResult",
    "RetryRung",
    "SanitizeReport",
    "StaleJournalError",
    "TornManifestError",
    "DeltaError",
    "DeltaPlan",
    "InvariantViolation",
    "StalePriorError",
    "WarmstartFit",
    "call_with_deadline",
    "chaos",
    "chaos_schedule",
    "check_invariants",
    "chunked",
    "committer",
    "config_hash",
    "default_ladder",
    "delta",
    "plan_delta",
    "faultinject",
    "fit_chunked",
    "is_resource_exhausted",
    "journal",
    "load_chaos_manifest",
    "merge_job_manifest",
    "merge_status",
    "panel_fingerprint",
    "plan",
    "prefetcher",
    "shard_spans",
    "resilient_fit",
    "runner",
    "sanitize",
    "source",
    "status",
    "status_counts",
    "unavailability_windows",
    "watchdog",
    "write_chaos_manifest",
]
