"""Device-mesh and sharding utilities — the distributed substrate.

The reference's distribution model is Spark data-parallelism over series keys
(hash-partitioned ``RDD[(K, Vector)]``, SURVEY.md Section 2.4).  The
TPU-native equivalent implemented here: a 1-D ``jax.sharding.Mesh`` with a
``"series"`` axis; the panel's ``[keys, time]`` array is placed with
``NamedSharding(mesh, P("series", None))`` so every chip owns a contiguous
block of whole series (a series is never split across chips — the same
invariant the reference's partitioning guarantees).  Cross-series aggregates
ride ``psum`` over ICI; the ``toInstants`` transpose becomes an XLA
``all_to_all``; a replicated sharding ``P(None, None)`` replaces Spark's
TorrentBroadcast of the shared index (SURVEY.md Section 5.8).

Multi-host: under ``jax.distributed``, the same code runs unchanged — the
mesh spans all processes' devices and XLA routes ICI/DCN collectives.

Sequence-sharding (the optional ``"time"`` axis) is provided for very long
series: reductions over time decompose into per-shard partials + ``psum``,
and scans hand carries across shards via ``ppermute`` (see
``ops/seqparallel.py``).

**Who uses what** (reconciled with the driver, ISSUE 6): two distinct
consumers ride this module.  *SPMD fits* (``panel.fit_*`` over a
mesh-attached panel, ``ops/seqparallel.py``) place ONE global array with
:func:`series_sharding` and let XLA partition one program across the
mesh.  The *durable chunk driver* (``reliability.fit_chunked(shard=True)``
/ ``mesh=``) instead runs one prefetch→compute→commit LANE per
series-axis device: :func:`lane_values` hands each lane its
device-resident block of rows — via a single
``NamedSharding(mesh, P("series", None))`` placement when the lane spans
are the even split, per-device ``device_put`` otherwise — and the lane
spans come from ``reliability.plan.shard_spans``, which partitions the
CHUNK GRID (whole chunks per shard, the same "a series is never split
across chips" invariant, coarsened to chunks) so the sharded walk visits
exactly the single-device walk's chunk boundaries and stays
bitwise-identical to it.  Under ``jax.distributed`` build the global
panel with :func:`distribute_panel`
(``jax.make_array_from_process_local_data``); each process then runs the
lanes of its own addressable shards.

**Elastic lanes** (ISSUE 11): the per-lane placement above is the
STARTING layout, not ownership.  A single-process sharded walk may move
chunks between lanes mid-job — a quarantined lane's uncommitted chunks
and a straggler's stolen tail are re-staged to the computing lane's
device on demand (``reliability.plan.RestagedPanel`` wraps the driver's
resident panel in a ``device_put``-per-chunk view; source-backed lanes
re-stage through ``SourceLane`` exactly as at startup).  Under
``jax.distributed`` rows of another process are not addressable here, so
multi-host walks keep the static layout — re-staging across hosts is the
open ROADMAP item 5 follow-on.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SERIES_AXIS = "series"
TIME_AXIS = "time"


def default_mesh(
    n_devices: Optional[int] = None,
    *,
    time_shards: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a mesh over the available devices.

    1-D ``(series,)`` by default; pass ``time_shards > 1`` for a 2-D
    ``(series, time)`` mesh used by sequence-parallel kernels.
    """
    devs = list(devices) if devices is not None else jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    n = len(devs)
    if time_shards > 1:
        if n % time_shards:
            raise ValueError(f"{n} devices not divisible by time_shards={time_shards}")
        arr = np.asarray(devs).reshape(n // time_shards, time_shards)
        return Mesh(arr, (SERIES_AXIS, TIME_AXIS))
    return Mesh(np.asarray(devs), (SERIES_AXIS,))


def series_sharding(mesh: Mesh) -> NamedSharding:
    """``[keys, time]`` sharded over keys, time replicated (or time-sharded
    on a 2-D mesh)."""
    if TIME_AXIS in mesh.axis_names:
        return NamedSharding(mesh, P(SERIES_AXIS, TIME_AXIS))
    return NamedSharding(mesh, P(SERIES_AXIS, None))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully replicated — the broadcast-index analog."""
    return NamedSharding(mesh, P())


def instant_sharding(mesh: Mesh) -> NamedSharding:
    """``[time, keys]`` sharded over time — the result layout of the
    ``to_instants`` transpose."""
    return NamedSharding(mesh, P(SERIES_AXIS, None))


def pad_to_multiple(n: int, m: int) -> int:
    """Smallest multiple of ``m`` that is >= ``n``."""
    return ((n + m - 1) // m) * m


def shard_series(values: jax.Array, mesh: Optional[Mesh]) -> jax.Array:
    """Place a ``[keys, time]`` array with the series sharding.

    The keys axis must already be padded to a multiple of the mesh's series
    size (``TimeSeriesPanel`` pads with NaN rows at construction).

    The placement is the mesh plane's cross-chip data movement (the analog
    of Spark's shuffle into hash partitions), so it runs under an
    ``obs.span`` (ROADMAP: span coverage for the sharded paths) — free
    no-op when the telemetry plane is disabled.
    """
    if mesh is None:
        return values
    from .. import obs

    with obs.span("mesh.shard_series", keys=int(values.shape[0]),
                  devices=int(np.prod(list(mesh.shape.values())))):
        return jax.device_put(values, series_sharding(mesh))


def series_devices(mesh: Mesh) -> list:
    """The devices along the series axis, in shard order — the lane owners
    of a sharded chunk walk (one lane per entry).

    The sharded DRIVER is 1-D by design: each lane runs a whole fit
    program on one device (time replicated), so a 2-D ``(series, time)``
    mesh — whose time axis belongs to the SPMD sequence-parallel kernels,
    not the chunk walk — is rejected rather than silently collapsed.
    """
    if TIME_AXIS in mesh.axis_names and mesh.shape[TIME_AXIS] > 1:
        raise ValueError(
            "the sharded chunk walk needs a 1-D (series,) mesh; "
            "time-sharding belongs to the SPMD fit path (ops/seqparallel), "
            f"got axes {mesh.axis_names} with shape {dict(mesh.shape)}")
    return list(mesh.devices.flat)


def distribute_panel(local_rows, mesh: Mesh) -> jax.Array:
    """Build the GLOBAL ``[keys, time]`` panel from this process's local
    rows — the multi-host ingest step of a sharded chunk walk.

    Single-process this is just the series-sharded placement; under
    ``jax.distributed`` it is ``jax.make_array_from_process_local_data``:
    every process contributes the rows it holds, and the returned global
    array's addressable shards are exactly the lanes this process will
    run (``reliability.fit_chunked(..., mesh=mesh)``).
    """
    sharding = series_sharding(mesh)
    if jax.process_count() <= 1:
        return jax.device_put(jax.numpy.asarray(local_rows), sharding)
    return jax.make_array_from_process_local_data(
        sharding, np.asarray(local_rows))


def lane_values(values, mesh: Mesh, spans) -> list:
    """Place each lane's row block on its series-axis device.

    ``spans`` is the chunk-grid partition from
    ``reliability.plan.shard_spans`` (ascending, contiguous, covering the
    panel).  Returns ``[(shard_id, lo, hi, device, lane_array), ...]`` for
    the lanes THIS process runs; each ``lane_array`` holds rows
    ``[lo, hi)`` resident on ``device``.

    Placement strategy, in order:

    - ``values`` is already a multi-process global array (built with
      :func:`distribute_panel`): the lanes ARE its addressable shards —
      zero data movement, but the sharding's split must match ``spans``
      (chunk-grid-aligned), else the caller must repartition.
    - the spans are the even split of the panel over all mesh devices
      (the north-star layout): ONE ``NamedSharding`` placement of the
      whole panel, lanes read from its addressable shards.
    - otherwise: one ``device_put`` of each span's slice to its device
      (uneven tails, fewer chunks than devices).

    Either way the lane bytes are exactly ``values[lo:hi]`` — the
    placement moves data, never changes it.
    """
    devs = series_devices(mesh)
    spans = [(int(lo), int(hi)) for lo, hi in spans]
    if len(spans) > len(devs):
        raise ValueError(
            f"{len(spans)} lane spans but only {len(devs)} series devices")
    pidx = jax.process_index()
    out = []
    if isinstance(values, jax.Array) and not values.is_fully_addressable:
        by_row = {}
        for s in values.addressable_shards:
            by_row[int(s.index[0].start or 0)] = s
        claimed = set()
        for i, (lo, hi) in enumerate(spans):
            s = by_row.get(lo)
            if s is None:
                continue  # another process's lane
            if int(s.data.shape[0]) != hi - lo:
                raise ValueError(
                    f"global panel shard at row {lo} holds "
                    f"{int(s.data.shape[0])} rows but the chunk-grid lane "
                    f"wants {hi - lo}; choose chunk_rows so the chunk grid "
                    "matches the even device split (or repartition with "
                    "distribute_panel)")
            claimed.add(lo)
            out.append((i, lo, hi, list(s.data.devices())[0], s.data))
        # a local shard NO span starts at would silently compute nothing —
        # on a process where no shard start hits a span lo, the size check
        # above never fires, so the misalignment must be caught here
        unclaimed = sorted(set(by_row) - claimed)
        if unclaimed:
            raise ValueError(
                f"global panel shards starting at rows {unclaimed} are not "
                "claimed by any chunk-grid lane span; choose chunk_rows so "
                "shard boundaries land on the chunk grid (or repartition "
                "with distribute_panel)")
        return out
    n_rows = int(values.shape[0])
    sizes = {hi - lo for lo, hi in spans}
    even = (len(spans) == len(devs) and len(sizes) == 1
            and n_rows % len(devs) == 0
            and all(d.process_index == pidx for d in devs))
    with obs_span("mesh.shard_lanes", keys=n_rows, lanes=len(spans),
                  devices=len(devs)):
        if even:
            g = jax.device_put(values, series_sharding(mesh))
            shards = sorted(g.addressable_shards,
                            key=lambda s: int(s.index[0].start or 0))
            for i, ((lo, hi), s) in enumerate(zip(spans, shards)):
                out.append((i, lo, hi, list(s.data.devices())[0], s.data))
        else:
            for i, (lo, hi) in enumerate(spans):
                d = devs[i]
                if d.process_index != pidx:
                    continue
                out.append((i, lo, hi, d, jax.device_put(values[lo:hi], d)))
    return out


def obs_span(name, **attrs):
    """Lazy obs import (parallel must stay importable before obs)."""
    from .. import obs

    return obs.span(name, **attrs)


@functools.lru_cache(maxsize=None)
def single_device_mesh() -> Mesh:
    return Mesh(np.asarray(jax.devices()[:1]), (SERIES_AXIS,))


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids: Optional[Sequence[int]] = None,
) -> Mesh:
    """Initialize the multi-host process group and return the global mesh.

    The reference rides Spark's driver/executor runtime for multi-machine
    work (Netty shuffle + TorrentBroadcast — SURVEY.md §5.8); the TPU-native
    equivalent is ``jax.distributed``: one Python process per host, every
    process calls this once before any other jax API, and the returned 1-D
    ``(series,)`` mesh spans ALL processes' devices — panels built with it
    shard over the full slice, with XLA routing collectives over ICI within
    a host's chips and DCN across hosts.

    On Cloud TPU (e.g. a v5e-8 pod slice) every argument is discovered from
    the environment, so the whole recipe is::

        # same script started on every host of the slice, e.g. with
        #   gcloud compute tpus tpu-vm ssh $TPU --worker=all \\
        #     --command="python train.py"
        from spark_timeseries_tpu.parallel import mesh as meshlib
        mesh = meshlib.init_distributed()          # no args on Cloud TPU
        panel = sts.from_observations(..., mesh=mesh)   # sharded ingest
        fit = arima.fit(panel.series_values(), (1, 1, 1))

    Elsewhere (CPU/GPU clusters, tests) pass the coordinator explicitly::

        mesh = meshlib.init_distributed("10.0.0.1:8476", num_processes=2,
                                        process_id=int(os.environ["RANK"]))

    Safe to call when already initialized (returns the mesh without
    re-initializing); single-process callers get the local-devices mesh,
    so code written against this entry point runs unchanged on one chip.
    """
    initialized = jax.distributed.is_initialized()
    explicit = coordinator_address is not None or num_processes is not None
    if not initialized and (explicit or _on_cloud_tpu_pod()):
        kwargs = {}
        if coordinator_address is not None:
            kwargs["coordinator_address"] = coordinator_address
        if num_processes is not None:
            kwargs["num_processes"] = num_processes
        if process_id is not None:
            kwargs["process_id"] = process_id
        if local_device_ids is not None:
            kwargs["local_device_ids"] = list(local_device_ids)
        try:
            jax.distributed.initialize(**kwargs)
        except (ValueError, RuntimeError):
            if explicit:  # the caller described a topology that failed: loud
                raise
            # pod-like env vars without a discoverable coordinator (single
            # host with TPU env leakage): fall back to the local mesh
            import warnings

            warnings.warn(
                "init_distributed: pod-like environment detected but "
                "jax.distributed could not auto-discover a coordinator; "
                "continuing single-process on local devices",
                stacklevel=2,
            )
    return default_mesh()


def _on_cloud_tpu_pod() -> bool:
    """True when MULTI-host TPU slice metadata is present (args
    discoverable).  Single-host TPU VMs set ``TPU_WORKER_HOSTNAMES=localhost``
    — one hostname is not a pod."""
    import os

    hostnames = [h for h in os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",") if h]
    return len(hostnames) > 1 or bool(os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"))
