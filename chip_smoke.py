#!/usr/bin/env python3
"""Chip smoke: the journaled ARIMA walk and the fit server, once, on the TPU.

The quickest proof that the system still starts on the chip.  ONE process —
the first and only one to touch JAX — drives the main path through the
entry points a user calls, at the full width of BASELINE's model
(ARIMA(1,1,1) over a ``[1,048,576, 1000]`` f32 panel in 131,072-row
chunks), and exits non-zero at the first thing that is not true:

  device gate -> backend gate -> Pallas/scan parity on the device ->
  journaled walk -> resume (bitwise) -> forecast -> fit server
  [-> ``--chips 4``: series-sharded walk + time-sharded fits]

Each leg prints one JSON line carrying the device identity and a smoke wall
(compile included, cold unless the compile cache already holds entries —
never a benchmark metric).  The last stdout line
of a passing run is ``{"ok": true, "device": {...}}``.  With no accelerator
the device gate fails in seconds and no result line is printed.

  python chip_smoke.py                 # one chip
  python chip_smoke.py --chips 4       # one four-chip host
  python chip_smoke.py --rehearse      # tiny sizes, CPU allowed: control
                                       # flow only, every line stamped so
"""

import argparse
import functools
import importlib.metadata
import json
import os
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

ORDER = (1, 1, 1)
PHI, THETA = 0.6, 0.3  # the generating process; the fit must recover them
FULL = dict(chunk_rows=131_072, n_chunks=8, n_time=1000, cell_rows=8192)
TINY = dict(chunk_rows=128, n_chunks=8, n_time=256, cell_rows=128)
HORIZON = 24


class SmokeFailure(RuntimeError):
    pass


def require(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def cache_entries(cache_dir):
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def libtpu_version():
    try:
        return importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        return None


def keep_manifest(journal_dir, out_path):
    """Copy the job manifest out and return its per-chunk host walls (the
    journal's own ``wall_s``: a lane's first chunk carries its compile)."""
    shutil.copy(os.path.join(journal_dir, "manifest.json"), out_path)
    with open(out_path) as f:
        chunks = json.load(f)["chunks"]
    lanes = {}
    for c in sorted(chunks, key=lambda c: c["lo"]):
        lanes.setdefault(c.get("owner", 0), []).append(c["wall_s"])
    return lanes


def make_panel(chunk_rows, n_chunks, n_time, seed=0):
    """Integrated ARMA(1,1) panel generated ON the device from a seed, chunk
    by chunk with donated placement (a concatenate would transiently hold
    the parts and the output)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gen_chunk(key):
        e = jax.random.normal(key, (chunk_rows, n_time), jnp.float32)

        def step(carry, e_t):
            y_prev, e_prev = carry
            y_t = PHI * y_prev + e_t + THETA * e_prev
            return (y_t, e_t), y_t

        _, y = jax.lax.scan(step, (e[:, 0], e[:, 0]), e[:, 1:].T)
        y = jnp.concatenate([e[:, :1], y.T], axis=1)
        return jnp.cumsum(y, axis=1)  # d=1 integration

    @functools.partial(jax.jit, donate_argnums=(0,))
    def place(panel, chunk, row0):
        return jax.lax.dynamic_update_slice(panel, chunk, (row0, 0))

    panel = jnp.zeros((chunk_rows * n_chunks, n_time), jnp.float32)
    keys = jax.random.split(jax.random.key(seed), n_chunks)
    for i in range(n_chunks):
        panel = place(panel, gen_chunk(keys[i]), jnp.int32(i * chunk_rows))
    return jax.block_until_ready(panel)


def check_recovery(params, what):
    """Median fitted (phi, theta) within 0.05 of the generating values —
    right, not merely finite.  Layout: ``[c, phi, theta]``."""
    med = np.nanmedian(np.asarray(params, np.float64), axis=0)
    phi, theta = float(med[1]), float(med[2])
    require(abs(phi - PHI) < 0.05 and abs(theta - THETA) < 0.05,
            f"{what}: median (phi, theta) = ({phi:.4f}, {theta:.4f}), "
            f"expected ({PHI}, {THETA}) +- 0.05")
    return [round(phi, 4), round(theta, 4)]


def results_equal(a, b):
    """Bitwise over every result array (the trailing field is ``meta``)."""
    return all(np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
               for x, y in zip(a[:-1], b[:-1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: also run the series-sharded walk and the "
                         "time-sharded fits; fails with fewer than four TPUs")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, CPU allowed, no chip assertion: every "
                         "line is stamped \"rehearsal\": true")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out",
                                                  "chip_smoke"),
                    help="where the leg lines and manifests are kept")
    args = ap.parse_args(argv)
    size = TINY if args.rehearse else FULL
    chunk_rows, n_chunks = size["chunk_rows"], size["n_chunks"]
    n_time, cell_rows = size["n_time"], size["cell_rows"]
    n_rows = chunk_rows * n_chunks

    # the package first: alone in a directory this script fails right here
    from spark_timeseries_tpu.utils import compile_cache

    cache_dir = compile_cache.configure()  # before the first backend use

    import jax
    import jax.numpy as jnp
    import jaxlib

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    os.makedirs(args.out, exist_ok=True)
    log_path = os.path.join(args.out, "chip_smoke.jsonl")
    open(log_path, "w").close()
    t_leg = time.perf_counter()
    entries0 = cache_entries(cache_dir)
    wall_kind = ("cold, compile included" if entries0 == 0 else
                 f"compile included, {entries0} cache entries at start")

    def leg(name, **fields):
        """One short JSON line per leg: device identity + a SMOKE wall
        (compile included) — never a BASELINE metric name."""
        nonlocal t_leg
        now = time.perf_counter()
        line = {"leg": name, "ok": True, "device": device,
                "smoke_wall_s": round(now - t_leg, 2),
                "wall_kind": wall_kind, "claim": None,
                **fields}
        if args.rehearse:
            line["rehearsal"] = True
        t_leg = now
        text = json.dumps(line)
        print(text, flush=True)
        with open(log_path, "a") as f:
            f.write(text + "\n")

    # -- device gate: before any compile ------------------------------------
    gate = dict(jax=jax.__version__, jaxlib=jaxlib.__version__,
                libtpu=libtpu_version(), cache_dir=cache_dir,
                cache_entries=entries0)
    if not args.rehearse:
        bad = sorted({d.platform for d in devs if d.platform != "tpu"})
        if bad:
            print(f"chip_smoke: device gate FAILED: jax.devices() reports "
                  f"platform {bad} ({len(devs)} x {devs[0].device_kind}); "
                  f"this check needs a TPU and takes no CPU path "
                  f"[{json.dumps(gate)}]", file=sys.stderr)
            return 1
    require(len(devs) >= args.chips,
            f"--chips {args.chips} needs {args.chips} devices, "
            f"jax.devices() has {len(devs)}")
    leg("device_gate", **gate)

    from spark_timeseries_tpu import reliability as rel
    from spark_timeseries_tpu import serving
    from spark_timeseries_tpu.models import arima
    from spark_timeseries_tpu.models.base import resolve_backend
    from spark_timeseries_tpu.obs.memory import peak_memory
    from spark_timeseries_tpu.ops import pallas_kernels as pk

    # -- backend gate: the default path resolves to the fused kernels -------
    backend = resolve_backend("auto", jnp.float32, n_time - 1,
                              pk.css_structural_ok(1, 1))
    require(args.rehearse or backend == "pallas",
            f"resolve_backend('auto') picked {backend!r}, not the fused "
            "Pallas kernels")
    leg("backend_gate", backend=backend)

    # -- kernel parity on the device: raised, not caught --------------------
    import bench

    parity = bench.check_backend_parity(jnp, not args.rehearse)
    leg("parity", checked=parity["checked"],
        **{k: round(v, 6) for k, v in parity.items()
           if isinstance(v, float)})

    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        # -- the walk, at the full width of BASELINE's model ----------------
        panel = make_panel(chunk_rows, n_chunks, n_time)
        leg("panel", shape=list(panel.shape), dtype=str(panel.dtype),
            generated="on device from seed 0")

        jdir = os.path.join(work, "journal")

        def walk(**kw):
            return rel.fit_chunked(arima.fit, panel, chunk_rows=chunk_rows,
                                   order=ORDER, **kw)

        res = walk(checkpoint_dir=jdir)
        meta = res.meta
        counts = meta["status_counts"]
        mem = peak_memory()
        require(meta["journal"]["chunks_committed"] == n_chunks
                and meta["journal"]["chunks_resumed"] == 0,
                f"walk journal: {meta['journal']}")
        require(meta["oom_backoffs"] == 0
                and meta["chunk_rows_final"] == chunk_rows
                and not meta["degraded"],
                f"walk degraded: oom_backoffs={meta['oom_backoffs']} "
                f"chunk_rows_final={meta['chunk_rows_final']} "
                f"events={meta['oom_events']} {meta['timeout_events']}")
        require(counts["OK"] >= 0.99 * n_rows, f"walk status: {counts}")
        require(args.rehearse or mem.source == "device",
                f"peak memory came from {mem.source!r}, not the device")
        chunk_walls = keep_manifest(
            jdir, os.path.join(args.out, "walk_manifest.json"))[0]
        leg("walk", rows=n_rows, chunk_rows_final=meta["chunk_rows_final"],
            chunk_walls_s=chunk_walls,
            chunks_committed=meta["journal"]["chunks_committed"],
            oom_backoffs=meta["oom_backoffs"], status_counts=counts,
            ladder_totals=meta.get("ladder_totals"),
            align_mode=meta.get("align_mode"),
            median_phi_theta=check_recovery(res.params, "walk"),
            peak_memory={"bytes": mem.bytes, "source": mem.source})

        # -- resume: the committed, host-visible result, re-read ------------
        res2 = walk(checkpoint_dir=jdir)
        require(res2.meta["journal"]["chunks_resumed"] == n_chunks,
                f"resume journal: {res2.meta['journal']}")
        require(results_equal(res, res2),
                "resumed result is not bitwise-equal to the walk's")
        leg("resume", chunks_resumed=res2.meta["journal"]["chunks_resumed"],
            bitwise_equal=True)

        # -- forecast on one chunk (the "tail" kernel) -----------------------
        fc = np.asarray(arima.forecast(
            jnp.asarray(res.params[:chunk_rows]), panel[:chunk_rows], ORDER,
            HORIZON))
        conv = np.asarray(res.converged[:chunk_rows])
        require(fc.shape == (chunk_rows, HORIZON), f"forecast {fc.shape}")
        require(bool(np.isfinite(fc[conv]).all()),
                "non-finite forecast on converged rows")
        leg("forecast", shape=list(fc.shape), rows_converged=int(conv.sum()))

        # -- the server: four tenants, one cell-sized request each -----------
        reqs = [np.asarray(panel[i * cell_rows:(i + 1) * cell_rows])
                for i in range(4)]
        srv = serving.FitServer(os.path.join(work, "server"),
                                cell_rows=cell_rows,
                                max_batch_rows=4 * cell_rows)
        srv.start()
        try:
            with ThreadPoolExecutor(4) as pool:
                futures = [
                    pool.submit(lambda i=i: srv.submit(
                        f"tenant-{i}", reqs[i], "arima",
                        order=ORDER).result(timeout=900))
                    for i in range(4)]
                answers = [f.result(timeout=960) for f in futures]
            counters = srv.health()["counters"]
        finally:
            srv.stop()
        require(srv.state() == "stopped", f"server state {srv.state()!r}")
        oks = [float(np.mean(a.status == rel.FitStatus.OK)) for a in answers]
        require(min(oks) >= 0.99, f"server OK fractions {oks}")
        require(counters["completed"] == 4 and counters["batches_run"] >= 1
                and counters["rows_fitted"] == 4 * cell_rows
                and counters["batch_failures"] == 0,
                f"server counters {counters}")
        served = np.concatenate([a.params for a in answers])
        leg("server", requests=4, rows_per_request=cell_rows,
            ok_fraction_min=round(min(oks), 4),
            batches_run=counters["batches_run"],
            rows_fitted=counters["rows_fitted"],
            median_phi_theta=check_recovery(served, "server"),
            max_abs_diff_vs_walk=float(np.nanmax(np.abs(
                served - res.params[:4 * cell_rows]))))

        # -- four chips: series-sharded walk + time-sharded fits -------------
        if args.chips == 4:
            sdir = os.path.join(work, "journal_sharded")
            rsh = walk(checkpoint_dir=sdir, shard=True)
            shards = rsh.meta["shards"]
            lane_devs = shards["devices"]
            n_lanes = min(len(devs), n_chunks)  # shard=True: every device
            require(len(set(lane_devs)) == n_lanes
                    and set(lane_devs) <= {str(d) for d in devs},
                    f"sharded walk lanes ran on {lane_devs}")
            require(shards["lanes_run"] == n_lanes
                    and rsh.meta["journal"]["merged_shards"] == n_lanes,
                    f"sharded walk: lanes_run={shards['lanes_run']} "
                    f"journal={rsh.meta['journal']}")
            require(rsh.meta["oom_backoffs"] == 0
                    and rsh.meta["chunk_rows_final"] == chunk_rows,
                    f"sharded walk backed off: {rsh.meta['oom_events']}")
            require(np.array_equal(np.asarray(rsh.params),
                                   np.asarray(res.params), equal_nan=True),
                    "sharded walk params differ from the one-chip walk's")
            lane_walls = keep_manifest(
                sdir, os.path.join(args.out, "sharded_manifest.json"))
            leg("sharded_walk", devices=lane_devs,
                lane_chunk_walls_s=lane_walls,
                lanes_run=shards["lanes_run"],
                merged_shards=rsh.meta["journal"]["merged_shards"],
                elastic=shards.get("elastic"),
                status_counts=rsh.meta["status_counts"],
                bitwise_equal_one_chip=True)

            import __graft_entry__ as graft

            graft.dryrun_time_sharded(devs[:4])
            leg("time_sharded", mesh="2x2 (series, time)",
                ran="sp_autocorr/cumsum/fill chain + sp_ewma/arima/garch/"
                    "argarch fits, finite")
    finally:
        shutil.rmtree(work, ignore_errors=True)

    leg("done", cache_dir=cache_dir, cache_entries=cache_entries(cache_dir))
    if args.rehearse:
        print(json.dumps({"ok": True, "rehearsal": True, "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
