"""Traffic kind ``walk``: whole journaled ``fit_chunked`` walks over one
panel, back to back — the batch owner's job.

Every walk gets a fresh ``checkpoint_dir`` (journaled, write-ahead,
host-visible when it returns) and every other ``fit_chunked`` argument stays
at its default, except what the configuration fixes (``chunk_rows``) and
what the mix asks for (``sharded``, ``residency``).  A walk that has started
is finished, so the window may overrun ``--seconds`` by at most one walk.

``series_per_s_chip`` is the converged, committed rows of a walk over the
MEDIAN walk period (one walk's start to the next one's), per chip: the
median of some tens of readings inside the run, not rows over the window's
wall.  On a one-chip machine, which shares its host's cores and journals
through a sandboxed filesystem, 5 of 336 walks stalled for 0.8 to 5.4 s
(my chip run 4, PR 23); one such stall moves rows-over-wall by 3 to 18% and
the median not at all.  The stalls are not hidden: ``walk_stall_share`` is
the share of the window they took.
"""

from __future__ import annotations

import contextlib
import importlib
import json
import os
import shutil
import time

import numpy as np

from benchmark import generators
from benchmark.reference import check as refcheck

BAD_STATUS = ("DIVERGED", "EXCLUDED", "TIMEOUT")
WALK_SPAN = "bench.walk"


def model_fit(config: dict):
    """``(fit function, keyword arguments)`` a configuration names."""
    module, _, attr = config["model"]["fit"].partition(":")
    kwargs = {k: tuple(v) if isinstance(v, list) else v
              for k, v in config["model"].get("kwargs", {}).items()}
    return getattr(importlib.import_module(module), attr), kwargs


def make_panel(run):
    """The configuration's panel under the mix's departures, on the cell's
    devices, from ``--seed``."""
    cfg = run.cell.config
    process = run.cell.plugin("processes", cfg["process"]["name"])
    return generators.build_panel(
        process.rows, cfg["process"], run.cell.traffic, run.seed,
        run.devices, int(cfg["rows"]), int(cfg["n_time"]),
        int(cfg["chunk_rows"]), int(cfg.get("population_seed", 0)))


def _chunk_walls(journal_dir: str) -> dict:
    """Per lane, the journal's own per-chunk host walls in row order."""
    with open(os.path.join(journal_dir, "manifest.json"),
              encoding="utf-8") as f:
        chunks = json.load(f)["chunks"]
    lanes = {}
    for c in sorted(chunks, key=lambda c: c["lo"]):
        lanes.setdefault(str(c.get("owner", c.get("shard_id", 0))),
                         []).append(float(c["wall_s"]))
    return lanes


def _record(res, journal_dir: str, wall_s: float, n_chunks: int) -> dict:
    """What one finished walk leaves for the metrics and for ``correct``
    (cheap: it runs between two walks of the window)."""
    meta = res.meta
    counts = meta["status_counts"]
    rows = int(res.converged.shape[0])
    return {
        "wall_s": wall_s, "rows": rows, "n_chunks": n_chunks,
        # not converged, or ended DIVERGED / EXCLUDED / TIMEOUT
        "rows_failed": max(rows - int(np.count_nonzero(res.converged)),
                           sum(counts[s] for s in BAD_STATUS)),
        "status_counts": counts,
        "ladder_totals": meta.get("ladder_totals"),
        "pipeline": {k: v for k, v in (meta.get("pipeline") or {}).items()
                     if k != "shards"},
        "oom_backoffs": meta["oom_backoffs"],
        "journal_dir": journal_dir,
        # computed by THIS walk at the configured size, nothing backed off
        # or timed out; _read_journals adds "every chunk committed"
        "sound": bool(
            (meta.get("journal") or {}).get("chunks_resumed", 0) == 0
            and meta["oom_backoffs"] == 0 and not meta["degraded"]
            and meta["chunk_rows_final"] == meta["chunk_rows_initial"]),
    }


def _read_journals(walks: list, keep=None) -> None:
    """After the window: each walk's per-chunk walls from its manifest,
    then the journal goes (but ``keep``, which the resume check re-reads)."""
    for w in walks:
        w["chunk_walls"] = _chunk_walls(w["journal_dir"])
        w["sound"] &= sum(len(v) for v in w["chunk_walls"].values()) \
            == w["n_chunks"]
        if w["journal_dir"] != keep:
            shutil.rmtree(w["journal_dir"], ignore_errors=True)


def setup(run) -> dict:
    from spark_timeseries_tpu import reliability as rel

    cfg, mix = run.cell.config, run.cell.traffic
    fit, fit_kwargs = model_fit(cfg)
    panel = make_panel(run)
    panel_s = run.since_device_s()
    run.log("panel", shape=list(panel.shape), dtype=str(panel.dtype),
            sharding=str(panel.sharding), since_device_s=panel_s)
    target = panel
    if mix.get("residency", "device") == "host":
        panel = np.asarray(panel)  # the device copy is dropped
        target = rel.HostChunkSource(panel)
    walk_kwargs = dict(chunk_rows=int(cfg["chunk_rows"]), **fit_kwargs)
    if mix.get("sharded"):
        walk_kwargs["shard"] = True

    def walk(journal_dir):
        return rel.fit_chunked(fit, target, checkpoint_dir=journal_dir,
                               **walk_kwargs)

    n_chunks = int(cfg["rows"]) // int(cfg["chunk_rows"])
    state = {"walk": walk, "panel": panel, "n_chunks": n_chunks}
    # warm-up: one whole walk compiles (or loads) every program this panel
    # needs — the chunk shape, the ladder's buckets, the commit path
    warm_dir = os.path.join(run.work_dir, "warm")
    t0 = time.perf_counter()
    res = walk(warm_dir)
    warm = _record(res, warm_dir, time.perf_counter() - t0, n_chunks)
    _read_journals([warm])
    # setup_s in three stretches, one reader each (layer_metrics/setup_*):
    # the device mark to the panel ready, the warm-up walk's first chunk
    # (the slowest lane's), the rest of that walk
    first = max(lane[0] for lane in warm["chunk_walls"].values())
    state.update(setup_panel_s=panel_s, setup_first_chunk_s=first,
                 setup_warm_walk_rest_s=warm["wall_s"] - first)
    run.log("warmup_walk", wall_s=warm["wall_s"],
            chunk_walls=warm["chunk_walls"],
            status_counts=warm["status_counts"],
            since_device_s=run.since_device_s())
    return state


def measure(run, state: dict) -> dict:
    mix = run.cell.traffic
    walks, last, journal_dir, starts = [], None, None, []
    trace_s = float(mix.get("trace_s", 4.0))
    traced = []  # indices of the walks inside the traced window
    t_start = t_trace = t1 = time.perf_counter()
    while True:
        i = len(walks)
        # the first walk settles the window; the trace opens after it and
        # closes at the first walk boundary past trace_s
        if run.tracer and i == 1:
            run.tracer.start()
            t_trace = time.perf_counter()
        journal_dir = os.path.join(run.work_dir, f"walk-{i:04d}")
        t0 = time.perf_counter()
        starts.append(t0)
        # the benchmark's own span: an idle gap inside a walk and outside
        # every chunk is the walk's fixed cost
        with run.tracer.span(WALK_SPAN) if run.tracer \
                else contextlib.nullcontext():
            res = state["walk"](journal_dir)
        t1 = time.perf_counter()
        if run.tracer and run.tracer.running:
            traced.append(i)
            if t1 - t_trace >= trace_s:
                run.tracer.stop()
        walks.append(_record(res, journal_dir, t1 - t0, state["n_chunks"]))
        last = (res, journal_dir)
        tracing_done = not run.tracer or (traced and not run.tracer.running)
        if t1 - t_start >= run.seconds and tracing_done:
            break
    wall_s = t1 - t_start  # first walk's start to last walk's return
    _read_journals(walks, keep=journal_dir)
    rows = sum(w["rows"] for w in walks)
    failed = sum(w["rows_failed"] for w in walks)
    # a walk's period: its start to the next walk's start (to its own
    # return for the last), so what lies between two walks counts; but not
    # the two periods that hold the profiler's start and its stop
    periods = np.diff(starts + [t1])
    if traced:
        periods = np.delete(periods, [0, traced[-1]])
    period = float(np.median(periods))
    run.log("window", walks=len(walks), traced_walks=traced,
            walk_period_p50_s=period, rows_over_wall=rows / wall_s,
            walk_walls_s=[round(w["wall_s"], 4) for w in walks],
            last_chunk_walls=walks[-1]["chunk_walls"],
            last_status_counts=walks[-1]["status_counts"],
            last_pipeline=walks[-1]["pipeline"])
    return {
        "attempted": rows, "failed": failed,
        "values": {"series_per_s_chip":
                   (rows - failed) / len(walks) / period / len(run.devices)},
        "walks": walks, "traced_walks": traced, "window_wall_s": wall_s,
        "walk_periods_s": periods, "last": last,
    }


def check(run, state: dict, result: dict) -> dict:
    """The parts of ``correct`` this kind owns (``run.py`` adds the device
    and the compile count)."""
    cfg = run.cell.config
    res, journal_dir = result["last"]
    flags = {"walks_sound": run.compare(
        "walks_unsound", sum(not w["sound"] for w in result["walks"]),
        "==", 0)}

    again = state["walk"](journal_dir)  # re-read from its journal
    reread = run.compare("resume_chunks_reread", int(
        again.meta["journal"].get("chunks_resumed") or 0), "==",
        state["n_chunks"])
    flags["resume_bitwise"] = run.compare("resume_arrays_differing", sum(
        not np.array_equal(np.asarray(a), np.asarray(b), equal_nan=True)
        for a, b in zip(res[:-1], again[:-1])), "==", 0) and reread
    shutil.rmtree(journal_dir, ignore_errors=True)

    ref = cfg["reference"]
    rng = np.random.default_rng([run.seed, 0xC0DE])
    idx = np.sort(rng.choice(int(cfg["rows"]), int(ref["rows"]),
                             replace=False))
    sample = np.asarray(state["panel"][idx])
    gaps = refcheck.loglik_gaps(
        run.cell.plugin("reference", ref["module"]),
        cfg["model"].get("kwargs", {}), sample, np.asarray(res.params)[idx])
    ref_flags, ref_log = against_reference(run, gaps, res.params)
    flags.update(ref_flags)
    run.log("check", **flags, reference_gap_median=float(np.median(gaps)),
            **ref_log)
    return flags


def against_reference(run, gaps, params) -> tuple:
    """``(flags, what the check line says)``: the ``reference`` and
    ``recovery`` parts of ``correct`` from the sampled rows' gaps and the
    fitted parameters, each number beside the configuration's limit."""
    cfg = run.cell.config
    ref = cfg["reference"]
    ok_share = float(np.mean(gaps <= float(ref["loglik_gap_max"])))
    rec = refcheck.recovery(params, cfg.get("recovery", []))
    flags = {
        "reference": run.compare(
            f"reference_share_within_gap_{ref['loglik_gap_max']}", ok_share,
            ">=", float(ref.get("min_share", 1.0))),
        "recovery": all([run.compare(  # a list: every entry is compared
            f"recovery_{r['name']}_abs_err", abs(r["median"] - r["value"]),
            "<=", r["tol"]) for r in rec])}
    return flags, {"reference_gap_max": float(np.max(gaps)),
                   "reference_ok_share": ok_share, "recovered": rec}
