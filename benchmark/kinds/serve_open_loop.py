"""Traffic kind ``serve-open-loop``: tenants send fit requests to a resident
``FitServer`` on a fixed schedule, whether or not it keeps up — the service
owner's traffic.

The schedule (arrival times, rows per request, tenants) is drawn from
``--seed`` by ``generators.request_schedule``; a request's rows are a slice
of the configuration's panel, fetched to the host during set-up, so what the
server receives are host ``numpy`` arrays.  One thread submits at the due
times; one collects, blocked in ``ticket.result()`` on the oldest open
request (no polling: a thread that wakes a thousand times a second takes the
interpreter lock from the server it is measuring).  That stamps a result
when it and every request admitted before it are done — exact while results
come in admission order, which one batch key guarantees; a mix whose
requests differ in model or fit arguments reads an upper bound.  Latency
runs from the time a request was DUE, so a stall is charged to every request
it delays.  The server is built with the configuration's ``server``
arguments over ``FitServer``'s own defaults, on a fresh root.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from benchmark import generators
from benchmark.kinds import walk as walk_kind
from benchmark.reference import check as refcheck


def _server_kwargs(run) -> dict:
    return {**run.cell.config.get("server", {}),
            **run.cell.traffic.get("server", {})}


def setup(run) -> dict:
    from spark_timeseries_tpu import serving

    cfg, mix = run.cell.config, run.cell.traffic
    panel = walk_kind.make_panel(run)
    pool_rows = int(mix["pool_rows"])
    pool = np.ascontiguousarray(np.asarray(panel[:pool_rows]))
    run.log("panel", shape=list(panel.shape), pool_rows=pool_rows)
    server = serving.FitServer(os.path.join(run.work_dir, "server"),
                               **_server_kwargs(run))
    server.start()
    state = {"server": server, "panel": panel, "pool": pool,
             "model": mix.get("model") or cfg["model"]["server_name"],
             "fit_kwargs": {**cfg["model"].get("kwargs", {}),
                            **mix.get("fit_kwargs", {})}}
    # warm-up: one batch per request shape the mix can produce.  Every cell
    # of the batch grid has the same shape, so a full batch and a request
    # smaller than one cell cover the mix.
    t0 = time.perf_counter()
    tickets = [server.submit("warmup", pool[:int(n)], state["model"],
                             request_id=f"warm-{run.seed}-{i}",
                             **state["fit_kwargs"])
               for i, n in enumerate(mix["warmup_rows"])]
    for t in tickets:
        t.result(timeout=1100)
    state["knobs_after_warmup"] = dict(server.health()["knobs"])
    run.log("warmup_batches", wall_s=time.perf_counter() - t0,
            knobs=state["knobs_after_warmup"])
    return state


def measure(run, state: dict) -> dict:
    mix, server, pool = run.cell.traffic, state["server"], state["pool"]
    sched = generators.request_schedule(mix, run.seed, run.seconds,
                                        pool.shape[0])
    n = len(sched["due_s"])
    submitted = np.full(n, np.nan)
    done = np.full(n, np.nan)
    tickets = [None] * n
    refused = np.zeros(n, bool)
    errors = []
    counters0 = dict(server.health()["counters"])
    submitted_one = threading.Event()
    t_start = time.perf_counter()

    def generate():
        for i in range(n):
            wait = t_start + sched["due_s"][i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            lo = int(sched["offset"][i])
            submitted[i] = time.perf_counter() - t_start
            try:
                tickets[i] = server.submit(
                    f"tenant-{int(sched['tenant'][i])}",
                    pool[lo:lo + int(sched["rows"][i])], state["model"],
                    request_id=f"s{run.seed}-{i:06d}", **state["fit_kwargs"])
            except Exception as e:  # noqa: BLE001 - refused or closed: a
                refused[i] = True   # failed request; the schedule goes on
                errors.append(repr(e)[:200])
            submitted_one.set()

    deadline = t_start + run.seconds + float(mix.get("drain_s", 30.0))

    def collect():
        for i in range(n):
            while tickets[i] is None and not refused[i]:
                if time.perf_counter() > deadline:
                    return
                submitted_one.wait(0.05)
                submitted_one.clear()
            if refused[i]:
                continue
            try:
                tickets[i].result(
                    timeout=max(0.0, deadline - time.perf_counter()))
            except TimeoutError:
                return  # the drain deadline: what is open stays unanswered
            except Exception:  # noqa: BLE001 - shed or failed: it is done,
                pass           # and counts as failed below
            done[i] = time.perf_counter() - t_start

    threads = [threading.Thread(target=generate, name="bench-loadgen"),
               threading.Thread(target=collect, name="bench-collect")]
    for th in threads:
        th.start()
    if run.tracer:
        # a few seconds in the middle of the window, from this thread
        time.sleep(min(float(mix.get("trace_lead_s", 3.0)),
                       run.seconds / 4))
        run.tracer.start()
        time.sleep(min(float(mix.get("trace_s", 4.0)), run.seconds / 2))
        run.tracer.stop()
    for th in threads:
        th.join()
    window_wall_s = time.perf_counter() - t_start
    health = server.health()

    answers = [None] * n
    good = np.zeros(n, bool)
    for i, t in enumerate(tickets):
        if t is not None and t.done() and t.error() is None:
            answers[i] = t.result(timeout=0)
            good[i] = float(np.mean(answers[i].converged)) >= 0.99
    # a request that failed, was refused or is still unanswered waited at
    # least until the drain deadline: it counts at that, never as missing
    latency = np.where(np.isfinite(done), done,
                       deadline - t_start) - sched["due_s"]
    rows_ok = int(sum(int(np.count_nonzero(a.converged))
                      for a, g in zip(answers, good) if g))
    late = submitted - sched["due_s"]
    run.log("window", requests=n, rows=int(sched["rows"].sum()),
            answered=int(np.isfinite(done).sum()),
            drained_s=window_wall_s - run.seconds,
            submit_late_p95_s=float(np.nanpercentile(late, 95)),
            latency_p50_p95_p99_max_s=[float(np.percentile(latency, q))
                                       for q in (50, 95, 99, 100)])
    return {
        "attempted": n, "failed": int(np.count_nonzero(~good)),
        "values": {
            "request_p50_s": float(np.percentile(latency, 50)),
            "request_p95_s": float(np.percentile(latency, 95)),
            "series_per_s_chip": rows_ok / window_wall_s / len(run.devices),
        },
        "schedule": sched, "submitted_s": submitted, "done_s": done,
        "latency_s": latency, "answers": answers, "good": good,
        "refused": int(np.count_nonzero(refused)), "errors": errors[:5],
        "window_wall_s": window_wall_s,
        "counters": {k: v - counters0.get(k, 0)
                     for k, v in health["counters"].items()},
        "knobs": dict(health["knobs"]),
        "queue_at_end": health["queue"],
    }


def check(run, state: dict, result: dict) -> dict:
    """The parts of ``correct`` this kind owns (``run.py`` adds the device
    and the compile count)."""
    cfg, server = run.cell.config, state["server"]
    sched, answers = result["schedule"], result["answers"]
    rng = np.random.default_rng([run.seed, 0xC0DE])
    answered = [i for i, a in enumerate(answers) if a is not None]
    if not answered:
        run.log("check", answered_any=False, refused=result["refused"],
                errors=result["errors"], counters=result["counters"])
        return {"answered_any": False}
    flags = {"answered_any": True}

    # the acknowledged result is the durable one
    durable = True
    for i in rng.choice(answered, min(8, len(answered)), replace=False):
        stored = server.result_for(f"s{run.seed}-{int(i):06d}")
        durable &= all(
            np.array_equal(getattr(stored, f), getattr(answers[i], f),
                           equal_nan=True)
            for f in ("params", "neg_log_likelihood", "converged", "iters",
                      "status"))
    flags["results_durable"] = bool(durable)
    server.stop()
    flags["server_stopped"] = server.state() == "stopped"
    # autotune moving cell_rows would change the one shape that was warmed
    flags["cell_rows_steady"] = (
        result["knobs"]["cell_rows"]
        == state["knobs_after_warmup"]["cell_rows"])

    ref = cfg["reference"]
    picks = [(int(i), int(rng.integers(sched["rows"][i])))
             for i in rng.choice(answered, int(ref["rows"]))]
    gaps = refcheck.loglik_gaps(
        run.cell.plugin("reference", ref["module"]),
        cfg["model"].get("kwargs", {}),
        [state["pool"][int(sched["offset"][i]) + r] for i, r in picks],
        [answers[i].params[r] for i, r in picks])
    ref_flags, ref_log = walk_kind.against_reference(
        run, gaps, np.concatenate([answers[i].params for i in answered]))
    flags.update(ref_flags)
    run.log("check", **flags, **ref_log,
            refused=result["refused"], errors=result["errors"],
            counters=result["counters"], knobs=result["knobs"],
            queue_at_end=result["queue_at_end"])
    return flags


def teardown(run, state: dict) -> None:
    """Leave no serve loop behind, whatever happened above."""
    server = state["server"]
    if server.state() not in ("stopped", "crashed"):
        server.stop(drain=False, timeout_s=30.0)
