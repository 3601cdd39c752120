#!/usr/bin/env python3
"""Keep a traced benchmark run small enough to carry home, and cut one
chunk out of it for a test.

``benchmark/run.py --trace 1 --keep-trace --out DIR`` leaves the profiler's
``.xplane.pb`` (tens of MB) under ``DIR/<cell>/trace``; only its reduction
goes into the result line.  Two steps make a recorded trace that the
benchmark's readers can be held to without a chip:

    python3 tools/record_trace.py dump DIR/<cell> OUT.json.gz
    python3 tools/record_trace.py cut OUT.json.gz CHUNK.json.gz \
        [--stage2] [--chunk K] [--expect METRIC ...]

``dump`` (on the machine with the chip, after the run) writes what
``trace_reduce.load_xplane`` reads — per device ``[instruction name, start,
dur, bytes]``, per host thread the annotated spans — with the run's ``obs``
span lines and its ``window`` line beside it.  ``cut`` (anywhere) takes one
chunk of the traced window (the ``K``-th, with ``--stage2`` of those whose
fit dispatched a stage 2): the events and spans inside the ``chunk`` span and
a margin, times shifted to 0 (``trace_reduce.cut``), a ``bench.window``
around them, and the ``obs`` lines of that chunk (its ``walk`` root, the
``chunk`` line and its descendants), the k-th ``chunk`` annotation of the
driver thread being the k-th ``chunk`` line of the traced walks.  With
``--expect`` it writes down beside them what ``device_phases.split`` and the
named per-layer readers return on the cut (``expect``), for a test to hold
them to.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import device_phases, span_idle, trace_reduce  # noqa: E402
from benchmark import manifest as mf  # noqa: E402

MARGIN_NS = 2_000_000  # around the chunk span, as the first recording had


def _lines(path: str) -> list:
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.strip()]


def dump(cell_dir: str, out: str) -> None:
    spans = [ev for ev in _lines(os.path.join(cell_dir, "obs.jsonl"))
             if ev.get("kind") == "span"]
    run = _lines(os.path.join(cell_dir, "run.jsonl"))
    names = {s["name"] for s in spans} | {trace_reduce.WINDOW_SPAN,
                                          "bench.walk"}
    data = trace_reduce.load_xplane(
        trace_reduce.find_xplane(os.path.join(cell_dir, "trace")),
        host_names=names)
    rec = {"what": f"{run[0]['workload']} seed {run[0]['seed']}: the whole "
                   "traced window as trace_reduce.load_xplane reads it",
           "trace": data, "spans": spans,
           "window": next(r for r in run if r["what"] == "window")}
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with gzip.open(out, "wt", encoding="utf-8") as f:
        json.dump(rec, f, separators=(",", ":"))


def traced_lines(rec: dict, name: str) -> list:
    """The ``obs`` lines of ``name`` inside the traced walks, by ``t0``."""
    roots = sorted(s["walk"] for s in rec["spans"] if s["name"] == "walk")
    wanted = {roots[i] for i in rec["window"]["traced_walks"]}
    return sorted((s for s in rec["spans"]
                   if s["name"] == name and s.get("walk") in wanted),
                  key=lambda s: s["t0"])


def as_run(trace, spans, cell, traced=(0,)):
    """What a layer-metric reader is handed (``benchmark/run.py``'s ``Run``),
    as far as the readers of a trace and of span lines look: ``trace`` as
    ``load_xplane`` gives it (or ``None``), the ``obs`` span lines, the
    cell, the indices of the traced walks."""
    return types.SimpleNamespace(
        trace=None if trace is None else trace_reduce.Trace(trace),
        spans=list(spans), result={"traced_walks": list(traced)}, cell=cell)


def expectations(cut_rec: dict, metrics) -> dict:
    cell = mf.resolve_cell(mf.load_manifest(), cut_rec["workload"])
    run = as_run(cut_rec["trace"], cut_rec["spans"], cell)
    return {"parts_s": device_phases.split(run),
            "metrics": {n: cell.plugin("layer_metrics", n).read(run)
                        for n in metrics}}


def cut(rec: dict, want_stage2: bool, k: int = 0, expect=()) -> dict:
    trace = trace_reduce.Trace(rec["trace"])
    driver = span_idle._driver_thread(trace)
    w0, w1 = trace.window
    chunks = sorted((s, d) for n, s, d in driver
                    if n == span_idle.DRIVER_SPAN and w0 <= s < w1)
    lines = traced_lines(rec, span_idle.DRIVER_SPAN)
    if len(chunks) != len(lines):
        raise SystemExit(f"{len(chunks)} chunk annotations in the window, "
                         f"{len(lines)} chunk lines in the traced walks")
    by_parent = {}
    for s in rec["spans"]:
        by_parent.setdefault(s.get("parent"), []).append(s)

    def family(line):
        out = [line]
        for child in by_parent.get(line["id"], []):
            out += family(child)
        return out

    found = [(c, line) for c, line in zip(chunks, lines)
             if not want_stage2
             or any(s["name"] == "fit.stage2" for s in family(line))]
    if len(found) <= k:
        raise SystemExit(f"{len(found)} such chunks in the window, no {k}-th")
    (start, dur), line = found[k]
    lo, hi = start - MARGIN_NS, start + dur + MARGIN_NS
    data = trace_reduce.cut(rec["trace"], lo, hi)
    data["host"].append({"thread": "recorded", "spans": [
        [trace_reduce.WINDOW_SPAN, 0, hi - lo]]})
    root = next(s for s in rec["spans"]
                if s["name"] == "walk" and s["walk"] == line["walk"])
    out = {"what": f"one chunk (rows {line['attrs']['lo']}-"
                   f"{line['attrs']['hi']}) of: {rec['what']}",
           "workload": rec["window"]["workload"],
           "trace": data, "spans": [root] + family(line)}
    if expect:
        out["expect"] = expectations(out, expect)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    d = sub.add_parser("dump")
    d.add_argument("cell_dir")
    d.add_argument("out")
    c = sub.add_parser("cut")
    c.add_argument("recorded")
    c.add_argument("out")
    c.add_argument("--stage2", action="store_true")
    c.add_argument("--chunk", type=int, default=0)
    c.add_argument("--expect", nargs="*", default=())
    args = ap.parse_args(argv)
    if args.cmd == "dump":
        dump(args.cell_dir, args.out)
        return 0
    with gzip.open(args.recorded, "rt", encoding="utf-8") as f:
        rec = json.load(f)
    with gzip.open(args.out, "wt", encoding="utf-8") as f:
        json.dump(cut(rec, args.stage2, args.chunk, args.expect), f,
                  separators=(",", ":"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
