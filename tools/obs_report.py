#!/usr/bin/env python
"""Render (or validate) a telemetry JSONL event log from the obs plane.

A fit run with ``obs.enable("run.jsonl")`` streams every span and event to
a JSONL file (schema: ``spark_timeseries_tpu.obs.recorder``).  This tool
answers the operator questions from that file alone — where did the wall
clock go (compile vs execute, chunk by chunk), which ladder rungs fired,
how long did journal commits take, what did memory peak at:

    python tools/obs_report.py RUN.jsonl              # timeline + metrics
    python tools/obs_report.py RUN.jsonl --json       # machine-readable
    python tools/obs_report.py RUN.jsonl --check \\
        [--manifest CKPT_DIR]                         # CI schema gate

``--check`` validates every line against the event schema (and, with
``--manifest``, the journal manifest's embedded ``telemetry`` block:
per-chunk span times present, counters present, peak memory non-null) and
exits 0/1 — the ci.sh telemetry smoke runs exactly this.

Sharded walks (ISSUE 6): a merged job manifest carries a ``shards`` block
and shard-tagged chunk entries/telemetry rows; ``--check --manifest``
validates that block (contiguous spans, in-range shard ids, shard-rooted
npz paths), and the rendered timeline splits into ONE LANE PER SHARD so
the eight concurrent walks read as eight rows, not one interleaved blur.

Auto-fit searches (ISSUE 9): every per-order walk tags its spans/events
with a ``grid`` coordinate, and the timeline splits into ONE LANE PER
ORDER; ``--check --manifest`` pointed at the search root validates the
``auto_manifest.json`` block (orders, stage-2 spend, selection counts)
and recurses into every per-order journal, and a per-order manifest's
``extra.auto_fit`` block is checked for grid coherence.

Fleets (ISSUE 18): every process in a serving fleet — N replicas plus
the storming client — streams to its own ``obs_<name>.jsonl`` at the
fleet root.  ``--fleet ROOT`` merges them into one view: per-process
lanes, elections / step-downs / degradation transitions as
annotations, chaos-manifest injections joined to their observed
consequences (injection -> victim silent -> survivor elected ->
takeover latency).  ``--trace REQUEST_ID`` renders one request's
cross-process causal timeline from its deterministic trace ids (and,
with ``--check``, GATES its reconstruction: a submit origin, a server
admission, exactly one ``client.result`` terminal, more than one
process).  ``--slo`` summarizes availability, client-observed latency
percentiles, and failover recovery.  Merged ordering trusts same-host
wall clocks; the client's ``*.clock.json`` sidecars carry per-endpoint
monotonic-clock offsets for the cross-host story.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

KINDS = ("meta", "span", "event", "metrics")
CHUNK_PHASES = ("compile+execute", "execute", "resumed", "timeout")
BUILD_SPAN = "program.build"
BUILD_CACHE = ("hit", "miss", "off")
MEM_SOURCES = ("device", "host_rss")


def load_events(path: str):
    """Parse the JSONL stream; returns (events, errors)."""
    events, errors = [], []
    try:
        with open(path, encoding="utf-8") as f:
            for i, line in enumerate(f, 1):
                line = line.strip()
                if not line:
                    continue
                try:
                    ev = json.loads(line)
                except json.JSONDecodeError as e:
                    errors.append(f"line {i}: does not parse ({e})")
                    continue
                if not isinstance(ev, dict):
                    errors.append(f"line {i}: not an object")
                    continue
                events.append((i, ev))
    except OSError as e:
        errors.append(f"cannot read {path}: {e}")
    return events, errors


_TRACE_HEX = set("0123456789abcdef")


def _trace_field_ok(v) -> bool:
    return (isinstance(v, str) and len(v) == 16
            and all(c in _TRACE_HEX for c in v))


def validate_trace_stamp(i: int, ev: dict, errors: list) -> None:
    """Schema v2 (ISSUE 18): a span/event line MAY carry a top-level
    ``trace`` object — absent is fine (tracing off, schema-v1 streams),
    present-but-malformed fails the gate."""
    if "trace" not in ev:
        return
    tr = ev["trace"]
    if not isinstance(tr, dict):
        errors.append(f"line {i}: trace is not an object: {tr!r}")
        return
    for f in ("trace_id", "span_id"):
        if not _trace_field_ok(tr.get(f)):
            errors.append(f"line {i}: trace.{f} is not 16 lowercase hex "
                          f"chars: {tr.get(f)!r}")
    if "parent_id" in tr and not _trace_field_ok(tr["parent_id"]):
        errors.append(f"line {i}: trace.parent_id invalid: "
                      f"{tr['parent_id']!r}")
    extra = set(tr) - {"trace_id", "span_id", "parent_id"}
    if extra:
        errors.append(f"line {i}: trace carries unknown keys "
                      f"{sorted(extra)}")


def _is_id(v) -> bool:
    return isinstance(v, int) and not isinstance(v, bool) and v > 0


def validate_span_identity(i: int, ev: dict, seen: set, errors: list) -> None:
    """Schema v3 (ISSUE 25): a span line MAY carry ``id`` / ``parent`` /
    ``walk`` — absent is fine (v1/v2 streams), malformed or repeated
    fails.  ``seen`` holds the ids of the run so far.  That a ``parent``
    resolves is NOT gated here: a SIGKILLed process's stream ends with
    children whose parents never closed, and ``--fleet --check`` must read
    those (``tests/test_obs.py`` holds a finished walk to it instead)."""
    if "id" not in ev:
        for f in ("parent", "walk"):
            if f in ev:
                errors.append(f"line {i}: span carries {f} but no id")
        return
    if not _is_id(ev["id"]):
        errors.append(f"line {i}: span id is not a positive integer: "
                      f"{ev['id']!r}")
    elif ev["id"] in seen:
        errors.append(f"line {i}: span id {ev['id']} repeats within its run")
    else:
        seen.add(ev["id"])
    parent = ev.get("parent")
    if parent is not None and not _is_id(parent):
        errors.append(f"line {i}: span parent is not a positive integer "
                      f"or null: {parent!r}")
    if "walk" in ev and not _is_id(ev["walk"]):
        errors.append(f"line {i}: span walk is not a positive integer: "
                      f"{ev['walk']!r}")


def validate_build(i: int, ev: dict, errors: list) -> None:
    """A ``program.build`` line's attributes (``utils.compile_cache``'s build
    log): which program, the three stretches of the build inside its wall,
    and what the persistent cache did."""
    attrs = ev.get("attrs") or {}
    if not isinstance(attrs.get("program"), str) or not attrs["program"]:
        errors.append(f"line {i}: program.build names no program")
    if attrs.get("cache") not in BUILD_CACHE:
        errors.append(f"line {i}: program.build cache is not one of "
                      f"{BUILD_CACHE}: {attrs.get('cache')!r}")
    parts = [attrs.get(f) for f in ("trace_s", "lower_s", "backend_s")]
    if not all(isinstance(v, (int, float)) and v >= 0 for v in parts):
        errors.append(f"line {i}: program.build trace_s / lower_s / "
                      f"backend_s invalid: {parts!r}")
    elif isinstance(ev.get("wall_s"), (int, float)) \
            and sum(parts) > ev["wall_s"] + 1e-3:
        errors.append(f"line {i}: program.build parts {parts!r} exceed its "
                      f"wall_s {ev['wall_s']!r}")


def validate_events(events, errors) -> list:
    """Schema check (see obs.recorder docstring); appends to ``errors``."""
    if not events and not errors:
        errors.append("no events in stream")
        return errors
    if events and events[0][1].get("kind") != "meta":
        errors.append("first event is not kind=meta")
    span_ids = set()  # of the run so far: ids restart with every meta line
    for i, ev in events:
        kind = ev.get("kind")
        if kind not in KINDS:
            errors.append(f"line {i}: unknown kind {kind!r}")
            continue
        if not isinstance(ev.get("ts"), (int, float)):
            errors.append(f"line {i}: missing/non-numeric ts")
        if kind in ("span", "event"):
            validate_trace_stamp(i, ev, errors)
        if kind == "meta":
            if not ev.get("run_id") or not isinstance(ev.get("schema"), int):
                errors.append(f"line {i}: meta missing run_id/schema")
            span_ids = set()
        elif kind == "span":
            validate_span_identity(i, ev, span_ids, errors)
            if not isinstance(ev.get("name"), str):
                errors.append(f"line {i}: span missing name")
            # a ``program.build`` line (ISSUE 54) is written once the build
            # has closed, from jax's own report of it: nobody read the
            # process clock at its start, so it carries no ``process_s``;
            # and ``obs.enable`` first writes the builds the process made
            # BEFORE the run, so its ``t0`` may precede the ``meta`` line's
            # ``ts`` (no other span's does, and a reader that takes the meta
            # line for the stream's start must not) — legal for this name
            built = ev.get("name") == BUILD_SPAN
            for f in ("wall_s",) if built else ("wall_s", "process_s"):
                v = ev.get(f)
                if not isinstance(v, (int, float)) or v < 0:
                    errors.append(f"line {i}: span {f} invalid: {v!r}")
            if built:
                validate_build(i, ev, errors)
            if not isinstance(ev.get("depth"), int) or ev["depth"] < 0:
                errors.append(f"line {i}: span depth invalid")
        elif kind == "event":
            if not isinstance(ev.get("name"), str):
                errors.append(f"line {i}: event missing name")
        elif kind == "metrics":
            if not isinstance(ev.get("counters"), dict):
                errors.append(f"line {i}: metrics missing counters dict")
    return errors


DEGRADATION_EVENT_FIELDS = {
    # ISSUE 17 degradation-ladder telemetry: event name -> required
    # fields.  A renamed or stripped field here silently breaks the
    # chaos post-mortem story, so the shapes are pinned.
    "fleet.step_down": ("owner", "reason"),
    "fleet.elected": ("owner", "token"),
    "fleet.fenced": ("owner", "token"),
    "fleet.standby_read": ("owner",),
    "fleet.torn_result": ("owner", "file"),
    "client.endpoint_circuit_open": ("endpoint",),
    "client.endpoint_recovered": ("endpoint",),
    "client.primary_learned": ("endpoint",),
    "client.hedge": ("req_id",),
    "transport.auth_failed": ("conn",),
    "server.storage_refusal": ("req_id",),
    "server.torn_result": ("path",),
}

FLEET_STATE_CODES = (0.0, 1.0, 2.0, 3.0, 4.0, 5.0, 6.0)


def validate_degradation(events) -> list:
    """Validate the degradation-ladder telemetry (ISSUE 17): the stream's
    final metrics snapshot must publish the ``fleet.state`` gauge with a
    value from the ladder's code table (full=0 … stopped=6), and every
    degradation event present must carry its pinned fields — the chaos
    soak and its ``advise_budget`` post-mortem read exactly these."""
    errors = []
    last_metrics = None
    for i, ev in events:
        if ev.get("kind") == "metrics":
            last_metrics = (i, ev)
        if ev.get("kind") != "event":
            continue
        need = DEGRADATION_EVENT_FIELDS.get(ev.get("name"))
        if not need:
            continue
        attrs = ev.get("attrs") or {}
        for f in need:
            if attrs.get(f) in (None, ""):
                errors.append(f"line {i}: degradation event "
                              f"{ev['name']} missing field {f!r}")
    if last_metrics is None:
        errors.append("degradation check: no metrics snapshot in stream")
        return errors
    i, m = last_metrics
    gauges = m.get("gauges") or {}
    state = gauges.get("fleet.state")
    if state is None:
        errors.append(f"line {i}: final metrics snapshot has no "
                      "fleet.state gauge (the degradation ladder is "
                      "not being published)")
    elif float(state) not in FLEET_STATE_CODES:
        errors.append(f"line {i}: fleet.state gauge {state!r} is not a "
                      f"ladder code {FLEET_STATE_CODES}")
    return errors


def validate_manifest_telemetry(ckpt_dir: str) -> list:
    """Validate the journal manifest's embedded ``telemetry`` block.

    An auto-fit search root (ISSUE 9: ``auto_manifest.json`` + per-order
    ``grid_*`` journals, no root ``manifest.json``) dispatches to
    :func:`validate_auto_manifest` instead, which checks the grid-level
    block and recurses into every per-order journal.
    """
    errors = []
    path = ckpt_dir
    if os.path.isdir(path):
        if (os.path.exists(os.path.join(path, "auto_manifest.json"))
                and not os.path.exists(os.path.join(path, "manifest.json"))):
            return validate_auto_manifest(path)
        if (os.path.exists(os.path.join(path, "backtest_manifest.json"))
                and not os.path.exists(os.path.join(path, "manifest.json"))):
            # a backtest campaign root (ISSUE 14): campaign manifest +
            # per-window fit journals, no root manifest.json
            return validate_backtest_manifest(path)
        if (os.path.exists(os.path.join(path, "tickloop.json"))
                and not os.path.exists(os.path.join(path, "manifest.json"))):
            # a tick-loop root (ISSUE 20): loop manifest + per-cycle
            # dirs, each holding its own fit/forecast journals + sink
            return validate_tickloop_root(path)
        path = os.path.join(path, "manifest.json")
    try:
        with open(path, "rb") as f:
            m = json.loads(f.read().decode())
    except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
        return [f"manifest {path}: unreadable ({e})"]
    t = m.get("telemetry")
    if not isinstance(t, dict):
        return [f"manifest {path}: no telemetry block"]
    chunks = t.get("chunks")
    if not isinstance(chunks, list) or not chunks:
        errors.append("telemetry.chunks missing/empty")
    else:
        for c in chunks:
            phase = c.get("phase")
            if phase not in CHUNK_PHASES:
                errors.append(f"chunk {c.get('lo')}: bad phase {phase!r}")
            if phase in ("compile+execute", "execute") and not isinstance(
                    c.get("wall_s"), (int, float)):
                errors.append(f"chunk {c.get('lo')}: missing wall_s")
    if not isinstance(t.get("counters"), dict):
        errors.append("telemetry.counters missing")
    pm = t.get("peak_memory") or {}
    if not isinstance(pm.get("bytes"), int) or pm["bytes"] <= 0:
        errors.append(f"telemetry.peak_memory.bytes invalid: "
                      f"{pm.get('bytes')!r}")
    if pm.get("source") not in MEM_SOURCES:
        errors.append(f"telemetry.peak_memory.source invalid: "
                      f"{pm.get('source')!r}")
    # input-staging block (ISSUE 5): optional — serial/unprefetched walks
    # journal none — but when present it must be well-formed, since
    # tools/advise_budget.py derives prefetch_depth from it.  A
    # host-resident walk (ISSUE 7) adds a staging_pool sub-block (and may
    # journal ONLY that when the walk ran serially): pool reuse counts,
    # H2D wall, and the donated-buffer peak must be present and sane —
    # the oversubscribed CI smoke gates on exactly this.
    st = t.get("input_staging")
    if st is not None:
        if not isinstance(st, dict):
            errors.append(f"telemetry.input_staging not a dict: {st!r}")
        else:
            for k in ("chunks_staged", "staged_hits", "staged_misses"):
                if k in st and not isinstance(st.get(k), int):
                    errors.append(f"telemetry.input_staging.{k} invalid: "
                                  f"{st.get(k)!r}")
            for k in ("staging_wall_s", "hidden_staging_s"):
                if k in st and not isinstance(st.get(k), (int, float)):
                    errors.append(f"telemetry.input_staging.{k} invalid: "
                                  f"{st.get(k)!r}")
            if not any(k in st for k in ("chunks_staged", "staging_pool")):
                errors.append("telemetry.input_staging carries neither "
                              "prefetch nor staging_pool accounting")
            pool = st.get("staging_pool")
            if pool is not None:
                if not isinstance(pool, dict):
                    errors.append("telemetry.input_staging.staging_pool "
                                  f"not a dict: {pool!r}")
                else:
                    for k in ("pool_hits", "pool_misses", "h2d_copies",
                              "h2d_bytes", "peak_live_device_bytes",
                              "peak_host_bytes"):
                        if not isinstance(pool.get(k), int) or pool[k] < 0:
                            errors.append(
                                f"telemetry.input_staging.staging_pool.{k} "
                                f"invalid: {pool.get(k)!r}")
                    if not isinstance(pool.get("h2d_wall_s"), (int, float)):
                        errors.append(
                            "telemetry.input_staging.staging_pool."
                            f"h2d_wall_s invalid: {pool.get('h2d_wall_s')!r}")
    errors += validate_manifest_shards(m, path)
    errors += validate_manifest_auto_extra(m, path)
    errors += validate_manifest_delta(m, path)
    errors += validate_manifest_sink(m, path)
    return errors


DELTA_CLASSES = ("adopted", "warm", "dirty", "new")


def validate_manifest_delta(m: dict, path: str) -> list:
    """Validate a delta walk's ``extra.delta`` provenance block
    (ISSUE 15).  Manifests without the block (ordinary walks) pass
    untouched; a walk that claims a delta plan must carry a coherent
    one: the classified chunk grid covers the panel exactly, the class
    counts tally, and every adopted chunk entry names the manifest its
    bytes were spliced from."""
    d = (m.get("extra") or {}).get("delta")
    if d is None:
        return []
    errors = []
    counts = d.get("counts")
    if not isinstance(counts, dict) or \
            set(counts) != set(DELTA_CLASSES):
        errors.append(f"extra.delta.counts malformed: {counts!r}")
        counts = {}
    grid = d.get("chunks")
    if not isinstance(grid, list) or not grid:
        errors.append("extra.delta.chunks missing/empty")
        grid = []
    tallies = {k: 0 for k in DELTA_CLASSES}
    pos = 0
    for ent in grid:
        if (not isinstance(ent, (list, tuple)) or len(ent) != 3
                or ent[2] not in DELTA_CLASSES):
            errors.append(f"extra.delta.chunks entry malformed: {ent!r}")
            continue
        lo, hi, cls = int(ent[0]), int(ent[1]), ent[2]
        if lo != pos or hi <= lo:
            errors.append(f"extra.delta.chunks not contiguous at "
                          f"[{lo}, {hi}) (expected lo={pos})")
        tallies[cls] += 1
        pos = max(pos, hi)
    if grid and pos != int(m.get("n_rows", -1)):
        errors.append(f"extra.delta.chunks cover [0, {pos}) but the "
                      f"panel has {m.get('n_rows')} rows")
    for k in DELTA_CLASSES:
        if counts and counts.get(k) != tallies[k]:
            errors.append(f"extra.delta.counts[{k!r}] = {counts.get(k)} "
                          f"but the classified grid holds {tallies[k]}")
    if not isinstance(d.get("source_manifest"), str):
        errors.append("extra.delta.source_manifest missing")
    adopted_entries = [e for e in m.get("chunks", [])
                       if isinstance(e.get("delta"), dict)
                       and e["delta"].get("class") == "adopted"]
    for e in adopted_entries:
        if not isinstance(e["delta"].get("source_manifest"), str):
            errors.append(f"adopted chunk [{e.get('lo')}, {e.get('hi')}) "
                          "does not name its source manifest")
        if e.get("status") != "committed":
            errors.append(f"adopted chunk [{e.get('lo')}, {e.get('hi')}) "
                          f"has status {e.get('status')!r} — adoption IS "
                          "a commit")
    if counts and len(adopted_entries) > counts.get("adopted", 0):
        errors.append(
            f"{len(adopted_entries)} adopted chunk entries exceed the "
            f"plan's adopted count {counts.get('adopted')}")
    return errors


def validate_sink_dir(sink_dir: str, *, expect_rows=None) -> list:
    """Validate a write-back sink directory (ISSUE 20): the durable
    ``sink_manifest.json`` parses, its recorded shards tile
    ``[0, n_rows)`` exactly, every shard file exists on disk, no
    unrecorded ``out_*.npz`` stray survived finalize, and the
    accounting block carries the footprint counters the CI smoke and
    the budget advisor read."""
    errors = []
    mp = os.path.join(sink_dir, "sink_manifest.json")
    try:
        with open(mp, "rb") as f:
            m = json.loads(f.read().decode())
    except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
        return [f"sink manifest {mp}: unreadable ({e})"]
    if m.get("kind") != "sink":
        errors.append(f"sink manifest: kind {m.get('kind')!r} != 'sink'")
    n_rows = m.get("n_rows")
    if not isinstance(n_rows, int) or n_rows < 1:
        errors.append(f"sink manifest: bad n_rows {n_rows!r}")
        n_rows = None
    if expect_rows is not None and n_rows is not None and \
            n_rows != int(expect_rows):
        errors.append(f"sink manifest: n_rows {n_rows} != walk rows "
                      f"{expect_rows}")
    shards = m.get("shards")
    if not isinstance(shards, list) or not shards:
        return errors + ["sink manifest: shards missing/empty"]
    pos = 0
    names = set()
    for s in shards:
        lo, hi, name = s.get("lo"), s.get("hi"), s.get("name")
        if not isinstance(lo, int) or not isinstance(hi, int) or \
                not isinstance(name, str) or hi <= lo:
            errors.append(f"sink shard entry malformed: {s!r}")
            continue
        if lo != pos:
            errors.append(f"sink shards not contiguous at [{lo}, {hi}) "
                          f"(expected lo={pos})")
        pos = max(pos, hi)
        names.add(name)
        if not os.path.exists(os.path.join(sink_dir, name)):
            errors.append(f"sink shard {name} missing on disk")
    if n_rows is not None and pos != n_rows:
        errors.append(f"sink shards cover [0, {pos}) but n_rows is "
                      f"{n_rows}")
    try:
        on_disk = sorted(os.listdir(sink_dir))
    except OSError as e:
        return errors + [f"sink dir unreadable: {e}"]
    for fn in on_disk:
        if fn.startswith("out_") and fn.endswith(".npz") \
                and fn not in names:
            errors.append(f"sink dir holds unrecorded shard {fn} "
                          "(finalize must sweep strays)")
    acct = m.get("accounting")
    if not isinstance(acct, dict):
        errors.append("sink manifest: accounting block missing")
    else:
        for k in ("writes", "spans", "bytes_written",
                  "peak_in_flight_bytes"):
            if not isinstance(acct.get(k), int) or acct[k] < 0:
                errors.append(f"sink accounting.{k} invalid: "
                              f"{acct.get(k)!r}")
        if not isinstance(acct.get("status_counts"), dict):
            errors.append("sink accounting.status_counts missing")
    return errors


def validate_manifest_sink(m: dict, path: str) -> list:
    """Validate a journaled walk's ``extra.sink`` block (ISSUE 20) and
    the write-back sink directory it points at.  Manifests without the
    block (no sink) pass untouched."""
    s = (m.get("extra") or {}).get("sink")
    if s is None:
        return []
    if not isinstance(s, dict):
        return [f"manifest {path}: extra.sink is not an object: {s!r}"]
    errors = []
    d = s.get("directory")
    if not isinstance(d, str) or not d:
        errors.append(f"extra.sink.directory invalid: {d!r}")
        return errors
    if not isinstance(s.get("depth"), int) or s["depth"] < 1:
        errors.append(f"extra.sink.depth invalid: {s.get('depth')!r}")
    if not os.path.isdir(d):
        errors.append(f"extra.sink.directory {d} is not a directory")
        return errors
    errors += [f"sink {d}: {e}"
               for e in validate_sink_dir(d, expect_rows=m.get("n_rows"))]
    return errors


TICKLOOP_STAGES = ("ticked", "appended", "fitted", "published")


def validate_tickloop_root(root: str) -> list:
    """Validate a tick-loop root (ISSUE 20): the ``tickloop.json`` loop
    manifest, every ``cycle_%05d`` dir's ``tick_manifest.json`` (stage
    progression, tick-count chain), and — for published cycles — the
    cycle's fit/forecast journals and write-back sink directory.  Only
    the LAST cycle may be mid-flight (anything but ``published``)."""
    import re as _re

    errors = []
    mp = os.path.join(root, "tickloop.json")
    try:
        with open(mp, "rb") as f:
            m = json.loads(f.read().decode())
    except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
        return [f"tickloop manifest {mp}: unreadable ({e})"]
    if m.get("kind") != "tickloop":
        errors.append(f"tickloop manifest: kind {m.get('kind')!r} != "
                      "'tickloop'")
    n_rows, n_time0 = m.get("n_rows"), m.get("n_time0")
    for k, v in (("n_rows", n_rows), ("n_time0", n_time0)):
        if not isinstance(v, int) or v < 1:
            errors.append(f"tickloop manifest: bad {k} {v!r}")
    if not isinstance(m.get("config"), dict):
        errors.append("tickloop manifest: config block missing")
    cycles = sorted(
        (int(mm.group(1)), name)
        for name in os.listdir(root)
        for mm in [_re.match(r"^cycle_(\d{5})$", name)] if mm)
    expect_t = n_time0 if isinstance(n_time0, int) else None
    for pos, (i, name) in enumerate(cycles):
        if i != pos:
            errors.append(f"cycle dirs not consecutive: {name} at "
                          f"position {pos}")
        cdir = os.path.join(root, name)
        cm_path = os.path.join(cdir, "tick_manifest.json")
        try:
            with open(cm_path, "rb") as f:
                cm = json.loads(f.read().decode())
        except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
            errors.append(f"{name}: tick_manifest.json unreadable ({e})")
            continue
        stage = cm.get("stage")
        if stage not in TICKLOOP_STAGES:
            errors.append(f"{name}: bad stage {stage!r}")
        elif stage != "published" and pos != len(cycles) - 1:
            errors.append(f"{name}: stage {stage!r} but later cycles "
                          "exist — only the last cycle may be mid-flight")
        if cm.get("cycle") != i:
            errors.append(f"{name}: cycle field {cm.get('cycle')!r} != "
                          f"{i}")
        n_ticks = cm.get("n_ticks")
        if not isinstance(n_ticks, int) or n_ticks < 1:
            errors.append(f"{name}: bad n_ticks {n_ticks!r}")
            n_ticks = None
        if expect_t is not None:
            if cm.get("t_before") != expect_t:
                errors.append(f"{name}: t_before {cm.get('t_before')!r} "
                              f"breaks the chain (expected {expect_t})")
            expect_t = (expect_t + n_ticks if n_ticks is not None
                        else None)
        if not isinstance(cm.get("ticks_digest"), str):
            errors.append(f"{name}: ticks_digest missing")
        if not os.path.exists(os.path.join(cdir, "ticks.npz")):
            errors.append(f"{name}: ticks.npz missing (the durable tick "
                          "record is the resume seed)")
        if not isinstance(cm.get("walls"), dict):
            errors.append(f"{name}: walls block missing")
        if stage != "published":
            continue
        pub = cm.get("published")
        if not isinstance(pub, dict):
            errors.append(f"{name}: published block missing")
        elif not isinstance(pub.get("status_counts"), dict):
            errors.append(f"{name}: published.status_counts missing")
        errors += [f"{name}/published: {e}" for e in
                   validate_sink_dir(os.path.join(cdir, "published"),
                                     expect_rows=n_rows)]
        for sub in ("fit", "forecast"):
            smp = os.path.join(cdir, sub, "manifest.json")
            if not os.path.exists(smp):
                errors.append(f"{name}: {sub}/manifest.json missing")
                continue
            try:
                with open(smp, "rb") as f:
                    sm = json.loads(f.read().decode())
            except (OSError, json.JSONDecodeError,
                    UnicodeDecodeError) as e:
                errors.append(f"{name}: {sub} manifest unreadable ({e})")
                continue
            if isinstance(sm.get("telemetry"), dict):
                errors += [f"{name}/{sub}: {e}" for e in
                           validate_manifest_telemetry(
                               os.path.join(cdir, sub))]
    return errors


def validate_manifest_auto_extra(m: dict, path: str) -> list:
    """Validate a per-order journal manifest's ``extra.auto_fit`` block
    (ISSUE 9).  Manifests without the block (non-auto walks) pass
    untouched; a walk that claims a grid position must carry a coherent
    one — the budget advisor and the search resume both read it.
    """
    a = (m.get("extra") or {}).get("auto_fit")
    if a is None:
        return []
    errors = []
    if not isinstance(a, dict):
        return [f"manifest {path}: extra.auto_fit is not an object: {a!r}"]
    gi, gn = a.get("grid_index"), a.get("grid_total")
    if not isinstance(gi, int) or not isinstance(gn, int) or not (
            0 <= gi < gn):
        errors.append(f"extra.auto_fit grid position invalid: index "
                      f"{gi!r} of {gn!r}")

    def _order_ok(od):
        return (isinstance(od, list) and len(od) == 3
                and all(isinstance(v, int) and v >= 0 for v in od))

    fused = a.get("fused_orders")
    if fused is not None:
        # a fused group walk (ISSUE 10): the chunks carry K same-d orders
        if not (isinstance(fused, list) and fused
                and all(isinstance(v, int) for v in fused)):
            errors.append(f"extra.auto_fit.fused_orders invalid: {fused!r}")
        else:
            if isinstance(gn, int) and not all(0 <= v < gn for v in fused):
                errors.append(f"extra.auto_fit.fused_orders {fused} out of "
                              f"range for grid_total {gn}")
            if isinstance(gi, int) and fused[0] != gi:
                errors.append(f"extra.auto_fit.fused_orders must lead with "
                              f"grid_index {gi}, got {fused}")
        ods = a.get("orders")
        if not (isinstance(ods, list) and ods
                and all(_order_ok(od) for od in ods)):
            errors.append(f"extra.auto_fit.orders invalid for fused walk: "
                          f"{ods!r}")
        elif len({od[1] for od in ods}) != 1:
            errors.append(f"extra.auto_fit.orders mix d values in one "
                          f"fused group: {ods!r}")
        elif isinstance(fused, list) and len(ods) != len(fused):
            errors.append(f"extra.auto_fit.orders count {len(ods)} != "
                          f"fused_orders count {len(fused)}")
    else:
        order = a.get("order")
        if not _order_ok(order):
            errors.append(f"extra.auto_fit.order invalid: {order!r}")
        seasonal = a.get("seasonal")
        if seasonal is not None and not (
                isinstance(seasonal, list) and len(seasonal) == 4
                and all(isinstance(v, int) for v in seasonal)):
            errors.append(f"extra.auto_fit.seasonal invalid: {seasonal!r}")
    if a.get("stage") not in ("full", "stage1", "winners", "stepwise"):
        errors.append(f"extra.auto_fit.stage invalid: {a.get('stage')!r}")
    if a.get("stage") == "stepwise" and not (
            isinstance(a.get("stepwise_pass"), int)
            and a["stepwise_pass"] >= 0):
        errors.append(f"extra.auto_fit.stepwise_pass invalid for a "
                      f"stepwise walk: {a.get('stepwise_pass')!r}")
    grid = (m.get("extra") or {}).get("grid") or {}
    if isinstance(gi, int) and grid.get("index") != gi:
        errors.append(f"extra.grid.index {grid.get('index')!r} disagrees "
                      f"with extra.auto_fit.grid_index {gi}")
    if fused is not None and grid.get("fused") != fused:
        errors.append(f"extra.grid.fused {grid.get('fused')!r} disagrees "
                      f"with extra.auto_fit.fused_orders {fused!r}")
    return errors


def validate_auto_manifest(root: str) -> list:
    """Validate an auto-fit search root (``auto_manifest.json``): the
    grid-level telemetry block — orders tried, per-order stage-2 spend,
    selection counts — plus every per-order journal found on disk."""
    path = root
    if os.path.isdir(path):
        path = os.path.join(path, "auto_manifest.json")
    try:
        with open(path, "rb") as f:
            m = json.loads(f.read().decode())
    except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
        return [f"auto manifest {path}: unreadable ({e})"]
    a = m.get("auto_fit")
    if not isinstance(a, dict):
        return [f"auto manifest {path}: no auto_fit block"]
    errors = []
    orders = a.get("orders")
    if not isinstance(orders, list) or not orders:
        errors.append("auto_fit.orders missing/empty")
        orders = []
    for i, o in enumerate(orders):
        if not isinstance(o, dict):
            errors.append(f"auto_fit.orders[{i}] is not an object: {o!r}")
            continue
        if o.get("grid_index") != i:
            errors.append(f"auto_fit.orders[{i}].grid_index is "
                          f"{o.get('grid_index')!r}")
        od = o.get("order")
        if not (isinstance(od, list) and len(od) == 3
                and all(isinstance(v, int) and v >= 0 for v in od)):
            errors.append(f"auto_fit.orders[{i}].order invalid: {od!r}")
        if not isinstance(o.get("selected_rows"), int) or \
                o["selected_rows"] < 0:
            errors.append(f"auto_fit.orders[{i}].selected_rows invalid: "
                          f"{o.get('selected_rows')!r}")
        if not isinstance(o.get("wall_s"), (int, float)):
            errors.append(f"auto_fit.orders[{i}].wall_s invalid: "
                          f"{o.get('wall_s')!r}")
    sc = a.get("selection_counts")
    if not isinstance(sc, dict) or not sc or not all(
            isinstance(v, int) and v >= 0 for v in sc.values()):
        errors.append(f"auto_fit.selection_counts missing/invalid: {sc!r}")
    elif isinstance(a.get("n_rows"), int) and \
            sum(sc.values()) != a["n_rows"]:
        errors.append(f"auto_fit.selection_counts sum "
                      f"{sum(sc.values())} != n_rows {a['n_rows']}")
    for key in ("stage1_wall_s", "stage2_wall_s", "stage2_spend_share"):
        if not isinstance(a.get(key), (int, float)):
            errors.append(f"auto_fit.{key} invalid: {a.get(key)!r}")
    if a.get("criterion") not in ("aicc", "aic", "bic"):
        errors.append(f"auto_fit.criterion invalid: {a.get('criterion')!r}")
    # fusion accounting (ISSUE 10): when present, the groups must
    # partition the grid exactly once — the resume path and the budget
    # advisor both read the group membership
    fg = a.get("fusion_groups")
    if fg is not None:
        if not (isinstance(fg, list) and fg
                and all(isinstance(e, dict) and isinstance(e.get("dir"), str)
                        and isinstance(e.get("orders"), list)
                        for e in fg)):
            errors.append(f"auto_fit.fusion_groups invalid: {fg!r}")
        else:
            seen = [g for e in fg for g in e["orders"]]
            if sorted(seen) != list(range(len(orders))):
                errors.append(
                    f"auto_fit.fusion_groups {seen} do not partition the "
                    f"{len(orders)}-order grid exactly once")
        if not (isinstance(a.get("diff_cache_hits"), int)
                and a["diff_cache_hits"] >= 0):
            errors.append(f"auto_fit.diff_cache_hits invalid: "
                          f"{a.get('diff_cache_hits')!r}")
    # stepwise accounting (ISSUE 19): the pass manifests must partition
    # the trial list in walk order — a SIGKILL'd search resumes by
    # replaying the pass sequence against these journals, and the budget
    # advisor reads the seed/convergence evidence
    sw = a.get("stepwise")
    if sw is not None:
        if not isinstance(sw, dict):
            errors.append(f"auto_fit.stepwise invalid: {sw!r}")
        else:
            passes = sw.get("passes")
            if not (isinstance(passes, list) and passes
                    and all(isinstance(p, dict) for p in passes)):
                errors.append(f"auto_fit.stepwise.passes missing/invalid: "
                              f"{passes!r}")
            else:
                covered = []
                for i, p in enumerate(passes):
                    if p.get("pass") != i:
                        errors.append(f"auto_fit.stepwise.passes[{i}].pass "
                                      f"is {p.get('pass')!r}")
                    if p.get("dir") != f"stepwise_{i:02d}":
                        errors.append(f"auto_fit.stepwise.passes[{i}].dir "
                                      f"is {p.get('dir')!r}, expected "
                                      f"'stepwise_{i:02d}'")
                    po = p.get("orders")
                    if not (isinstance(po, list) and po
                            and all(isinstance(v, int) for v in po)):
                        errors.append(f"auto_fit.stepwise.passes[{i}]"
                                      f".orders invalid: {po!r}")
                    else:
                        covered += po
                    if not isinstance(p.get("new_rows_won"), int) or \
                            p["new_rows_won"] < 0:
                        errors.append(f"auto_fit.stepwise.passes[{i}]"
                                      ".new_rows_won invalid: "
                                      f"{p.get('new_rows_won')!r}")
                    if not isinstance(p.get("wall_s"), (int, float)):
                        errors.append(f"auto_fit.stepwise.passes[{i}]"
                                      f".wall_s invalid: {p.get('wall_s')!r}")
                if covered and covered != list(range(len(orders))):
                    errors.append(
                        "auto_fit.stepwise passes do not partition the "
                        f"{len(orders)}-order trial list in walk order: "
                        f"{covered}")
            if not isinstance(sw.get("converged"), bool):
                errors.append(f"auto_fit.stepwise.converged invalid: "
                              f"{sw.get('converged')!r}")
            if sw.get("orders_tried") != len(orders):
                errors.append(f"auto_fit.stepwise.orders_tried "
                              f"{sw.get('orders_tried')!r} != "
                              f"{len(orders)} recorded orders")
            if not (isinstance(sw.get("seed"), list) and sw.get("seed")):
                errors.append(f"auto_fit.stepwise.seed missing/empty: "
                              f"{sw.get('seed')!r}")
        # every trial must say which pass walked it — the per-order
        # journal dirs live under stepwise_%02d/ namespaces keyed on it
        for i, o in enumerate(orders):
            if isinstance(o, dict) and not isinstance(
                    o.get("stepwise_pass"), int):
                errors.append(f"auto_fit.orders[{i}].stepwise_pass missing "
                              "for a stepwise search")
    # recurse into every per-order journal the search left on disk: each
    # is an ordinary chunk-walk manifest and must pass the same gate
    if os.path.isdir(root):
        for d in sorted(m.get("grid_dirs") or []):
            sub = os.path.join(root, d)
            if os.path.exists(os.path.join(sub, "manifest.json")):
                errors += [f"{d}: {e}"
                           for e in validate_manifest_telemetry(sub)]
    return errors


def validate_backtest_manifest(root: str) -> list:
    """Validate a rolling-origin backtest campaign root (ISSUE 14).

    Checks the campaign-level ``backtest_manifest.json`` (identity
    fields, ascending origins, per-window entries with metric vectors of
    horizon length), verifies each committed window's metrics npz exists
    and matches its recorded content digest, and recurses into every
    window's fit-walk journal when it carries a telemetry block.
    """
    import hashlib

    import numpy as np

    errors = []
    mp = os.path.join(root, "backtest_manifest.json")
    try:
        with open(mp, "rb") as f:
            m = json.loads(f.read().decode())
    except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
        return [f"backtest manifest {mp}: unreadable ({e})"]
    if m.get("kind") != "backtest":
        errors.append(f"backtest manifest: kind {m.get('kind')!r} != "
                      "'backtest'")
    for key in ("campaign_hash", "panel_fingerprint", "model"):
        if not isinstance(m.get(key), str) or not m.get(key):
            errors.append(f"backtest manifest: missing {key}")
    horizon = m.get("horizon")
    if not isinstance(horizon, int) or horizon < 1:
        errors.append(f"backtest manifest: bad horizon {horizon!r}")
        horizon = None
    origins = m.get("origins")
    if (not isinstance(origins, list) or not origins
            or origins != sorted(origins)):
        errors.append(f"backtest manifest: origins not an ascending "
                      f"list: {origins!r}")
        origins = None
    windows = m.get("windows")
    if not isinstance(windows, list):
        return errors + ["backtest manifest: windows missing"]
    seen = set()
    for w in windows:
        i = w.get("index")
        if not isinstance(i, int) or (origins is not None
                                      and not 0 <= i < len(origins)):
            errors.append(f"backtest window {i!r}: bad index")
            continue
        if i in seen:
            errors.append(f"backtest window {i}: duplicate entry")
        seen.add(i)
        if origins is not None and w.get("origin") != origins[i]:
            errors.append(f"backtest window {i}: origin {w.get('origin')} "
                          f"!= manifest origins[{i}] {origins[i]}")
        if w.get("status") not in ("committed", "timeout"):
            errors.append(f"backtest window {i}: bad status "
                          f"{w.get('status')!r}")
            continue
        wc = w.get("window_class")
        if wc is not None and wc not in ("adopted", "warm", "cold"):
            errors.append(f"backtest window {i}: bad window_class {wc!r}")
        if w.get("status") != "committed":
            continue
        for key in ("mae", "rmse", "mape"):
            v = w.get(key)
            if (not isinstance(v, list)
                    or (horizon is not None and len(v) != horizon)):
                errors.append(f"backtest window {i}: {key} is not a "
                              f"length-{horizon} vector")
        mf = w.get("metrics_file")
        if mf:
            npz_path = os.path.join(root, mf)
            import zipfile

            try:
                with np.load(npz_path, allow_pickle=False) as z:
                    arrays = {key: np.array(z[key]) for key in z.files}
            except (OSError, ValueError, KeyError,
                    zipfile.BadZipFile) as e:
                errors.append(f"backtest window {i}: metrics shard "
                              f"{mf} unreadable ({e})")
                continue
            h = hashlib.sha256()
            for name in sorted(arrays):
                a = np.ascontiguousarray(arrays[name])
                h.update(f"{name}:{a.shape}:{a.dtype}".encode())
                h.update(a.tobytes())
            if h.hexdigest()[:16] != w.get("digest"):
                errors.append(f"backtest window {i}: metrics shard "
                              f"digest mismatch (torn write?)")
        fd = w.get("fit_dir")
        if fd:
            wmp = os.path.join(root, fd, "manifest.json")
            if not os.path.exists(wmp):
                errors.append(f"backtest window {i}: fit journal "
                              f"{fd}/manifest.json missing")
            else:
                try:
                    with open(wmp, "rb") as f:
                        wm = json.loads(f.read().decode())
                except (OSError, json.JSONDecodeError,
                        UnicodeDecodeError) as e:
                    errors.append(f"backtest window {i}: fit manifest "
                                  f"unreadable ({e})")
                    continue
                if isinstance(wm.get("telemetry"), dict):
                    errors += [f"window {i}: {e2}" for e2 in
                               validate_manifest_telemetry(
                                   os.path.join(root, fd))]
    d = m.get("delta")
    if d is not None:
        # a delta-warm campaign (ISSUE 20): the manifest records what
        # window-level adoption kept from the prior campaign
        if not isinstance(d, dict):
            errors.append(f"backtest manifest: delta block is not an "
                          f"object: {d!r}")
        else:
            if not isinstance(d.get("prior_campaign_hash"), str):
                errors.append("backtest delta: prior_campaign_hash "
                              "missing")
            pt = d.get("prior_n_time")
            if not isinstance(pt, int) or pt < 1:
                errors.append(f"backtest delta: bad prior_n_time {pt!r}")
            for key in ("adopted", "recomputed"):
                v = d.get(key)
                if not isinstance(v, int) or v < 0:
                    errors.append(f"backtest delta: bad {key} {v!r}")
    return errors


def validate_manifest_shards(m: dict, path: str) -> list:
    """Validate a merged sharded-job manifest's ``shards`` block (ISSUE 6).

    Unsharded manifests (no block) pass untouched.  A merged manifest must
    carry contiguous per-shard spans covering the panel, per-shard
    accounting, chunk entries tagged with an in-range ``shard_id`` whose
    row range sits inside their shard's span and whose npz path is rooted
    in that shard's namespace, and (when telemetry rode along) shard tags
    on the merged timeline rows.

    Elastic walks (ISSUE 11): a chunk REASSIGNED by quarantine or a steal
    legitimately sits outside its committing namespace's nominal span —
    allowed iff the entry carries its ``owner`` lane tag; the per-shard
    ``owner``/``chunks_reassigned_in`` fields and the top-level
    ``rebalance`` block (quarantine causes, steal counts, reassigned
    total — which must agree with the per-shard counts) are validated
    when present.
    """
    shards = m.get("shards")
    if shards is None and not m.get("merged_from_shards"):
        return []
    errors = []
    errors += _validate_rebalance(m)
    if not isinstance(shards, list) or not shards:
        return errors + [f"manifest {path}: merged_from_shards set but "
                         "shards block missing/empty"]
    if m.get("merged_from_shards") != len(shards):
        errors.append(f"shards block has {len(shards)} entries but "
                      f"merged_from_shards={m.get('merged_from_shards')}")
    prev_hi = 0
    for i, s in enumerate(shards):
        if not isinstance(s, dict):
            errors.append(f"shards[{i}] is not an object: {s!r}")
            continue
        if s.get("shard_id") != i:
            errors.append(f"shards[{i}].shard_id is {s.get('shard_id')!r}")
        lo, hi = s.get("lo"), s.get("hi")
        if not isinstance(lo, int) or not isinstance(hi, int) or lo >= hi:
            errors.append(f"shards[{i}] span invalid: [{lo!r}, {hi!r})")
            continue
        if lo != prev_hi:
            errors.append(f"shards[{i}] span not contiguous: lo {lo} "
                          f"after hi {prev_hi}")
        prev_hi = hi
        for k in ("chunks_committed", "chunks_timeout"):
            if not isinstance(s.get(k), int) or s[k] < 0:
                errors.append(f"shards[{i}].{k} invalid: {s.get(k)!r}")
        if not isinstance(s.get("dir"), str):
            errors.append(f"shards[{i}].dir invalid: {s.get('dir')!r}")
        # elastic merges (ISSUE 11) stamp each namespace with its owner
        # lane and how many committed chunks were reassigned in
        if "owner" in s and s["owner"] != s.get("shard_id"):
            errors.append(f"shards[{i}].owner {s['owner']!r} != shard_id "
                          f"{s.get('shard_id')!r}")
        if "chunks_reassigned_in" in s and (
                not isinstance(s["chunks_reassigned_in"], int)
                or s["chunks_reassigned_in"] < 0):
            errors.append(f"shards[{i}].chunks_reassigned_in invalid: "
                          f"{s['chunks_reassigned_in']!r}")
    n_rows = m.get("n_rows")
    if isinstance(n_rows, int) and prev_hi and prev_hi != n_rows:
        errors.append(f"shard spans cover [0, {prev_hi}) but n_rows is "
                      f"{n_rows}")
    # spans only from well-formed entries: a malformed shard was already
    # reported above, and chunks pointing at it get the not-in-block error
    spans = {s.get("shard_id"): (s["lo"], s["hi"]) for s in shards
             if isinstance(s, dict)
             and isinstance(s.get("lo"), int) and isinstance(s.get("hi"), int)}
    for c in m.get("chunks", []):
        sid = c.get("shard_id")
        if sid is None:
            # a later single-device walk ADOPTING the merged manifest
            # commits retried chunks at the root, untagged and root-rooted
            # — the documented one-directional adoption contract, not a
            # merge bug
            continue
        span = spans.get(sid)
        if span is None:
            errors.append(f"chunk {c.get('lo')}: shard_id {sid!r} not in "
                          "the shards block")
            continue
        if not (span[0] <= c.get("lo", -1) and c.get("hi", 1 << 60) <= span[1]):
            # a chunk outside its committing namespace's nominal span is
            # only legitimate when elastically REASSIGNED — the owner tag
            # says which lane computed it (ISSUE 11)
            if not isinstance(c.get("owner"), int):
                errors.append(f"chunk [{c.get('lo')}, {c.get('hi')}) "
                              f"outside its shard {sid} span {span} and "
                              "not owner-tagged (no elastic reassignment "
                              "can explain it)")
            elif c["owner"] != sid:
                errors.append(f"chunk {c.get('lo')}: owner {c['owner']} "
                              f"disagrees with committing namespace {sid}")
        elif isinstance(c.get("owner"), int) and c["owner"] != sid:
            errors.append(f"chunk {c.get('lo')}: owner {c['owner']} "
                          f"disagrees with committing namespace {sid}")
        d = next((s.get("dir") for s in shards
                  if isinstance(s, dict) and s.get("shard_id") == sid), None)
        if "shard" in c and isinstance(d, str) and \
                not str(c["shard"]).startswith(d + "/"):
            errors.append(f"chunk {c.get('lo')}: npz path {c['shard']!r} "
                          f"not rooted in shard namespace {d!r}")
    for row in ((m.get("telemetry") or {}).get("chunks") or []):
        sid = row.get("shard")
        if sid is not None and sid not in spans:
            errors.append(f"telemetry chunk {row.get('lo')}: shard tag "
                          f"{sid!r} not in the shards block")
    # the rebalance block's reassigned total must agree with what the
    # chunk entries actually show — a drifting count means the merge's
    # reconciliation and the supervisor's record no longer describe the
    # same job
    rb = m.get("rebalance")
    if isinstance(rb, dict) and isinstance(rb.get("reassigned_chunks"), int):
        observed = sum(
            1 for c in m.get("chunks", [])
            if c.get("status") == "committed"
            and c.get("shard_id") in spans
            and not (spans[c["shard_id"]][0] <= c.get("lo", -1)
                     and c.get("hi", 1 << 60) <= spans[c["shard_id"]][1]))
        if observed != rb["reassigned_chunks"]:
            errors.append(f"rebalance.reassigned_chunks "
                          f"{rb['reassigned_chunks']} != {observed} "
                          "owner-tagged chunks outside their namespace "
                          "span")
    return errors


def _validate_rebalance(m: dict) -> list:
    """Validate a merged manifest's elastic ``rebalance`` block (ISSUE 11);
    absent (static/pre-elastic walks, multi-host jobs) passes untouched."""
    rb = m.get("rebalance")
    if rb is None:
        return []
    if not isinstance(rb, dict):
        return [f"rebalance block is not an object: {rb!r}"]
    errors = []
    for k in ("steals", "lane_retries_used", "reassigned_chunks",
              "reassigned_spans"):
        if not isinstance(rb.get(k), int) or rb[k] < 0:
            errors.append(f"rebalance.{k} invalid: {rb.get(k)!r}")
    q = rb.get("quarantined")
    if not isinstance(q, list):
        errors.append(f"rebalance.quarantined invalid: {q!r}")
        return errors
    n_shards = m.get("merged_from_shards")
    for i, rec in enumerate(q):
        if not isinstance(rec, dict):
            errors.append(f"rebalance.quarantined[{i}] not an object: "
                          f"{rec!r}")
            continue
        sid = rec.get("shard_id")
        if not isinstance(sid, int) or (
                isinstance(n_shards, int) and not 0 <= sid < n_shards):
            errors.append(f"rebalance.quarantined[{i}].shard_id invalid: "
                          f"{sid!r}")
        if not isinstance(rec.get("cause"), str) or not rec["cause"]:
            errors.append(f"rebalance.quarantined[{i}].cause missing")
        if not isinstance(rec.get("retries"), int) or rec["retries"] < 0:
            errors.append(f"rebalance.quarantined[{i}].retries invalid: "
                          f"{rec.get('retries')!r}")
    return errors


def validate_prom_sink(prom_path: str, events) -> list:
    """Validate a Prometheus-textfile sink output (ISSUE 12 satellite).

    Delegates to ``obs.promsink.validate_textfile`` — exposition-format
    syntax plus, when the event stream carries a final ``metrics``
    snapshot, the registry cross-check: every counter/gauge/histogram in
    the snapshot must appear in the textfile under its mapped name (a
    rename/drop fails here instead of silently emptying a dashboard).
    The serving gauges the sink adds on top of the registry are allowed —
    the contract is "nothing vanishes", not "nothing extra".
    """
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        from spark_timeseries_tpu.obs import promsink
    except Exception as e:  # noqa: BLE001 - tooling must degrade loudly
        return [f"cannot import obs.promsink to validate {prom_path}: {e}"]
    snapshot = None
    for _, ev in events:
        if ev.get("kind") == "metrics":
            snapshot = {k: ev.get(k) for k in ("counters", "gauges",
                                               "histograms")}
    return [f"prom {prom_path}: {e}"
            for e in promsink.validate_textfile(prom_path,
                                                snapshot=snapshot)]


# ---------------------------------------------------------------------------
# fleet view (ISSUE 18): N replica streams + the client stream, one story
# ---------------------------------------------------------------------------

FLEET_ANNOTATIONS = (
    "fleet.elected", "fleet.step_down", "fleet.fenced",
    "fleet.standby_read", "fleet.torn_result", "server.storage_refusal",
    "client.endpoint_circuit_open", "client.endpoint_half_open",
    "client.endpoint_probe_failed", "client.endpoint_recovered",
    "client.endpoint_redirected", "client.primary_learned",
    # warm routing (ISSUE 19): which leg each auto-fit submit took —
    # across a failover these show whether the survivor stayed warm —
    # and any fenced/failed profile write that forced a cold next pass
    "server.route", "server.profile_refused",
)


def _import_pkg():
    """Make the package importable from the repo checkout (the
    validate_prom_sink pattern)."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def load_fleet(root: str):
    """Load every per-process stream at a fleet root.

    The convention (tests/_chaos_worker.py, tests/_fleet_worker.py):
    each process streams to ``obs_<name>.jsonl`` — replicas under their
    owner name, the storming client as ``obs_client.jsonl`` — next to
    the optional ``chaos_manifest.json`` and the client's
    ``*.clock.json`` offset sidecars.

    Returns ``(streams, merged, clocks, manifest, errors)``:
    ``streams`` maps stream name to the ``(line_no, event)`` list from
    :func:`load_events`; ``merged`` is every line across streams,
    tagged with its ``stream`` name and sorted by ``ts`` (wall clock —
    a same-host ordering; the clock sidecars carry the per-endpoint
    monotonic offsets a cross-host merge would need).
    """
    import glob

    streams, errors = {}, []
    for p in sorted(glob.glob(os.path.join(root, "obs_*.jsonl"))):
        name = os.path.basename(p)[len("obs_"):-len(".jsonl")]
        evs, errs = load_events(p)
        streams[name] = evs
        errors += [f"[{name}] {e}" for e in errs]
    if not streams:
        errors.append(f"fleet root {root}: no obs_*.jsonl streams")
    merged = []
    for name, evs in streams.items():
        for _, ev in evs:
            merged.append({**ev, "stream": name})
    merged.sort(key=lambda ev: (ev["ts"] if isinstance(
        ev.get("ts"), (int, float)) else 0.0))
    clocks = {}
    for p in sorted(glob.glob(os.path.join(root, "*.clock.json"))):
        try:
            with open(p, encoding="utf-8") as f:
                clocks[os.path.basename(p)] = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            errors.append(f"clock sidecar {p}: unreadable ({e})")
    manifest = None
    mp = os.path.join(root, "chaos_manifest.json")
    if os.path.exists(mp):
        try:
            with open(mp, encoding="utf-8") as f:
                manifest = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            errors.append(f"chaos manifest {mp}: unreadable ({e})")
    return streams, merged, clocks, manifest, errors


def _derive_trace(request_id: str):
    """Re-derive ``(trace_id, tracing_module)`` for a request id via
    ``obs.tracing`` — the package is the single source of truth for the
    deterministic derivation, so the tool cannot drift from it."""
    _import_pkg()
    from spark_timeseries_tpu.obs import tracing

    return tracing.derive_trace_id(str(request_id)), tracing


def check_trace(merged, request_id: str) -> list:
    """The ci reconstruction gate (ISSUE 18): one stormed request's
    causal timeline must exist, cross processes, and terminate exactly
    once — a submit origin on the client stream, a server admission on
    a replica stream, and exactly one ``client.result`` terminal (a
    SIGKILLed primary shows a SECOND admission on the survivor, never a
    second terminal)."""
    try:
        tid, _ = _derive_trace(request_id)
    except Exception as e:  # noqa: BLE001 - tooling degrades loudly
        return [f"cannot import obs.tracing to derive trace ids: {e}"]
    mine = [ev for ev in merged
            if (ev.get("trace") or {}).get("trace_id") == tid]
    if not mine:
        return [f"trace {request_id}: no lines carry trace_id {tid}"]
    errors = []
    names = [ev.get("name") for ev in mine]
    streams = sorted({ev["stream"] for ev in mine})
    if "client.submit" not in names:
        errors.append(f"trace {request_id}: no client.submit origin")
    if "server.admit" not in names:
        errors.append(f"trace {request_id}: no server.admit — the "
                      "request never shows up on a replica's timeline")
    n_results = names.count("client.result")
    if n_results != 1:
        errors.append(f"trace {request_id}: {n_results} client.result "
                      "terminals (the contract is exactly one)")
    if len(streams) < 2:
        errors.append(f"trace {request_id}: confined to {streams} — a "
                      "fleet trace must cross processes")
    return errors


def render_trace(merged, request_id: str) -> list:
    """Render one request's causal story across every stream (the
    request-level timeline, then each joined batch trace); returns the
    :func:`check_trace` errors so the render and the gate agree."""
    try:
        tid, tracing = _derive_trace(request_id)
    except Exception as e:  # noqa: BLE001 - tooling degrades loudly
        print(f"cannot derive trace ids: {e}", file=sys.stderr)
        return [str(e)]
    mine = [ev for ev in merged
            if (ev.get("trace") or {}).get("trace_id") == tid]
    print(f"trace {request_id}  trace_id={tid}  ({len(mine)} lines, "
          f"streams {sorted({ev['stream'] for ev in mine})})")

    def _line(ev, t0, pad="  "):
        attrs = ev.get("attrs") or {}
        attrs_s = " ".join(f"{k}={v}" for k, v in attrs.items())
        tail = (f"wall {ev.get('wall_s', 0.0):.4f}s"
                if ev.get("kind") == "span" else "*")
        ts = ev.get("ts") if isinstance(ev.get("ts"), (int, float)) else t0
        print(f"{pad}{ts - t0:9.3f}  [{ev['stream']:<8}] "
              f"{ev.get('name', ''):<24} {tail:<16} {attrs_s}")

    if mine:
        t0 = min(ev["ts"] for ev in mine
                 if isinstance(ev.get("ts"), (int, float)))
        for ev in mine:
            _line(ev, t0)
        # the batch level: the fit work itself runs under the BATCH's
        # content-derived trace; server.batch_member joins the two
        bids = sorted({(ev.get("attrs") or {}).get("batch_id")
                       for ev in mine
                       if ev.get("name") == "server.batch_member"
                       and (ev.get("attrs") or {}).get("batch_id")})
        for bid in bids:
            btid = tracing.derive_trace_id(str(bid))
            bmine = [ev for ev in merged
                     if (ev.get("trace") or {}).get("trace_id") == btid]
            print(f"  batch {bid}  trace_id={btid}  "
                  f"({len(bmine)} lines):")
            for ev in bmine:
                _line(ev, t0, pad="    ")
    return check_trace(merged, request_id)


def _join_chaos(manifest: dict, merged):
    """Join the manifest's injections to their observed consequences
    via ``reliability.chaos.join_injections`` (package import — single
    source of truth for the ordinal-join semantics); None when the
    package is unimportable."""
    _import_pkg()
    try:
        from spark_timeseries_tpu.reliability import chaos
    except Exception as e:  # noqa: BLE001 - tooling degrades loudly
        print(f"cannot import reliability.chaos for the injection join: "
              f"{e}", file=sys.stderr)
        return None
    return chaos.join_injections(manifest.get("fired") or [], merged)


def compute_slo(merged, manifest=None) -> dict:
    """Fleet SLO summary from the merged timeline: availability (the
    share of submitted requests that reached their exactly-once
    terminal), client-observed latency percentiles (first
    ``client.submit`` to first ``client.result`` per request id), and
    failover recovery (takeover latencies from the injection join when
    a chaos manifest rode along)."""
    submits, results = {}, {}
    for ev in merged:
        if ev.get("kind") != "event":
            continue
        rid = (ev.get("attrs") or {}).get("req_id")
        ts = ev.get("ts")
        if rid is None or not isinstance(ts, (int, float)):
            continue
        if ev.get("name") == "client.submit":
            submits.setdefault(rid, ts)
        elif ev.get("name") == "client.result":
            results.setdefault(rid, ts)
    lat = sorted(results[r] - submits[r] for r in results if r in submits)

    def pct(p):
        if not lat:
            return None
        k = max(0, min(len(lat) - 1,
                       int(round(p / 100.0 * (len(lat) - 1)))))
        return round(lat[k], 6)

    takeovers = []
    if manifest:
        joins = _join_chaos(manifest, merged) or []
        takeovers = [j["takeover_latency_s"] for j in joins
                     if j.get("takeover_latency_s") is not None]
    done = sum(1 for r in results if r in submits)
    return {
        "requests_submitted": len(submits),
        "requests_completed": done,
        "availability": round(done / len(submits), 4) if submits else None,
        "latency_p50_s": pct(50),
        "latency_p99_s": pct(99),
        "elections": sum(1 for ev in merged
                         if ev.get("name") == "fleet.elected"),
        "takeover_latencies_s": takeovers,
    }


def render_fleet(streams, merged, clocks, manifest) -> None:
    """The merged fleet view: one lane per process, then the fleet
    annotations (elections, step-downs, circuit transitions), the
    injection-consequence join, and the clock-offset sidecars."""
    stamps = [ev["ts"] for ev in merged
              if isinstance(ev.get("ts"), (int, float))]
    t0 = min(stamps) if stamps else 0.0
    print(f"fleet telemetry: {len(streams)} streams, "
          f"{len(merged)} lines")
    for name in sorted(streams):
        mine = [ev for ev in merged if ev["stream"] == name
                and ev.get("kind") in ("span", "event")]
        n_spans = sum(1 for ev in mine if ev["kind"] == "span")
        print(f"\n  lane {name}  ({n_spans} spans, "
              f"{len(mine) - n_spans} events):")
        for ev in mine:
            attrs = ev.get("attrs") or {}
            attrs_s = " ".join(f"{k}={v}" for k, v in attrs.items())
            mark = " " if ev["kind"] == "span" else "*"
            tr = ev.get("trace") or {}
            tid = f"  [{tr['trace_id']}]" if tr.get("trace_id") else ""
            ts = ev.get("ts") if isinstance(ev.get("ts"),
                                            (int, float)) else t0
            print(f"    {ts - t0:9.3f}  {mark} {ev.get('name', ''):<26} "
                  f"{attrs_s}{tid}")
    ann = [ev for ev in merged if ev.get("kind") == "event"
           and ev.get("name") in FLEET_ANNOTATIONS]
    if ann:
        print(f"\n  fleet annotations ({len(ann)}):")
        for ev in ann:
            attrs = ev.get("attrs") or {}
            attrs_s = " ".join(f"{k}={v}" for k, v in attrs.items())
            ts = ev.get("ts") if isinstance(ev.get("ts"),
                                            (int, float)) else t0
            print(f"    {ts - t0:9.3f}  [{ev['stream']}] "
                  f"{ev['name']} {attrs_s}")
    if manifest:
        joins = _join_chaos(manifest, merged)
        if joins:
            print("\n  chaos injections -> consequences:")
            for j in joins:
                inj = j.get("injection") or {}
                line = (f"    t={inj.get('fired_at_s')}s "
                        f"{inj.get('kind')} {inj.get('target')}")
                if j.get("observed"):
                    line += (f" -> victim {j.get('victim')} fell silent; "
                             f"{j.get('survivor')} elected with token "
                             f"{j.get('elected_token')} (takeover "
                             f"{j.get('takeover_latency_s')}s)")
                else:
                    line += " -> no ownership change observed"
                print(line)
    if clocks:
        print("\n  clock-offset sidecars (endpoint monotonic vs client):")
        for name, rec in sorted(clocks.items()):
            for ep, est in sorted((rec.get("endpoints") or {}).items()):
                print(f"    {name}: {ep} offset "
                      f"{est.get('offset_s')}s (rtt {est.get('rtt_s')}s)")


def summarize(events) -> dict:
    """Timeline + final metrics snapshot of the LATEST run in the stream.

    ``obs.enable(path)`` appends (a crashed run's events survive a rerun
    with the same path), so one file can hold several runs, each starting
    at its own ``meta`` line — report the last one rather than splicing
    runs into a garbled timeline.
    """
    meta_idx = [i for i, (_, ev) in enumerate(events)
                if ev.get("kind") == "meta"]
    run = [ev for _, ev in events[meta_idx[-1]:]] if meta_idx \
        else [ev for _, ev in events]
    meta = run[0] if run and run[0].get("kind") == "meta" else {}
    spans = [ev for ev in run if ev.get("kind") == "span"]
    points = [ev for ev in run if ev.get("kind") == "event"]
    metrics = [ev for ev in run if ev.get("kind") == "metrics"]
    return {
        "run_id": meta.get("run_id"),
        "schema": meta.get("schema"),
        "n_runs_in_stream": max(len(meta_idx), 1),
        "n_spans": len(spans),
        "n_events": len(points),
        "spans": spans,
        "events": points,
        "metrics": metrics[-1] if metrics else None,
    }


def _render(s: dict) -> None:
    extra = (f"  (latest of {s['n_runs_in_stream']} runs in stream)"
             if s.get("n_runs_in_stream", 1) > 1 else "")
    print(f"telemetry run {s['run_id']}  schema {s['schema']}  "
          f"{s['n_spans']} spans, {s['n_events']} events{extra}")
    rows = sorted(s["spans"] + s["events"],
                  key=lambda ev: ev.get("t0", ev.get("ts", 0.0)))
    if rows:
        t_start = min(ev.get("t0", ev.get("ts", 0.0)) for ev in rows)

        def _row(ev, pad="  "):
            off = ev.get("t0", ev.get("ts", 0.0)) - t_start
            indent = "  " * ev.get("depth", 0)
            attrs = ev.get("attrs") or {}
            attrs_s = " ".join(f"{k}={v}" for k, v in attrs.items())
            if ev["kind"] == "span":
                cpu = (f"cpu {ev['process_s']:8.4f}s" if "process_s" in ev
                       else " " * 13)  # a program.build line has none
                print(f"{pad}{off:9.3f}  {indent}{ev['name']:<24} "
                      f"wall {ev['wall_s']:9.4f}s  {cpu}  {attrs_s}")
            else:
                print(f"{pad}{off:9.3f}  {indent}* {ev['name']:<22} {attrs_s}")

        # host-resident walks (ISSUE 7) stage every chunk through the
        # staging pool; those spans (stage.h2d under stage.overlap) get
        # their own lane so the input pipeline reads as one row — the
        # H2D wall is then visually comparable against the compute lane.
        # Scoped to runs that actually staged H2D: an in-HBM prefetched
        # walk also emits stage.overlap spans (device slices, no pool),
        # and those must stay in their chronological timeline
        staging = []
        if any(ev.get("name") == "stage.h2d" for ev in rows):
            staging = [ev for ev in rows
                       if str(ev.get("name", "")).startswith("stage.")]
            staging_ids = {id(ev) for ev in staging}
            rows = [ev for ev in rows if id(ev) not in staging_ids]
        # backtest campaigns (ISSUE 14) wrap each expanding window in a
        # backtest.window span: split the stream into ONE LANE PER
        # WINDOW (rows falling inside the window's wall interval) so the
        # refit-and-score sweep reads as W parallel-structured rows,
        # with campaign-level rows kept in their own section
        wins = [ev for ev in rows if ev["kind"] == "span"
                and ev.get("name") == "backtest.window"]
        if wins:
            wins.sort(key=lambda ev: (ev.get("attrs") or {})
                      .get("window", 0))
            taken = {id(ev) for ev in wins}
            print(f"\ntimeline (s from start; {len(wins)} backtest "
                  "window lanes):")
            for wspan in wins:
                attrs = wspan.get("attrs") or {}
                w0 = wspan.get("t0", 0.0)
                w1 = w0 + wspan.get("wall_s", 0.0)
                mine = [ev for ev in rows if id(ev) not in taken
                        and w0 <= ev.get("t0", ev.get("ts", 0.0)) <= w1]
                taken.update(id(ev) for ev in mine)
                print(f"  window {attrs.get('window')} "
                      f"origin={attrs.get('origin')}  "
                      f"({len(mine)} rows, wall "
                      f"{wspan.get('wall_s', 0.0):.4f}s):")
                for ev in mine:
                    _row(ev, pad="    ")
            drv = [ev for ev in rows if id(ev) not in taken]
            if drv:
                print("  campaign driver:")
                for ev in drv:
                    _row(ev, pad="    ")
            rows = []
        # sharded walks (ISSUE 6) tag every lane's spans/events with its
        # shard id: split the merged stream into ONE LANE PER SHARD so the
        # concurrent walks read as parallel rows, with the driver-level
        # rows (merge, panel spans) kept in their own section
        lanes = sorted({(ev.get("attrs") or {}).get("shard") for ev in rows
                        if (ev.get("attrs") or {}).get("shard") is not None})
        if lanes:
            drv = [ev for ev in rows
                   if (ev.get("attrs") or {}).get("shard") is None]
            # elastic lane events (ISSUE 11) are shard-tagged, so each
            # quarantine/steal/retry already renders INSIDE its lane's row
            # below; the header totals make a degraded run visible at a
            # glance
            elastic_names = ("lane.quarantine", "lane.steal", "lane.retry")
            reb = [ev for ev in rows if ev["kind"] == "event"
                   and ev.get("name") in elastic_names]
            header = f"\ntimeline (s from start; {len(lanes)} sharded lanes"
            if reb:
                counts = {n: sum(1 for ev in reb if ev["name"] == n)
                          for n in elastic_names}
                header += (f"; elastic: {counts['lane.quarantine']} "
                           f"quarantined, {counts['lane.steal']} steals, "
                           f"{counts['lane.retry']} retries")
            print(header + "):")
            for sid in lanes:
                mine = [ev for ev in rows
                        if (ev.get("attrs") or {}).get("shard") == sid]
                wall = sum(ev.get("wall_s", 0.0) for ev in mine
                           if ev["kind"] == "span")
                print(f"  lane shard={sid}  ({len(mine)} rows, "
                      f"span wall {wall:.4f}s):")
                for ev in mine:
                    _row(ev, pad="    ")
            if drv:
                print("  driver:")
                for ev in drv:
                    _row(ev, pad="    ")
        else:
            # auto-fit order search (ISSUE 9): every per-order walk tags
            # its spans/events with its grid index — split the stream into
            # ONE LANE PER ORDER so the G candidate walks read as G rows
            # (the sharded-lane treatment, keyed on the grid), with the
            # search-level rows (selection, panel spans) kept separate
            grids = sorted({(ev.get("attrs") or {}).get("grid")
                            for ev in rows
                            if (ev.get("attrs") or {}).get("grid")
                            is not None})
            if grids:
                drv = [ev for ev in rows
                       if (ev.get("attrs") or {}).get("grid") is None]
                print(f"\ntimeline (s from start; {len(grids)} order-grid "
                      "lanes):")
                for gid in grids:
                    mine = [ev for ev in rows
                            if (ev.get("attrs") or {}).get("grid") == gid]
                    wall = sum(ev.get("wall_s", 0.0) for ev in mine
                               if ev["kind"] == "span")
                    label = next(
                        ((ev.get("attrs") or {}).get("order")
                         for ev in mine
                         if (ev.get("attrs") or {}).get("order")), None)
                    print(f"  lane grid={gid}"
                          + (f" order={label}" if label else "")
                          + f"  ({len(mine)} rows, span wall {wall:.4f}s):")
                    for ev in mine:
                        _row(ev, pad="    ")
                if drv:
                    print("  search driver:")
                    for ev in drv:
                        _row(ev, pad="    ")
            elif rows:  # empty when the campaign lanes consumed them
                print("\ntimeline (s from start):")
                for ev in rows:
                    _row(ev)
        if staging:
            h2d = [ev for ev in staging if ev.get("name") == "stage.h2d"
                   and ev["kind"] == "span"]
            wall = sum(ev.get("wall_s", 0.0) for ev in staging
                       if ev["kind"] == "span")
            mb = sum((ev.get("attrs") or {}).get("bytes", 0)
                     for ev in h2d) / 1e6
            print(f"  staging pool lane  ({len(staging)} rows, "
                  f"span wall {wall:.4f}s, {mb:.2f} MB H2D):")
            for ev in staging:
                _row(ev, pad="    ")
    m = s["metrics"]
    if m:
        print("\ncounters:")
        for k, v in sorted((m.get("counters") or {}).items()):
            print(f"  {k:<40} {v}")
        gauges = m.get("gauges") or {}
        if gauges:
            print("gauges:")
            for k, v in sorted(gauges.items()):
                print(f"  {k:<40} {v}")
        hists = m.get("histograms") or {}
        if hists:
            print("histograms (count/mean/max seconds):")
            for k, h in sorted(hists.items()):
                if h.get("count"):
                    print(f"  {k:<40} n={h['count']:<6} "
                          f"mean={h.get('mean', 0):.5f} "
                          f"max={h.get('max', 0):.5f}")
    else:
        print("\n(no metrics snapshot in stream — run obs.disable() or an "
              "instrumented fit to emit one)")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("events", nargs="?", default=None,
                    help="telemetry JSONL path (obs.enable(path)); "
                         "omitted in --fleet mode")
    ap.add_argument("--check", action="store_true",
                    help="validate the event schema and exit 0/1")
    ap.add_argument("--fleet", default=None, metavar="ROOT",
                    help="fleet mode (ISSUE 18): merge every "
                         "obs_*.jsonl stream at ROOT (+ *.clock.json "
                         "sidecars + chaos_manifest.json) into one "
                         "view; composes with --check/--trace/--slo")
    ap.add_argument("--trace", default=None, metavar="REQUEST_ID",
                    help="with --fleet: render REQUEST_ID's causal "
                         "timeline across every process; with --check, "
                         "gate its reconstruction (submit origin, "
                         "server admission, exactly one terminal, "
                         "more than one process)")
    ap.add_argument("--slo", action="store_true",
                    help="with --fleet: availability / latency "
                         "percentiles / failover-recovery summary")
    ap.add_argument("--manifest", default=None, metavar="CKPT_DIR",
                    help="with --check: also validate the journal "
                         "manifest's embedded telemetry block")
    ap.add_argument("--prom", default=None, metavar="PROM_FILE",
                    help="with --check: validate a Prometheus-textfile "
                         "sink output (obs.promsink) — exposition syntax "
                         "plus name/label agreement with the event "
                         "stream's final metrics snapshot, so a renamed "
                         "counter cannot silently vanish from dashboards")
    ap.add_argument("--degradation", action="store_true",
                    help="with --check: validate the degradation-ladder "
                         "telemetry (ISSUE 17) — the fleet.state gauge "
                         "in the final metrics snapshot and the pinned "
                         "fields of step-down/circuit/hedge/auth events")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable summary instead of the report")
    args = ap.parse_args()
    if args.events is None and args.fleet is None:
        ap.error("need a telemetry JSONL path (or --fleet ROOT)")
    if args.trace is not None and args.fleet is None:
        ap.error("--trace needs --fleet ROOT (the causal timeline "
                 "spans every process's stream)")

    if args.fleet is not None:
        streams, merged, clocks, manifest, errors = load_fleet(args.fleet)
        if args.check:
            for name, evs in sorted(streams.items()):
                errors += [f"[{name}] {e}"
                           for e in validate_events(evs, [])]
            if args.trace is not None:
                errors += check_trace(merged, args.trace)
            if errors:
                for e in errors:
                    print(f"obs_report: FAIL {e}", file=sys.stderr)
                sys.exit(1)
            n = sum(len(v) for v in streams.values())
            extra = ""
            if args.trace is not None:
                tid, _ = _derive_trace(args.trace)
                mine = [ev for ev in merged
                        if (ev.get("trace") or {}).get("trace_id") == tid]
                extra = (f" + trace {args.trace} reconstructed "
                         f"({len(mine)} lines across "
                         f"{len({ev['stream'] for ev in mine})} streams, "
                         "1 terminal)")
            print(f"obs_report: OK — fleet {args.fleet}: "
                  f"{len(streams)} streams, {n} events valid{extra}")
            return
        for e in errors:
            print(f"obs_report: WARNING {e}", file=sys.stderr)
        if args.json:
            out = {"streams": {n: len(v) for n, v in streams.items()},
                   "slo": compute_slo(merged, manifest)}
            if args.trace is not None:
                out["trace_errors"] = check_trace(merged, args.trace)
            print(json.dumps(out, indent=1, sort_keys=True, default=repr))
            return
        shown = False
        if args.trace is not None:
            shown = True
            for e in render_trace(merged, args.trace):
                print(f"obs_report: WARNING {e}", file=sys.stderr)
        if args.slo:
            shown = True
            print("\nfleet SLO:" if args.trace else "fleet SLO:")
            for k, v in compute_slo(merged, manifest).items():
                print(f"  {k:<24} {v}")
        if not shown:
            render_fleet(streams, merged, clocks, manifest)
        return

    events, errors = load_events(args.events)
    if args.check:
        errors = validate_events(events, errors)
        if args.manifest:
            errors += validate_manifest_telemetry(args.manifest)
        if args.prom:
            errors += validate_prom_sink(args.prom, events)
        if args.degradation:
            errors += validate_degradation(events)
        if errors:
            for e in errors:
                print(f"obs_report: FAIL {e}", file=sys.stderr)
            sys.exit(1)
        n = len(events)
        extra = f" + manifest {args.manifest}" if args.manifest else ""
        if args.prom:
            extra += f" + prom textfile {args.prom}"
        if args.degradation:
            extra += " + degradation-ladder telemetry"
        print(f"obs_report: OK — {n} events valid{extra}")
        return
    if errors:
        for e in errors:
            print(f"obs_report: WARNING {e}", file=sys.stderr)
    s = summarize(events)
    if args.json:
        print(json.dumps(s, indent=1, sort_keys=True, default=repr))
        return
    _render(s)


if __name__ == "__main__":
    try:
        main()
    except BrokenPipeError:
        # downstream closed early (`obs_report … | grep -q`, ci.sh under
        # pipefail): not an error — mirror the standard CLI convention
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        sys.exit(0)
