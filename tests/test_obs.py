"""Telemetry plane tests (ISSUE 3, tier-1 CPU).

Two contracts dominate: (1) **invariance** — telemetry observes, never
participates: a fit with the plane enabled is bitwise-identical to the same
fit disabled, including across a journaled kill-and-resume; (2) the
**disabled path is structurally free** — every entry point returns one
shared no-op object, no events accumulate, and result metadata gains no
keys, so pre-PR behavior is preserved byte for byte.  On top of those, the
acceptance scenario: a journaled 8-chunk fit with telemetry on produces a
schema-valid JSONL event log, a manifest ``telemetry`` block with
per-chunk compile/execute span times and ladder-rung counters, and a
non-null peak-memory reading on CPU (host-RSS fallback).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from _obs_helpers import _assert_bitwise, _span_lines
from spark_timeseries_tpu import obs
from spark_timeseries_tpu import reliability as rel
from spark_timeseries_tpu.models import arima
from spark_timeseries_tpu.reliability import faultinject as fi
from spark_timeseries_tpu.utils import optim

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.usefixtures("plane_off")


def _ar_panel(b=32, t=96, seed=7, phi=0.6):
    rng = np.random.default_rng(seed)
    e = rng.normal(size=(b, t)).astype(np.float32)
    y = np.zeros_like(e)
    y[:, 0] = e[:, 0]
    for i in range(1, t):
        y[:, i] = phi * y[:, i - 1] + e[:, i]
    return y


def _fit(y, d=None, **kw):
    return rel.fit_chunked(arima.fit, y, chunk_rows=4, checkpoint_dir=d,
                           order=(1, 0, 0), max_iters=15, **kw)


# ---------------------------------------------------------------------------
# disabled path: structurally a no-op
# ---------------------------------------------------------------------------


class TestDisabled:
    def test_disabled_entry_points_are_shared_noops(self):
        assert not obs.enabled()
        assert obs.span("a") is obs.span("b") is obs.NULL_SPAN
        assert obs.counter("a") is obs.gauge("b") is obs.histogram("c")
        assert obs.snapshot() is None
        assert obs.summary() is None
        obs.event("e", x=1)  # swallowed, no recorder exists
        obs.emit_metrics()
        obs.closed_span("program.build", 0.0, 1.0, program="p")  # swallowed

    def test_disabled_fit_adds_no_meta_and_no_manifest_block(self, tmp_path):
        d = str(tmp_path / "j")
        res = _fit(_ar_panel(), d)
        assert "telemetry" not in res.meta
        m = json.load(open(os.path.join(d, "manifest.json")))
        assert "telemetry" not in m
        assert m["chunks"][0]["peak_hbm_bytes"]  # fallback fills it anyway

    def test_disable_is_idempotent(self):
        obs.disable()
        obs.disable()


# ---------------------------------------------------------------------------
# invariance: telemetry observes, never participates
# ---------------------------------------------------------------------------


class TestInvariance:
    def test_enabled_fit_bitwise_equals_disabled_fit(self, tmp_path):
        y = _ar_panel()
        ref = _fit(y)  # plane off
        obs.enable(str(tmp_path / "ev.jsonl"))
        got = _fit(y)
        _assert_bitwise(got, ref)
        assert "telemetry" in got.meta

    def test_kill_and_resume_with_telemetry_is_bitwise(self, tmp_path):
        """The satellite bar: a journaled crash/resume run with telemetry
        ENABLED matches an uninterrupted (uninstrumented) run bitwise."""
        y = _ar_panel()
        full = _fit(y)  # plane off, unjournaled reference
        d = str(tmp_path / "j")
        obs.enable(str(tmp_path / "ev.jsonl"))
        with pytest.raises(fi.SimulatedCrash):
            _fit(y, d, _journal_commit_hook=fi.crash_after_commits(2))
        res = _fit(y, d)
        _assert_bitwise(res, full)
        assert res.meta["journal"]["chunks_resumed"] == 2
        t = res.meta["telemetry"]
        phases = [c["phase"] for c in t["chunks"]]
        assert phases.count("resumed") == 2
        assert phases.count("execute") + phases.count("compile+execute") == 6

    def test_per_fit_counter_deltas_across_one_enable(self, tmp_path):
        """One obs.enable() spanning two fits: fit B's summary must report
        B's own counts, not inherit fit A's failures (per-fit deltas)."""
        y = _ar_panel()
        obs.enable()
        ff = fi.failing_fit(arima.fit, y, rows=[2], n_failures=9)
        ra = rel.fit_chunked(ff, y, chunk_rows=16, order=(1, 0, 0),
                             max_iters=15)
        assert ra.meta["telemetry"]["counters"]["fit_status.DIVERGED"] == 1
        d = str(tmp_path / "j")
        rb = _fit(y, d)
        assert rb.meta["telemetry"]["counters"]["fit_status.DIVERGED"] == 0
        assert rb.meta["telemetry"]["counters"]["fit_status.OK"] == 32
        m = json.load(open(os.path.join(d, "manifest.json")))
        assert m["telemetry"]["counters"]["fit_status.DIVERGED"] == 0

    def test_mid_run_disable_never_crashes_the_fit(self):
        """disable() landing while a chunked fit is mid-walk (another fit
        in the process tearing down its telemetry) must not take the fit
        down; the partial telemetry block is dropped, never null."""
        import threading
        import time as _t

        y = _ar_panel()
        obs.enable()
        th = threading.Thread(
            target=lambda: (_t.sleep(0.05), obs.disable()))
        slow = fi.hanging_fit(arima.fit, [0, 1], sleep_s=0.2)
        th.start()
        res = rel.fit_chunked(slow, y, chunk_rows=8, resilient=False,
                              order=(1, 0, 0), max_iters=15)
        th.join()
        assert res.params.shape[0] == 32
        t = res.meta.get("telemetry")
        assert t is None or isinstance(t, dict)  # present or dropped, no null

    def test_profile_mode_does_not_change_results(self, tmp_path):
        y = _ar_panel(b=8)
        ref = _fit(y)
        obs.enable(str(tmp_path / "ev.jsonl"), profile=True)
        got = _fit(y)
        _assert_bitwise(got, ref)


# ---------------------------------------------------------------------------
# the acceptance scenario: journaled 8-chunk fit, full surface validated
# ---------------------------------------------------------------------------


class TestAcceptance:
    def test_journaled_8_chunk_fit_full_telemetry_surface(self, tmp_path):
        # 32 rows / chunk_rows=4 -> 8 chunks, of a length no other test of
        # this process fits: the first chunk's programs are built under it
        y = _ar_panel(t=97)
        jsonl = str(tmp_path / "ev.jsonl")
        ck = str(tmp_path / "journal")
        obs.enable(jsonl)
        res = _fit(y, ck)
        t = res.meta["telemetry"]

        # per-chunk compile/execute span times
        assert len(t["chunks"]) == 8
        assert t["chunks"][0]["phase"] == "compile+execute"
        assert all(c["phase"] == "execute" for c in t["chunks"][1:])
        assert all(c["wall_s"] >= 0 and c["process_s"] >= 0
                   for c in t["chunks"])

        # ladder-rung counters present (zero: nothing failed), sanitizer
        # actions, journal commit latency, per-status totals
        for k in ("ladder.retry.attempted", "ladder.retry.rescued",
                  "ladder.fallback.attempted", "ladder.fallback.rescued"):
            assert k in t["counters"]
        assert t["counters"]["sanitize.rows_checked"] == 32
        assert t["counters"]["fit_status.OK"] == 32
        assert t["histograms"]["journal.commit_s"]["count"] == 8

        # non-null peak memory on CPU (host-RSS fallback), source recorded
        assert t["peak_memory"]["bytes"] > 0
        assert t["peak_memory"]["source"] in ("device", "host_rss")

        # manifest embeds the same block; per-chunk entries carry source
        m = json.load(open(os.path.join(ck, "manifest.json")))
        assert m["telemetry"]["run_id"] == t["run_id"]
        assert all(e["peak_hbm_bytes"] and e["peak_hbm_source"]
                   for e in m["chunks"])

        obs.disable()  # flush the closing metrics line

        # the JSONL stream validates under the CI schema gate
        out = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "tools", "obs_report.py"),
             jsonl, "--check", "--manifest", ck],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        # and renders without error
        out = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "tools", "obs_report.py"),
             jsonl],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert "chunk" in out.stdout and "counters:" in out.stdout

    def test_inspect_journal_prints_telemetry(self, tmp_path):
        y = _ar_panel(b=8, t=98)  # a length of its own: chunk 0 builds
        ck = str(tmp_path / "journal")
        obs.enable()
        rel.fit_chunked(arima.fit, y, chunk_rows=4, checkpoint_dir=ck,
                        order=(1, 0, 0), max_iters=15)
        out = subprocess.run(
            [sys.executable,
             os.path.join(_ROOT, "tools", "inspect_journal.py"), ck],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert "telemetry (obs run" in out.stdout
        assert "compile+execute" in out.stdout


# ---------------------------------------------------------------------------
# subsystem units: spans, metrics, recorder, memory, failure dumps
# ---------------------------------------------------------------------------


class TestSpansAndMetrics:
    def test_nested_spans_record_depth_and_order(self, tmp_path):
        p = str(tmp_path / "ev.jsonl")
        obs.enable(p)
        with obs.span("outer"):
            with obs.span("inner", k=1):
                pass
        obs.disable()
        spans = _span_lines(p)
        assert [s["name"] for s in spans] == ["inner", "outer"]
        assert spans[0]["depth"] == 1 and spans[1]["depth"] == 0
        assert spans[0]["attrs"] == {"k": 1}

    def test_metrics_registry_semantics(self):
        obs.enable()
        obs.counter("c").inc()
        obs.counter("c").add(4)
        obs.gauge("g").set(7)
        obs.gauge("peak").max(3)
        obs.gauge("peak").max(1)  # keeps the max
        for v in (0.5, 1.5, 1.0):
            obs.histogram("h").observe(v)
        s = obs.snapshot()
        assert s["counters"]["c"] == 5
        assert s["gauges"]["g"] == 7 and s["gauges"]["peak"] == 3
        h = s["histograms"]["h"]
        assert h["count"] == 3 and h["min"] == 0.5 and h["max"] == 1.5
        assert h["mean"] == pytest.approx(1.0)

    def test_flight_recorder_ring_is_bounded(self, tmp_path):
        obs.enable(ring_size=4)
        for i in range(10):
            obs.event("e", i=i)
        tail = obs.core._STATE.recorder.tail()
        assert len(tail) == 4
        assert tail[-1]["attrs"]["i"] == 9

    def test_enable_returns_fresh_run(self):
        r1 = obs.enable()
        obs.counter("x").inc()
        r2 = obs.enable()  # finalizes the first run
        assert r1 != r2
        assert obs.snapshot()["counters"] == {}

    def test_peak_memory_never_null_on_cpu(self):
        pm = obs.peak_memory()
        assert pm.bytes and pm.bytes > 0
        assert pm.source in ("device", "host_rss")


class TestFailureDump:
    def test_fit_failure_dumps_recorder_tail(self, tmp_path):
        y = _ar_panel(b=8)
        obs.enable(str(tmp_path / "ev.jsonl"))
        # OOM at the floor: backoff cannot help -> OOMBackoffExceeded
        of = fi.oom_fit(arima.fit, max_rows=2)
        with pytest.raises(rel.OOMBackoffExceeded):
            rel.fit_chunked(of, y, chunk_rows=8, min_chunk_rows=4,
                            resilient=False, order=(1, 0, 0), max_iters=15)
        path = obs.last_crash_dump()
        assert path and os.path.exists(path)
        evs = [json.loads(l) for l in open(path)]
        names = [e.get("name") for e in evs if e["kind"] == "event"]
        assert "fit.failure" in names and "chunk.oom_backoff" in names
        assert evs[-1]["kind"] == "metrics"
        assert evs[-1]["counters"]["chunked.oom_backoffs"] >= 1

    def test_disabled_failure_dumps_nothing(self):
        obs.enable()  # fresh run clears any previous crash record...
        obs.disable()  # ...and the plane is OFF for the failing fit
        y = _ar_panel(b=8)
        of = fi.oom_fit(arima.fit, max_rows=2)
        with pytest.raises(rel.OOMBackoffExceeded):
            rel.fit_chunked(of, y, chunk_rows=8, min_chunk_rows=4,
                            resilient=False, order=(1, 0, 0), max_iters=15)
        assert obs.last_crash_dump() is None


# ---------------------------------------------------------------------------
# instrumented neighbors: ladder counters, map_series cache, optim stage 2
# ---------------------------------------------------------------------------


class TestDeferredScalars:
    """``obs.defer`` / ``obs.settle`` (ISSUE 38): device scalars kept for a
    span that closes later on the same thread."""

    class Untouchable:
        def __getattribute__(self, name):
            raise AssertionError(f"a disabled defer touched .{name}")

    def test_disabled_defer_returns_before_its_arguments(self):
        assert obs.defer(stage2_iters=self.Untouchable()) is None
        assert obs.settle() == {}

    def test_repeated_names_sum_and_settle_empties(self):
        obs.enable()
        obs.defer(a=1, b=jnp.int32(2))
        obs.defer(a=jnp.asarray(3, jnp.int32))
        got = obs.settle()
        assert got == {"a": 4, "b": 2}
        assert all(type(v) is int for v in got.values())
        assert obs.settle() == {}

    def test_unsettled_handles_are_folded_not_piled_up(self):
        # a fit outside resilient_fit defers and nobody settles: the pile
        # stays bounded and the sum exact
        from spark_timeseries_tpu.obs import core

        obs.enable()
        for _ in range(3 * core._DEFERRED_MAX + 5):
            obs.defer(a=jnp.int32(2))
        assert len(core._TLS.deferred[1]["a"]) <= core._DEFERRED_MAX
        assert obs.settle() == {"a": 2 * (3 * core._DEFERRED_MAX + 5)}

    def test_pending_is_the_threads_own_and_dies_with_the_run(self):
        import threading

        obs.enable()
        obs.defer(a=1)
        seen = []
        t = threading.Thread(target=lambda: seen.append(obs.settle()))
        t.start()
        t.join()
        assert seen == [{}]
        obs.disable()  # the handles are dropped unread
        obs.enable()
        assert obs.settle() == {}

    def test_a_raising_fit_leaves_nothing_for_the_next_chunk(self, tmp_path):
        calls = []

        def fit(y, **kw):
            calls.append(1)
            if len(calls) == 1:
                obs.defer(stage2_iters=5, stage2_trials=40)
                raise RuntimeError("boom")
            return arima.fit(y, (1, 0, 0), max_iters=5)

        p = str(tmp_path / "ev.jsonl")
        obs.enable(p)
        y = _ar_panel(b=4)
        with pytest.raises(RuntimeError):
            rel.resilient_fit(fit, y, ladder=())
        rel.resilient_fit(fit, y, ladder=())
        obs.disable()
        back = [s for s in _span_lines(p) if s["name"] == "fit.readback"]
        assert len(back) == 1
        assert set(back[0]["attrs"]) == {"rows", "iters_max", "iters_sum",
                                         "failed"}


class TestInstrumentation:
    def test_ladder_counters_count_attempts_and_rescues(self):
        y = _ar_panel(b=8)
        ff = fi.failing_fit(arima.fit, y, rows=[2], n_failures=1)
        obs.enable()
        rel.resilient_fit(ff, y, order=(1, 0, 0), max_iters=15)
        s = obs.snapshot()
        assert s["counters"]["ladder.retry.attempted"] == 1
        assert s["counters"]["ladder.retry.rescued"] == 1
        assert s["counters"]["ladder.fallback.attempted"] == 0

    def test_watchdog_timeout_counted(self):
        import time as _t

        from spark_timeseries_tpu.reliability import watchdog as wd

        obs.enable()
        with pytest.raises(wd.DeadlineExceeded):
            wd.call_with_deadline(lambda: _t.sleep(5.0), 0.1)
        assert obs.snapshot()["counters"]["watchdog.deadline_exceeded"] == 1

    def test_map_series_cache_hit_miss_counters(self):
        from spark_timeseries_tpu import index as dtix
        from spark_timeseries_tpu import panel as panel_mod

        idx = dtix.uniform("2024-01-01", periods=16,
                           frequency=dtix.DayFrequency(1))
        p = panel_mod.TimeSeriesPanel(
            idx, [f"s{i}" for i in range(4)],
            np.arange(64, dtype=np.float32).reshape(4, 16))
        obs.enable()
        p.map_series(lambda v: v * 2.0)
        p.map_series(lambda v: v * 2.0)  # textually identical -> cache hit
        s = obs.snapshot()
        assert s["counters"]["panel.map_series.cache_hits"] >= 1
        assert s["counters"].get("panel.map_series.cache_misses", 0) >= 1

    def test_optim_stage2_compact_trace_counter(self):
        rng = np.random.default_rng(0)
        scales = jnp.asarray(
            rng.uniform(0.05, 50.0, size=(64, 3)).astype(np.float32))
        target = jnp.asarray(rng.normal(size=(64, 3)).astype(np.float32))

        def fb(x):
            r = (x - target) * scales
            return jnp.sum(r**2, axis=-1)

        def straggler_fun(idx):
            sc, tg = scales[idx], target[idx]
            return lambda x: jnp.sum(((x - tg) * sc) ** 2, axis=-1)

        obs.enable()
        optim.minimize_lbfgs_batched(
            fb, jnp.zeros((64, 3), jnp.float32), max_iters=60,
            straggler_fun=straggler_fun, straggler_cap=16)
        assert obs.snapshot()["counters"]["optim.stage2_compact_traces"] >= 1

    def test_compat_fit_model_span_recorded(self, tmp_path):
        from spark_timeseries_tpu.compat import sparkts

        p = str(tmp_path / "ev.jsonl")
        obs.enable(p)
        sparkts.EWMA.fit_model(jnp.asarray(_ar_panel(b=2, t=64)))
        obs.disable()
        spans = [json.loads(l) for l in open(p)
                 if json.loads(l).get("kind") == "span"]
        assert any(s["name"] == "compat.fit_model"
                   and s["attrs"]["model"] == "EWMA" for s in spans)


# ---------------------------------------------------------------------------
# span identity and the walk path's spans (ISSUE 25, schema v3)
# ---------------------------------------------------------------------------


def _traced_walk(tmp_path, name="ev.jsonl", **kw):
    """One journaled two-chunk walk with the plane on: ``(result, span
    lines)``."""
    p = str(tmp_path / name)
    obs.enable(p)
    res = _fit(_ar_panel(b=8), str(tmp_path / (name + ".journal")), **kw)
    obs.disable()
    return res, _span_lines(p)


_CHUNK_PHASES = ["chunk.plan", "chunk", "fit.readback", "chunk.submit"]


class TestSpanIdentity:
    def test_ids_unique_and_every_parent_in_the_same_walk(self, tmp_path):
        _, spans = _traced_walk(tmp_path)
        by_id = {s["id"]: s for s in spans}
        assert len(by_id) == len(spans)
        assert all(isinstance(i, int) and i > 0 for i in by_id)
        roots = [s for s in spans if s["parent"] is None]
        assert [s["name"] for s in roots] == ["walk"]
        for s in spans:
            assert s["walk"] == roots[0]["walk"]
            if s["parent"] is not None:
                assert by_id[s["parent"]]["walk"] == s["walk"], s

    def test_link_and_scope_carry_parent_and_walk_across_threads(self):
        import threading

        from spark_timeseries_tpu.obs import core

        obs.enable()
        with obs.walk_span() as root, obs.span("submitter") as sub:
            link = obs.span_link()

            def work():
                with obs.span("handed", parent=link):
                    pass
                with obs.span_scope(link), obs.span("adopted"):
                    with obs.span("nested"):
                        pass
                with obs.span("stray"):
                    pass

            th = threading.Thread(target=work)
            th.start()
            th.join(timeout=30)
            assert not th.is_alive()
        got = {s["name"]: s for s in core._STATE.recorder.tail()
               if s["kind"] == "span"}
        assert link == (sub.id, root.walk)
        assert got["handed"]["parent"] == got["adopted"]["parent"] == sub.id
        assert got["nested"]["parent"] == got["adopted"]["id"]
        assert got["handed"]["walk"] == got["nested"]["walk"] == root.walk
        assert got["stray"]["parent"] is None and "walk" not in got["stray"]

    def test_a_span_left_open_is_closed_by_its_parent(self):
        from spark_timeseries_tpu.obs import core

        obs.enable()
        with pytest.raises(RuntimeError):
            with obs.span("outer") as outer:
                obs.span("entered.by.hand").__enter__()
                raise RuntimeError("boom")
        got = {s["name"]: s for s in core._STATE.recorder.tail()
               if s["kind"] == "span"}
        assert got["entered.by.hand"]["parent"] == outer.id
        assert got["entered.by.hand"]["error"] == "RuntimeError"
        assert obs.current_span() is obs.NULL_SPAN  # the stack is empty
        with obs.span("next") as nxt:
            assert nxt.parent is None

    def test_two_walks_in_one_enable_get_two_walk_ids(self, tmp_path):
        p = str(tmp_path / "ev.jsonl")
        obs.enable(p)
        y = _ar_panel(b=8)
        _fit(y)
        _fit(y, str(tmp_path / "j"))
        obs.disable()
        spans = _span_lines(p)
        roots = [s for s in spans if s["name"] == "walk"]
        assert [r["walk"] for r in roots] == [1, 2]
        assert [r["attrs"]["journaled"] for r in roots] == [False, True]
        assert roots[0]["attrs"] == {"rows": 8, "chunk_rows": 4, "lanes": 1,
                                     "journaled": False}
        for r in roots:
            mine = [s for s in spans if s.get("walk") == r["walk"]]
            assert sum(s["name"] == "chunk" for s in mine) == 2
            assert {s["name"] for s in mine} >= {"walk.open", "walk.close"}

    def test_profile_annotation_carries_the_span_id(self, monkeypatch):
        from spark_timeseries_tpu.obs import core

        made = []

        class Annotation:
            def __init__(self, name, **stats):
                made.append((name, stats))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(core, "_trace_annotation", lambda: Annotation)
        obs.enable(profile=True)
        with obs.span("chunk") as sp:
            pass
        assert made == [("chunk", {"span_id": sp.id})]

    @pytest.mark.parametrize("line,ok", [
        # a v2 line: no identity at all
        ({"kind": "span", "name": "chunk", "t0": 1.0, "wall_s": 0.1,
          "process_s": 0.1, "depth": 0}, True),
        ({"kind": "span", "name": "chunk", "t0": 1.0, "wall_s": 0.1,
          "process_s": 0.1, "depth": 0, "id": 7, "parent": None,
          "walk": 1}, True),
        ({"kind": "span", "name": "chunk", "t0": 1.0, "wall_s": 0.1,
          "process_s": 0.1, "depth": 0, "id": "7", "parent": None}, False),
        ({"kind": "span", "name": "chunk", "t0": 1.0, "wall_s": 0.1,
          "process_s": 0.1, "depth": 0, "id": 7, "parent": 0}, False),
        ({"kind": "span", "name": "chunk", "t0": 1.0, "wall_s": 0.1,
          "process_s": 0.1, "depth": 0, "parent": 3}, False),
    ], ids=["v2", "v3", "id-not-int", "parent-not-positive",
            "parent-without-id"])
    def test_obs_report_check_takes_v2_and_v3_lines(self, tmp_path, line, ok):
        p = str(tmp_path / "ev.jsonl")
        with open(p, "w") as f:
            f.write(json.dumps({"kind": "meta", "schema": 2 if "id" not in
                                line else 3, "run_id": "r", "pid": 1,
                                "ts": 1.0}) + "\n")
            f.write(json.dumps(dict(line, ts=1.0)) + "\n")
        out = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "tools", "obs_report.py"),
             p, "--check"], capture_output=True, text=True, timeout=120)
        assert (out.returncode == 0) == ok, out.stderr

    def test_obs_report_check_refuses_a_repeated_id(self, tmp_path):
        _, spans = _traced_walk(tmp_path)
        p = str(tmp_path / "ev.jsonl")
        with open(p, "a") as f:
            f.write(json.dumps(spans[0]) + "\n")
        out = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "tools", "obs_report.py"),
             p, "--check"], capture_output=True, text=True, timeout=120)
        assert out.returncode == 1 and "repeats" in out.stderr


class TestWalkPathSpans:
    def test_each_chunk_has_its_four_phases_in_order(self, tmp_path):
        _, spans = _traced_walk(tmp_path)
        root = next(s for s in spans if s["name"] == "walk")
        # a span's line is written when it closes, so the driver's own
        # lines (the walk's direct children and fit.readback) are in order
        driver = [s for s in sorted(spans, key=lambda s: s["t0"])
                  if s["name"] in _CHUNK_PHASES]

        def chunk_lo(s):
            if s["name"] == "fit.readback":
                return _chunk_of(s, spans)["attrs"]["lo"]
            return s["attrs"]["lo"] if "hi" in s["attrs"] else None

        for lo, hi in ((0, 4), (4, 8)):
            mine = [s for s in driver if chunk_lo(s) == lo]
            assert [s["name"] for s in mine] == _CHUNK_PHASES
            plan, chunk, readback, submit = mine
            assert plan["attrs"]["hi"] == chunk["attrs"]["hi"] == hi
            assert submit["attrs"] == {"lo": lo, "hi": hi}
            assert plan["parent"] == chunk["parent"] == submit["parent"] \
                == root["id"]
            assert readback["parent"] == chunk["id"]
            assert readback["attrs"]["rows"] == 4
        # the walk's last turn (the final drain) plans nothing
        last = [s for s in driver if s["name"] == "chunk.plan"
                and "hi" not in s["attrs"]]
        assert [s["attrs"] for s in last] == [{"lo": 8}]

    def test_commit_overlap_names_its_submitter(self, tmp_path):
        _, spans = _traced_walk(tmp_path)
        by_id = {s["id"]: s for s in spans}
        commits = [s for s in spans if s["name"] == "commit.overlap"]
        assert len(commits) == 2
        for c in commits:
            sub = by_id[c["parent"]]
            assert sub["name"] == "chunk.submit"
            assert sub["attrs"] == c["attrs"]  # the same [lo, hi)
            assert c["depth"] == 0  # first on the committer thread's stack

    def test_stage_overlap_names_the_chunk_that_scheduled_it(self, tmp_path):
        _, spans = _traced_walk(tmp_path)
        by_id = {s["id"]: s for s in spans}
        staged = [s for s in spans if s["name"] == "stage.overlap"]
        assert [s["attrs"] for s in staged] == [{"lo": 4, "hi": 8}]
        assert by_id[staged[0]["parent"]]["name"] == "chunk"
        assert by_id[staged[0]["parent"]]["attrs"]["lo"] == 0

    def test_watchdog_worker_spans_name_their_chunk(self, tmp_path):
        _, spans = _traced_walk(tmp_path, chunk_budget_s=120.0)
        by_id = {s["id"]: s for s in spans}
        inner = [s for s in spans
                 if s["name"] in ("sanitize", "fit.primary", "fit.readback")]
        assert len(inner) == 6
        for s in inner:
            assert by_id[s["parent"]]["name"] == "chunk"
            assert s["depth"] == 0  # the worker thread's own stack
            assert s["walk"] == 1

    def test_readback_counts_the_rows_iterations(self, tmp_path):
        res, spans = _traced_walk(tmp_path)
        iters = np.asarray(res.iters)
        for s in (s for s in spans if s["name"] == "fit.readback"):
            lo = _chunk_of(s, spans)["attrs"]["lo"]
            mine = iters[lo:lo + 4]
            assert s["attrs"] == {"rows": 4, "iters_max": int(mine.max()),
                                  "iters_sum": int(mine.sum()), "failed": 0}

    def test_walk_off_emits_nothing_and_is_bitwise(self, tmp_path):
        """The invariance contract over the new sites: the walk that
        emitted the tree above, with the plane off, touches no recorder
        and returns the same bytes."""
        from spark_timeseries_tpu.obs import core

        on, spans = _traced_walk(tmp_path, chunk_budget_s=120.0)
        assert {s["name"] for s in spans} >= {
            "walk", "walk.open", "chunk.plan", "chunk", "fit.readback",
            "chunk.submit", "commit.overlap", "walk.close"}
        off = _fit(_ar_panel(b=8), str(tmp_path / "off"),
                   chunk_budget_s=120.0)
        assert core._STATE.recorder is None
        assert obs.span_link() is None
        assert obs.current_span() is obs.walk_span() is obs.NULL_SPAN
        _assert_bitwise(on, off)
        assert "telemetry" not in off.meta

    def test_failed_open_closes_the_walk_tree(self, tmp_path):
        p = str(tmp_path / "ev.jsonl")
        obs.enable(p)
        with pytest.raises(ValueError):
            _fit(_ar_panel(b=8), grid=(3, 2))  # refused inside walk.open
        assert obs.current_span() is obs.NULL_SPAN
        obs.disable()
        spans = {s["name"]: s for s in _span_lines(p)}
        assert set(spans) == {"walk", "walk.open"}
        assert spans["walk.open"]["error"] == spans["walk"]["error"] \
            == "ValueError"


def _chunk_of(span, spans):
    by_id = {s["id"]: s for s in spans}
    while span["name"] != "chunk":
        span = by_id[span["parent"]]
    return span
