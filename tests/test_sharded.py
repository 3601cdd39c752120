"""Sharded chunk-walk tests (ISSUE 6, tier-1 CPU, 8 forced devices).

The acceptance bar: ``fit_chunked(shard=True)`` partitions the chunk grid
across the mesh's series-axis devices — one journaled prefetch → compute →
commit lane per shard — and the result is BITWISE-IDENTICAL to the
single-device walk on the same panel; a crash/preemption resume replays
only the shard chunks that did not commit; and shard/process 0 writes
exactly ONE merged job manifest.  Plus the plan/scheduler extraction
itself (satellite: serial, pipelined, and sharded walks all build from the
same ``ExecutionPlan``; plan knobs stay outside the journal config hash so
journals cross-resume between modes), exercised in-process on the forced
8-device CPU mesh from ``conftest.py`` — no subprocess, no skips.
"""

import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from spark_timeseries_tpu import index as dtix
from spark_timeseries_tpu import obs
from spark_timeseries_tpu import panel as panel_mod
from spark_timeseries_tpu.compat import sparkts
from spark_timeseries_tpu.models import arima, ewma
from spark_timeseries_tpu.parallel import mesh as meshlib
from spark_timeseries_tpu import reliability as rel
from spark_timeseries_tpu.reliability import faultinject as fi
from spark_timeseries_tpu.reliability import plan as plan_mod

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ar_panel(b=48, t=96, seed=7, phi=0.6):
    rng = np.random.default_rng(seed)
    e = rng.normal(size=(b, t)).astype(np.float32)
    y = np.zeros_like(e)
    y[:, 0] = e[:, 0]
    for i in range(1, t):
        y[:, i] = phi * y[:, i - 1] + e[:, i]
    return y


def _assert_bitwise(a, b):
    for f in ("params", "neg_log_likelihood", "converged", "iters", "status"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f"field {f!r} differs")


def _manifest(d):
    return json.load(open(os.path.join(d, "manifest.json")))


# ---------------------------------------------------------------------------
# shard_spans: the chunk-grid partition the bitwise contract rests on
# ---------------------------------------------------------------------------


class TestShardSpans:
    def test_even_split(self):
        assert list(plan_mod.shard_spans(64, 8, 8)) == [
            (i * 8, (i + 1) * 8) for i in range(8)]

    def test_whole_chunks_per_shard(self):
        # 10 chunks over 4 shards: 3/3/2/2 chunks, never a split chunk
        spans = list(plan_mod.shard_spans(80, 8, 4))
        assert spans == [(0, 24), (24, 48), (48, 64), (64, 80)]
        for lo, hi in spans:
            assert lo % 8 == 0  # every boundary is a single-device boundary

    def test_ragged_tail(self):
        # 52 rows in chunks of 8: 7 chunks, last one short — the tail stays
        # inside the last span and boundaries stay on the chunk grid
        spans = list(plan_mod.shard_spans(52, 8, 4))
        assert spans[0] == (0, 16) and spans[-1][1] == 52
        assert [hi - lo for lo, hi in spans] == [16, 16, 16, 4]

    def test_fewer_chunks_than_shards(self):
        spans = list(plan_mod.shard_spans(16, 8, 8))
        assert spans == [(0, 8), (8, 16)]  # 2 chunks -> 2 lanes, not 8

    def test_single_shard(self):
        assert list(plan_mod.shard_spans(100, 8, 1)) == [(0, 100)]

    def test_covers_panel_contiguously(self):
        for b, c, s in ((100, 7, 5), (33, 4, 8), (8, 8, 8), (9, 2, 3)):
            spans = list(plan_mod.shard_spans(b, c, s))
            assert spans[0][0] == 0 and spans[-1][1] == b
            for (_, h1), (l2, _) in zip(spans, spans[1:]):
                assert h1 == l2


# ---------------------------------------------------------------------------
# bitwise identity: sharded == single-device, across knob surfaces
# ---------------------------------------------------------------------------


class TestShardedBitwise:
    def test_sharded_matches_single_device(self, lane_mesh):
        y = _ar_panel()
        single = rel.fit_chunked(ewma.fit, y, chunk_rows=6, resilient=False)
        shard = rel.fit_chunked(ewma.fit, y, chunk_rows=6, resilient=False,
                                shard=True)
        _assert_bitwise(shard, single)
        sh = shard.meta["shards"]
        assert sh["n_shards"] == 8 and sh["lanes_run"] == 8
        assert len(set(sh["devices"])) == 8  # one lane per device
        assert "shards" not in single.meta

    def test_default_chunking_one_chunk_per_shard(self, lane_mesh):
        y = _ar_panel(b=64)
        single = rel.fit_chunked(ewma.fit, y, chunk_rows=8, resilient=False)
        shard = rel.fit_chunked(ewma.fit, y, resilient=False, shard=True)
        _assert_bitwise(shard, single)  # 64/8 devices -> 8-row chunks
        assert shard.meta["chunk_rows_initial"] == 8
        assert shard.meta["chunks_run"] == 8

    def test_uneven_tail_lanes(self, lane_mesh):
        # 52 rows in chunks of 8 -> 7 chunks over 8 devices: 7 lanes, the
        # last walking the short tail chunk; boundaries match single-device
        y = _ar_panel(b=52)
        single = rel.fit_chunked(ewma.fit, y, chunk_rows=8, resilient=False)
        shard = rel.fit_chunked(ewma.fit, y, chunk_rows=8, resilient=False,
                                shard=True)
        _assert_bitwise(shard, single)
        assert shard.meta["shards"]["n_shards"] == 7

    def test_explicit_mesh_subset(self, cpu_devices):
        y = _ar_panel(b=32)
        mesh4 = meshlib.default_mesh(devices=cpu_devices[:4])
        single = rel.fit_chunked(ewma.fit, y, chunk_rows=4, resilient=False)
        shard = rel.fit_chunked(ewma.fit, y, chunk_rows=4, resilient=False,
                                mesh=mesh4)
        _assert_bitwise(shard, single)
        assert shard.meta["shards"]["n_shards"] == 4

    def test_resilient_sharded_matches(self, lane_mesh):
        y = _ar_panel(b=32)
        y[3, 10:14] = np.nan  # the ladder path, per lane
        single = rel.fit_chunked(arima.fit, y, chunk_rows=4, resilient=True,
                                 order=(1, 0, 0), max_iters=20)
        shard = rel.fit_chunked(arima.fit, y, chunk_rows=4, resilient=True,
                                shard=True, order=(1, 0, 0), max_iters=20)
        _assert_bitwise(shard, single)

    def test_time_sharded_mesh_rejected(self, cpu_devices):
        mesh2d = meshlib.default_mesh(time_shards=2, devices=cpu_devices)
        with pytest.raises(ValueError, match="1-D"):
            rel.fit_chunked(ewma.fit, _ar_panel(b=16), chunk_rows=4,
                            resilient=False, mesh=mesh2d)

    def test_panel_fit_shard_knob(self, lane_mesh):
        y = _ar_panel(b=32)
        ix = dtix.uniform("2022-01-03", y.shape[1], dtix.DayFrequency(1))
        p = panel_mod.TimeSeriesPanel(ix, [f"s{i}" for i in range(32)],
                                      jnp.asarray(y))
        single = p.fit("ewma", chunk_rows=4, resilient=False)
        shard = p.fit("ewma", chunk_rows=4, resilient=False, shard=True)
        _assert_bitwise(shard, single)
        assert shard.meta["shards"]["n_shards"] == 8

    def test_compat_fit_model_shard_knob(self, lane_mesh, tmp_path):
        y = _ar_panel(b=16)
        plain = sparkts.EWMA.fit_model(y, checkpoint_dir=str(tmp_path / "a"),
                                       chunk_rows=2)
        sharded = sparkts.EWMA.fit_model(y, checkpoint_dir=str(tmp_path / "b"),
                                         chunk_rows=2, shard=True)
        np.testing.assert_array_equal(np.asarray(plain.params),
                                      np.asarray(sharded.params))
        assert _manifest(str(tmp_path / "b"))["merged_from_shards"] == 8


# ---------------------------------------------------------------------------
# journaled sharded walks: namespaces, the merge, crash/resume
# ---------------------------------------------------------------------------


class TestShardedJournal:
    def _fit(self, y, d=None, **kw):
        kw.setdefault("chunk_rows", 4)
        kw.setdefault("resilient", False)
        kw.setdefault("max_iters", 20)
        return rel.fit_chunked(arima.fit, y, checkpoint_dir=d,
                               order=(1, 0, 0), **kw)

    @pytest.mark.slow  # tier-1 budget: runs in ci.sh's unfiltered pass;
    # sibling sharded-bitwise tests keep the walk itself in tier-1
    def test_merged_manifest_structure(self, lane_mesh, tmp_path):
        y = _ar_panel(b=32)  # 8 chunks over 8 lanes
        d = str(tmp_path / "j")
        res = self._fit(y, d, shard=True)
        # exactly ONE root manifest; lanes journal under shard namespaces
        roots = glob.glob(os.path.join(d, "**", "manifest.json"),
                          recursive=True)
        assert roots == [os.path.join(d, "manifest.json")]
        assert sorted(os.path.basename(p) for p in glob.glob(
            os.path.join(d, "shard_*"))) == [
                f"shard_{i:05d}" for i in range(8)]
        m = _manifest(d)
        assert m["merged_from_shards"] == 8
        assert [s["shard_id"] for s in m["shards"]] == list(range(8))
        assert all(s["chunks_committed"] == 1 for s in m["shards"])
        # merged entries are shard-tagged, sorted, and their npz paths
        # resolve from the ROOT (the single-device adoption contract)
        los = [c["lo"] for c in m["chunks"]]
        assert los == sorted(los) and len(los) == 8
        for c in m["chunks"]:
            assert c["shard_id"] == c["lo"] // 4
            assert os.path.exists(os.path.join(d, c["shard"]))
        j = res.meta["journal"]
        assert j["merged_shards"] == 8 and j["chunks_committed"] == 8
        assert j["chunks_resumed"] == 0

    def test_crash_resume_replays_only_uncommitted(self, lane_mesh, tmp_path):
        # 16 chunks over 8 lanes (2 each): the crash lands while most lanes
        # still have an unwalked second chunk, so the resume genuinely
        # recomputes, not just rehydrates
        y = _ar_panel(b=64)
        full = self._fit(y)
        d = str(tmp_path / "j")
        with pytest.raises(fi.SimulatedCrash):
            self._fit(y, d, shard=True,
                      _journal_commit_hook=fi.crash_after_commits(3))
        assert not os.path.exists(os.path.join(d, "manifest.json"))
        committed = sum(
            sum(1 for c in json.load(open(mp))["chunks"]
                if c["status"] == "committed")
            for mp in glob.glob(os.path.join(d, "shard_*", "manifest.*.json")))
        # every lane dies on its first raising commit (itself durable), so
        # some chunks are durable, the rest pending
        assert 3 <= committed < 16
        res = self._fit(y, d, shard=True)
        _assert_bitwise(res, full)
        assert res.meta["journal"]["chunks_resumed"] == committed
        assert res.meta["journal"]["chunks_committed"] == 16

    def test_cross_mode_resume_sharded_pipeline_knobs(self, lane_mesh,
                                                      tmp_path):
        """Plan knobs (pipeline, prefetch) stay outside the config hash:
        a sharded journal written pipelined resumes under a serial sharded
        walk of the same job."""
        y = _ar_panel(b=32)
        full = self._fit(y)
        d = str(tmp_path / "j")
        with pytest.raises(fi.SimulatedCrash):
            self._fit(y, d, shard=True, pipeline=True,
                      _journal_commit_hook=fi.crash_after_commits(3))
        res = self._fit(y, d, shard=True, pipeline=False, prefetch_depth=0)
        _assert_bitwise(res, full)
        assert res.meta["journal"]["chunks_resumed"] >= 3

    def test_merged_manifest_adopted_by_single_device_walk(self, lane_mesh,
                                                           tmp_path):
        """The merged job manifest satisfies the resume contract for a
        LATER single-device walk of the same (panel, config): every chunk
        rehydrates from its shard-namespace npz, zero recomputes."""
        y = _ar_panel(b=32)
        d = str(tmp_path / "j")
        sharded = self._fit(y, d, shard=True)
        single = self._fit(y, d)  # same dir, no shard= — adopts the merge
        _assert_bitwise(single, sharded)
        assert single.meta["journal"]["chunks_resumed"] == 8
        assert single.meta["chunks_run"] == 8

    def test_stale_shard_layout_rejected(self, cpu_devices, tmp_path):
        y = _ar_panel(b=32)
        d = str(tmp_path / "j")
        self._fit(y, d, shard=True)  # 8 lanes
        mesh4 = meshlib.default_mesh(devices=cpu_devices[:4])
        with pytest.raises(rel.StaleJournalError, match="shard layout"):
            self._fit(y, d, mesh=mesh4)  # 4 lanes: another job's boundaries

    def test_sharded_telemetry_merged_timeline(self, lane_mesh, tmp_path):
        y = _ar_panel(b=32)
        d = str(tmp_path / "j")
        off = self._fit(y)
        obs.enable(str(tmp_path / "ev.jsonl"))
        try:
            on = self._fit(y, d, shard=True)
        finally:
            obs.disable()
        _assert_bitwise(on, off)  # telemetry stays bitwise-inert
        chunks = on.meta["telemetry"]["chunks"]
        assert [c["lo"] for c in chunks] == sorted(c["lo"] for c in chunks)
        assert sorted({c["shard"] for c in chunks}) == list(range(8))
        # the merged manifest carries the shard-tagged timeline
        m = _manifest(d)
        assert {c["shard"] for c in m["telemetry"]["chunks"]} == set(range(8))
        # per-shard overlap accounting rides meta["pipeline"]["shards"]
        pipe = on.meta["pipeline"]
        assert [s["shard"] for s in pipe["shards"]] == list(range(8))
        assert pipe["commits_background"] == 8

    @pytest.mark.slow  # 4 fresh 8-device interpreters (~1 min): tier-2 here;
    # ci.sh runs this EXACT smoke unconditionally, and the in-process
    # crash-resume coverage above stays tier-1
    def test_sigkill_smoke_subprocess(self, tmp_path):
        """Real process death mid-sharded-job (the ci.sh smoke, runnable
        here with ``-m slow``): SIGKILL after 5 durable commits, resume,
        bitwise vs uninterrupted sharded AND single-device runs, one merged
        manifest."""
        worker = os.path.join(_ROOT, "tests", "_sharded_worker.py")
        r = subprocess.run([sys.executable, worker, "--smoke"], cwd=_ROOT,
                           env={**os.environ, "JAX_PLATFORMS": "cpu"},
                           capture_output=True, text=True, timeout=900)
        assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
        assert "PASS" in r.stdout


# ---------------------------------------------------------------------------
# the plan/scheduler extraction (satellite): one plan, one-to-N lanes
# ---------------------------------------------------------------------------


class TestPlanExtraction:
    def test_exports(self):
        # the extraction is the public seam scale-out builds on
        for name in ("ExecutionPlan", "LaneRunner", "LaneSpec",
                     "shard_spans"):
            assert hasattr(rel, name)

    def test_single_lane_runner_reproduces_fit_chunked(self):
        """The extracted LaneRunner IS the former fit_chunked loop: a
        hand-built single-lane plan walks to the same bytes."""
        y = _ar_panel(b=16)
        ref = rel.fit_chunked(ewma.fit, y, chunk_rows=4, resilient=False)
        plan = plan_mod.ExecutionPlan(
            n_rows=16, chunk_rows=4, min_chunk_rows=1, max_backoffs=8,
            resilient=False, policy="impute", ladder=None,
            checkpoint_dir=None, resume="auto", chunk_budget_s=None,
            job_budget_s=None, pipeline=True, pipeline_depth=2,
            prefetch_depth=1, align_mode=None,
            lanes=(plan_mod.LaneSpec(0, 0, 16),), process_index=0)
        runner = plan_mod.LaneRunner(plan, plan.lanes[0], ewma.fit, {},
                                     jnp.asarray(y))
        out = runner.run()
        assert not plan.sharded
        assert [(lo, hi) for lo, hi, _ in out.pieces] == [
            (0, 4), (4, 8), (8, 12), (12, 16)]
        got = np.concatenate([np.asarray(p.params) for _, _, p in out.pieces])
        np.testing.assert_array_equal(got, np.asarray(ref.params))

    def test_same_plan_three_modes_bitwise(self, lane_mesh, tmp_path):
        """Serial, pipelined, and sharded walks are the same ExecutionPlan
        with different knobs/lane counts — same chunk grid, same bytes."""
        y = _ar_panel(b=32)
        kw = dict(chunk_rows=4, resilient=False, order=(1, 0, 0),
                  max_iters=20)
        serial = rel.fit_chunked(arima.fit, y, pipeline=False, **kw)
        piped = rel.fit_chunked(
            arima.fit, y, checkpoint_dir=str(tmp_path / "p"), **kw)
        sharded = rel.fit_chunked(
            arima.fit, y, shard=True, checkpoint_dir=str(tmp_path / "s"),
            **kw)
        _assert_bitwise(piped, serial)
        _assert_bitwise(sharded, serial)
        # same chunk grid in both journals (single manifest each)
        grid = lambda d: [(c["lo"], c["hi"])
                          for c in _manifest(d)["chunks"]]
        assert grid(str(tmp_path / "p")) == grid(str(tmp_path / "s"))

    def test_oom_backoff_is_per_lane(self, lane_mesh):
        """OOM backoff budgets and chunk halving are per lane: every lane
        that trips RESOURCE_EXHAUSTED halves its OWN chunks (8 backoffs,
        one per lane, each shard-tagged), yet the walk still lands on the
        single-device walk's halved grid — and its bytes."""
        y = _ar_panel(b=32)
        single = rel.fit_chunked(fi.oom_fit(ewma.fit, 3), y, chunk_rows=4,
                                 min_chunk_rows=1, resilient=False)
        shard = rel.fit_chunked(fi.oom_fit(ewma.fit, 3), y, chunk_rows=4,
                                min_chunk_rows=1, resilient=False,
                                shard=True)
        _assert_bitwise(shard, single)
        # the single-device walk halves ONCE (4 -> 2 sticks for the rest);
        # the sharded walk halves once IN EVERY lane
        assert single.meta["oom_backoffs"] == 1
        assert shard.meta["oom_backoffs"] == 8
        assert sorted(e["shard"] for e in shard.meta["oom_events"]) == list(
            range(8))
        assert shard.meta["degraded"]

    def test_job_deadline_shared_across_lanes(self, lane_mesh):
        y = _ar_panel(b=32)
        res = rel.fit_chunked(ewma.fit, y, chunk_rows=4, resilient=False,
                              shard=True, job_budget_s=0.0)
        assert res.meta["status_counts"]["TIMEOUT"] == 32
        assert all(e["scope"] == "job" for e in res.meta["timeout_events"])


# ---------------------------------------------------------------------------
# review hardening: multi-process edge cases and tool robustness
# ---------------------------------------------------------------------------


class TestReviewHardening:
    def _fit(self, y, d=None, **kw):
        kw.setdefault("chunk_rows", 4)
        kw.setdefault("resilient", False)
        kw.setdefault("max_iters", 20)
        return rel.fit_chunked(arima.fit, y, checkpoint_dir=d,
                               order=(1, 0, 0), **kw)

    def test_zero_lane_process_returns_empty_local_result(
            self, lane_mesh, tmp_path, monkeypatch):
        """A jax.distributed process whose addressable devices own no lane
        (``lane_values`` legitimately returns ``[]`` for it) returns an
        empty LOCAL result and still joins the manifest barrier — it must
        not crash on the empty concatenate or an empty journal list."""
        monkeypatch.setattr(meshlib, "lane_values",
                            lambda yb, mesh, spans: [])
        y = _ar_panel(b=32)
        d = str(tmp_path / "j")
        res = rel.fit_chunked(arima.fit, y, checkpoint_dir=d, chunk_rows=4,
                              resilient=False, max_iters=20, order=(1, 0, 0),
                              mesh=lane_mesh, process_index=1)
        assert np.asarray(res.params).shape[0] == 0
        assert np.asarray(res.status).shape == (0,)
        assert res.meta["chunks_run"] == 0
        j = res.meta["journal"]
        assert j["dir"] == os.path.abspath(d)
        assert j["merged_shards"] is None
        assert j["chunks_resumed"] == 0

    def test_check_survives_malformed_shards_block(self):
        """``--check`` reports malformed ``shards`` entries as validation
        errors instead of crashing on them."""
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "obs_report", os.path.join(_ROOT, "tools", "obs_report.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        m = {"merged_from_shards": 3, "n_rows": 32,
             "shards": ["bogus",
                        {"shard_id": 1, "lo": "x", "hi": None},
                        {"shard_id": 2, "lo": 16, "hi": 32,
                         "chunks_committed": 1, "chunks_timeout": 0}],
             "chunks": [{"lo": 0, "hi": 8, "shard_id": 0,
                         "shard": "shard_00000/chunk.npz"},
                        {"lo": 16, "hi": 24, "shard_id": 2,
                         "shard": "shard_00002/chunk.npz"}]}
        errors = mod.validate_manifest_shards(m, "manifest.json")
        assert any("shards[0]" in e for e in errors)   # non-dict entry
        assert any("shards[1]" in e for e in errors)   # non-int span
        # a chunk pointing at a malformed shard gets the not-in-block
        # error; the well-formed shard's chunk still validates
        assert any("shard_id 0" in e for e in errors)

    def test_check_accepts_adopted_root_chunks(self):
        """A merged manifest later extended by a single-device walk holds
        untagged root-committed chunk entries (the one-directional
        adoption contract) — ``--check`` must accept them."""
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "obs_report", os.path.join(_ROOT, "tools", "obs_report.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        m = {"merged_from_shards": 2, "n_rows": 32,
             "shards": [{"shard_id": 0, "lo": 0, "hi": 16, "dir": "shard_00000",
                         "chunks_committed": 2, "chunks_timeout": 0},
                        {"shard_id": 1, "lo": 16, "hi": 32, "dir": "shard_00001",
                         "chunks_committed": 1, "chunks_timeout": 1}],
             "chunks": [{"lo": 0, "hi": 8, "shard_id": 0,
                         "shard": "shard_00000/c0.npz"},
                        # retried TIMEOUT chunk recommitted by the adopting
                        # single-device walk: untagged, root-relative npz
                        {"lo": 24, "hi": 32, "shard": "c24.npz"}]}
        assert mod.validate_manifest_shards(m, "manifest.json") == []

    def test_sharded_walk_rejects_foreign_root_manifest(self, lane_mesh,
                                                        tmp_path):
        """Lanes only open shard namespaces, so a foreign job's root
        manifest must be rejected UP FRONT — not silently destroyed by
        the merge after the whole walk computed."""
        y = _ar_panel(b=32)
        d = str(tmp_path / "j")
        self._fit(y, d)  # job A: single-device, writes the root manifest
        y2 = _ar_panel(b=32, seed=9)  # job B: different panel fingerprint
        with pytest.raises(rel.StaleJournalError, match="root manifest"):
            self._fit(y2, d, shard=True)
        # job A's write-ahead record survives untouched
        assert "merged_from_shards" not in _manifest(d)

    def test_sharded_walk_over_same_job_root_manifest(self, lane_mesh,
                                                      tmp_path):
        """Same (panel, config): the sharded walk recomputes into fresh
        shard namespaces (the documented one-directional adoption) and
        the merge replaces the root manifest with the merged record."""
        y = _ar_panel(b=32)
        d = str(tmp_path / "j")
        single = self._fit(y, d)
        res = self._fit(y, d, shard=True)
        _assert_bitwise(res, single)
        assert _manifest(d)["merged_from_shards"] == 8

    def test_plan_sharded_is_global_shard_count(self):
        """A jax.distributed process may run ONE local lane of a sharded
        walk: ``sharded`` (and with it lane shard-tagging) must key on
        the GLOBAL shard count, not the local lane count."""
        base = dict(n_rows=16, chunk_rows=4, min_chunk_rows=1,
                    max_backoffs=8, resilient=False, policy="impute",
                    ladder=None, checkpoint_dir=None, resume="auto",
                    chunk_budget_s=None, job_budget_s=None, pipeline=True,
                    pipeline_depth=2, prefetch_depth=1, align_mode=None,
                    process_index=1)
        one_lane = (plan_mod.LaneSpec(3, 8, 12),)
        assert plan_mod.ExecutionPlan(lanes=one_lane, n_shards=4,
                                      **base).sharded
        assert not plan_mod.ExecutionPlan(lanes=one_lane, **base).sharded

    def test_sharded_walk_tags_compile_per_lane(self, lane_mesh, tmp_path):
        """Executables are cached per device placement, so EVERY lane's
        first chunk pays its own compile — the telemetry must tag one
        compile+execute chunk per shard, not one per walk."""
        # 16 chunks over 8 lanes, 2 per lane, of a length no other test of
        # this process fits: what the tag reads is the build log, and a
        # shape already built is loaded, not built
        y = _ar_panel(b=64, t=91)
        obs.enable(str(tmp_path / "ev.jsonl"))
        try:
            res = rel.fit_chunked(ewma.fit, y, chunk_rows=4, resilient=False,
                                  shard=True)
        finally:
            obs.disable()
        chunks = res.meta["telemetry"]["chunks"]
        compiled = {c["shard"] for c in chunks
                    if c["phase"] == "compile+execute"}
        assert compiled == set(range(8))
