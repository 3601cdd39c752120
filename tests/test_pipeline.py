"""Pipelined chunk execution tests (ISSUE 4, tier-1 CPU).

The acceptance bar: the pipelined driver (background committer, bounded
queue) is BITWISE-IDENTICAL to the serial ``pipeline=False`` walk — with
and without journaling, telemetry on and off — a kill with commits in
flight resumes exactly like a serial crash, OOM backoff and watchdog
timeouts drain the commit queue deterministically, and the committer never
reorders manifest updates.  Plus the knob surfaces (panel / compat) and
the opt-in persistent compilation cache.
"""

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
from spark_timeseries_tpu import reliability as rel
from spark_timeseries_tpu.compat import sparkts
from spark_timeseries_tpu.models import arima
from spark_timeseries_tpu.reliability import FitStatus
from spark_timeseries_tpu.reliability import faultinject as fi

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ar_panel(b=32, t=120, seed=7, phi=0.6):
    rng = np.random.default_rng(seed)
    e = rng.normal(size=(b, t)).astype(np.float32)
    y = np.zeros_like(e)
    y[:, 0] = e[:, 0]
    for i in range(1, t):
        y[:, i] = phi * y[:, i - 1] + e[:, i]
    return y


def _fit(y, d=None, fit_fn=None, **kw):
    kw.setdefault("chunk_rows", 8)
    kw.setdefault("resilient", False)
    kw.setdefault("max_iters", 25)
    return rel.fit_chunked(fit_fn or arima.fit, y, checkpoint_dir=d,
                           order=(1, 0, 0), **kw)


def _assert_bitwise(a, b):
    for f in ("params", "neg_log_likelihood", "converged", "iters", "status"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f"field {f!r} differs")


def _manifest(d):
    return json.load(open(os.path.join(d, "manifest.json")))


def _spans(d, status="committed"):
    return sorted((c["lo"], c["hi"]) for c in _manifest(d)["chunks"]
                  if c["status"] == status)


# ---------------------------------------------------------------------------
# bitwise identity: pipelined == serial, journal on/off, telemetry on/off
# ---------------------------------------------------------------------------


class TestBitwiseIdentity:
    def test_pipelined_matches_serial_journaled(self, tmp_path):
        y = _ar_panel()
        plain = _fit(y)  # unjournaled reference
        d_ser, d_pipe = str(tmp_path / "ser"), str(tmp_path / "pipe")
        ser = _fit(y, d_ser, pipeline=False)
        pipe = _fit(y, d_pipe, pipeline=True, pipeline_depth=3)
        _assert_bitwise(ser, plain)
        _assert_bitwise(pipe, plain)
        # identical chunk grids in both manifests
        assert _spans(d_ser) == _spans(d_pipe) == [(0, 8), (8, 16),
                                                   (16, 24), (24, 32)]
        # only the pipelined run carries the overlap accounting
        assert "pipeline" not in ser.meta
        assert pipe.meta["pipeline"]["depth"] == 3
        assert pipe.meta["pipeline"]["commits_background"] == 4

    def test_pipelined_matches_serial_resilient(self, tmp_path):
        # the resilient path (sanitize + ladder) hands the committer
        # host-side arrays; a NaN-poisoned panel exercises the ladder
        y = _ar_panel()
        y[3, 10:14] = np.nan
        ser = _fit(y, str(tmp_path / "a"), resilient=True, pipeline=False)
        pipe = _fit(y, str(tmp_path / "b"), resilient=True, pipeline=True)
        _assert_bitwise(pipe, ser)

    def test_telemetry_on_off(self, tmp_path):
        y = _ar_panel()
        off = _fit(y, str(tmp_path / "off"))
        obs.enable(str(tmp_path / "ev.jsonl"))
        try:
            on = _fit(y, str(tmp_path / "on"))
        finally:
            obs.disable()
        _assert_bitwise(on, off)
        assert "telemetry" in on.meta and "telemetry" not in off.meta

    def test_cross_mode_resume(self, tmp_path):
        """Pipeline knobs are excluded from the config hash: a journal
        written by a pipelined run must resume under a serial run (and
        vice versa) bitwise-identically."""
        y = _ar_panel()
        full = _fit(y)
        d = str(tmp_path / "j")
        with pytest.raises(fi.SimulatedCrash):
            _fit(y, d, pipeline=True,
                 _journal_commit_hook=fi.crash_after_commits(2))
        res = _fit(y, d, pipeline=False)  # resume SERIALLY
        _assert_bitwise(res, full)
        assert res.meta["journal"]["chunks_resumed"] == 2
        # and a fully serial journal resumes under the pipelined driver
        d2 = str(tmp_path / "j2")
        with pytest.raises(fi.SimulatedCrash):
            _fit(y, d2, pipeline=False,
                 _journal_commit_hook=fi.crash_after_commits(2))
        res2 = _fit(y, d2, pipeline=True)
        _assert_bitwise(res2, full)
        assert res2.meta["journal"]["chunks_resumed"] == 2


# ---------------------------------------------------------------------------
# commit protocol: in-order, single-writer, crash windows
# ---------------------------------------------------------------------------


class TestCommitProtocol:
    def test_committer_never_reorders_manifest_updates(self, tmp_path):
        events = []

        def hook(ev, lo):
            events.append((ev, lo))

        y = _ar_panel()
        _fit(y, str(tmp_path / "j"), pipeline_depth=4,
             _journal_commit_hook=hook)
        committed = [lo for ev, lo in events if ev == "committed"]
        shards = [lo for ev, lo in events if ev == "shard_written"]
        # strict walk order for both the shard writes and the manifest
        # updates, and shard-before-manifest per chunk (the hook fires
        # between the two, so the interleaving proves the ordering)
        assert committed == [0, 8, 16, 24]
        assert shards == [0, 8, 16, 24]
        order = [e for e in events if e[0] in ("shard_written", "committed")]
        for lo in (0, 8, 16, 24):
            assert order.index(("shard_written", lo)) < order.index(
                ("committed", lo))

    def test_crash_with_commits_in_flight_resumes_bitwise(self, tmp_path):
        y = _ar_panel()
        full = _fit(y)
        d = str(tmp_path / "j")
        with pytest.raises(fi.SimulatedCrash):
            _fit(y, d, pipeline_depth=3,
                 _journal_commit_hook=fi.crash_after_commits(2))
        # in-order commits: exactly the chunks before the crash are durable
        assert _spans(d) == [(0, 8), (8, 16)]
        res = _fit(y, d, pipeline_depth=3)
        _assert_bitwise(res, full)
        assert res.meta["journal"]["chunks_resumed"] == 2
        assert res.meta["journal"]["chunks_committed"] == 4

    def test_mid_commit_crash_leaves_recoverable_orphan(self, tmp_path):
        y = _ar_panel()
        d = str(tmp_path / "j")
        with pytest.raises(fi.SimulatedCrash):
            _fit(y, d, pipeline_depth=3,
                 _journal_commit_hook=fi.crash_after_commits(
                     3, mid_commit=True))
        assert _spans(d) == [(0, 8), (8, 16)]
        # the orphan shard exists but the manifest does not name it
        assert os.path.exists(os.path.join(d, "chunk_000000016_000000024.npz"))
        res = _fit(y, d)
        _assert_bitwise(res, _fit(y))
        assert res.meta["journal"]["chunks_resumed"] == 2


# ---------------------------------------------------------------------------
# deterministic drain: OOM backoff, watchdog timeouts, fetch-time errors
# ---------------------------------------------------------------------------


class TestDeterministicDrain:
    def test_oom_backoff_matches_serial(self, tmp_path):
        y = _ar_panel()
        mk = lambda: fi.oom_fit(arima.fit, max_rows=4)
        ref = _fit(y, fit_fn=mk(), chunk_rows=16, min_chunk_rows=2,
                   pipeline=False)
        d_ser, d_pipe = str(tmp_path / "ser"), str(tmp_path / "pipe")
        ser = _fit(y, d_ser, fit_fn=mk(), chunk_rows=16, min_chunk_rows=2,
                   pipeline=False)
        pipe = _fit(y, d_pipe, fit_fn=mk(), chunk_rows=16, min_chunk_rows=2,
                    pipeline=True, pipeline_depth=3)
        _assert_bitwise(ser, ref)
        _assert_bitwise(pipe, ref)
        assert _spans(d_ser) == _spans(d_pipe)
        assert pipe.meta["oom_backoffs"] == ser.meta["oom_backoffs"] == 2

    def test_chunk_timeout_drains_queue_before_mark(self, tmp_path):
        y = _ar_panel()
        d = str(tmp_path / "j")
        hf = fi.hanging_fit(arima.fit, [2], sleep_s=10.0)
        res = _fit(y, d, fit_fn=hf, chunk_budget_s=0.5, pipeline_depth=4)
        # every commit BEFORE the hung chunk is durable before the TIMEOUT
        # mark lands (the drain point), and the walk finished the rest
        m = _manifest(d)
        stat = {(c["lo"], c["hi"]): c["status"] for c in m["chunks"]}
        assert stat[(16, 24)] == "TIMEOUT"
        assert sum(1 for s in stat.values() if s == "committed") == 3
        counts = res.meta["status_counts"]
        assert counts["TIMEOUT"] == 8
        assert (np.asarray(res.status[16:24]) == FitStatus.TIMEOUT).all()
        # manifest chunk list stays sorted by row range (in-order protocol)
        los = [c["lo"] for c in m["chunks"]]
        assert los == sorted(los)

    def test_job_budget_exhausted_closes_cleanly(self, tmp_path):
        y = _ar_panel()
        d = str(tmp_path / "j")
        res = _fit(y, d, job_budget_s=0.0, pipeline_depth=3)
        assert res.meta["status_counts"]["TIMEOUT"] == 32
        assert res.meta["journal"]["chunks_timeout"] == 4
        assert res.meta["pipeline"]["commits_background"] == 0

    def test_fetch_oom_rolls_walk_back(self, tmp_path):
        """resilient=False pieces are fetched on the committer thread; an
        XLA RESOURCE_EXHAUSTED surfacing THERE (async dispatch) must roll
        the walk back to the failed chunk and re-enter OOM backoff — not
        crash the job, not corrupt the manifest."""

        class _PoisonedPiece:
            def __init__(self, real):
                self._real = real
                self._armed = True

            @property
            def params(self):
                if self._armed:
                    self._armed = False
                    raise RuntimeError(
                        "RESOURCE_EXHAUSTED: simulated OOM during result "
                        "fetch (fault injection)")
                return self._real.params

            def __getattr__(self, name):
                return getattr(self._real, name)

        calls = {"n": 0}

        def fit_poison(yb, **kw):
            r = arima.fit(yb, **kw)
            calls["n"] += 1
            if calls["n"] == 2 and yb.shape[0] == 8:
                return _PoisonedPiece(r)
            return r

        y = _ar_panel()
        d = str(tmp_path / "j")
        res = rel.fit_chunked(fit_poison, y, chunk_rows=8, min_chunk_rows=2,
                              resilient=False, checkpoint_dir=d,
                              order=(1, 0, 0), max_iters=25,
                              pipeline_depth=3)
        assert res.meta["oom_backoffs"] == 1
        assert res.meta["oom_events"][0]["at_row"] == 8
        # exact partition: [0,8) at full width, halved chunks from row 8
        spans = _spans(d)
        assert spans[0] == (0, 8) and spans[-1][1] == 32
        assert all(a[1] == b[0] for a, b in zip(spans, spans[1:]))
        assert all(hi - lo == 4 for lo, hi in spans[1:])
        assert res.meta["status_counts"].get("TIMEOUT", 0) == 0
        # a resume of the same journal rehydrates every shard bitwise
        again = rel.fit_chunked(fit_poison, y, chunk_rows=8, min_chunk_rows=2,
                                resilient=False, checkpoint_dir=d,
                                order=(1, 0, 0), max_iters=25)
        _assert_bitwise(again, res)
        assert again.meta["journal"]["chunks_resumed"] == len(spans)

    def test_commit_error_is_not_swallowed_unjournaled_path(self, tmp_path):
        # a non-OOM worker failure must propagate with its original type
        def hook(ev, lo):
            if ev == "committed" and lo == 8:
                raise OSError("disk full (simulated)")

        y = _ar_panel()
        with pytest.raises(OSError, match="disk full"):
            _fit(y, str(tmp_path / "j"), pipeline_depth=3,
                 _journal_commit_hook=hook)


# ---------------------------------------------------------------------------
# the result is assembled on the committer thread, the read-back copies on
# write (ISSUE 49: a wide result's host passes left the driver's thread)
# ---------------------------------------------------------------------------


def _chunk_arrays(n, k=3, dtype=np.float32, fill=1.0):
    return {"params": np.full((n, k), fill, dtype),
            "nll": np.full(n, fill, dtype),
            "converged": np.ones(n, bool),
            "iters": np.full(n, 3, np.int32),
            "status": np.zeros(n, np.int8)}


class TestResultAssembly:
    @pytest.fixture()
    def taken(self, monkeypatch):
        """What every ``ResultAssembly.take`` of the test returned."""
        from spark_timeseries_tpu.reliability import plan as plan_mod

        seen = []
        real = plan_mod.ResultAssembly.take

        def spy(self, pieces):
            out = real(self, pieces)
            seen.append(out is not None)
            return out

        monkeypatch.setattr(plan_mod.ResultAssembly, "take", spy)
        return seen

    @pytest.mark.parametrize("resilient", [False, True])
    def test_journaled_pipelined_walk_takes_it_bitwise(self, tmp_path, taken,
                                                       resilient):
        y = _ar_panel()
        plain = _fit(y, resilient=resilient)  # no journal: concatenated
        assert taken == []
        res = _fit(y, str(tmp_path / "j"), resilient=resilient)
        assert taken == [True]
        _assert_bitwise(res, plain)
        for f in ("params", "neg_log_likelihood", "converged", "iters",
                  "status"):
            a, b = getattr(res, f), getattr(plain, f)
            assert a.dtype == b.dtype and a.shape == b.shape
            assert a.flags.writeable and a.flags.c_contiguous

    @pytest.mark.parametrize("how", ["resumed", "serial", "timeout"])
    def test_any_other_walk_concatenates(self, tmp_path, taken, how):
        y = _ar_panel()
        d = str(tmp_path / "j")
        if how == "resumed":
            first = _fit(y, d)
            res = _fit(y, d)
            _assert_bitwise(res, first)
            assert taken == [True, False]
        elif how == "serial":
            res = _fit(y, d, pipeline=False)
            _assert_bitwise(res, _fit(y))
            assert taken == []  # no committer, no assembly
        else:
            res = _fit(y, d, job_budget_s=0.0)
            assert res.meta["status_counts"]["TIMEOUT"] == 32
            assert taken == [False]

    @pytest.mark.parametrize("fault", ["none", "missing", "stale", "width",
                                       "dtype", "short"])
    def test_take_hands_over_only_the_walks_own_pieces(self, fault):
        from spark_timeseries_tpu.reliability.plan import ResultAssembly

        asm = ResultAssembly(24)
        pieces = [(0, 8, None), (8, 16, None), (16, 24, None)]
        for lo, hi, _ in pieces:
            if fault == "missing" and lo == 8:
                continue
            arrays = _chunk_arrays(hi - lo, fill=float(lo))
            if fault == "width" and lo == 16:
                arrays["params"] = np.zeros((hi - lo, 4), np.float32)
            if fault == "dtype" and lo == 16:
                arrays["nll"] = arrays["nll"].astype(np.float64)
            asm.place(lo, hi, arrays)
        if fault == "stale":  # a rolled-back chunk's boundaries
            asm.place(8, 12, _chunk_arrays(4))
        if fault == "short":
            pieces = pieces[:2]
        out = asm.take(pieces)
        if fault != "none":
            assert out is None
            return
        params, nll, conv, iters, status = out
        np.testing.assert_array_equal(params[:, 0], np.repeat([0., 8., 16.], 8))
        np.testing.assert_array_equal(nll, np.repeat([0., 8., 16.], 8))
        assert params.shape == (24, 3) and conv.all() and status.dtype == np.int8

    @pytest.mark.parametrize("bad", ["none", "nan_param", "inf_param",
                                     "nan_nll", "not_converged"])
    def test_failed_mask_is_the_by_row_rule(self, bad):
        from spark_timeseries_tpu.models.base import FitResult
        from spark_timeseries_tpu.reliability.runner import _failed_mask

        rng = np.random.default_rng(3)
        params = rng.normal(size=(64, 33)).astype(np.float32)
        nll = rng.normal(size=64).astype(np.float32)
        conv = np.ones(64, bool)
        if bad == "nan_param":
            params[5, 32] = np.nan
        elif bad == "inf_param":
            params[[7, 9], 0] = [np.inf, -np.inf]
        elif bad == "nan_nll":
            nll[11] = np.nan
        elif bad == "not_converged":
            conv[13] = False
        want = ~(conv & np.isfinite(params).all(axis=-1) & np.isfinite(nll))
        got = _failed_mask(FitResult(params, nll, conv, np.zeros(64, np.int32)))
        np.testing.assert_array_equal(got, want)
        assert got.sum() == {"none": 0, "inf_param": 2}.get(bad, 1)

    @pytest.mark.parametrize("rewritten", [False, True])
    def test_readback_copies_params_on_write_only(self, rewritten):
        y = jnp.asarray(_ar_panel(b=8))
        direct = arima.fit(y, order=(1, 0, 0), max_iters=25)

        def fit(yb, **kw):
            r = arima.fit(yb, **kw)
            if rewritten:  # one row the empty ladder marks DIVERGED
                r = r._replace(converged=r.converged.at[2].set(False))
            return r

        res = rel.resilient_fit(fit, y, order=(1, 0, 0), max_iters=25,
                                ladder=())
        failed = ~np.asarray(res.converged)
        assert failed.sum() == int(rewritten)
        assert res.params.flags.writeable == rewritten
        assert np.isnan(res.params[failed]).all()
        assert (res.status[failed] == FitStatus.DIVERGED).all()
        np.testing.assert_array_equal(res.params[~failed],
                                      np.asarray(direct.params)[~failed])


    @pytest.mark.parametrize("host_fit", [False, True])
    def test_rescued_rows_are_scattered_on_the_device(self, host_fit):
        """A rung's rows land in the device's array (read back once more:
        the read-only buffer again); a fit that returns host arrays keeps
        the host scatter.  Either way the rows are the rung's own."""
        y = jnp.asarray(_ar_panel(b=16))
        direct = arima.fit(y, order=(1, 0, 0), max_iters=25)
        flagged = [3, 11]

        def fit(yb, **kw):
            r = arima.fit(yb, **kw)
            if yb.shape[0] == 16:  # the primary fit leaves two rows undone
                r = r._replace(converged=r.converged.at[
                    jnp.asarray(flagged)].set(False))
            if host_fit:
                r = r._replace(params=np.asarray(r.params))
            return r

        rung = rel.RetryRung("retry", int(FitStatus.RETRIED), {}, 0.0)
        res = rel.resilient_fit(fit, y, order=(1, 0, 0), max_iters=25,
                                ladder=(rung,))
        assert res.meta["ladder"][0]["rescued"] == 2
        assert (res.status[flagged] == FitStatus.RETRIED).all()
        assert res.converged.all()
        assert res.params.flags.writeable == host_fit
        sub = arima.fit(y[jnp.asarray(flagged + [3] * 6)], order=(1, 0, 0),
                        max_iters=25)
        np.testing.assert_array_equal(res.params[flagged],
                                      np.asarray(sub.params)[:2])
        others = np.setdiff1d(np.arange(16), flagged)
        np.testing.assert_array_equal(res.params[others],
                                      np.asarray(direct.params)[others])


# ---------------------------------------------------------------------------
# knob surfaces: panel.fit, compat fit_model
# ---------------------------------------------------------------------------


class TestKnobSurfaces:
    def test_panel_fit_pipeline_knobs(self, tmp_path):
        y = _ar_panel(b=12, t=120)
        idx = dtix.uniform("2024-01-01", periods=120,
                           frequency=dtix.DayFrequency(1))
        p = panel_mod.TimeSeriesPanel(idx, [f"s{i}" for i in range(12)], y)
        d = str(tmp_path / "j")
        r1 = p.fit("arima", order=(1, 0, 0), max_iters=25, chunk_rows=4,
                   resilient=False, checkpoint_dir=d, pipeline=False)
        r2 = p.fit("arima", order=(1, 0, 0), max_iters=25, chunk_rows=4,
                   resilient=False, checkpoint_dir=d, pipeline_depth=3)
        _assert_bitwise(r1, r2)
        assert r2.meta["journal"]["chunks_resumed"] == 3

    def test_compat_fit_model_pipeline_depth(self, tmp_path):
        y = _ar_panel(b=8, t=120)
        plain = sparkts.ARIMA.fit_model(1, 0, 0, jnp.asarray(y))
        d = str(tmp_path / "j")
        durable = sparkts.ARIMA.fit_model(1, 0, 0, jnp.asarray(y),
                                          checkpoint_dir=d, chunk_rows=4,
                                          pipeline_depth=3)
        np.testing.assert_array_equal(np.asarray(durable.params),
                                      np.asarray(plain.params))
        serial = sparkts.ARIMA.fit_model(1, 0, 0, jnp.asarray(y),
                                         checkpoint_dir=d, chunk_rows=4,
                                         pipeline=False)
        np.testing.assert_array_equal(np.asarray(serial.params),
                                      np.asarray(plain.params))


# ---------------------------------------------------------------------------
# overlap accounting + telemetry surface
# ---------------------------------------------------------------------------


class TestOverlapAccounting:
    def test_meta_pipeline_block(self, tmp_path):
        y = _ar_panel()
        res = _fit(y, str(tmp_path / "j"), pipeline_depth=2)
        p = res.meta["pipeline"]
        assert p["depth"] == 2
        assert p["commits_background"] == 4
        assert p["commit_wall_s"] >= 0.0
        assert p["hidden_commit_s"] <= p["commit_wall_s"] + 1e-9
        if p["overlap_efficiency"] is not None:
            assert 0.0 <= p["overlap_efficiency"] <= 1.0
        # the input side rides in the same block (ISSUE 5)
        assert p["prefetch_depth"] == 1
        assert p["hidden_staging_s"] <= p["staging_wall_s"] + 1e-9
        # an unjournaled pipelined walk carries ONLY the input-staging
        # accounting (no committer ran); the serial walk carries none
        up = _fit(y).meta["pipeline"]
        assert "commits_background" not in up
        assert up["chunks_staged"] + up["staged_misses"] >= 4 - 1
        assert "pipeline" not in _fit(y, str(tmp_path / "s"),
                                      pipeline=False).meta
        # prefetch_depth=0 disables staging without touching the committer
        r0 = _fit(y, str(tmp_path / "z"), prefetch_depth=0)
        assert "chunks_staged" not in r0.meta["pipeline"]
        assert r0.meta["pipeline"]["commits_background"] == 4

    def test_committer_metrics_registered(self, tmp_path):
        obs.enable()
        try:
            _fit(_ar_panel(), str(tmp_path / "j"), pipeline_depth=2)
            snap = obs.snapshot()
        finally:
            obs.disable()
        assert "committer.queue_depth" in snap["gauges"]
        assert "committer.hidden_commit_s" in snap["gauges"]
        assert snap["histograms"]["span.commit.overlap"]["count"] == 4
        assert snap["histograms"]["journal.commit_s"]["count"] == 4


# ---------------------------------------------------------------------------
# persistent compilation cache (utils.compile_cache)
# ---------------------------------------------------------------------------


class TestCompileCache:
    """The ONE resolver of the cache directory (``compile_cache.configure``):
    the environment places the cache; unset, it is ``<checkout>/.jax_cache``."""

    @staticmethod
    def _resolve(cache_env):
        """``configure()`` in a fresh interpreter (jax reads the variable at
        import) -> [returned dir, jax's config value, config.update calls]."""
        code = (
            "import jax\n"
            "calls = []\n"
            "real = jax.config.update\n"
            "jax.config.update = lambda *a, **k: (calls.append(a), "
            "real(*a, **k))[1]\n"
            "from spark_timeseries_tpu.utils import compile_cache\n"
            "print(compile_cache.configure())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "print(len(calls))\n")
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        r = subprocess.run(
            [sys.executable, "-c", code], cwd=REPO_ROOT,
            env={**env, "JAX_PLATFORMS": "cpu", **cache_env},
            capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr
        return r.stdout.split()

    def test_env_places_cache_and_resolver_sets_nothing(self, tmp_path):
        want = str(tmp_path / "placed")
        assert self._resolve({"JAX_COMPILATION_CACHE_DIR": want}) == [
            want, want, "0"]

    def test_default_is_checkout_jax_cache(self):
        want = os.path.join(REPO_ROOT, ".jax_cache")
        assert self._resolve({}) == [want, want, "1"]
