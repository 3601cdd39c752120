"""Dispatch-ahead input pipeline tests (ISSUE 5, tier-1 CPU).

Two halves, same acceptance bar as the committer (ISSUE 4):

- **Static align-mode plan**: a sliced chunk walk probes the panel's
  alignment mode at most ONCE (zero per-chunk host syncs — counted by
  ``models.base``'s ``align.host_probes``), the hint threads through every
  model fit, a wrong hint surfaces as flagged rows or a raise (never
  silently wrong numbers), and the resilient ladder downgrades the hint
  when the sanitizer changed a chunk's NaN pattern.
- **ChunkPrefetcher**: the prefetched walk is BITWISE-IDENTICAL to the
  serial one — journal on/off, telemetry on/off — a crash with staged
  slices in flight resumes exactly like a serial crash, OOM backoff
  invalidates staged slices at the halved boundary, and serial and
  prefetched journals cross-resume (the input pipeline is excluded from
  the journal config hash just like the committer knobs).
"""

import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from spark_timeseries_tpu import obs
from spark_timeseries_tpu import reliability as rel
from spark_timeseries_tpu.models import arima, base as model_base, ewma
from spark_timeseries_tpu.reliability import FitStatus, runner
from spark_timeseries_tpu.reliability import faultinject as fi
from spark_timeseries_tpu.reliability.prefetcher import ChunkPrefetcher


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


def _spans(d, status="committed"):
    m = json.load(open(os.path.join(d, "manifest.json")))
    return sorted((c["lo"], c["hi"]) for c in m["chunks"]
                  if c["status"] == status)


# ---------------------------------------------------------------------------
# static align-mode plan
# ---------------------------------------------------------------------------


class TestAlignModePlan:
    def test_sliced_walk_probes_at_most_once(self, tmp_path):
        """The plan eliminates the per-chunk NaN-probe host sync: a 4-chunk
        sliced walk pays ONE panel-level probe, not four per-slice ones —
        with or without the journal, pipelined or serial."""
        for i, kw in enumerate(({}, {"pipeline": False},
                                {"d": str(tmp_path / "j")})):
            y = jnp.asarray(_ar_panel(seed=11))  # fresh array: cold cache
            obs.enable()
            try:
                c0 = obs.snapshot()["counters"].get("align.host_probes", 0)
                _fit(y, kw.pop("d", None), **kw)
                c1 = obs.snapshot()["counters"].get("align.host_probes", 0)
            finally:
                obs.disable()
            assert c1 - c0 == 1, f"probes={c1 - c0} for case {i}"

    def test_caller_hint_skips_even_the_one_probe(self):
        y = jnp.asarray(_ar_panel(seed=12))
        obs.enable()
        try:
            c0 = obs.snapshot()["counters"].get("align.host_probes", 0)
            res = _fit(y, align_mode="general")
            c1 = obs.snapshot()["counters"].get("align.host_probes", 0)
        finally:
            obs.disable()
        assert c1 - c0 == 0
        assert res.meta["align_mode"] == "general"

    def test_plan_is_recorded_and_bitwise_inert(self):
        """The panel-level mode is exact for every row slice: planned and
        per-chunk-probed walks run the same compiled programs, so hinting
        'dense' on a dense panel changes nothing."""
        y = jnp.asarray(_ar_panel(seed=13))
        res_plan = _fit(y)  # plan derived by the one probe
        res_hint = _fit(y, align_mode="dense")
        _assert_bitwise(res_plan, res_hint)
        assert res_plan.meta["align_mode"] == "dense"

    def test_hint_with_nonaccepting_fit_fn_raises(self):
        # explicit signature WITHOUT align_mode (a **kwargs fit would
        # forward the hint): the driver must refuse rather than drop it
        def no_hint_fit(yb, order=(1, 0, 0), max_iters=25):
            return arima.fit(yb, order, max_iters=max_iters)

        with pytest.raises(TypeError, match="align_mode"):
            _fit(_ar_panel(), align_mode="general", fit_fn=no_hint_fit)

    def test_unknown_mode_raises_everywhere(self):
        y = jnp.asarray(_ar_panel(b=4, t=40))
        with pytest.raises(ValueError, match="unknown align_mode"):
            ewma.fit(y, align_mode="bogus")
        with pytest.raises(ValueError, match="unknown align_mode"):
            _fit(np.asarray(y), align_mode="bogus")

    def test_too_strong_hint_flags_rows_not_silent(self):
        """resolve_align_mode contract: 'dense' on a panel with NaNs
        poisons those rows' objectives (DIVERGED), and 'no-trailing' on a
        trailing-NaN row excludes it (NaN params) — the wrong hint is
        LOUD, never a silently misfitted estimate."""
        rng = np.random.default_rng(0)
        y = rng.normal(size=(4, 40)).astype(np.float32)
        y[1, :5] = np.nan  # leading NaNs: the data is "no-trailing"
        r = ewma.fit(jnp.asarray(y), align_mode="dense")
        assert not bool(np.asarray(r.converged)[1])
        assert np.asarray(r.status)[1] == FitStatus.DIVERGED
        # healthy rows are untouched by the (correct-for-them) hint
        assert bool(np.asarray(r.converged)[0])

        y2 = rng.normal(size=(4, 40)).astype(np.float32)
        y2[2, -1] = np.nan  # trailing NaN: the data is "general"
        r2 = ewma.fit(jnp.asarray(y2), align_mode="no-trailing")
        assert np.asarray(r2.status)[2] == FitStatus.EXCLUDED
        assert np.isnan(np.asarray(r2.params)[2]).all()
        assert bool(np.asarray(r2.converged)[0])

    def test_resilient_downgrades_hint_on_sanitized_chunks(self):
        """The ladder holds the hint back until the sanitizer has run:
        a repaired chunk fits under 'general' (repairs change the NaN
        pattern), an untouched chunk keeps the fast plan."""
        seen = []

        def spy_fit(yb, align_mode=None, **kw):
            seen.append(align_mode)
            return arima.fit(yb, (1, 0, 0), max_iters=25)

        clean = _ar_panel(b=8, t=120)
        runner.resilient_fit(spy_fit, jnp.asarray(clean),
                             align_mode="dense")
        assert seen[0] == "dense"

        dirty = clean.copy()
        dirty[3, 10:14] = np.nan  # sanitizer imputes: chunk was MODIFIED
        seen.clear()
        runner.resilient_fit(spy_fit, jnp.asarray(dirty),
                             align_mode="dense")
        assert seen[0] == "general"

    def test_journal_config_hash_covers_the_plan(self, tmp_path):
        """A resumed run must fit under the SAME plan: a different
        align_mode is a different compiled program, so the journal rejects
        it as a config mismatch instead of splicing mixed-plan chunks."""
        y = _ar_panel()
        d = str(tmp_path / "j")
        with pytest.raises(fi.SimulatedCrash):
            _fit(y, d, align_mode="general",
                 _journal_commit_hook=fi.crash_after_commits(2))
        with pytest.raises(rel.StaleJournalError):
            _fit(y, d, align_mode="dense")


# ---------------------------------------------------------------------------
# prefetched walk: bitwise identity + durability interactions
# ---------------------------------------------------------------------------


class TestPrefetchedWalk:
    def test_prefetched_matches_serial_journal_and_telemetry_matrix(
            self, tmp_path):
        y = _ar_panel()
        ref = _fit(y, pipeline=False)
        i = 0
        for journaled in (False, True):
            for tele in (False, True):
                i += 1
                d = str(tmp_path / f"j{i}") if journaled else None
                if tele:
                    obs.enable(str(tmp_path / f"ev{i}.jsonl"))
                try:
                    got = _fit(y, d, prefetch_depth=2)
                finally:
                    if tele:
                        obs.disable()
                _assert_bitwise(got, ref)
                p = got.meta["pipeline"]
                # 4 chunks: the first is always an inline miss (nothing
                # scheduled yet), the remaining 3 were staged ahead
                assert p["staged_hits"] == 3
                assert p["staged_misses"] == 1

    def test_crash_with_staged_slice_resumes_bitwise(self, tmp_path):
        """The crash window with a staged-but-untaken slice in flight:
        resume recomputes exactly the uncommitted chunks, bitwise."""
        y = _ar_panel()
        full = _fit(y, pipeline=False)
        d = str(tmp_path / "j")
        with pytest.raises(fi.SimulatedCrash):
            _fit(y, d, prefetch_depth=2,
                 _journal_commit_hook=fi.crash_after_commits(2))
        assert _spans(d) == [(0, 8), (8, 16)]
        res = _fit(y, d, prefetch_depth=2)
        _assert_bitwise(res, full)
        assert res.meta["journal"]["chunks_resumed"] == 2
        # the resumed walk staged only the spans it actually computed
        assert res.meta["pipeline"]["chunks_staged"] <= 2

    def test_oom_backoff_invalidates_staged_slices(self, tmp_path):
        """An OOM-halved boundary makes every staged prediction wrong: the
        driver drops them (freeing exactly the HBM the retry needs) and
        the walk still lands bitwise on the serial result."""
        y = _ar_panel()
        mk = lambda: fi.oom_fit(arima.fit, max_rows=4)  # noqa: E731
        ref = _fit(y, fit_fn=mk(), chunk_rows=16, min_chunk_rows=2,
                   pipeline=False)
        d = str(tmp_path / "j")
        got = _fit(y, d, fit_fn=mk(), chunk_rows=16, min_chunk_rows=2,
                   prefetch_depth=2)
        _assert_bitwise(got, ref)
        p = got.meta["pipeline"]
        assert got.meta["oom_backoffs"] == 2
        assert p["staged_invalidated"] >= 1
        # the post-backoff grid is what the journal committed
        spans = _spans(d)
        assert spans[0] == (0, 4) and spans[-1][1] == 32
        assert all(a[1] == b[0] for a, b in zip(spans, spans[1:]))

    def test_cross_mode_resume_serial_and_prefetched(self, tmp_path):
        """The input pipeline is excluded from the journal config hash: a
        serial journal resumes under a prefetched walk and vice versa."""
        y = _ar_panel()
        full = _fit(y, pipeline=False)
        d = str(tmp_path / "a")
        with pytest.raises(fi.SimulatedCrash):
            _fit(y, d, pipeline=False,
                 _journal_commit_hook=fi.crash_after_commits(2))
        res = _fit(y, d, prefetch_depth=2)  # resume PREFETCHED
        _assert_bitwise(res, full)
        assert res.meta["journal"]["chunks_resumed"] == 2
        d2 = str(tmp_path / "b")
        with pytest.raises(fi.SimulatedCrash):
            _fit(y, d2, prefetch_depth=2,
                 _journal_commit_hook=fi.crash_after_commits(2))
        res2 = _fit(y, d2, pipeline=False)  # resume SERIALLY
        _assert_bitwise(res2, full)
        assert res2.meta["journal"]["chunks_resumed"] == 2

    def test_staging_oom_enters_backoff_ladder(self, monkeypatch):
        """A RESOURCE_EXHAUSTED staging the slice (a fresh HBM allocation)
        is delivered at take() and rolls into the same backoff as a
        fit-time OOM."""

        class _OOMOnSlice:
            def __init__(self, arr, fail_lo):
                self._arr, self._fail = arr, fail_lo

            def __getitem__(self, key):
                # a slice of the full chunk width does not fit there, a
                # halved one does: whoever stages it first (the staging
                # worker for the fit ahead, whose RESOURCE_EXHAUSTED is no
                # event of the walk, then the chunk's own turn) meets it
                if isinstance(key, slice) and key.start == self._fail \
                        and key.stop - key.start == 8:
                    raise RuntimeError(
                        "RESOURCE_EXHAUSTED: simulated staging OOM")
                return self._arr[key]

        real = ChunkPrefetcher

        def faulty(panel, *, depth=1):
            return real(_OOMOnSlice(panel, 8), depth=depth)

        y = _ar_panel()
        ref = _fit(y, pipeline=False)
        from spark_timeseries_tpu.reliability import prefetcher as pf_mod
        monkeypatch.setattr(pf_mod, "ChunkPrefetcher", faulty)
        got = _fit(y, min_chunk_rows=2, prefetch_depth=2)
        assert got.meta["oom_backoffs"] == 1
        assert got.meta["oom_events"][0]["at_row"] == 8
        for f in ("converged", "status"):
            np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                          np.asarray(getattr(ref, f)))

    def test_depth_2_stages_two_spans_ahead(self, monkeypatch):
        """prefetch_depth must not be inert past 1: during chunk N the
        driver schedules the next TWO spans (take() freed N's slot)."""
        calls = []
        real = ChunkPrefetcher

        class Spy(real):
            def schedule(self, lo, hi):
                calls.append((lo, hi))
                super().schedule(lo, hi)

        from spark_timeseries_tpu.reliability import prefetcher as pf_mod
        monkeypatch.setattr(pf_mod, "ChunkPrefetcher", Spy)
        got = _fit(_ar_panel(), prefetch_depth=2)
        # first iteration (chunk [0,8)) predicts [8,16) AND [16,24)
        assert calls[:2] == [(8, 16), (16, 24)]
        assert (24, 32) in calls
        assert got.meta["pipeline"]["staged_hits"] == 3

    def test_var_keyword_fit_fn_gets_no_auto_hint(self):
        """AUTO-injection of the plan requires an explicitly named
        align_mode parameter: a **kwargs fit_fn forwarding to a strict
        inner solver must keep working on sliced walks."""

        def strict_solver(yb, order, max_iters):
            return arima.fit(yb, order, max_iters=max_iters)

        def kw_fit(yb, **kw):
            return strict_solver(yb, **kw)  # align_mode would TypeError

        res = _fit(_ar_panel(), fit_fn=kw_fit)
        assert "align_mode" not in res.meta
        assert bool(np.asarray(res.converged).any())

    def test_hung_staging_is_bounded_by_chunk_budget(self, monkeypatch):
        """take() waits INSIDE the watchdog window: a staging wait that
        never resolves (e.g. queued behind an abandoned computation) is
        bounded by chunk_budget_s and flags the chunk TIMEOUT instead of
        hanging the job."""
        import time as _t

        real = ChunkPrefetcher

        class Hang(real):
            def take(self, lo, hi):
                if lo == 16:
                    _t.sleep(5.0)
                return super().take(lo, hi)

        from spark_timeseries_tpu.reliability import prefetcher as pf_mod
        monkeypatch.setattr(pf_mod, "ChunkPrefetcher", Hang)
        y = _ar_panel()
        res = _fit(y, chunk_budget_s=0.75, prefetch_depth=1)
        st = np.asarray(res.status)
        assert (st[16:24] == FitStatus.TIMEOUT).all()
        assert (st[:16] != FitStatus.TIMEOUT).all()
        assert (st[24:] != FitStatus.TIMEOUT).all()

    def test_resilient_prefetched_matches_serial(self, tmp_path):
        y = _ar_panel()
        y[3, 10:14] = np.nan
        ser = _fit(y, str(tmp_path / "a"), resilient=True, pipeline=False)
        pre = _fit(y, str(tmp_path / "b"), resilient=True, prefetch_depth=2)
        _assert_bitwise(pre, ser)


# ---------------------------------------------------------------------------
# ChunkPrefetcher unit behavior
# ---------------------------------------------------------------------------


class TestChunkPrefetcherUnit:
    def test_hit_miss_and_stats(self):
        y = np.arange(80, dtype=np.float32).reshape(8, 10)
        pf = ChunkPrefetcher(y, depth=1)
        pf.schedule(0, 4)
        got = pf.take(0, 4)
        np.testing.assert_array_equal(np.asarray(got), y[0:4])
        got2 = pf.take(4, 8)  # never scheduled: inline miss
        np.testing.assert_array_equal(np.asarray(got2), y[4:8])
        st = pf.close()
        assert (st.staged, st.hits, st.misses) == (1, 1, 1)
        assert st.staging_wall_s >= 0.0
        assert st.hidden_s <= st.staging_wall_s + 1e-9

    def test_depth_bounds_inflight_slices(self):
        y = np.zeros((16, 4), np.float32)
        pf = ChunkPrefetcher(y, depth=1)
        pf.schedule(0, 4)
        pf.schedule(4, 8)  # over depth: ignored
        pf.take(0, 4)
        st = pf.close()
        assert st.staged == 1

    def test_invalidate_drops_predictions(self):
        y = np.zeros((16, 4), np.float32)
        pf = ChunkPrefetcher(y, depth=2)
        pf.schedule(0, 4)
        pf.schedule(4, 8)
        pf.invalidate()
        pf.take(0, 4)  # post-invalidate: must be an inline miss
        st = pf.close()
        assert st.invalidated == 2
        assert st.hits == 0 and st.misses == 1

    def test_stale_spans_dropped_at_take(self):
        # a resume-skipped span must not pin a depth slot forever
        y = np.zeros((16, 4), np.float32)
        pf = ChunkPrefetcher(y, depth=1)
        pf.schedule(0, 4)
        pf.take(8, 12)  # the walk moved past [0,4): slot freed
        pf.schedule(12, 16)  # depth slot is available again
        pf.take(12, 16)
        st = pf.close()
        assert st.invalidated == 1
        assert st.hits == 1

    def test_staging_error_delivered_at_take(self):
        class _Boom:
            def __getitem__(self, key):
                raise RuntimeError("RESOURCE_EXHAUSTED: boom")

        pf = ChunkPrefetcher(_Boom(), depth=1)
        pf.schedule(0, 4)
        with pytest.raises(RuntimeError, match="boom"):
            pf.take(0, 4)
        pf.close()
