"""Auto model selection (ISSUE 9), beside ``test_auto.py``: the winners
stage-2 economy, the seasonal CSS extension, and the surfaces — the manifest,
the tools (obs_report / advise_budget), the compile-cache reuse counters,
``panel.auto_fit`` and the compat layer.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from _auto_cases import (
    KNOWN_ORDERS, _eq, assert_results_equal, make_ar_panel, make_known_panel,
    make_seasonal_panel)
from spark_timeseries_tpu import obs
from spark_timeseries_tpu import reliability as rel
from spark_timeseries_tpu.models import arima, auto
from spark_timeseries_tpu.reliability.status import FitStatus


# ---------------------------------------------------------------------------
# winners stage-2 economy
# ---------------------------------------------------------------------------


class TestWinnersMode:
    def test_agrees_on_easy_panel_and_records_spend(self):
        y = make_known_panel()
        full = auto.auto_fit(jnp.asarray(y), KNOWN_ORDERS, max_iters=25)
        win = auto.auto_fit(jnp.asarray(y), KNOWN_ORDERS, max_iters=25,
                            stage2="winners", stage1_iters=8)
        assert _eq(win.order_index, full.order_index)
        am = win.meta["auto_fit"]
        assert am["stage2"] == "winners"
        assert 0.0 < am["stage2_spend_share"] <= 1.0
        s2_rows = [m.get("stage2_rows") for m in am["orders"]]
        assert sum(s2_rows) == y.shape[0]  # every row refit exactly once
        # winning params carry the FULL budget: converged like the full fit
        assert np.asarray(win.converged).all()

    def test_winner_params_match_full_fit_of_winner(self):
        # rows that select order g in both modes get g's full-budget fit;
        # winners-mode params must be a genuine full fit (converged, finite)
        y = make_ar_panel(b=16, t=100)
        win = auto.auto_fit(jnp.asarray(y), [(1, 0, 0), (0, 0, 1)],
                            max_iters=25, stage2="winners", stage1_iters=6)
        assert (win.order_index == 0).all()
        assert np.isfinite(win.params[:, :2]).all()
        assert np.isnan(win.params[:, 2:]).all() or win.params.shape[1] == 2

    def test_winners_inherits_walk_knobs(self):
        # review hardening: the winner refit runs under the SAME contract
        # as the sweeps — a resilient search with interior-NaN rows must
        # not scatter DIVERGED refits over rows the sweep repaired
        y = make_ar_panel(b=16, t=100)
        y[2, 40:43] = np.nan  # interior NaNs: sanitizer-imputed
        res = auto.auto_fit(jnp.asarray(y), [(1, 0, 0), (0, 0, 1)],
                            max_iters=25, stage2="winners",
                            stage1_iters=8, resilient=True)
        assert res.order_index[2] >= 0
        assert np.isfinite(res.params[2, :2]).all()
        assert res.status[2] in (FitStatus.SANITIZED, FitStatus.OK,
                                 FitStatus.RETRIED, FitStatus.FALLBACK)

    def test_winners_source_stays_host_resident(self):
        # review hardening: a source-backed winners refit streams the
        # gathered rows through a HostChunkSource (batched contiguous
        # reads), matching the in-HBM winners search bitwise
        y = make_ar_panel(b=16, t=96, seed=9)
        kw = dict(max_iters=20, stage2="winners", stage1_iters=6,
                  chunk_rows=8)
        a = auto.auto_fit(jnp.asarray(y), [(1, 0, 0), (0, 0, 1)], **kw)
        b2 = auto.auto_fit(rel.HostChunkSource(y), [(1, 0, 0), (0, 0, 1)],
                           **kw)
        assert_results_equal(a, b2)
        sub = auto._gather_rows(rel.HostChunkSource(y),
                                np.array([0, 1, 2, 5, 6, 0, 0, 0]))
        assert isinstance(sub, rel.HostChunkSource)
        buf = np.empty((8, 96), np.float32)
        sub.read_rows(0, 8, buf)
        assert np.array_equal(buf, y[[0, 1, 2, 5, 6, 0, 0, 0]])

    def test_winners_criterion_matches_returned_nll(self):
        # review hardening: the reported criterion must be recomputed
        # from the full-budget refit's nll, not left at the stage-1 value
        y = make_ar_panel(b=16, t=100, seed=8)
        specs = [(1, 0, 0), (0, 0, 1)]
        win = auto.auto_fit(jnp.asarray(y), specs, max_iters=25,
                            stage2="winners", stage1_iters=6)
        g = int(win.order_index[0])
        assert (win.order_index == g).all()  # easy panel: one winner
        sel_spec = auto.normalize_orders(specs)[g]
        expect = np.asarray(auto.criterion_matrix(
            [sel_spec], jnp.asarray(win.neg_log_likelihood)[None, :],
            auto.panel_n_valid(jnp.asarray(y))))[0]
        assert np.allclose(win.criterion, expect, rtol=0, atol=0)

    def test_winners_job_budget_bounds_the_whole_search(self):
        # the whole-search budget covers the fused economy's stage 2 too:
        # an exhausted budget TIMEOUTs instead of dispatching refits
        y = make_ar_panel(b=16, t=96)
        res = auto.auto_fit(jnp.asarray(y), [(1, 0, 0), (0, 0, 1)],
                            max_iters=15, chunk_rows=8, stage2="winners",
                            stage1_iters=6, job_budget_s=1e-9)
        assert (res.order_index == -1).all()
        assert (res.status == FitStatus.TIMEOUT).all()

    def test_winners_journaled_resume(self, tmp_path):
        y = make_ar_panel(b=16, t=96, seed=4)
        kw = dict(max_iters=20, stage2="winners", stage1_iters=6,
                  chunk_rows=8)
        ref = auto.auto_fit(jnp.asarray(y), [(1, 0, 0), (0, 0, 1)],
                            checkpoint_dir=str(tmp_path / "a"), **kw)
        # fused economy: the stage-1 sweep journals under the fusion
        # group's grid_*_s1 dir; the per-basin refits are warm-started
        # recomputations of the journaled sweep, so no _winners journals
        assert os.path.exists(tmp_path / "a" / "grid_00000_s1"
                              / "manifest.json")
        assert not os.path.exists(tmp_path / "a" / "grid_00000_winners")
        res = auto.auto_fit(jnp.asarray(y), [(1, 0, 0), (0, 0, 1)],
                            checkpoint_dir=str(tmp_path / "a"), **kw)
        assert_results_equal(ref, res)

    def test_winners_fuse1_journaled_resume_bitwise_pr8(self, tmp_path):
        # the fuse=1 escape hatch keeps PR 8's journaled refit walks
        y = make_ar_panel(b=16, t=96, seed=4)
        kw = dict(max_iters=20, stage2="winners", stage1_iters=6,
                  chunk_rows=8, fuse=1)
        ref = auto.auto_fit(jnp.asarray(y), [(1, 0, 0), (0, 0, 1)],
                            checkpoint_dir=str(tmp_path / "a"), **kw)
        assert os.path.exists(tmp_path / "a" / "grid_00000_s1"
                              / "manifest.json")
        assert os.path.exists(tmp_path / "a" / "grid_00000_winners"
                              / "manifest.json")
        res = auto.auto_fit(jnp.asarray(y), [(1, 0, 0), (0, 0, 1)],
                            checkpoint_dir=str(tmp_path / "a"), **kw)
        assert_results_equal(ref, res)

    def test_manifest_grid_dirs_scoped_to_this_search(self, tmp_path):
        # review hardening: a winners run after a full run in the SAME
        # directory must not advertise the full run's journals as its own
        y = make_ar_panel(b=16, t=96)
        kw = dict(max_iters=15, chunk_rows=8)
        auto.auto_fit(jnp.asarray(y), [(1, 0, 0)],
                      checkpoint_dir=str(tmp_path), **kw)
        auto.auto_fit(jnp.asarray(y), [(1, 0, 0)], stage2="winners",
                      stage1_iters=6, checkpoint_dir=str(tmp_path), **kw)
        man = json.load(open(tmp_path / "auto_manifest.json"))
        assert "grid_00000" not in man["grid_dirs"]
        assert "grid_00000_s1" in man["grid_dirs"]


# ---------------------------------------------------------------------------
# seasonal candidates
# ---------------------------------------------------------------------------


class TestSeasonal:
    def test_seasonal_fit_recovers_coefficient(self):
        s = 4
        y = make_seasonal_panel(s=s)
        r = arima.fit(jnp.asarray(y), (0, 0, 0), seasonal=(1, 0, 0, s),
                      max_iters=40)
        assert np.asarray(r.converged).mean() >= 0.9
        sphi = np.asarray(r.params)[:, 1]
        assert abs(float(np.nanmean(sphi)) - 0.7) < 0.1

    def test_seasonal_candidate_wins_on_seasonal_panel(self):
        s = 4
        y = make_seasonal_panel(s=s)
        grid = [(1, 0, 0), (0, 0, 0, (1, 0, 0, s))]
        res = auto.auto_fit(jnp.asarray(y), grid, max_iters=30)
        assert (np.asarray(res.order_index) == 1).mean() >= 0.9

    def test_seasonal_validation(self):
        y = make_ar_panel(b=4, t=64)
        with pytest.raises(ValueError, match="period"):
            arima.fit(jnp.asarray(y), (1, 0, 0), seasonal=(1, 0, 0, 1))
        with pytest.raises(ValueError, match="scan backend"):
            arima.fit(jnp.asarray(y), (1, 0, 0), seasonal=(1, 0, 0, 4),
                      backend="pallas")
        with pytest.raises(ValueError, match="optimizing"):
            arima.fit(jnp.asarray(y), (1, 0, 0), seasonal=(1, 0, 0, 4),
                      method="hannan-rissanen")
        with pytest.raises(ValueError, match="too short"):
            arima.fit(jnp.asarray(y[:, :12]), (1, 0, 0),
                      seasonal=(1, 1, 1, 6))

    def test_expanded_polynomial_cross_terms(self):
        # (1 - 0.5L)(1 - 0.4L^2) -> lags [0.5, 0.4, -0.2]
        coefs = np.asarray(arima._expand_seasonal_poly(
            jnp.asarray([0.5], jnp.float32), jnp.asarray([0.4], jnp.float32),
            2, -1.0))
        assert np.allclose(coefs, [0.5, 0.4, -0.2])
        # MA side adds the cross term
        coefs = np.asarray(arima._expand_seasonal_poly(
            jnp.asarray([0.5], jnp.float32), jnp.asarray([0.4], jnp.float32),
            2, 1.0))
        assert np.allclose(coefs, [0.5, 0.4, 0.2])


# ---------------------------------------------------------------------------
# surfaces: meta, manifest, tools, panel/compat, counters
# ---------------------------------------------------------------------------


class TestSurfaces:
    def test_meta_and_auto_manifest(self, tmp_path):
        y = make_ar_panel(b=16, t=96)
        res = auto.auto_fit(jnp.asarray(y), [(1, 0, 0), (0, 0, 1)],
                            max_iters=15, chunk_rows=8,
                            checkpoint_dir=str(tmp_path))
        am = res.meta["auto_fit"]
        assert am["criterion"] == "aicc" and am["n_rows"] == 16
        assert [m["grid_index"] for m in am["orders"]] == [0, 1]
        assert all("wall_s" in m and "selected_rows" in m
                   for m in am["orders"])
        assert sum(am["selection_counts"].values()) == 16
        man = json.load(open(tmp_path / "auto_manifest.json"))
        assert man["kind"] == "auto_fit"
        # both orders share d=0: ONE fused group walk
        assert man["grid_dirs"] == ["grid_00000"]
        assert man["auto_fit"]["fusion_groups"] == [
            {"dir": "grid_00000", "orders": [0, 1]}]
        assert man["auto_fit"]["diff_cache_hits"] == 1

    def test_obs_report_validates_auto_manifest(self, tmp_path):
        import obs_report

        y = make_ar_panel(b=16, t=96)
        obs.enable()
        try:
            auto.auto_fit(jnp.asarray(y), [(1, 0, 0), (0, 0, 1)],
                          max_iters=15, chunk_rows=8,
                          checkpoint_dir=str(tmp_path))
        finally:
            obs.disable()
        assert obs_report.validate_manifest_telemetry(str(tmp_path)) == []
        # corrupt the selection counts: the gate must flag it
        man = json.load(open(tmp_path / "auto_manifest.json"))
        man["auto_fit"]["selection_counts"]["(1, 0, 0)"] = -1
        (tmp_path / "auto_manifest.json").write_text(json.dumps(man))
        errs = obs_report.validate_manifest_telemetry(str(tmp_path))
        assert any("selection_counts" in e for e in errs)

    def test_obs_report_flags_bad_auto_extra(self, tmp_path):
        import obs_report

        y = make_ar_panel(b=8, t=80)
        obs.enable()
        try:
            auto.auto_fit(jnp.asarray(y), [(1, 0, 0)], max_iters=10,
                          chunk_rows=4, checkpoint_dir=str(tmp_path))
        finally:
            obs.disable()
        sub = tmp_path / "grid_00000" / "manifest.json"
        m = json.load(open(sub))
        assert obs_report.validate_manifest_auto_extra(m, str(sub)) == []
        m["extra"]["auto_fit"]["grid_index"] = 7
        errs = obs_report.validate_manifest_auto_extra(m, str(sub))
        assert errs and any("grid" in e for e in errs)

    def test_advise_budget_auto(self, tmp_path):
        import advise_budget

        y = make_ar_panel(b=16, t=96)
        obs.enable()
        try:
            auto.auto_fit(jnp.asarray(y), [(1, 0, 0), (0, 0, 1)],
                          max_iters=15, chunk_rows=8,
                          checkpoint_dir=str(tmp_path))
        finally:
            obs.disable()
        a = advise_budget.advise_auto(str(tmp_path))
        assert a["auto_fit"] is True
        assert a["suggest"]["orders_per_pass"] == 2
        assert a["suggest"]["chunk_rows_grid"] is not None
        assert a["observed"]["orders_with_wins"] >= 1

    def test_compile_cache_counters_measure_reuse(self):
        y = make_ar_panel(b=16, t=96)
        obs.enable()
        try:
            c0 = (obs.snapshot() or {}).get("counters", {})
            auto.auto_fit(jnp.asarray(y), [(1, 0, 0)], max_iters=10,
                          chunk_rows=4)
            c1 = (obs.snapshot() or {}).get("counters", {})
        finally:
            obs.disable()
        hits = c1.get("compile_cache.hit", 0) - c0.get("compile_cache.hit", 0)
        # 4 chunks through one order's program: >= 3 chunk-level reuses
        assert hits >= 3
        stats = auto._compile_cache.program_cache_stats()
        assert stats["hits"] + stats["misses"] > 0

    def test_panel_auto_fit(self):
        from spark_timeseries_tpu import index as dtix
        from spark_timeseries_tpu.panel import TimeSeriesPanel

        y = make_ar_panel(b=8, t=80)
        idx = dtix.uniform("2024-01-01", periods=80,
                           frequency=dtix.DayFrequency(1))
        panel = TimeSeriesPanel(idx, [f"s{i}" for i in range(8)],
                                jnp.asarray(y))
        res = panel.auto_fit([(1, 0, 0), (0, 0, 1)], max_iters=15)
        assert res.order_index.shape == (8,)
        assert (res.order_index == 0).all()
        with pytest.raises(ValueError, match="source shape"):
            panel.auto_fit([(1, 0, 0)], source=np.zeros((4, 80), np.float32))

    def test_compat_auto_fit(self):
        from spark_timeseries_tpu.compat import sparkts

        y = make_ar_panel(b=6, t=100)
        m = sparkts.ARIMA.auto_fit(y[0], [(1, 0, 0), (0, 0, 1)],
                                   max_iters=20)
        assert isinstance(m, sparkts.ARIMAModel)
        assert m.order == (1, 0, 0)
        assert np.isfinite(m.criterion_value)
        ms = sparkts.ARIMA.auto_fit(y, [(1, 0, 0), (0, 0, 1)], max_iters=20)
        assert len(ms) == 6 and all(mm.order == (1, 0, 0) for mm in ms)
        assert ms[0].auto_result.meta["auto_fit"]["criterion"] == "aicc"

    def test_compat_auto_fit_seasonal_winner(self):
        # review hardening: a seasonal winner must NOT come back as an
        # ARIMAModel (whose forecast/effects would silently drop the
        # seasonal terms) — it is a SeasonalARIMAModel whose
        # forecast-family methods raise until seasonal forecasting lands
        from spark_timeseries_tpu.compat import sparkts

        s = 4
        y = make_seasonal_panel(b=4, s=s)
        m = sparkts.ARIMA.auto_fit(
            y[0], [(1, 0, 0), (0, 0, 0, (1, 0, 0, s))], max_iters=30)
        assert isinstance(m, sparkts.SeasonalARIMAModel)
        assert m.order == (0, 0, 0) and m.seasonal == (1, 0, 0, s)
        with pytest.raises(NotImplementedError, match="seasonal"):
            m.forecast(y[0], 5)
        assert np.isfinite(m.log_likelihood_css(y[0]))
        # save/load round-trips through the compat model registry
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            m.save(os.path.join(td, "m"))
            m2 = sparkts.load_model(os.path.join(td, "m"))
            assert isinstance(m2, sparkts.SeasonalARIMAModel)
            assert m2.seasonal == (1, 0, 0, s)
            assert np.array_equal(m2.coefficients, m.coefficients)
