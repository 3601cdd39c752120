"""Auto model selection (ISSUE 9): batched order search over the panel.

Covers the acceptance contracts:
- synthetic panels with known per-row orders recover the truth;
- ``auto_fit`` selection is bitwise-identical to an exhaustive per-order
  full-fit argmin on the same panel/chunk layout;
- journaled resume mid-grid is bitwise vs an uninterrupted search (a real
  SIGKILL variant lives in ``tests/_autofit_worker.py``, run by ci.sh and
  the slow-marked subprocess test here);
- a sharded 8-lane auto-fit matches the single-device search bitwise;
plus the fused groups and the grid coordinate on the execution plan;
``test_auto_winners.py`` holds the winners stage-2 economy, the seasonal CSS
extension and the surfaces.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from _auto_cases import (
    FIELDS, KNOWN_ORDERS, _eq, assert_results_equal, make_ar_panel,
    make_known_panel, make_seasonal_panel)
from spark_timeseries_tpu import obs
from spark_timeseries_tpu import reliability as rel
from spark_timeseries_tpu.models import arima, auto
from spark_timeseries_tpu.reliability import faultinject as fi
from spark_timeseries_tpu.reliability.status import FitStatus


# ---------------------------------------------------------------------------
# grid spec + criteria
# ---------------------------------------------------------------------------


class TestOrdersSpec:
    def test_default_grid(self):
        specs = auto.normalize_orders(None)
        assert specs == auto.normalize_orders(auto.DEFAULT_ORDERS)
        assert all(s.seasonal is None for s in specs)

    def test_seasonal_entry(self):
        specs = auto.normalize_orders([(1, 0, 0), (1, 0, 1, (1, 1, 0, 12))])
        assert specs[1].seasonal == (1, 1, 0, 12)
        assert specs[1].label == "(1, 0, 1)x(1, 1, 0, 12)"
        assert specs[1].lag_span() == (1 + 12, 1, 12)
        assert specs[1].n_params(True) == 1 + 1 + 1 + 1

    def test_orderspec_passthrough_and_zero_seasonal(self):
        specs = auto.normalize_orders(
            [auto.OrderSpec((2, 0, 0)), (1, 0, 0, (0, 0, 0, 7))])
        assert specs[0].order == (2, 0, 0)
        assert specs[1].seasonal is None  # all-zero structure drops out

    @pytest.mark.parametrize("bad", [
        [], [(1, 0)], [(1, 0, -1, 0)], [(1, 0, 0), (1, 0, 0)],
        [(1, 0, 0, (1, 0, 0, 1))],
    ])
    def test_bad_grids_raise(self, bad):
        with pytest.raises(ValueError):
            auto.normalize_orders(bad)

    def test_criteria_penalties(self):
        # same nll everywhere: the smaller model must win under every
        # criterion, and AICc must penalize harder than AIC at small n
        nll = jnp.zeros((2, 4), jnp.float32)
        nv = jnp.full((4,), 40, jnp.int32)
        specs = [(1, 0, 0), (2, 0, 2)]
        aic = np.asarray(auto.criterion_matrix(specs, nll, nv,
                                               criterion="aic"))
        aicc = np.asarray(auto.criterion_matrix(specs, nll, nv,
                                                criterion="aicc"))
        bic = np.asarray(auto.criterion_matrix(specs, nll, nv,
                                               criterion="bic"))
        for c in (aic, aicc, bic):
            assert (c[0] < c[1]).all()
        assert (aicc > aic).all()

    def test_nonfinite_nll_is_ineligible(self):
        nll = jnp.asarray([[np.nan, 0.0]], jnp.float32)
        c = np.asarray(auto.criterion_matrix([(1, 0, 0)], nll[0][None],
                                             jnp.asarray([40, 40])))
        assert np.isinf(c[0, 0]) and np.isfinite(c[0, 1])

    def test_unknown_criterion_raises(self):
        y = make_ar_panel(b=4, t=60)
        with pytest.raises(ValueError, match="criterion"):
            auto.auto_fit(jnp.asarray(y), [(1, 0, 0)], criterion="hqic")
        with pytest.raises(ValueError, match="stage2"):
            auto.auto_fit(jnp.asarray(y), [(1, 0, 0)], stage2="cheap")


class TestPanelNValid:
    def test_spans(self):
        y = np.ones((4, 10), np.float32)
        y[1, :3] = np.nan           # leading
        y[2, 8:] = np.nan           # trailing
        y[3] = np.nan               # all-NaN
        nv = auto.panel_n_valid(y)
        assert nv.tolist() == [10, 7, 8, 0]

    def test_device_and_source_agree(self):
        y = make_ar_panel(b=8, t=64)
        y[0, :5] = np.nan
        a = auto.panel_n_valid(jnp.asarray(y))
        b = auto.panel_n_valid(y)
        c = auto.panel_n_valid(rel.HostChunkSource(y))
        assert np.array_equal(a, b) and np.array_equal(b, c)


# ---------------------------------------------------------------------------
# selection correctness + the bitwise exhaustive-argmin contract
# ---------------------------------------------------------------------------


class TestSelection:
    def test_known_orders_recovered(self):
        y = make_known_panel()
        res = auto.auto_fit(jnp.asarray(y), KNOWN_ORDERS, max_iters=30)
        want = np.repeat([0, 1, 2], 8)
        assert (np.asarray(res.order_index) == want).mean() >= 0.9
        counts = res.meta["auto_fit"]["selection_counts"]
        assert sum(counts.values()) == y.shape[0]

    def test_fuse1_bitwise_vs_exhaustive_argmin(self):
        # the PINNED PR 8 contract (ISSUE 10 regression test): fuse=1 is
        # the per-order path, and its selection (and the winner's
        # params/nll/criterion) must be BITWISE what a caller would get
        # from exhaustive independent full fits + argmin
        y = make_known_panel()
        res = auto.auto_fit(jnp.asarray(y), KNOWN_ORDERS, max_iters=30,
                            fuse=1)
        fits = [arima.fit(jnp.asarray(y), o, max_iters=30)
                for o in KNOWN_ORDERS]
        sel = auto.select_orders(KNOWN_ORDERS, fits,
                                 auto.panel_n_valid(jnp.asarray(y)))
        for f in FIELDS:
            assert _eq(getattr(res, f), sel[f]), f

    def test_fuse1_bitwise_vs_exhaustive_bic(self):
        y = make_known_panel(seed=5)
        res = auto.auto_fit(jnp.asarray(y), KNOWN_ORDERS, criterion="bic",
                            max_iters=25, fuse=1)
        fits = [arima.fit(jnp.asarray(y), o, max_iters=25)
                for o in KNOWN_ORDERS]
        sel = auto.select_orders(KNOWN_ORDERS, fits,
                                 auto.panel_n_valid(jnp.asarray(y)),
                                 criterion="bic")
        assert _eq(res.order_index, sel["order_index"])
        assert _eq(res.criterion, sel["criterion"])

    def test_all_nan_rows_select_none(self):
        y = make_ar_panel(b=8, t=80)
        y[3] = np.nan
        res = auto.auto_fit(jnp.asarray(y), [(1, 0, 0), (0, 0, 1)],
                            max_iters=15)
        assert res.order_index[3] == -1
        assert np.isnan(res.params[3]).all()
        assert res.status[3] == FitStatus.EXCLUDED
        assert res.meta["auto_fit"]["selection_counts"]["none"] == 1

    def test_return_criteria_matrix(self):
        y = make_ar_panel(b=6, t=80)
        res = auto.auto_fit(jnp.asarray(y), [(1, 0, 0), (0, 0, 1)],
                            max_iters=15, return_criteria=True)
        cm = res.meta["criteria_matrix"]
        assert cm.shape == (2, 6)
        picked = cm[np.asarray(res.order_index), np.arange(6)]
        assert np.allclose(picked, res.criterion)

    def test_tie_breaks_to_earlier_grid_entry(self):
        # identical (k, p_full, d_full) meta + identical nll -> exact
        # criterion ties; argmin must pick the EARLIER grid entry.  (No
        # two distinct orders share that meta, so drive the selection
        # program directly with a synthetic tie.)
        b = 3
        meta = ((2, 1, 0), (2, 1, 0))
        out = auto._select_program(meta, "aicc")(
            jnp.zeros((2, b, 2), jnp.float32), jnp.zeros((2, b), jnp.float32),
            jnp.ones((2, b), bool), jnp.zeros((2, b), jnp.int32),
            jnp.zeros((2, b), jnp.int8), jnp.full((b,), 50, jnp.int32))
        order_idx = np.asarray(out[5])
        assert (order_idx == 0).all()


# ---------------------------------------------------------------------------
# durability: chunked / journaled / resumed / sharded
# ---------------------------------------------------------------------------


class TestDurability:
    def test_journaled_pipelined_matches_serial_unjournaled(self, tmp_path):
        y = make_known_panel()
        kw = dict(max_iters=20, chunk_rows=8)
        plain = auto.auto_fit(jnp.asarray(y), KNOWN_ORDERS,
                              pipeline=False, **kw)
        j = auto.auto_fit(jnp.asarray(y), KNOWN_ORDERS,
                          checkpoint_dir=str(tmp_path / "j"),
                          pipeline_depth=3, **kw)
        assert_results_equal(plain, j)
        # fused layout: orders 0 and 1 share d=0 -> one group walk under
        # grid_00000 (chunks carry the whole group); order 2 (d=1) is a
        # singleton with the classic per-order journal
        m = json.load(open(tmp_path / "j" / "grid_00000" / "manifest.json"))
        assert m["extra"]["grid"] == {"index": 0, "total": 3,
                                      "fused": [0, 1]}
        af = m["extra"]["auto_fit"]
        assert af["fused_orders"] == [0, 1]
        assert af["orders"] == [list(KNOWN_ORDERS[0]), list(KNOWN_ORDERS[1])]
        assert af["stage"] == "full"
        assert not (tmp_path / "j" / "grid_00001").exists()
        m2 = json.load(open(tmp_path / "j" / "grid_00002" / "manifest.json"))
        assert m2["extra"]["grid"] == {"index": 2, "total": 3}
        assert m2["extra"]["auto_fit"]["order"] == list(KNOWN_ORDERS[2])

    def test_resume_mid_grid_bitwise(self, tmp_path):
        y = make_known_panel(seed=2)
        kw = dict(max_iters=20, chunk_rows=8)
        ref = auto.auto_fit(jnp.asarray(y), KNOWN_ORDERS,
                            checkpoint_dir=str(tmp_path / "ref"), **kw)
        # crash inside the SECOND group's walk: the fused group {0, 1}
        # commits its 3 chunks, then the singleton order-2 walk commits 1
        # of 3 — the kill lands MID-GROUP-SEQUENCE with a fused journal
        # fully durable and a per-order journal torn mid-walk
        with pytest.raises(fi.SimulatedCrash):
            auto.auto_fit(jnp.asarray(y), KNOWN_ORDERS,
                          checkpoint_dir=str(tmp_path / "b"),
                          _journal_commit_hook=fi.crash_after_commits(4),
                          **kw)
        g0 = json.load(open(tmp_path / "b" / "grid_00000"
                            / "manifest.json"))
        assert len([c for c in g0["chunks"]
                    if c["status"] == "committed"]) == 3
        g2 = json.load(open(tmp_path / "b" / "grid_00002"
                            / "manifest.json"))
        assert len([c for c in g2["chunks"]
                    if c["status"] == "committed"]) == 1
        res = auto.auto_fit(jnp.asarray(y), KNOWN_ORDERS,
                            checkpoint_dir=str(tmp_path / "b"), **kw)
        assert_results_equal(ref, res)

    def test_resume_is_rejected_for_different_grid_config(self, tmp_path):
        y = make_ar_panel(b=16, t=80)
        auto.auto_fit(jnp.asarray(y), [(1, 0, 0)], max_iters=10,
                      chunk_rows=8, checkpoint_dir=str(tmp_path))
        with pytest.raises(rel.StaleJournalError):
            auto.auto_fit(jnp.asarray(y), [(2, 0, 0)], max_iters=10,
                          chunk_rows=8, checkpoint_dir=str(tmp_path))

    def test_sharded_8_lane_matches_single_device(self, lane_mesh):
        y = make_known_panel()
        kw = dict(max_iters=15, chunk_rows=4)
        r1 = auto.auto_fit(jnp.asarray(y), KNOWN_ORDERS, **kw)
        r8 = auto.auto_fit(jnp.asarray(y), KNOWN_ORDERS, shard=True,
                           mesh=lane_mesh, **kw)
        assert_results_equal(r1, r8)

    def test_host_source_matches_in_hbm(self):
        y = make_ar_panel(b=16, t=96)
        kw = dict(max_iters=15, chunk_rows=8)
        a = auto.auto_fit(jnp.asarray(y), [(1, 0, 0), (0, 1, 1)], **kw)
        b = auto.auto_fit(rel.HostChunkSource(y), [(1, 0, 0), (0, 1, 1)],
                          **kw)
        assert_results_equal(a, b)

    def test_job_budget_bounds_the_whole_search(self):
        y = make_ar_panel(b=16, t=96)
        res = auto.auto_fit(jnp.asarray(y), [(1, 0, 0), (0, 0, 1)],
                            max_iters=15, chunk_rows=8,
                            job_budget_s=1e-9)
        # nothing dispatched: every row TIMEOUT, nothing selectable
        assert (res.order_index == -1).all()
        assert (res.status == FitStatus.TIMEOUT).all()

    def test_grid_coordinate_on_plain_walk(self, tmp_path):
        y = make_ar_panel(b=16, t=80)
        obs.enable()
        try:
            res = rel.fit_chunked(arima.fit, jnp.asarray(y), chunk_rows=8,
                                  resilient=False, order=(1, 0, 0),
                                  max_iters=10, grid=(1, 3),
                                  checkpoint_dir=str(tmp_path))
        finally:
            obs.disable()
        assert res.meta["grid"] == {"index": 1, "total": 3}
        assert all(c.get("grid") == 1
                   for c in res.meta["telemetry"]["chunks"])
        m = json.load(open(tmp_path / "manifest.json"))
        assert m["extra"]["grid"] == {"index": 1, "total": 3}
        with pytest.raises(ValueError, match="grid index"):
            rel.fit_chunked(arima.fit, jnp.asarray(y), grid=(3, 3),
                            resilient=False, order=(1, 0, 0))


# ---------------------------------------------------------------------------
# fused multi-order execution (ISSUE 10)
# ---------------------------------------------------------------------------


class TestFused:
    """Fused-vs-per-order equivalence: selection indices identical and
    per-order params/criteria matching across fused, per-order, journaled
    + crash-resumed-mid-group, sharded (8-lane), and ChunkSource-streamed
    walks — plus the fusion-group partition and the loud-contract edges."""

    def _assert_fused_matches_per_order(self, res_f, res_1):
        # selection must be IDENTICAL; the winner's params/criteria match
        # numerically (the fused program pads coefficient vectors and
        # shares one lockstep loop, so bitwise is fuse=1's contract)
        assert _eq(res_f.order_index, res_1.order_index)
        assert np.allclose(np.asarray(res_f.params),
                           np.asarray(res_1.params),
                           rtol=1e-2, atol=1e-2, equal_nan=True)
        assert np.allclose(np.asarray(res_f.criterion),
                           np.asarray(res_1.criterion),
                           rtol=1e-3, atol=1e-3, equal_nan=True)
        assert np.allclose(np.asarray(res_f.neg_log_likelihood),
                           np.asarray(res_1.neg_log_likelihood),
                           rtol=1e-3, atol=1e-3, equal_nan=True)

    def test_fusion_groups_partition(self):
        grid = [(1, 0, 0), (0, 0, 1), (1, 1, 0), (1, 0, 1), (1, 1, 1)]
        assert auto.fusion_groups(grid, "auto") == ((0, 1, 3), (2, 4))
        assert auto.fusion_groups(grid, 2) == ((0, 1), (2, 4), (3,))
        assert auto.fusion_groups(grid, 1) == tuple(
            (g,) for g in range(5))
        with pytest.raises(ValueError, match="fuse"):
            auto.fusion_groups(grid, 0)

    def test_fused_matches_per_order(self):
        y = make_known_panel()
        kw = dict(max_iters=30)
        res_f = auto.auto_fit(jnp.asarray(y), KNOWN_ORDERS, **kw)
        res_1 = auto.auto_fit(jnp.asarray(y), KNOWN_ORDERS, fuse=1, **kw)
        self._assert_fused_matches_per_order(res_f, res_1)
        am = res_f.meta["auto_fit"]
        assert am["fuse"] == "auto"
        assert [g["orders"] for g in am["fusion_groups"]] == [[0, 1], [2]]
        assert am["diff_cache_hits"] == 1  # orders 0 and 1 share (d=0)

    def test_fused_crash_resume_mid_group(self, tmp_path):
        # the SIGKILL-mid-GROUP contract: crash while the fused group's
        # own chunks are mid-walk, resume, bitwise vs uninterrupted fused
        y = make_known_panel(seed=7)
        kw = dict(max_iters=20, chunk_rows=8)
        ref = auto.auto_fit(jnp.asarray(y), KNOWN_ORDERS,
                            checkpoint_dir=str(tmp_path / "ref"), **kw)
        with pytest.raises(fi.SimulatedCrash):
            auto.auto_fit(jnp.asarray(y), KNOWN_ORDERS,
                          checkpoint_dir=str(tmp_path / "b"),
                          _journal_commit_hook=fi.crash_after_commits(2),
                          **kw)
        # died INSIDE the fused group {0, 1}'s walk: 2 of 3 chunks durable
        g0 = json.load(open(tmp_path / "b" / "grid_00000"
                            / "manifest.json"))
        assert len([c for c in g0["chunks"]
                    if c["status"] == "committed"]) == 2
        assert not os.path.exists(tmp_path / "b" / "grid_00002")
        res = auto.auto_fit(jnp.asarray(y), KNOWN_ORDERS,
                            checkpoint_dir=str(tmp_path / "b"), **kw)
        assert_results_equal(ref, res)
        assert res.meta["auto_fit"]["diff_cache_hits"] == 1

    def test_fused_sharded_8_lane_matches_single_device(self, lane_mesh):
        y = make_known_panel()
        kw = dict(max_iters=15, chunk_rows=4)
        r1 = auto.auto_fit(jnp.asarray(y), KNOWN_ORDERS, **kw)
        r8 = auto.auto_fit(jnp.asarray(y), KNOWN_ORDERS, shard=True,
                           mesh=lane_mesh, **kw)
        assert_results_equal(r1, r8)

    def test_fused_source_streamed_matches_in_hbm(self):
        y = make_known_panel(seed=3)
        kw = dict(max_iters=20, chunk_rows=8)
        a = auto.auto_fit(jnp.asarray(y), KNOWN_ORDERS, **kw)
        b2 = auto.auto_fit(rel.HostChunkSource(y), KNOWN_ORDERS, **kw)
        assert_results_equal(a, b2)

    def test_fused_seasonal_shares_diff_cache(self):
        # plain and seasonal candidates with the same d fuse into one
        # group; with D=0 the seasonal variant's differencing signature
        # IS the plain one, so all three orders share ONE differenced
        # panel (two cache hits)
        y = make_seasonal_panel(b=8, s=4)
        grid = [(1, 0, 0), (0, 0, 1), (0, 0, 0, (1, 0, 0, 4))]
        res_f = auto.auto_fit(jnp.asarray(y), grid, max_iters=30)
        res_1 = auto.auto_fit(jnp.asarray(y), grid, max_iters=30, fuse=1)
        assert _eq(res_f.order_index, res_1.order_index)
        assert (np.asarray(res_f.order_index) == 2).mean() >= 0.9
        am = res_f.meta["auto_fit"]
        assert [g["orders"] for g in am["fusion_groups"]] == [[0, 1, 2]]
        assert am["diff_cache_hits"] == 2  # one signature across 3 orders

    def test_fit_grid_validation(self):
        y = make_ar_panel(b=4, t=64)
        with pytest.raises(ValueError, match="same-d"):
            arima.fit_grid(jnp.asarray(y), (((1, 0, 0), None),
                                            ((0, 1, 1), None)))
        # the kernels take a group of plain orders (PR 36): what is refused
        # is a native Pallas run off the TPU, by resolve_backend as for
        # every fit, and a group the grid kernels cannot take
        with pytest.raises(ValueError, match="natively"):
            arima.fit_grid(jnp.asarray(y), (((1, 0, 0), None),),
                           backend="pallas")
        with pytest.raises(ValueError, match="seasonal member"):
            arima.fit_grid(jnp.asarray(y), (((1, 0, 0), None),
                                            ((0, 0, 0), (1, 0, 0, 4))),
                           backend="pallas-interpret")
        with pytest.raises(ValueError, match="at least one"):
            arima.fit_grid(jnp.asarray(y), ())
        assert arima.grid_pack_width(
            (((1, 0, 0), None), ((0, 0, 1), None))) == 2 * (2 + 5)
        # a D=0 seasonal spec shares the plain signature; seasonal
        # DIFFERENCING (D>0) is its own key
        assert arima.grid_diff_cache_keys(
            (((1, 0, 0), None), ((0, 0, 1), None),
             ((0, 0, 0), (1, 0, 0, 4)))) == 1
        assert arima.grid_diff_cache_keys(
            (((1, 0, 0), None), ((0, 0, 0), (0, 1, 1, 4)))) == 2

    def test_fused_rejects_unsupported_fit_kwargs(self):
        y = make_ar_panel(b=8, t=64)
        with pytest.raises(ValueError, match="fuse=1"):
            auto.auto_fit(jnp.asarray(y), [(1, 0, 0), (0, 0, 1)],
                          count_evals=True)
        with pytest.raises(ValueError, match="natively"):
            auto.auto_fit(jnp.asarray(y), [(1, 0, 0), (0, 0, 1)],
                          backend="pallas")
        with pytest.raises(ValueError, match="seasonal member"):
            auto.auto_fit(jnp.asarray(y),
                          [(1, 0, 0), (0, 0, 0, (1, 0, 0, 4))],
                          backend="pallas-interpret")
        # singleton groups never hit the fused program: pallas rides
        y2 = make_ar_panel(b=8, t=64, seed=2)
        res = auto.auto_fit(jnp.asarray(y2), [(1, 0, 0), (0, 1, 1)],
                            max_iters=10, backend="scan")
        assert res.order_index.shape == (8,)

    def test_fused_resilient_keeps_sanitized_status(self):
        # resilient transitions are ROW-wide facts: a sanitizer-repaired
        # row must come back SANITIZED from the demuxed selection, not
        # silently OK (the pack statuses come from the final fit, which
        # saw already-repaired data)
        y = make_ar_panel(b=16, t=100)
        y[2, 40:43] = np.nan
        res = auto.auto_fit(jnp.asarray(y), [(1, 0, 0), (0, 0, 1)],
                            max_iters=20, chunk_rows=8, resilient=True)
        assert res.order_index[2] >= 0
        assert res.status[2] == FitStatus.SANITIZED

    def test_fused_resilient_heterogeneous_k_no_phantom_retries(self):
        # review hardening: the pack is ALL-FINITE by construction — with
        # heterogeneous per-order k in one group (k_max padding) a
        # NaN-padded pack would fail the resilient runner's per-row
        # finiteness mask and feed the ENTIRE panel through the retry
        # ladder on every chunk
        y = make_ar_panel(b=16, t=100)
        grid = [(1, 0, 0), (1, 0, 1)]  # same d, k = 2 vs 3
        obs.enable()
        try:
            c0 = (obs.snapshot() or {}).get("counters", {})
            res = auto.auto_fit(jnp.asarray(y), grid, max_iters=25,
                                chunk_rows=8, resilient=True)
            c1 = (obs.snapshot() or {}).get("counters", {})
        finally:
            obs.disable()
        attempted = sum(v - c0.get(k, 0) for k, v in c1.items()
                        if k.startswith("ladder.") and
                        k.endswith(".attempted"))
        assert attempted == 0  # clean panel: nothing enters the ladder
        assert (np.asarray(res.status) == FitStatus.OK).all()
        plain = auto.auto_fit(jnp.asarray(y), grid, max_iters=25,
                              chunk_rows=8)
        assert _eq(res.order_index, plain.order_index)

    def test_fused_resilient_all_excluded_row_is_shielded(self):
        # an all-NaN row is EXCLUDED by every order: the row summary must
        # be EXCLUDED (min severity = every order refused) so the ladder's
        # retry-cannot-help shield holds and the row skips the rungs
        y = make_ar_panel(b=16, t=100)
        y[3] = np.nan
        res = auto.auto_fit(jnp.asarray(y), [(1, 0, 0), (0, 0, 1)],
                            max_iters=20, chunk_rows=8, resilient=True,
                            policy="exclude")
        assert res.order_index[3] == -1
        assert res.status[3] == FitStatus.EXCLUDED
        assert (np.asarray(res.order_index)[np.arange(16) != 3] >= 0).all()

    def test_fused_all_nan_row_selects_none(self):
        y = make_ar_panel(b=8, t=80)
        y[3] = np.nan
        res = auto.auto_fit(jnp.asarray(y), [(1, 0, 0), (0, 0, 1)],
                            max_iters=15)
        assert res.order_index[3] == -1
        assert np.isnan(res.params[3]).all()
        assert res.status[3] == FitStatus.EXCLUDED

    def test_advise_budget_suggests_fuse(self, tmp_path):
        import advise_budget

        y = make_known_panel()
        obs.enable()
        try:
            auto.auto_fit(jnp.asarray(y), KNOWN_ORDERS, max_iters=15,
                          chunk_rows=8, checkpoint_dir=str(tmp_path))
        finally:
            obs.disable()
        a = advise_budget.advise_auto(str(tmp_path))
        assert a["suggest"]["fuse"] >= 1
        assert a["observed"]["max_same_d_orders"] == 2
        assert a["observed"]["diff_cache_hits"] == 1
        assert a["observed"]["fuse_used"] == "auto"

    def test_obs_report_validates_fused_manifests(self, tmp_path):
        import obs_report

        y = make_known_panel()
        obs.enable()
        try:
            auto.auto_fit(jnp.asarray(y), KNOWN_ORDERS, max_iters=15,
                          chunk_rows=8, checkpoint_dir=str(tmp_path))
        finally:
            obs.disable()
        assert obs_report.validate_manifest_telemetry(str(tmp_path)) == []
        # corrupt the fused block: the gate must flag it
        sub = tmp_path / "grid_00000" / "manifest.json"
        m = json.load(open(sub))
        assert obs_report.validate_manifest_auto_extra(m, str(sub)) == []
        m["extra"]["auto_fit"]["fused_orders"] = [0, 7]
        errs = obs_report.validate_manifest_auto_extra(m, str(sub))
        assert errs and any("fused" in e for e in errs)
        man = json.load(open(tmp_path / "auto_manifest.json"))
        man["auto_fit"]["fusion_groups"][0]["orders"] = [0]
        (tmp_path / "auto_manifest.json").write_text(json.dumps(man))
        errs = obs_report.validate_auto_manifest(str(tmp_path))
        assert any("fusion_groups" in e for e in errs)


# ---------------------------------------------------------------------------
# real-SIGKILL smoke (subprocess; ci.sh runs the same orchestration)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_autofit_sigkill_resume_smoke():
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "_autofit_worker.py")
    r = subprocess.run([sys.executable, worker, "--smoke"],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "PASS" in r.stdout
