"""Auto model selection (ISSUE 9): batched order search over the panel.

Covers the acceptance contracts:
- synthetic panels with known per-row orders recover the truth;
- ``auto_fit`` selection is bitwise-identical to an exhaustive per-order
  full-fit argmin on the same panel/chunk layout;
- journaled resume mid-grid is bitwise vs an uninterrupted search (a real
  SIGKILL variant lives in ``tests/_autofit_worker.py``, run by ci.sh and
  the slow-marked subprocess test here);
- a sharded 8-lane auto-fit matches the single-device search bitwise;
plus the seasonal CSS extension, the winners stage-2 economy, the grid
coordinate on the execution plan, the compile-cache reuse counters, and
the tools (obs_report / advise_budget) surfaces.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from spark_timeseries_tpu import obs
from spark_timeseries_tpu import reliability as rel
from spark_timeseries_tpu.models import arima, auto
from spark_timeseries_tpu.reliability import faultinject as fi
from spark_timeseries_tpu.reliability.status import FitStatus

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")
sys.path.insert(0, TOOLS)

FIELDS = ("params", "neg_log_likelihood", "converged", "iters", "status",
          "order_index", "criterion")


def _eq(a, b):
    a = np.asarray(a)
    return np.array_equal(a, np.asarray(b), equal_nan=a.dtype.kind == "f")


def assert_results_equal(r1, r2, fields=FIELDS):
    for f in fields:
        assert _eq(getattr(r1, f), getattr(r2, f)), f


def make_known_panel(rows_per=8, t=120, seed=0):
    """Rows 0..7 AR(1), 8..15 MA(1), 16..23 ARIMA(1,1,0) — each block's
    true order is on the grid, so selection has a known answer."""
    rng = np.random.default_rng(seed)
    b = 3 * rows_per
    e = rng.normal(size=(b, t)).astype(np.float32)
    y = np.zeros_like(e)
    for i in range(t):
        y[:rows_per, i] = (0.7 * y[:rows_per, i - 1] if i else 0) \
            + e[:rows_per, i]
    y[rows_per:2 * rows_per] = e[rows_per:2 * rows_per]
    y[rows_per:2 * rows_per, 1:] += 0.6 * e[rows_per:2 * rows_per, :-1]
    w = y[2 * rows_per:]
    for i in range(1, t):
        w[:, i] = (w[:, i - 1]
                   + 0.6 * (w[:, i - 1] - (w[:, i - 2] if i > 1 else 0))
                   + e[2 * rows_per:, i])
    return y


KNOWN_ORDERS = [(1, 0, 0), (0, 0, 1), (1, 1, 0)]


def make_ar_panel(b=24, t=120, seed=0, phi=0.7):
    rng = np.random.default_rng(seed)
    e = rng.normal(size=(b, t)).astype(np.float32)
    y = np.zeros_like(e)
    for i in range(t):
        y[:, i] = (phi * y[:, i - 1] if i else 0) + e[:, i]
    return y


def make_seasonal_panel(b=12, t=160, s=4, seed=3, sphi=0.7):
    rng = np.random.default_rng(seed)
    e = rng.normal(size=(b, t)).astype(np.float32)
    y = np.zeros_like(e)
    for i in range(t):
        y[:, i] = (sphi * y[:, i - s] if i >= s else 0) + e[:, i]
    return y


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
