"""``fit.stage1`` / ``fit.stage2`` on the lazy optimizer path (ISSUEs 25, 34-39):
the stage gate's spans carry the gate's numbers, tracing never changes a fit,
and ``count_evals`` instruments the fit that runs.  ``test_obs.py`` holds the
plane itself and the walk path's spans.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _obs_helpers import _assert_bitwise, _span_lines
from _pallas_helpers import _dist_parity
from spark_timeseries_tpu import obs
from spark_timeseries_tpu import reliability as rel
from spark_timeseries_tpu.models import arima
from spark_timeseries_tpu.ops import pallas_kernels as pk
from spark_timeseries_tpu.utils import optim

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.usefixtures("plane_off")


class TestStageGateSpans:
    """``fit.stage1`` / ``fit.stage2`` on the lazy path the chip runs
    (pallas, batch >= the compaction gate): here the interpreted kernel at
    the smallest batch the gate admits."""

    @pytest.fixture()
    def lazy(self, monkeypatch):
        monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
        seen = []
        real = arima._fit_stage1_program

        def spy(*static):
            run = real(*static)

            def run1(*args):
                out, aux = run(*args)
                seen.append(aux["starts"][0]["carry"])
                return out, aux

            return run1

        monkeypatch.setattr(arima, "_fit_stage1_program", spy)
        rng = np.random.default_rng(0)
        y = jnp.asarray(np.cumsum(rng.normal(size=(2048, 40)),
                                  axis=1).astype(np.float32))
        return y, seen

    # 14 iterations let stage 1 stop at the cap with budget left (stage 2
    # runs); 8 exhaust the budget with rows undone (the gate skips it)
    @pytest.mark.parametrize("max_iters,stage2", [(14, True), (8, False)])
    def test_stage_spans_carry_the_gates_numbers(self, lazy, tmp_path,
                                                 max_iters, stage2):
        y, seen = lazy
        fit = lambda: arima.fit(y, (1, 1, 1), backend="pallas-interpret",  # noqa: E731
                                max_iters=max_iters)
        off = fit()
        p = str(tmp_path / "ev.jsonl")
        obs.enable(p)
        with obs.span("fit.primary") as primary:
            on = fit()
        obs.disable()
        _assert_bitwise(on, off)
        spans = {s["name"]: s for s in _span_lines(p)}
        carry = seen[-1]
        s1 = spans["fit.stage1"]
        # series_block: what the value-only CSS kernel takes per grid
        # step over these rows, from the kernel file's own rule
        # lag_terms / lag_span: the live lag terms a CSS kernel step pays
        # (phi_1 and theta_1) and how far they reach
        # adjoint_panels: the panel-sized operands of the objective's
        # adjoint call, y3 and e3 (it forms the cotangent itself, ISSUE 35)
        # adjoint_series_block: what that call takes per grid step, by the
        # same rule (ISSUE 37)
        # trials / iter_passes / starts: what the lockstep loop's carry
        # counted, summed over the starts (ISSUE 38); tail_trials: those of
        # the trials that ran on the line search's tail (ISSUE 39)
        assert s1["attrs"] == {
            "rows": 2048, "iters": int(carry.k),
            "undone": int(carry.undone), "starts": 1,
            "iter_passes": int(carry.k), "trials": int(carry.trials),
            "tail_trials": int(carry.tail_trials),
            "series_block": pk.css_series_block(2048, 39, (1, 1, 1)),
            "adjoint_series_block": pk.css_series_block(
                2048, 39, (1, 1, 1), "adjoint"),
            "lag_terms": 2, "lag_span": 1, "adjoint_panels": 2}
        assert s1["attrs"]["series_block"] in (1024, 2048)
        assert s1["attrs"]["adjoint_series_block"] in (1024, 2048)
        assert all(type(s1["attrs"][k]) is int
                   for k in ("iters", "undone", "trials", "iter_passes"))
        assert s1["attrs"]["trials"] >= s1["attrs"]["iters"]
        assert 0 < s1["attrs"]["tail_trials"] < s1["attrs"]["trials"]
        assert s1["parent"] == primary.id
        assert s1["attrs"]["undone"] > 0
        assert (s1["attrs"]["iters"] < max_iters) == stage2
        assert ("fit.stage2" in spans) == stage2
        if stage2:
            assert spans["fit.stage2"]["attrs"] == {
                "rows": optim.compaction_cap(2048), "series_block": 1024,
                "adjoint_series_block": 1024,
                "lag_terms": 2, "lag_span": 1, "adjoint_panels": 2}
            assert spans["fit.stage2"]["parent"] == primary.id

    def test_seasonal_fit_opens_the_same_spans(self, monkeypatch, tmp_path):
        # a seasonal order takes the same gate (ISSUE 34): the stage spans
        # with the gate's numbers, and what its kernel step pays — the
        # airline model's three live MA lags 1, 4, 5 of the five the
        # expanded polynomial has
        monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
        fit = _lazy_sarima()
        off = fit()
        p = str(tmp_path / "ev.jsonl")
        obs.enable(p)
        with obs.span("fit.primary") as primary:
            on = fit()
        obs.disable()
        _assert_bitwise(on, off)
        spans = {s["name"]: s for s in _span_lines(p)}
        s1, s2 = spans["fit.stage1"], spans["fit.stage2"]
        assert set(s1["attrs"]) == {"rows", "iters", "undone", "starts",
                                    "iter_passes", "trials", "tail_trials",
                                    "series_block", "adjoint_series_block",
                                    "lag_terms", "lag_span",
                                    "adjoint_panels"}
        assert s1["attrs"]["rows"] == 2048 and s1["attrs"]["undone"] > 0
        assert s1["attrs"]["series_block"] == pk.css_series_block(
            2048, 55, ((), 0, (1, 4, 5)))
        assert s1["attrs"]["adjoint_series_block"] == pk.css_series_block(
            2048, 55, ((), 0, (1, 4, 5)), "adjoint")
        assert s2["attrs"] == {"rows": optim.compaction_cap(2048),
                               "series_block": 1024,
                               "adjoint_series_block": 1024, "lag_terms": 3,
                               "lag_span": 5, "adjoint_panels": 2}
        assert (s1["attrs"]["lag_terms"], s1["attrs"]["lag_span"]) == (3, 5)
        assert s1["parent"] == s2["parent"] == primary.id

    def test_grid_fit_spans_count_cells(self, monkeypatch, tmp_path):
        # a fused order search takes the same gate (ISSUE 36): its stage
        # spans count CELLS (orders x rows) and say what one kernel call
        # carries — the K orders, the union's lag terms and their reach
        monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
        rng = np.random.default_rng(3)
        y = jnp.asarray(np.cumsum(rng.normal(size=(256, 40)),
                                  axis=1).astype(np.float32))
        specs = tuple(((p, 1, q), None) for p in range(3) for q in range(3))
        fit = lambda: arima.fit_grid(  # noqa: E731
            y, specs, backend="pallas-interpret", max_iters=14)
        off = fit()
        p = str(tmp_path / "ev.jsonl")
        obs.enable(p)
        with obs.span("fit.primary") as primary:
            on = fit()
        obs.disable()
        _assert_bitwise(on, off)
        spans = {s["name"]: s for s in _span_lines(p)}
        s1, s2 = spans["fit.stage1"], spans["fit.stage2"]
        cells, cap = 9 * 256, 1024  # the grid's cap: whole 1,024-cell blocks
        static = {"orders": 9, "cells": cells, "lag_terms": 4, "lag_span": 2,
                  "adjoint_panels": 2}
        assert {k: v for k, v in s1["attrs"].items()
                if k not in ("iters", "undone", "iter_passes",
                             "trials", "tail_trials")} == {
            "rows": cells, "starts": 1, **static,
            "series_block": pk.css_grid_series_block(9, 256, 39, 2, 2),
            "adjoint_series_block": pk.css_grid_series_block(
                9, 256, 39, 2, 2, "adjoint")}
        assert 0 < s1["attrs"]["undone"] <= cap
        assert 0 < s1["attrs"]["iters"] < 14
        # the grid's line search runs until its slowest CELL accepts
        assert s1["attrs"]["iter_passes"] == s1["attrs"]["iters"] \
            <= s1["attrs"]["trials"]
        assert s2["attrs"] == {
            "rows": cap, **static,
            "series_block": pk.css_grid_series_block(1, cap, 39, 2, 2),
            "adjoint_series_block": pk.css_grid_series_block(
                1, cap, 39, 2, 2, "adjoint")}
        assert s1["parent"] == s2["parent"] == primary.id

    @pytest.mark.parametrize("family", ["arima", "sarima", "holtwinters",
                                        "garch"])
    def test_count_evals_instruments_the_fit_that_runs(self, monkeypatch,
                                                       tmp_path, family):
        # the flag selects no program: a counted fit takes the lazy pair,
        # returns the uncounted fit's bits, and its info is the gate's
        monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
        fit = _LAZY_FITS[family]()
        plain = fit()
        p = str(tmp_path / "ev.jsonl")
        obs.enable(p)
        counted, info = fit(count_evals=True)
        obs.disable()
        _assert_bitwise(counted, plain)
        spans = {s["name"]: s for s in _span_lines(p)}
        at = spans["fit.stage1"]["attrs"]["iters"]
        assert "fit.stage2" in spans
        # every family names its objective kernel's block on both stages
        assert spans["fit.stage1"]["attrs"]["series_block"] in (1024, 2048)
        assert spans["fit.stage2"]["attrs"]["series_block"] == 1024
        # and its adjoint's, by the kernel file's rule at each stage's rows
        # (Holt-Winters' additive adjoint reads one panel and takes its own
        # entry of the table)
        rule = _ADJOINT_BLOCKS[family]
        cap = optim.compaction_cap(2048)
        assert [spans[s]["attrs"]["adjoint_series_block"]
                for s in ("fit.stage1", "fit.stage2")] == [rule(2048),
                                                           rule(cap)]
        assert rule(131072) == 1024 * (
            pk._ADJOINT_R["hw"][False] if family == "holtwinters" else
            pk._ADJOINT_R["garch" if family == "garch" else "css"])
        # and the panel-sized operands of its objective's adjoint call
        panels = 1 if family == "holtwinters" else 2
        assert all(spans[s]["attrs"]["adjoint_panels"] == panels
                   for s in ("fit.stage1", "fit.stage2"))
        assert int(info["cap"]) == optim.compaction_cap(2048)
        assert int(info["compact_at"]) == at
        evals = np.asarray(info["ls_evals"])
        assert evals[:at].min() >= 1 and evals[at:].any()

    @pytest.mark.parametrize("family", ["arima", "sarima", "holtwinters",
                                        "garch"])
    def test_loop_counts_equal_count_evals_sums(self, monkeypatch, tmp_path,
                                                family):
        # ISSUE 38: the carry's scalar is count_evals's history summed —
        # stage 1's on the gate's span, stage 2's (deferred at its dispatch,
        # never waited for there) on the read-back's
        monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
        fit = _LAZY_FITS[family]()
        plain, info = fit(count_evals=True)
        evals, at = np.asarray(info["ls_evals"]), int(info["compact_at"])
        p = str(tmp_path / "ev.jsonl")
        obs.enable(p)
        res = rel.resilient_fit(lambda _y, **kw: fit(**kw),
                                jnp.zeros((2048, 2)), sanitize=False,
                                ladder=())
        obs.disable()
        assert np.array_equal(res.iters, np.asarray(plain.iters))
        spans = {s["name"]: s for s in _span_lines(p)}
        s1, back = spans["fit.stage1"]["attrs"], spans["fit.readback"]["attrs"]
        assert s1["starts"] == 1 and s1["iter_passes"] == s1["iters"] == at
        assert s1["trials"] == int(evals[:at].sum())
        assert back["stage2_trials"] == int(evals[at:].sum()) > 0
        assert back["stage2_iters"] == back["iters_max"] - at > 0
        assert all(type(back[k]) is int
                   for k in ("stage2_iters", "stage2_trials"))
        out = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "tools", "obs_report.py"),
             p, "--check"], capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr

    def test_no_stage2_reads_back_zero_and_zero(self, lazy, tmp_path):
        # the budget ends in stage 1 (8 iterations): the lazy path ran, so
        # the read-back says that no stage 2 did; nothing stays pending
        y, _ = lazy
        p = str(tmp_path / "ev.jsonl")
        obs.enable(p)
        rel.resilient_fit(arima.fit, y, order=(1, 1, 1), max_iters=8,
                          backend="pallas-interpret", ladder=())
        assert obs.settle() == {}
        obs.disable()
        spans = {s["name"]: s for s in _span_lines(p)}
        assert "fit.stage2" not in spans and "fit.stage1" in spans
        back = spans["fit.readback"]["attrs"]
        assert (back["stage2_iters"], back["stage2_trials"]) == (0, 0)

    def test_gate_with_tracing_off_reads_undone_and_k_only(self, lazy):
        # ISSUE 38: off, the gate makes the parent's transfers (undone, and
        # k where a stage 2 may follow) and not one more; on, it reads the
        # carry's trials — here a leaf that refuses to be read
        y, seen = lazy
        fit = lambda: arima.fit(y, (1, 1, 1), max_iters=8,  # noqa: E731
                                backend="pallas-interpret")
        want = fit()

        class Unread:
            def __int__(self):
                raise AssertionError("the gate read carry.trials")

            __index__ = __array__ = copy_to_host_async = __int__

        real = arima._fit_stage1_program  # the fixture's spy

        def blind(*static):
            def run1(*args):
                out, aux = real(*static)(*args)
                (start,) = aux["starts"]
                carry = start["carry"]._replace(trials=Unread())
                return out, {**aux, "starts": ({**start, "carry": carry},)}

            return run1

        arima._fit_stage1_program = blind  # the fixture's patch undoes it
        _assert_bitwise(fit(), want)
        obs.enable()
        with pytest.raises(AssertionError, match="carry.trials"):
            fit()

    def test_fit_under_a_callers_jit_is_the_composed_program(
            self, monkeypatch, tmp_path):
        # a traced panel cannot be gated on the host: the fit runs stage 1
        # and stage 2 in one trace, with no stage spans, to the eager lazy
        # fit's answer (another compiled program: the slow groups' rule)
        monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
        rng = np.random.default_rng(0)
        y = jnp.asarray(np.cumsum(rng.normal(size=(2048, 40)),
                                  axis=1).astype(np.float32))
        fit = lambda v: arima.fit(v, (1, 1, 1), max_iters=13,  # noqa: E731
                                  backend="pallas-interpret",
                                  align_mode="dense")
        eager = fit(y)
        p = str(tmp_path / "ev.jsonl")
        obs.enable(p)
        stage2 = obs.counter("optim.stage2_compact_traces")
        before = stage2.value
        traced = jax.jit(fit)(y)
        assert stage2.value == before + 1  # stage 2 is in that one trace
        obs.disable()
        assert not any(s["name"].startswith("fit.stage")
                       for s in _span_lines(p))
        _dist_parity(eager, traced, conv_floor=0.3)


def _lazy_arima():
    rng = np.random.default_rng(0)
    y = jnp.asarray(np.cumsum(rng.normal(size=(2048, 40)),
                              axis=1).astype(np.float32))
    return lambda **kw: arima.fit(y, (1, 1, 1), backend="pallas-interpret",
                                  max_iters=14, **kw)


def _lazy_sarima():
    # (1-L)(1-L^4) y = (1 - 0.4 L)(1 - 0.6 L^4) e: the airline model at s = 4
    rng = np.random.default_rng(34)
    e = rng.normal(size=(2048, 68))
    w = e[:, 5:] - 0.4 * e[:, 4:-1] - 0.6 * e[:, 1:-4] + 0.24 * e[:, :-5]
    y = np.cumsum(w, axis=1)[:, 3:]
    for i in range(4, y.shape[1]):
        y[:, i] += y[:, i - 4]
    y = jnp.asarray(y.astype(np.float32))
    return lambda **kw: arima.fit(y, (0, 1, 1), seasonal=(0, 1, 1, 4),
                                  backend="pallas-interpret", max_iters=14,
                                  **kw)


def _lazy_holtwinters():
    from spark_timeseries_tpu.models import holtwinters as hw

    rng = np.random.default_rng(32)
    tt = np.arange(48, dtype=np.float32)
    w = (10 + 0.02 * tt[None, :] + 2 * np.sin(2 * np.pi * tt[None, :] / 12)
         + 0.3 * rng.normal(size=(2048, 48))).astype(np.float32)
    w = jnp.asarray(w)
    return lambda **kw: hw.fit(w, 12, backend="pallas-interpret",
                               max_iters=13, **kw)


def _lazy_garch():
    from spark_timeseries_tpu.models import garch

    rng = np.random.default_rng(31)
    r = jnp.asarray((rng.normal(size=(2048, 64)) * 0.1).astype(np.float32))
    return lambda **kw: garch.fit(r, backend="pallas-interpret",
                                  max_iters=13, **kw)


# each family's ``rows -> adjoint_series_block`` at its lazy fit's shapes
_ADJOINT_BLOCKS = {
    "arima": lambda rows: pk.css_series_block(rows, 39, (1, 1, 1),
                                              "adjoint"),
    "sarima": lambda rows: pk.css_series_block(rows, 55, ((), 0, (1, 4, 5)),
                                               "adjoint"),
    "holtwinters": lambda rows: pk.hw_series_block(rows, 48, 12, "adjoint"),
    "garch": lambda rows: pk.garch_series_block(rows, 64, "adjoint"),
}
_LAZY_FITS = {"arima": _lazy_arima, "sarima": _lazy_sarima,
              "holtwinters": _lazy_holtwinters,
              "garch": _lazy_garch}
