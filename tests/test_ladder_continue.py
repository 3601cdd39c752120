"""The ladder continues a fit (ISSUE 53): a row that ran out of budget
enters the next rung at the point it reached, ``garch.fit`` takes
``init_params``, and every other failed row is treated as before.

(a) ``garch.fit(init_params=)`` on both backends; (b) ``resilient_fit`` with a
recording stub fit: what each rung is handed, and the rungs' budgets; (c) a
small real GARCH panel whose primary budget is cut; (d) the rung spans'
``continued`` / ``iters`` / ``rescued``; and the three callers that discover
``init_params`` from a fit's signature, with a GARCH fit.
"""

from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import generators, manifest
from benchmark.processes import garch11_returns
from benchmark.reference import check
from benchmark.reference import garch11 as ref
from spark_timeseries_tpu import forecasting as fc
from spark_timeseries_tpu import obs
from spark_timeseries_tpu import reliability as rel
from spark_timeseries_tpu.models import garch
from spark_timeseries_tpu.models.base import FitResult
from spark_timeseries_tpu.reliability import runner
from spark_timeseries_tpu.reliability.status import FitStatus

with open(os.path.join(manifest.BENCH_DIR, "configs", "garch11.json"),
          encoding="utf-8") as _f:
    CONFIG = json.load(_f)
BACKENDS = ["scan", "pallas-interpret"]


def panel(rows, n_time, seed=5):
    """``[rows, n_time]`` f32 of ``garch11``'s process, on the CPU."""
    return generators.build_panel(
        garch11_returns.rows, CONFIG["process"], {}, seed, jax.devices()[:1],
        rows, n_time, rows, CONFIG["population_seed"])


# -- (a) garch.fit takes a start ----------------------------------------------


@pytest.fixture(scope="module")
def small():
    return panel(16, 256)


@pytest.fixture(scope="module", params=BACKENDS)
def fitted(request, small):
    return request.param, garch.fit(small, backend=request.param)


def test_a_converged_fits_own_point_is_a_fixed_point(small, fitted):
    backend, base = fitted
    assert bool(np.all(np.asarray(base.converged)))
    again = garch.fit(small, backend=backend, init_params=base.params)
    assert bool(np.all(np.asarray(again.converged)))
    assert int(np.asarray(again.iters).max()) <= 2
    # the same objective within the fit's own tolerance (1e-4, relative)
    f0, f1 = (np.asarray(r.neg_log_likelihood) for r in (base, again))
    np.testing.assert_allclose(f1, f0, rtol=1e-4)
    assert np.all(f1 <= f0 + 1e-4 * np.abs(f0))


def test_a_row_with_a_nan_start_is_the_fit_without_the_keyword(small, fitted):
    backend, base = fitted
    init = np.array(base.params)
    init[3] = np.nan
    init[7, 1] = np.inf
    mixed = garch.fit(small, backend=backend, init_params=jnp.asarray(init))
    for row in (3, 7):
        assert int(mixed.iters[row]) == int(base.iters[row])
        # one program with the operand, one without: the same start, and
        # at most another fusion's last place
        np.testing.assert_allclose(np.asarray(mixed.params[row]),
                                   np.asarray(base.params[row]), rtol=1e-3)
        np.testing.assert_allclose(float(mixed.neg_log_likelihood[row]),
                                   float(base.neg_log_likelihood[row]),
                                   rtol=1e-5)
    # ... beside rows that DID start at their point
    assert int(mixed.iters[4]) <= 2 < int(base.iters[4])


def test_an_infeasible_start_is_no_start(small, fitted):
    backend, base = fitted
    init = np.array(base.params)
    init[0] = [1e-5, 0.6, 0.5]  # alpha + beta >= 1
    init[1] = [-1e-5, 0.1, 0.8]  # omega <= 0
    init[2] = [0.0, 0.1, 0.8]
    init[3] = [1e-5, -0.1, 0.8]  # a negative share
    start = garch._start_from(jnp.asarray(init), jnp.full((16, 3), 0.25))
    assert np.array_equal(np.asarray(start[:4]), np.full((4, 3), 0.25))
    assert np.array_equal(np.asarray(start[4:]), init[4:])
    assert bool(np.all(np.isfinite(jax.vmap(garch._from_natural)(start))))
    res = garch.fit(small, backend=backend, init_params=jnp.asarray(init))
    assert bool(np.all(np.isfinite(np.asarray(res.params))))
    assert bool(np.all(np.asarray(res.converged)))
    assert [int(i) for i in res.iters[:4]] == [int(i) for i in base.iters[:4]]


def test_one_start_serves_a_batch_and_a_single_series(small):
    point = jnp.asarray([2e-5, 0.08, 0.85], jnp.float32)
    batch = garch.fit(small[:4], backend="scan", init_params=point)
    one = garch.fit(small[0], backend="scan", init_params=point)
    assert batch.params.shape == (4, 3) and one.params.shape == (3,)
    np.testing.assert_allclose(np.asarray(one.params),
                               np.asarray(batch.params[0]), rtol=1e-3)


# -- (b) what the rungs are handed: a recording stub --------------------------

END = np.array([0.1, 0.2, 0.3], np.float32)  # where the primary left a row


def stub_fit(script, takes_init=True):
    """A fit with ``garch.fit``'s budget (80 in its SIGNATURE) that records
    each call's keyword arguments; ``script(call, ids, max_iters, init)``
    gives the call's ``(params, converged, iters)``, a row's identity riding
    in its first observation."""
    calls = []

    def run(y, kwargs):
        record = dict(kwargs)
        init = record.pop("init_params", None)
        if init is not None:
            init = record["init_params"] = np.asarray(init)
        calls.append(record)
        ids = np.asarray(y)[:, 0].astype(int)
        params, conv, iters = script(len(calls) - 1, ids,
                                     kwargs["max_iters"], init)
        params = np.asarray(params, np.float32)
        nll = np.where(np.isfinite(params).all(axis=1), 1.0, np.nan)
        return FitResult(jnp.asarray(params), jnp.asarray(nll, jnp.float32),
                         jnp.asarray(conv), jnp.asarray(iters, jnp.int32),
                         None)

    if takes_init:
        def fit(y, *, max_iters: int = 80, backend: str = "auto",
                compact: bool = True, init_params=None):
            return run(y, dict(max_iters=max_iters, backend=backend,
                               compact=compact, init_params=init_params))
    else:
        def fit(y, *, max_iters: int = 80, backend: str = "auto",
                compact: bool = True):
            return run(y, dict(max_iters=max_iters, backend=backend,
                               compact=compact))
    fit.calls = calls
    return fit


def ids_panel(rows):
    y = np.random.default_rng(0).normal(size=(rows, 12)).astype(np.float32)
    y[:, 0] = np.arange(rows)
    return y


def parents_start(primary_params, pad_idx, perturb, rng):
    """The start the ladder handed a failed row before ISSUE 53, written out
    again: the primary's point, jittered relative to its own magnitude."""
    base = np.nan_to_num(primary_params[pad_idx], nan=0.0, posinf=0.0,
                         neginf=0.0)
    jitter = perturb * (1.0 + np.abs(base)) * rng.standard_normal(base.shape)
    return (base + jitter).astype(np.float32)


def test_b_a_row_out_of_budget_goes_up_from_where_it_stands():
    def script(call, ids, max_iters, init):
        n = len(ids)
        if call == 0:  # row 2 runs the primary's budget out, the rest converge
            return (np.tile(END, (n, 1)), ids != 2,
                    np.where(ids == 2, max_iters, 7))
        if call == 1:  # and rung 1's
            return init + 0.5, np.zeros(n, bool), np.full(n, max_iters)
        return init + 0.25, np.ones(n, bool), np.full(n, 3)

    fit = stub_fit(script)
    res = rel.resilient_fit(fit, ids_panel(4), sanitize=False)
    primary, retry, fallback = fit.calls
    assert "init_params" not in primary
    # rung 1: the primary's end point, UNPERTURBED, in every slot of the
    # bucket (the pad slots repeat the row)
    assert retry["max_iters"] == 160 and retry["backend"] == "auto"
    assert np.array_equal(retry["init_params"], np.tile(END, (8, 1)))
    # rung 2: rung 1's end point, on the scan backend
    assert (fallback["max_iters"], fallback["backend"],
            fallback["compact"]) == (320, "scan", False)
    assert np.array_equal(fallback["init_params"], np.tile(END + 0.5, (8, 1)))
    assert res.status[2] == FitStatus.FALLBACK
    np.testing.assert_allclose(res.params[2], END + 0.75)
    assert [(r["rung"], r["attempted"], r["continued"], r["iters"],
             r["rescued"]) for r in res.meta["ladder"]] == [
        ("retry", 1, 1, 160, 0), ("fallback", 1, 1, 3, 1)]


@pytest.mark.parametrize("case", ["stalled", "non-finite", "no-init_params"])
def test_b_every_other_failed_row_gets_what_it_got(case):
    """A row stopped BEFORE its budget, a non-finite one, and any row of a
    fit without ``init_params``: the keyword arguments of both rungs are the
    parent's, bit for bit."""
    left = END.copy()
    if case == "non-finite":
        left[1] = np.nan

    def script(call, ids, max_iters, init):
        n = len(ids)
        if call == 0:
            iters = 37 if case == "stalled" else max_iters
            return (np.where((ids == 2)[:, None], left, END), ids != 2,
                    np.where(ids == 2, iters, 7))
        if call == 1:  # fails again, out of budget: and still not continued
            return (np.tile(END, (n, 1)) + 1.0, np.zeros(n, bool),
                    np.full(n, 5 if case == "stalled" else max_iters))
        return np.tile(END, (n, 1)), np.ones(n, bool), np.full(n, 3)

    fit = stub_fit(script, takes_init=case != "no-init_params")
    res = rel.resilient_fit(fit, ids_panel(4), sanitize=False, seed=11)
    _, retry, fallback = fit.calls
    assert (retry["max_iters"], fallback["max_iters"]) == (160, 320)
    if case == "no-init_params":
        assert "init_params" not in retry and "init_params" not in fallback
    else:
        rng = np.random.default_rng(11)
        primary = np.tile(left, (4, 1))
        pad_idx = np.full(8, 2)
        assert np.array_equal(retry["init_params"],
                              parents_start(primary, pad_idx, 0.05, rng))
        if case == "stalled":  # rung 2 too starts from the PRIMARY's point
            assert np.array_equal(fallback["init_params"],
                                  parents_start(primary, pad_idx, 0.2, rng))
    assert res.status[2] == FitStatus.FALLBACK
    continued = [r["continued"] for r in res.meta["ladder"]]
    # a non-finite row that rung 1 leaves FINITE and out of budget has
    # become a row to continue; the others never are
    assert continued == ([0, 1] if case == "non-finite" else [0, 0])
    if case == "non-finite":
        assert np.array_equal(fallback["init_params"],
                              np.tile(END + 1.0, (8, 1)))


def test_b_a_bucket_mixes_continued_and_perturbed_rows():
    def script(call, ids, max_iters, init):
        n = len(ids)
        if call == 0:  # row 1 stalls, row 3 runs out of budget
            return (np.tile(END, (n, 1)) * (1 + ids[:, None]),
                    (ids != 1) & (ids != 3),
                    np.where(ids == 3, max_iters, 9))
        return init, np.ones(n, bool), np.full(n, 2)

    fit = stub_fit(script)
    res = rel.resilient_fit(fit, ids_panel(5), sanitize=False, seed=3)
    retry = fit.calls[1]
    primary = (np.tile(END, (5, 1))
               * (1 + np.arange(5)[:, None])).astype(np.float32)
    pad_idx = np.array([1, 3, 1, 1, 1, 1, 1, 1])
    want = parents_start(primary, pad_idx, 0.05, np.random.default_rng(3))
    want[1] = primary[3]
    assert np.array_equal(retry["init_params"], want)
    assert res.meta["ladder"][0]["continued"] == 1
    assert list(res.status[[1, 3]]) == [FitStatus.RETRIED] * 2


@pytest.mark.parametrize("keyword,budgets", [
    ({}, (160, 320)), ({"max_iters": 100}, (200, 400)),
    ({"max_iters": 30}, (120, 240)), ("partial-50", (120, 240))],
    ids=["signature-80", "keyword-100", "keyword-30", "partial-50"])
def test_b_the_rungs_budgets_follow_the_fits_own(keyword, budgets):
    def script(call, ids, max_iters, init):
        n = len(ids)
        return (np.tile(END, (n, 1)), np.full(n, call == 2),
                np.full(n, max_iters))

    fit = stub_fit(script)
    call = fit
    if keyword == "partial-50":
        call, keyword = functools.partial(fit, max_iters=50), {}
    rel.resilient_fit(call, ids_panel(2), sanitize=False, **keyword)
    assert tuple(c["max_iters"] for c in fit.calls[1:]) == budgets
    ladder = runner.default_ladder(call, keyword.get("max_iters"))
    assert tuple(r.kwargs["max_iters"] for r in ladder) == budgets


def test_b_the_real_fits_budgets():
    from spark_timeseries_tpu.models import arima, holtwinters

    def budgets(fit):
        return tuple(r.kwargs["max_iters"]
                     for r in runner.default_ladder(fit))

    assert budgets(garch.fit) == (160, 320)
    assert budgets(arima.fit) == budgets(holtwinters.fit) == (120, 240)
    assert budgets(lambda y: None) == (120, 240)


# -- (c), (d) a real panel whose primary budget is cut ------------------------

CUT = 14  # iterations: ten rows of the 256 need more


def from_scratch(r, *, max_iters=80, tol=None, backend="auto", compact=True,
                 align_mode=None):
    """``garch.fit`` as the parent had it: no ``init_params``, so every rung
    starts again from the moment start."""
    return garch.fit(r, max_iters=max_iters, tol=tol, backend=backend,
                     compact=compact, align_mode=align_mode)


@pytest.fixture(scope="module")
def cut_walks(tmp_path_factory):
    y = panel(256, 256)
    path = str(tmp_path_factory.mktemp("obs") / "ev.jsonl")
    obs.enable(path)
    try:
        res = rel.fit_chunked(garch.fit, y, chunk_rows=128, max_iters=CUT)
    finally:
        obs.disable()
    with open(path, encoding="utf-8") as f:
        spans = [ev for ev in map(json.loads, f) if ev.get("kind") == "span"]
    parent = rel.fit_chunked(from_scratch, y, chunk_rows=128, max_iters=CUT)
    return np.asarray(y), res, parent, spans


def test_c_rows_out_of_budget_are_rescued_in_fewer_iterations(cut_walks):
    y, res, parent, _ = cut_walks
    rows = np.nonzero(np.asarray(res.status) != FitStatus.OK)[0]
    assert rows.size >= 8
    assert bool(np.all(np.asarray(res.status)[rows] == FitStatus.RETRIED))
    assert bool(np.all(np.asarray(res.converged)))
    mine, theirs = (r.meta["ladder_totals"] for r in (res, parent))
    assert set(mine) == set(theirs) == {"retry"}
    assert mine["retry"]["attempted"] == mine["retry"]["rescued"] \
        == mine["retry"]["continued"] == rows.size
    assert theirs["retry"]["continued"] == 0
    assert theirs["retry"]["rescued"] == rows.size
    assert mine["retry"]["iters"] < theirs["retry"]["iters"]
    # and they end where the configuration's rule wants them: within one
    # unit of log-likelihood of the reference's float64 optimum
    gaps = check.loglik_gaps(ref, {}, y[rows], np.asarray(res.params)[rows])
    assert gaps.max() <= CONFIG["reference"]["loglik_gap_max"]


def test_d_the_rung_spans_say_what_the_rule_did(cut_walks):
    _, res, _, spans = cut_walks
    rungs = [s for s in spans if s["name"].startswith("fit.rung.")]
    assert {s["name"] for s in rungs} == {"fit.rung.retry"}
    by_id = {s["id"]: s for s in spans}
    for s in rungs:
        a = s["attrs"]
        assert set(a) == {"rows", "cap", "continued", "iters", "rescued"}
        assert all(type(v) is int for v in a.values())
        assert a["continued"] == a["rescued"] == a["rows"] <= a["cap"]
        assert 0 < a["iters"] <= 160
        assert by_id[s["parent"]]["name"] == "chunk"
    totals = res.meta["ladder_totals"]["retry"]
    assert sum(s["attrs"]["iters"] for s in rungs) == totals["iters"]
    assert sum(s["attrs"]["continued"] for s in rungs) == totals["continued"]


# -- who else finds init_params on garch.fit ----------------------------------


def test_backtest_warm_refits_take_a_garch_fit(tmp_path):
    y = panel(16, 200)
    bt = fc.run_backtest(y, "garch", 4, n_windows=3, chunk_rows=8,
                         fit_kwargs={"max_iters": 60, "backend": "scan"},
                         checkpoint_dir=str(tmp_path / "c"))
    assert [w["status"] for w in bt.windows] == ["committed"] * 3
    assert [w["warm_start"] for w in bt.windows] == [False, True, True]
    assert np.all(np.isfinite(bt.metrics["mae_h"]))


def test_delta_warmstart_takes_a_garch_fit(tmp_path):
    kw = dict(chunk_rows=8, resilient=False, max_iters=60, backend="scan")
    y = np.asarray(panel(16, 232))
    prior = rel.fit_chunked(garch.fit, y[:, :200],
                            checkpoint_dir=str(tmp_path / "prior"), **kw)
    grown = rel.fit_chunked(garch.fit, y,
                            checkpoint_dir=str(tmp_path / "grown"),
                            delta_from=str(tmp_path / "prior"), **kw)
    assert grown.meta["delta"]["warmstart"] is True
    assert grown.meta["delta"]["counts"]["warm"] == 2
    assert bool(np.all(np.asarray(grown.converged)))
    # a warm row starts beside its optimum, not at the moment start
    assert np.asarray(grown.iters).sum() < np.asarray(prior.iters).sum()
    # and ends where a cold fit of the grown panel does, as far as the fit's
    # relative stopping rule tells two points of the flat omega-beta valley
    # apart (3.9: no likelihood-ratio test at 95% does, 3 parameters)
    cold = garch.fit(y, max_iters=60, backend="scan")
    gap = np.abs(np.asarray(grown.neg_log_likelihood)
                 - np.asarray(cold.neg_log_likelihood))
    assert np.median(gap) < 0.1 and gap.max() < 3.9


def test_the_probe_plan_reads_garchs_budget():
    from spark_timeseries_tpu.reliability import delta as delta_mod

    rows = delta_mod._PROBE_MIN_ROWS
    assert delta_mod._probe_plan(garch.fit, rows, {}) == (80, 5)
    assert delta_mod._probe_plan(garch.fit_argarch, rows, {}) is None
