"""The ``hw-mult24`` configuration of the benchmark, held on the CPU at small
sizes (ISSUE 44): the plain reference
(``benchmark/reference/holtwinters_multiplicative.py``) against the package's
own objective, the generating process, and ``holtwinters.fit(model_type=
"multiplicative")`` at its default THREE starts through ``lockstep.fit``'s
lazy path on the interpreted Pallas kernels — what the spans say of the
starts, of the stage-2 dispatches and of the merge, the one deferred scalar
``merge_switched``, and the fitted parameters against the reference's
optimum.  Rows come from the configuration's own process, seeded."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _obs_helpers import _span_lines
from benchmark import generators, manifest
from benchmark.processes import seasonal_multiplicative as process
from benchmark.reference import check
from benchmark.reference import holtwinters_multiplicative as ref
from spark_timeseries_tpu import obs
from spark_timeseries_tpu import reliability as rel
from spark_timeseries_tpu.models import holtwinters as hw
from spark_timeseries_tpu.utils import optim

with open(os.path.join(manifest.BENCH_DIR, "configs", "hw-mult24.json"),
          encoding="utf-8") as _f:
    CONFIG = json.load(_f)
KW = CONFIG["model"]["kwargs"]
LAZY_ROWS = optim.COMPACT_MIN_BATCH  # 4,096: the gate as shipped
FIT_KW = dict(period=24, model_type="multiplicative",
              backend="pallas-interpret")


def panel(rows, n_time, seed=5):
    """``[rows, n_time]`` f32 of the configuration's process, on the CPU."""
    return generators.build_panel(
        process.rows, CONFIG["process"], {}, seed, jax.devices()[:1], rows,
        n_time, rows, CONFIG["population_seed"])


def _assert_bitwise(a, b):
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)


# -- the configuration says what the issue says --------------------------------


def test_configuration_cuts_no_width():
    assert KW == {"period": 24, "model_type": "multiplicative",
                  "backend": "pallas"}  # n_starts, max_iters, tol: defaults
    assert (CONFIG["n_time"], CONFIG["dtype"], CONFIG["chunk_rows"]) \
        == (960, "float32", 131072)
    assert set(CONFIG["reduced"]) <= {"rows"}
    assert CONFIG["rows"] >= 262144 and CONFIG["rows"] % 131072 == 0
    assert (CONFIG["reduced"] == []) == (CONFIG["rows"] == 1048576)
    assert len(CONFIG["source"]) <= 200
    assert CONFIG["reference"]["loglik_gap_max"] <= 3.9
    assert CONFIG["reference"]["min_share"] >= 0.9
    assert ref.STARTS == hw._MULTISTART_NATS  # written out, not imported


# -- the reference is the model's objective ------------------------------------

PARAMS = ([0.3, 0.1, 0.1], [0.05, 0.0, 0.9], [0.9, 0.5, 0.02],
          [0.12, 0.05, 0.6])


@pytest.mark.parametrize("lead", [0, 29], ids=["dense", "late-start"])
@pytest.mark.parametrize("params", PARAMS, ids=lambda p: f"a{p[0]}g{p[2]}")
def test_reference_sse_is_the_models(params, lead):
    """``holtwinters.sse(..., multiplicative=True)`` (the scan, float64) on
    the right-aligned valid span equals the reference on the row with its
    NaNs: the same seeds from the first two seasons, the same three
    quotients, errors from the second season on."""
    row = np.asarray(panel(8, 200)[2], np.float64)
    row[:lead] = np.nan
    sse, n_eff = ref.objective(params, row, KW)
    assert n_eff == 200 - lead - 24
    aligned = jnp.asarray(np.concatenate([np.zeros(lead), row[lead:]]))
    ours = float(hw.sse(jnp.asarray(params, jnp.float64), aligned, 24, True,
                        200 - lead))
    assert ours == pytest.approx(sse, rel=1e-10)


def test_reference_optimum_takes_the_best_of_three_starts():
    rows = np.asarray(panel(8, 240))
    best = np.array([ref.optimum(y, KW) for y in rows])
    assert np.all((best >= 0) & (best <= 1))
    assert np.all(np.abs(check.loglik_gaps(ref, KW, rows, best)) < 1e-9)
    for start in ref.STARTS:  # no start's own value beats it
        assert np.all(check.loglik_gaps(
            ref, KW, rows, np.tile(start, (len(rows), 1))) >= 0)


# -- the process draws what the configuration says -----------------------------


def test_process_is_positive_and_one_draw_a_row():
    p = CONFIG["process"]
    par = np.asarray(process.draw_params(jax.random.key(1), 8192, p),
                     np.float64)
    assert par.shape == (8192, len(process.PARAMS))
    for name in ("level", "drift", "level_noise", "amplitude", "amplitude2",
                 "noise"):
        v, (lo, hi) = par[:, process.PARAMS.index(name)], p[name]
        assert lo <= v.min() and v.max() <= hi * (1 + 1e-6)
        assert v.min() < lo + 0.02 * (hi - lo) + 0.02 * lo  # the whole range
        assert v.max() > hi - 0.02 * (hi - lo)
    season = np.asarray(process.profile(jnp.asarray(par, jnp.float32), 24))
    assert season.shape == (8192, 24) and np.all(season > 0)
    assert np.allclose(season.mean(axis=1), 1.0, atol=1e-5)
    y = np.asarray(panel(512, 960))
    assert y.shape == (512, 960) and y.dtype == np.float32
    assert np.isfinite(y).all() and y.min() > 0
    # rows are not one generating point: the first day's means spread
    # over the level's decade, the day-over-day swing over the amplitudes
    day0 = y[:, :24].mean(axis=1)
    assert day0.min() < 15 and day0.max() > 70
    swing = (y[:, :24].max(axis=1) - y[:, :24].min(axis=1)) / day0
    assert swing.min() < 0.5 and swing.max() > 0.9


# -- three starts through one gate ---------------------------------------------


class _Seen:
    """What the three programs were handed and gave back, last call."""

    aux = results = None


@pytest.fixture()
def spied(monkeypatch):
    seen = _Seen()

    def spy1(*static):
        run = real1(*static)

        def run1(*args):
            out, seen.aux = run(*args)
            return out, seen.aux

        return run1

    def spy_merge(*static):
        run = real_merge(*static)

        def merge(results, fin):
            seen.results = results
            return run(results, fin)

        return merge

    real1, real_merge = hw._fit_stage1_program, hw._merge_starts_program
    monkeypatch.setattr(hw, "_fit_stage1_program", spy1)
    monkeypatch.setattr(hw, "_merge_starts_program", spy_merge)
    return seen


def _choice_not_the_first(results) -> int:
    """``holtwinters._select_best_start``'s rule once more, in numpy: the
    rows whose choice among the starts' results is not start 0."""
    f = np.stack([np.asarray(r.f) for r in results])
    f = np.where(np.isfinite(f), f, np.inf)
    conv = np.stack([np.asarray(r.converged) for r in results])
    eligible = np.where(conv.any(axis=0)[None, :], conv, True)
    f_elig = np.where(eligible, f, np.inf)
    near = eligible & (f_elig <= f_elig.min(axis=0)[None, :]
                       * np.float32(1 + 1e-3) + np.float32(1e-12))
    smooth = np.stack([np.asarray(jnp.sum(
        optim.sigmoid_to_interval(r.x, 0.0, 1.0), axis=-1)) for r in results])
    return int(np.sum(np.argmin(np.where(near, smooth, np.inf), axis=0) != 0))


# 60: every start stops at the cap with budget left, three stage 2s and the
# re-merge; 3: the budget ends in stage 1, nothing is dispatched after it
@pytest.mark.parametrize("max_iters,reran", [(60, True), (3, False)])
def test_spans_tell_the_starts_and_the_merge(spied, tmp_path, max_iters,
                                             reran):
    y = panel(LAZY_ROWS, 120)
    fit = lambda: rel.resilient_fit(  # noqa: E731
        hw.fit, y, max_iters=max_iters, sanitize=False, ladder=(), **FIT_KW)
    off = fit()
    path = str(tmp_path / "ev.jsonl")
    obs.enable(path)
    on = fit()
    assert obs.settle() == {}  # the read-back took what was deferred
    obs.disable()
    _assert_bitwise((on.params, on.neg_log_likelihood, on.iters),
                    (off.params, off.neg_log_likelihood, off.iters))
    spans = _span_lines(path)
    by_name = {s["name"]: s for s in spans}
    starts = spied.aux["starts"]
    undone = [int(s["carry"].undone) for s in starts]
    ks = [int(s["carry"].k) for s in starts]
    s1 = by_name["fit.stage1"]["attrs"]
    assert s1["starts"] == len(starts) == 3
    assert s1["undone_by_start"] == undone and sum(undone) == s1["undone"]
    assert s1["iters_by_start"] == ks and max(ks) == s1["iters"]
    assert sum(ks) == s1["iter_passes"]
    # a stage 2 names the start it finishes, in the starts' order
    stage2 = [s for s in spans if s["name"] == "fit.stage2"]
    want = [i for i in range(3) if undone[i] > 0 and ks[i] < max_iters]
    assert [s["attrs"]["start"] for s in stage2] == want
    assert bool(want) == reran
    # the merge's span exactly when a stage 2 reran
    assert [s["attrs"] for s in spans if s["name"] == "fit.merge"] \
        == [{"starts": 3}] * reran
    primary = by_name["fit.primary"]["id"]
    assert {s["parent"] for s in spans if s["name"] in (
        "fit.stage1", "fit.stage2", "fit.merge")} == {primary}
    # merge_switched: of the program whose result came back, the stage-1
    # merge's or the re-merge's that replaced it, never their sum
    final = spied.results if reran else [s["res"] for s in starts]
    back = by_name["fit.readback"]["attrs"]
    assert type(back["merge_switched"]) is int
    assert back["merge_switched"] == _choice_not_the_first(final)
    assert 0 < back["merge_switched"] < LAZY_ROWS
    if reran:
        assert spied.results is not None
        first = int(spied.aux["merge_switched"])
        assert first == _choice_not_the_first([s["res"] for s in starts])
        assert back["merge_switched"] != first + _choice_not_the_first(final)
        assert back["stage2_iters"] > 0
    else:
        assert spied.results is None and back["stage2_iters"] == 0


@pytest.mark.parametrize("max_iters", [60, 3])
def test_tracing_off_never_reads_merge_switched(spied, monkeypatch,
                                                max_iters):
    """Off, the gate reads ``undone`` and ``k`` per start and not one scalar
    more (PR 38's idiom): here a ``merge_switched`` leaf, stage 1's and the
    re-merge's, that refuses to be read; on, it is what ``obs.defer``
    touches first."""
    y = panel(LAZY_ROWS, 120)
    fit = lambda: hw.fit(y, max_iters=max_iters, **FIT_KW)  # noqa: E731
    want = fit()

    class Unread:
        def __int__(self):
            raise AssertionError("somebody read merge_switched")

        __index__ = __array__ = copy_to_host_async = __int__

    spy1, spy_merge = hw._fit_stage1_program, hw._merge_starts_program

    def blind1(*static):
        def run1(*args):
            out, aux = spy1(*static)(*args)
            return out, {**aux, "merge_switched": Unread()}

        return run1

    def blind_merge(*static):
        return lambda results, fin: (
            spy_merge(*static)(results, fin)[0], Unread())

    monkeypatch.setattr(hw, "_fit_stage1_program", blind1)
    monkeypatch.setattr(hw, "_merge_starts_program", blind_merge)
    _assert_bitwise(fit(), want)
    obs.enable()
    try:
        with pytest.raises(AssertionError, match="merge_switched"):
            fit()
    finally:
        obs.settle()
        obs.disable()


def test_one_start_families_report_as_before(tmp_path):
    """The additive model declares the same ``merge`` and runs ONE start:
    no ``start``, no per-start tuples, no ``fit.merge`` span, nothing
    deferred but stage 2's two counts — its span lines are the parent's."""
    y = panel(LAZY_ROWS, 120)
    path = str(tmp_path / "ev.jsonl")
    obs.enable(path)
    rel.resilient_fit(hw.fit, y, period=24, backend="pallas-interpret",
                      sanitize=False, ladder=())
    obs.disable()
    spans = _span_lines(path)
    by_name = {s["name"]: s for s in spans}
    assert "fit.stage2" in by_name and "fit.merge" not in by_name
    assert "start" not in by_name["fit.stage2"]["attrs"]
    assert not {"undone_by_start", "iters_by_start"} \
        & set(by_name["fit.stage1"]["attrs"])
    assert "merge_switched" not in by_name["fit.readback"]["attrs"]
    assert by_name["fit.readback"]["attrs"]["stage2_iters"] > 0


def test_fit_is_within_the_configurations_gap():
    """The comparison that decides ``correct`` on the chip, held here on the
    lazy path's own result: of a seeded sample at least the configuration's
    ``min_share`` lose at most its ``loglik_gap_max`` against the best of
    the reference's three starts; parameters moved by 0.2 do not pass."""
    y = panel(LAZY_ROWS, 120)
    res = hw.fit(y, **FIT_KW)
    limit = CONFIG["reference"]
    idx = np.sort(np.random.default_rng(44).choice(
        LAZY_ROWS, limit["rows"], replace=False))
    rows, par = np.asarray(y)[idx], np.asarray(res.params)[idx]
    gaps = check.loglik_gaps(ref, KW, rows, par)
    assert np.mean(gaps <= limit["loglik_gap_max"]) >= limit["min_share"]
    moved = check.loglik_gaps(ref, KW, rows, np.clip(par + 0.2, 0, 1))
    assert np.mean(moved <= limit["loglik_gap_max"]) < limit["min_share"]
