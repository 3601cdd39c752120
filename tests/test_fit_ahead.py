"""The lane fits one chunk ahead of its walk (ISSUE 56, tier-1 CPU).

``plan.LaneRunner`` keeps ONE chunk's fit in flight ahead of the chunk it
is finishing, on the prefetcher's fit-ahead thread
(``prefetcher.ChunkPrefetcher.fit_ahead``).  Held here: the walk that fits
ahead is the ``pipeline=False`` walk bit for bit, arrays and journal shards,
in every family; a rung that runs while a fit is in flight gives the serial
ladder; every way a fit ahead is dropped leaves the serial result and
nothing of the dropped fit behind; the walk's own knobs decide where nothing
is fitted ahead; a crashed walk resumes bitwise; and a rung that has to
BUILD its program lets the fit in flight make its last dispatch first.
"""

import functools
import hashlib
import json
import os
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from spark_timeseries_tpu import obs
from spark_timeseries_tpu import reliability as rel
from spark_timeseries_tpu.models import (arima, ewma, garch, holtwinters,
                                         regression_arima)
from spark_timeseries_tpu.reliability import faultinject as fi
from spark_timeseries_tpu.reliability import plan as plan_mod
from spark_timeseries_tpu.utils import compile_cache as cc

from _obs_helpers import _span_lines as _all_span_lines

FIELDS = ("params", "neg_log_likelihood", "converged", "iters", "status")


def _span_lines(path, name=None):
    return [s for s in _all_span_lines(path)
            if name is None or s["name"] == name]


def _panel(b=40, t=96, seed=3, positive=False):
    rng = np.random.default_rng(seed)
    e = rng.normal(size=(b, t)).astype(np.float32)
    y = np.zeros_like(e)
    for i in range(1, t):
        y[:, i] = 0.5 * y[:, i - 1] + e[:, i]
    season = 2.0 * np.sin(2 * np.pi * np.arange(t) / 12.0)
    y = y + season.astype(np.float32)
    return y + 20.0 if positive else y


FAMILIES = {
    "arima": (arima.fit, {"order": (1, 0, 1), "max_iters": 20}, _panel()),
    "holtwinters": (holtwinters.fit, {"period": 12, "max_iters": 20},
                    _panel(positive=True)),
    "garch": (garch.fit, {"max_iters": 20}, 0.1 * _panel(seed=4)),
    "harmonic": (regression_arima.fit_harmonic,
                 {"periods": (12, 48), "harmonics": (2, 1), "max_iters": 20},
                 _panel(seed=5)),
}


def _walk(fit, kw, y, d=None, **more):
    more.setdefault("chunk_rows", 8)
    return rel.fit_chunked(fit, jnp.asarray(y), checkpoint_dir=d, **kw,
                           **more)


def _same(a, b):
    for f in FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f"field {f!r} differs")


def _manifest(d):
    with open(os.path.join(d, "manifest.json"), encoding="utf-8") as fh:
        return json.load(fh)


def _shards(d):
    """Committed chunks in manifest order: span, backoff state, shard bytes."""
    out = []
    for c in _manifest(d)["chunks"]:
        with open(os.path.join(d, c["shard"]), "rb") as fh:
            out.append((c["lo"], c["hi"], c.get("chunk_rows_after"),
                        c["status"], hashlib.sha256(fh.read()).hexdigest()))
    return out


def _ahead(res):
    p = res.meta["pipeline"]
    return p["fits_ahead"], p["fits_ahead_taken"]


def _no_thread_left():
    assert not [t.name for t in threading.enumerate()
                if t.name.startswith("fit-ahead")]


def _on_ahead_thread():
    return threading.current_thread().name.startswith("fit-ahead")


# -- bitwise, every family -----------------------------------------------------


@pytest.mark.parametrize("resilient", [True, False])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_walk_that_fits_ahead_is_the_serial_walk(tmp_path, family, resilient):
    fit, kw, y = FAMILIES[family]
    d_a, d_s = str(tmp_path / "ahead"), str(tmp_path / "serial")
    serial = _walk(fit, kw, y, d_s, pipeline=False, resilient=resilient)
    ahead = _walk(fit, kw, y, d_a, resilient=resilient)
    _same(ahead, serial)
    assert _shards(d_a) == _shards(d_s)
    assert serial.meta.get("pipeline") is None
    started, taken = _ahead(ahead)
    # five chunks: the first has no predecessor, and the chunk after one
    # that BUILT a program (a process's first walk of a shape) is not
    # fitted ahead
    assert 3 <= taken == started <= 4
    assert ahead.meta.get("ladder_totals") == serial.meta.get("ladder_totals")
    _no_thread_left()
    # a second walk of the same shapes builds nothing: every chunk but the
    # first comes from a fit ahead
    again = _walk(fit, kw, y, resilient=resilient)
    _same(again, serial)
    assert _ahead(again) == (4, 4)


@pytest.mark.parametrize("family", ["garch", "harmonic"])
def test_rung_beside_a_fit_in_flight_gives_the_serial_ladder(family):
    """A budget so small that rows of every chunk reach the ladder: the rung
    of chunk i runs on the driver while chunk i + 1's fit is in flight."""
    fit, kw, y = FAMILIES[family]
    kw = {**kw, "max_iters": 3}
    serial = _walk(fit, kw, y, pipeline=False)
    assert serial.meta["ladder_totals"]["retry"]["attempted"] > 0
    _walk(fit, kw, y)  # the shapes' builds
    ahead = _walk(fit, kw, y)
    _same(ahead, serial)
    assert ahead.meta["ladder_totals"] == serial.meta["ladder_totals"]
    assert ahead.meta["status_counts"] == serial.meta["status_counts"]
    assert _ahead(ahead) == (4, 4)


# -- where nothing is fitted ahead ---------------------------------------------


@pytest.mark.parametrize("knobs", [
    {"prefetch_depth": 0}, {"pipeline": False}, {"chunk_budget_s": 60.0},
    {"chunk_rows": 64}], ids=lambda k: next(iter(k)))
def test_the_walks_own_knobs_decide_where_nothing_is_fitted_ahead(knobs):
    fit, kw, y = FAMILIES["arima"]
    ref = _walk(fit, kw, y, pipeline=False)
    calls = []

    @functools.wraps(fit)
    def spy(yb, **k):
        calls.append(_on_ahead_thread())
        return fit(yb, **k)

    got = _walk(spy, kw, y, **knobs)
    _same(got, ref)
    assert not any(calls)
    assert (got.meta.get("pipeline") or {}).get("fits_ahead", 0) == 0


# -- every way a fit ahead is dropped ------------------------------------------


def _warm(kw, y, **more):
    """The shapes' builds, so that the walk under test starts its first fit
    ahead behind chunk 0."""
    _walk(arima.fit, kw, y, **more)


def test_oom_of_the_fit_ahead_is_no_oom_event_of_the_walk(tmp_path):
    fit, kw, y = FAMILIES["arima"]
    ref = _walk(fit, kw, y, pipeline=False)
    _warm(kw, y)
    seen = []

    @functools.wraps(fit)
    def oom_ahead(yb, **k):
        seen.append(_on_ahead_thread())
        if _on_ahead_thread():
            raise fi.SimulatedResourceExhausted(1 << 30)
        return fit(yb, **k)

    d = str(tmp_path / "j")
    got = _walk(oom_ahead, kw, y, d)
    _same(got, ref)
    assert got.meta["oom_backoffs"] == 0 and not got.meta["degraded"]
    assert got.meta["chunk_rows_final"] == got.meta["chunk_rows_initial"]
    # ONE fit ahead was started; after its RESOURCE_EXHAUSTED the lane fits
    # nothing ahead for the rest of the walk, and every chunk is fitted at
    # its turn
    assert _ahead(got) == (1, 0) and seen.count(True) == 1
    assert [(c[0], c[1]) for c in _shards(d)] == [
        (lo, lo + 8) for lo in range(0, 40, 8)]
    _no_thread_left()


def test_any_other_error_of_the_fit_ahead_is_raised_at_its_turn(tmp_path):
    fit, kw, y = FAMILIES["arima"]
    _warm(kw, y)

    @functools.wraps(fit)
    def bad_ahead(yb, **k):
        if _on_ahead_thread():
            raise ValueError("the chunk's own fault")
        return fit(yb, **k)

    d = str(tmp_path / "j")
    with pytest.raises(ValueError, match="the chunk's own fault"):
        _walk(bad_ahead, kw, y, d)
    # the chunk before it was committed first, nothing of the failed one
    assert [(c[0], c[1]) for c in _shards(d)] == [(0, 8)]
    _no_thread_left()


def test_rollback_drops_the_fit_ahead(tmp_path):
    """A commit whose fetch meets RESOURCE_EXHAUSTED on the committer thread
    rolls the walk back; what was fitted ahead of it is dropped and the
    re-chunked walk is the serial backoff's."""

    class Poisoned:
        def __init__(self, real):
            self._real, self._armed = real, True

        @property
        def params(self):
            if self._armed:
                self._armed = False
                raise RuntimeError("RESOURCE_EXHAUSTED: simulated, at fetch")
            return self._real.params

        def __getattr__(self, name):
            return getattr(self._real, name)

    def poison_second(first_of_second):
        def fit(yb, **k):
            r = arima.fit(yb, **k)
            if yb.shape[0] == 8 and float(yb[0, -1]) == first_of_second \
                    and not fit.done:
                fit.done = True
                return Poisoned(r)
            return r
        fit.done = False
        return fit

    _, kw, y = FAMILIES["arima"]
    kw = {**kw, "resilient": False}
    # the serial walk fetches inline and cannot be poisoned: what it gives
    # at the boundaries the rollback leaves, [0, 8) whole and halves after it
    ref8 = _walk(arima.fit, kw, y, pipeline=False)
    ref4 = _walk(arima.fit, kw, y, pipeline=False, chunk_rows=4)
    _warm(kw, y)
    d = str(tmp_path / "j")
    got = _walk(poison_second(float(y[8, -1])), kw, y, d, min_chunk_rows=2)
    assert got.meta["oom_backoffs"] == 1
    assert got.meta["oom_events"][0]["at_row"] == 8
    for f in FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, f)),
            np.concatenate([np.asarray(getattr(ref8, f))[:8],
                            np.asarray(getattr(ref4, f))[8:]]), err_msg=f)
    spans = [(c[0], c[1]) for c in _shards(d)]
    assert spans == [(0, 8)] + [(lo, lo + 4) for lo in range(8, 40, 4)]
    # the fit of [16, 24), in flight when the commit of [8, 16) failed, is
    # in no shard and no piece; the halved walk fits ahead again
    started, taken = _ahead(got)
    assert taken < started
    _no_thread_left()


def test_deadline_drops_the_fit_ahead(tmp_path):
    fit, kw, y = FAMILIES["arima"]
    ref = _walk(fit, kw, y, pipeline=False)
    _warm(kw, y)

    @functools.wraps(fit)
    def slow(yb, **k):
        if not _on_ahead_thread():
            time.sleep(0.4)  # chunk 0 alone spends the job's budget
        return fit(yb, **k)

    d = str(tmp_path / "j")
    obs.enable(str(tmp_path / "ev.jsonl"))
    try:
        got = _walk(slow, kw, y, d, job_budget_s=0.3)
    finally:
        obs.disable()
    assert got.meta["status_counts"]["TIMEOUT"] == 32
    for f in FIELDS[:4]:
        np.testing.assert_array_equal(np.asarray(getattr(got, f))[:8],
                                      np.asarray(getattr(ref, f))[:8])
    m = _manifest(d)
    assert [(c["lo"], c["status"]) for c in m["chunks"]] == [
        (0, "committed")] + [(lo, "TIMEOUT") for lo in range(8, 40, 8)]
    assert _ahead(got) == (1, 0)
    (sp,) = _span_lines(str(tmp_path / "ev.jsonl"), "fit.ahead")
    assert sp["attrs"]["taken"] is False
    assert sp["attrs"]["dropped_for"] == "deadline"
    # no telemetry row says the dropped chunk was fitted
    rows = got.meta["telemetry"]["chunks"]
    assert [(r["lo"], r["phase"]) for r in rows] == [(0, "execute")] + [
        (lo, "timeout") for lo in range(8, 40, 8)]
    _no_thread_left()


def _lane(y, fit, chunk_rows=8, **over):
    cfg = dict(
        n_rows=y.shape[0], chunk_rows=chunk_rows, min_chunk_rows=1,
        max_backoffs=8, resilient=False, policy="impute", ladder=None,
        checkpoint_dir=None, resume="auto", chunk_budget_s=None,
        job_budget_s=None, pipeline=True, pipeline_depth=2, prefetch_depth=1,
        align_mode=None, lanes=(plan_mod.LaneSpec(0, 0, y.shape[0]),),
        process_index=0, n_shards=2, elastic=True)
    cfg.update(over)
    plan = plan_mod.ExecutionPlan(**cfg)
    return plan_mod.LaneRunner(plan, plan.lanes[0], fit, {}, jnp.asarray(y))


def test_steal_drops_the_fit_ahead(tmp_path):
    """A thief takes the tail of the lane's span while the next chunk's fit
    is in flight: every prediction past a steal is dropped, the fit ahead
    included (a fit the lane repeats at its turn, never a wrong one)."""
    y = _panel(b=48)
    _lane(y, ewma.fit).run()  # the walk's builds
    box = {}

    def fit(yb, **k):
        if _on_ahead_thread() and float(yb[0, -1]) == float(y[8, -1]):
            # wait until the driver is at [8, 16)'s turn, waiting for this
            # fit with [16, 24)'s started behind it
            pf, until = box["runner"].prefetcher, time.time() + 30
            while time.time() < until:
                with pf._lock:
                    behind = pf._ahead
                if behind is not None and behind.key[0] == 16:
                    break
                time.sleep(0.01)
            box["stolen"] = box["runner"].try_steal()
        return ewma.fit(yb, **k)

    obs.enable(str(tmp_path / "ev.jsonl"))
    try:
        runner = box["runner"] = _lane(y, fit)
        res = runner.run()
    finally:
        obs.disable()
    assert box["stolen"] == (32, 48) and runner.hi == 32
    assert [(lo, hi) for lo, hi, _ in res.pieces] == [
        (lo, lo + 8) for lo in range(0, 32, 8)]
    ref = ewma.fit(jnp.asarray(y))
    got = np.concatenate([np.asarray(p.params) for _, _, p in res.pieces])
    np.testing.assert_array_equal(got, np.asarray(ref.params)[:32])
    # started: [8, 16) taken, [16, 24) dropped, [24, 32) taken
    assert res.pf_stats.fits_ahead == 3 and res.pf_stats.fits_ahead_taken == 2
    dropped = [s["attrs"] for s in _span_lines(str(tmp_path / "ev.jsonl"),
                                               "fit.ahead")
               if not s["attrs"]["taken"]]
    assert [(a["lo"], a["dropped_for"]) for a in dropped] == [(16, "steal")]
    _no_thread_left()


def test_a_boundary_the_prediction_missed_drops_the_fit_ahead():
    """The walk's turn decides another span than the fit ahead assumed (here:
    the chunk size changed under it): dropped as ``boundary``, refitted."""
    y = _panel(b=32)
    for rows in (8, 4):
        _lane(y, ewma.fit, chunk_rows=rows).run()  # the walks' builds
    box = {}

    def fit(yb, **k):
        if _on_ahead_thread() and "done" not in box:
            box["done"] = True
            box["runner"].chunk = 4  # as a backoff elsewhere would
        return ewma.fit(yb, **k)

    runner = box["runner"] = _lane(y, fit, elastic=False)
    res = runner.run()
    spans = [(lo, hi) for lo, hi, _ in res.pieces]
    # the turn that first sees the new size drops the fit started for the
    # old one (which turn that is depends on when the thread got to run)
    assert spans[0] == (0, 8) and spans[-1] == (28, 32)
    assert all(a[1] == b[0] for a, b in zip(spans, spans[1:]))
    widths = [hi - lo for lo, hi in spans]
    assert set(widths) == {8, 4} and widths == sorted(widths, reverse=True)
    got = np.concatenate([np.asarray(p.params) for _, _, p in res.pieces])
    np.testing.assert_array_equal(got, np.asarray(ewma.fit(jnp.asarray(y))
                                                  .params))
    assert res.pf_stats.fits_ahead_taken < res.pf_stats.fits_ahead
    _no_thread_left()


@pytest.mark.parametrize("how", ["resumed", "torn_shard", "crash_in_flight"])
def test_journal_decides_what_is_fitted_ahead(tmp_path, how):
    """A chunk the journal holds is loaded, not fitted ahead; a committed
    chunk whose shard is gone is recomputed at its recorded boundary, at its
    turn; a walk killed with a fit in flight resumes bitwise."""
    fit, kw, y = FAMILIES["arima"]
    ref = _walk(fit, kw, y, pipeline=False)
    _warm(kw, y)
    d = str(tmp_path / "j")
    calls = []

    @functools.wraps(fit)
    def spy(yb, **k):
        calls.append((float(yb[0, -1]), _on_ahead_thread()))
        return fit(yb, **k)

    with pytest.raises(fi.SimulatedCrash):
        _walk(spy, kw, y, d,
              _journal_commit_hook=fi.crash_after_commits(2))
    assert [(c[0], c[1]) for c in _shards(d)] == [(0, 8), (8, 16)]
    if how == "crash_in_flight":
        # chunk [16, 24) or later was in flight, ahead, when the walk died
        assert any(ahead for _, ahead in calls)
    _no_thread_left()
    if how == "torn_shard":
        os.remove(os.path.join(d, _manifest(d)["chunks"][1]["shard"]))
    del calls[:]
    got = _walk(spy, kw, y, d)
    _same(got, ref)
    fitted_ahead = {first for first, ahead in calls if ahead}
    firsts = {lo: float(y[lo, -1]) for lo in range(0, 40, 8)}
    assert firsts[0] not in {f for f, _ in calls}  # loaded, not fitted
    if how == "torn_shard":
        # the recompute of [8, 16) is forced, on the driver, at its turn
        assert (firsts[8], False) in calls and firsts[8] not in fitted_ahead
        assert got.meta["journal"]["chunks_resumed"] == 1
    else:
        assert firsts[8] not in {f for f, _ in calls}
        assert got.meta["journal"]["chunks_resumed"] == 2
    started, taken = _ahead(got)
    assert taken == started == len(fitted_ahead) >= 1
    assert [(c[0], c[1]) for c in _shards(d)] == [
        (lo, lo + 8) for lo in range(0, 40, 8)]


# -- the span, and the build rule ----------------------------------------------


def test_fit_ahead_span_names_its_chunk_and_carries_the_stage_spans(tmp_path):
    fit, kw, y = FAMILIES["arima"]
    _walk(fit, kw, y)
    path = str(tmp_path / "ev.jsonl")
    obs.enable(path)
    try:
        _walk(fit, kw, y, str(tmp_path / "j"))
    finally:
        obs.disable()
    chunks = {s["attrs"]["lo"]: s for s in _span_lines(path, "chunk")}
    aheads = _span_lines(path, "fit.ahead")
    assert [a["attrs"]["lo"] for a in aheads] == [8, 16, 24, 32]
    for a in aheads:
        at = a["attrs"]
        assert at["taken"] is True and "dropped_for" not in at
        assert at["hi"] == at["lo"] + 8
        assert at["thread"] == f"fit-ahead:[{at['lo']}, {at['hi']})"
        # launched by the chunk before it, or by the one before that (while
        # the driver waited for ITS fit ahead)
        launcher = [lo for lo, c in chunks.items() if c["id"] == a["parent"]]
        assert launcher and launcher[0] in (at["lo"] - 8, at["lo"] - 16)
        assert a["walk"] == chunks[at["lo"]]["walk"]
        kids = {s["name"] for s in _span_lines(path)
                if s["parent"] == a["id"]}
        assert {"sanitize", "fit.primary"} <= kids
    # the read-back stays with the chunk's turn, on the driver
    for lo in (8, 16, 24, 32):
        (rb,) = [s for s in _span_lines(path, "fit.readback")
                 if s["parent"] == chunks[lo]["id"]]
        assert rb["attrs"]["rows"] == 8


def test_a_rung_that_builds_lets_the_fit_in_flight_dispatch_first(tmp_path):
    """With a rung whose program is not built yet, whatever the driver's
    thread builds beside a fit in flight is built AFTER that fit's last
    dispatch: a build that began inside a fit ahead's own interval (its
    span's open to ``dispatched_s``) waited at its start (the log's
    interval holds the wait) and ends after it."""
    fit, kw, y = FAMILIES["garch"]
    y = y[:, :84]  # a length no test of this process has built a rung for
    kw = {**kw, "max_iters": 3}
    _walk(fit, kw, y, ladder=())  # the primary's builds alone

    @functools.wraps(fit)
    def slow_ahead(yb, **k):
        if _on_ahead_thread():
            time.sleep(0.3)  # the fit in flight is still dispatching ...
        return fit(yb, **k)  # ... when the rung of the chunk before is due

    path = str(tmp_path / "ev.jsonl")
    began = time.time()  # (the log is bounded: by time, not by position)
    obs.enable(path)
    try:
        res = _walk(slow_ahead, kw, y)
    finally:
        obs.disable()
    assert res.meta["ladder_totals"]["retry"]["attempted"] > 0
    mine = [b for b in cc.builds()
            if b["t0"] >= began and b["thread"] == "MainThread"]
    assert any(b["program"] == "garch._fit_program" for b in mine), \
        "the rung's program was built in this walk"
    aheads = [(s["t0"], s["t0"] + s["attrs"]["dispatched_s"])
              for s in _span_lines(path, "fit.ahead")]
    assert len(aheads) == 4
    waited = 0
    for b in mine:
        b0, b1 = b["t0"], b["t0"] + b["wall_s"]
        for a0, a1 in aheads:
            if a0 < b1 and b0 < a1:  # began beside the fit in flight ...
                assert b1 >= a1 - 1e-3, b  # ... and did its work after it
                waited += 1
    assert waited >= 1
