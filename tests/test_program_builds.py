"""The build log and its ``program.build`` spans (ISSUE 54, tier-1 CPU).

``utils/compile_cache.py`` assembles one record per executable the process
builds or loads from jax's own ``jax.monitoring`` events; with the plane on
each is a ``program.build`` span, and ``obs.enable`` first writes what the
log already holds.  Held here: a record's shape and arithmetic (on real
builds, and on hand-fed events where a real build cannot be steered: a hit,
no cache directory, a stale trace), that nothing is recorded and no listener
called where nothing is built, that the listeners change no result, the
``chunk`` span's ``builds`` / ``build_s``, and the benchmark's six
``setup_*`` readers on fakes of ``run``.

Every function and shape is this file's own, so that an earlier test's
build in the same process cannot stand in for one of these.
"""

import json
import os
import subprocess
import sys
import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _obs_helpers import _assert_bitwise, _span_lines
from benchmark import manifest as mf
from benchmark import setup_builds
from spark_timeseries_tpu import obs
from spark_timeseries_tpu import reliability as rel
from spark_timeseries_tpu.models import arima, base
from spark_timeseries_tpu.utils import compile_cache as cc

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = ["setup_programs_built", "setup_cache_misses",
           "setup_trace_lower_s", "setup_cache_read_s",
           "setup_cold_compile_s", "setup_build_share"]

pytestmark = pytest.mark.usefixtures("plane_off")


def _new(before):
    """Records appended since ``before = cc.builds()`` (the log is bounded:
    once it is full, found from the tail by the last record held then)."""
    now = cc.builds()
    if len(now) < 512:
        return now[len(before):]
    last = before[-1] if before else None
    for i in range(len(now) - 1, -1, -1):
        if now[i] == last:
            return now[i + 1:]
    return now


def _named(name, fn):
    fn.__name__ = name
    return fn


def _check_record(r):
    assert r["cache"] in ("hit", "miss", "off")
    for f in ("trace_s", "lower_s", "backend_s", "compiled_s", "wall_s"):
        assert r[f] >= 0, (f, r)
    assert r["trace_s"] + r["lower_s"] + r["backend_s"] <= r["wall_s"] + 1e-5
    assert (r["retrieval_s"] is not None) == (r["cache"] == "hit")
    assert isinstance(r["program"], str) and r["program"]
    assert isinstance(r["thread"], str)


# ---------------------------------------------------------------------------
# real builds
# ---------------------------------------------------------------------------


class TestRecords:
    def test_first_call_one_record_second_none(self):
        f = jax.jit(_named("pb_first_call", lambda x: jnp.tanh(x) * 3 + 1))
        x = jnp.ones((5, 7), jnp.float32)
        jax.block_until_ready(x)
        before = cc.builds()
        f(x)
        new = [r for r in _new(before) if r["program"] == "pb_first_call"]
        assert len(new) == 1
        _check_record(new[0])
        assert new[0]["thread"] == threading.current_thread().name
        assert new[0]["trace_s"] > 0 and new[0]["lower_s"] > 0
        before = cc.builds()
        f(x)
        assert _new(before) == []

    def test_inner_jit_trace_counted_once(self):
        spans = []

        def on_span(event, start, end, fun_name="", **_):
            if event == cc._TRACE and fun_name.startswith("pb_nest_"):
                spans.append((fun_name, start, end))

        inner = jax.jit(_named("pb_nest_inner", lambda x: jnp.cos(x) + 2))
        outer = jax.jit(_named("pb_nest_outer", lambda x: inner(x) * inner(x + 1)))
        x = jnp.ones((3, 11), jnp.float32)
        jax.block_until_ready(x)
        jax.monitoring.register_event_time_span_listener(on_span)
        try:
            before = cc.builds()
            outer(x)
        finally:
            jax.monitoring.unregister_event_time_span_listener(on_span)
        new = _new(before)
        # one executable: the inner jit is traced inside the outer's trace
        # and compiled as part of it
        assert [r["program"] for r in new] == ["pb_nest_outer"]
        by = {n: e - s for n, s, e in spans}
        assert set(by) == {"pb_nest_inner", "pb_nest_outer"}
        assert new[0]["trace_s"] == pytest.approx(by["pb_nest_outer"], abs=2e-6)
        assert new[0]["trace_s"] < by["pb_nest_outer"] + by["pb_nest_inner"]
        _check_record(new[0])

    def test_jit_program_names_the_builder(self):
        @base.jit_program
        def _pb_alpha_program(k):
            def run(x):
                return x * k + 1

            return run

        @base.jit_program
        def _pb_beta_program(k):
            def run(x):
                return x - k

            return run

        x = jnp.ones((2, 13), jnp.float32)
        jax.block_until_ready(x)
        before = cc.builds()
        _pb_alpha_program(3)(x)
        _pb_beta_program(3)(x)
        names = [r["program"] for r in _new(before)]
        assert names == [
            "test_program_builds.TestRecords.test_jit_program_names_the_"
            "builder.<locals>._pb_alpha_program",
            "test_program_builds.TestRecords.test_jit_program_names_the_"
            "builder.<locals>._pb_beta_program"]
        assert not {"run", "<lambda>"} & set(names)

    def test_model_programs_carry_their_builders_names(self):
        rng = np.random.default_rng(54)
        y = rng.normal(size=(4, 53)).astype(np.float32).cumsum(1)
        before = cc.builds()
        arima.fit(y, order=(1, 0, 0), max_iters=7)
        names = {r["program"] for r in _new(before)}
        assert "arima._fit_program" in names
        assert not {"run", "<lambda>"} & names

    def test_worker_thread_build_names_its_thread_and_parent(self, tmp_path):
        path = str(tmp_path / "ev.jsonl")
        f = jax.jit(_named("pb_worker", lambda x: jnp.exp(-x) + 5))
        x = jnp.ones((3, 17), jnp.float32)
        jax.block_until_ready(x)
        obs.enable(path)
        ids = {}

        def work():
            with obs.span("pb.worker.outer"):
                with obs.span("pb.worker.inner") as sp:
                    ids["inner"] = sp.id
                    f(x)

        with obs.span("pb.main") as main_sp:
            t = threading.Thread(target=work, name="pb-worker-thread")
            t.start()
            t.join()
        obs.disable()
        rec = [r for r in cc.builds() if r["program"] == "pb_worker"]
        assert len(rec) == 1 and rec[0]["thread"] == "pb-worker-thread"
        lines = [s for s in _span_lines(path, builds=True)
                 if s["name"] == "program.build"
                 and s["attrs"]["program"] == "pb_worker"]
        assert len(lines) == 1
        # the span open on the BUILDING thread, not the one open on main
        assert lines[0]["parent"] == ids["inner"] != main_sp.id
        assert lines[0]["depth"] == 2 and "process_s" not in lines[0]
        assert lines[0]["t0"] == rec[0]["t0"]
        assert lines[0]["wall_s"] == rec[0]["wall_s"]
        assert lines[0]["attrs"]["thread"] == "pb-worker-thread"

    def test_enable_writes_the_backlog_with_true_t0(self, tmp_path):
        x = jnp.ones((2, 19), jnp.float32)
        jax.block_until_ready(x)
        jax.jit(_named("pb_backlog_a", lambda x: x * 7 - 1))(x)
        jax.jit(_named("pb_backlog_b", lambda x: x / 7 + 1))(x)
        held = {r["program"]: r for r in cc.builds()
                if r["program"].startswith("pb_backlog_")}
        assert set(held) == {"pb_backlog_a", "pb_backlog_b"}
        path = str(tmp_path / "ev.jsonl")
        obs.enable(path)
        with obs.span("pb.after"):
            pass
        obs.disable()
        with open(path, encoding="utf-8") as fh:
            events = [json.loads(line) for line in fh]
        meta_ts = events[0]["ts"]
        lines = {s["attrs"]["program"]: s for s in events
                 if s.get("name") == "program.build"
                 and s["attrs"]["program"] in held}
        assert set(lines) == set(held)
        for name, s in lines.items():
            assert s["t0"] == held[name]["t0"] < meta_ts
            assert s["wall_s"] == held[name]["wall_s"]
            assert s["parent"] is None and "walk" not in s
            assert s["attrs"]["cache"] == held[name]["cache"]
        # the backlog comes first, in the log's order, ids the run's own
        builds = [s for s in events if s.get("name") == "program.build"]
        assert [s["id"] for s in builds] == list(range(1, len(builds) + 1))
        # (the log's order is the order the builds CLOSED in: two threads
        # that build at once, a lane's fit ahead beside its driver, close
        # theirs out of their t0's order)
        assert [s["t0"] for s in builds] == [r["t0"] for r in cc.builds()]
        out = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "tools", "obs_report.py"),
             path, "--check"], capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stdout + out.stderr
        out = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "tools", "obs_report.py"),
             path], capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert "program.build" in out.stdout

    def test_every_record_of_the_process_adds_up(self):
        jax.jit(_named("pb_adds_up", lambda x: x + 11))(jnp.ones(23))
        log = cc.builds()
        assert log and len(log) <= 512
        for r in log:
            _check_record(r)


# ---------------------------------------------------------------------------
# nothing built: nothing recorded, no listener called, nothing changed
# ---------------------------------------------------------------------------


def _panel(b=12, t=59, seed=54):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(b, t)).astype(np.float32).cumsum(1)


def _walk(y, d=None, **kw):
    return rel.fit_chunked(arima.fit, y, chunk_rows=4, checkpoint_dir=d,
                           order=(0, 0, 1), max_iters=9, **kw)


class TestQuietWhenNothingIsBuilt:
    def test_repeated_walk_adds_no_record_and_calls_no_listener(
            self, tmp_path):
        y = _panel()
        first = _walk(y, str(tmp_path / "a"))
        calls = []

        def count(*a, **k):
            calls.append(a[0])

        registered = [
            (jax.monitoring.register_event_listener,
             jax.monitoring.unregister_event_listener),
            (jax.monitoring.register_event_duration_secs_listener,
             jax.monitoring.unregister_event_duration_listener),
            (jax.monitoring.register_event_time_span_listener,
             jax.monitoring.unregister_event_time_span_listener),
            (jax.monitoring.register_scalar_listener,
             jax.monitoring.unregister_scalar_listener)]
        before = cc.builds()
        mine = cc.thread_builds()
        for reg, _ in registered:
            reg(count)
        try:
            again = _walk(y, str(tmp_path / "b"))
        finally:
            for _, unreg in registered:
                unreg(count)
        # jax reports nothing where nothing is built, so none of the log's
        # own listeners ran either: they hang on the same four lists
        assert calls == []
        assert _new(before) == [] and cc.thread_builds() == mine
        _assert_bitwise(again, first)

    def test_fit_is_bitwise_with_listeners_on_and_off(self, monkeypatch):
        y = _panel(t=61, seed=55)
        assert cc._listening  # conftest's configure() registered them
        on = _walk(y)
        jax.clear_caches()  # so that the second fit builds again, unlogged
        listen = cc.listen
        cc._unlisten()
        # a jit_program lookup would register them again
        monkeypatch.setattr(cc, "listen", lambda: None)
        try:
            before = cc.builds()
            off = _walk(y)
            assert _new(before) == []  # no listener, no record
        finally:
            monkeypatch.setattr(cc, "listen", listen)
            cc.listen()
        _assert_bitwise(off, on)
        jax.clear_caches()
        before = cc.builds()
        back = _walk(y)
        assert "arima._fit_program" in {r["program"] for r in _new(before)}
        _assert_bitwise(back, on)

    def test_chunk_span_says_what_was_built_under_it(self, tmp_path):
        y = _panel(t=67, seed=56)  # a shape this process has not built
        path = str(tmp_path / "ev.jsonl")
        obs.enable(path)
        res = _walk(y, str(tmp_path / "j"))
        again = _walk(y, str(tmp_path / "k"))
        obs.disable()
        phases = [c["phase"] for c in res.meta["telemetry"]["chunks"]]
        assert phases == ["compile+execute", "execute", "execute"]
        assert {c["phase"] for c in again.meta["telemetry"]["chunks"]} \
            == {"execute"}
        spans = _span_lines(path, builds=True)
        chunks = [s for s in spans if s["name"] == "chunk"]
        assert len(chunks) == 6
        first, rest = chunks[0], chunks[1:]
        assert first["attrs"]["phase"] == "compile+execute"
        assert first["attrs"]["builds"] >= 1
        assert 0 < first["attrs"]["build_s"] <= first["wall_s"] + 1e-3
        assert all(s["attrs"]["phase"] == "execute"
                   and s["attrs"]["builds"] == 0
                   and s["attrs"]["build_s"] == 0 for s in rest)
        # the builds under the first chunk name a span inside it as parent
        # and carry the walk's number
        by_id = {s["id"]: s for s in spans}

        def under(s, root):
            while s is not None:
                if s["id"] == root["id"]:
                    return True
                s = by_id.get(s["parent"])
            return False

        inside = [s for s in spans if s["name"] == "program.build"
                  and s["parent"] is not None and under(s, first)]
        assert len(inside) == first["attrs"]["builds"]
        assert {s["walk"] for s in inside} == {first["walk"]}
        assert sum(s["wall_s"] for s in inside) == pytest.approx(
            first["attrs"]["build_s"], abs=1e-4)
        assert "arima._fit_program" in {s["attrs"]["program"] for s in inside}


# ---------------------------------------------------------------------------
# the assembly, on hand-fed events (a thread of its own: a fresh state)
# ---------------------------------------------------------------------------


def _feed(events):
    """Run jax's event sequence through the log's listeners on a fresh
    thread; returns the records it appended.  ``events``: tuples
    ``("enter", event, t)``, ``("exit", event, t0, t1, fun_name)``,
    ``("event", name)``, ``("duration", name, secs)``."""
    out = []

    def run():
        before = cc.builds()
        for e in events:
            if e[0] == "enter":
                cc._on_scalar(e[1], e[2], fun_name="x")
            elif e[0] == "exit":
                cc._on_time_span(e[1], e[2], e[3], fun_name=e[4])
            elif e[0] == "event":
                cc._on_event(e[1])
            else:
                cc._on_duration(e[1], e[2])
        out.extend(_new(before))

    t = threading.Thread(target=run, name="pb-fed")
    t.start()
    t.join()
    return out


def _build(name, t, trace=(0.0, 1.0), lower=(1.5, 2.0), backend=(2.5, 4.0),
           inner=(), cache=()):
    ev = []
    if trace:
        ev.append(("enter", cc._TRACE, t + trace[0]))
        for a, b in inner:
            ev.append(("enter", cc._TRACE, t + a))
            ev.append(("exit", cc._TRACE, t + a, t + b, "inner"))
        ev.append(("exit", cc._TRACE, t + trace[0], t + trace[1], name))
    if lower:
        ev.append(("enter", cc._LOWER, t + lower[0]))
        ev.append(("exit", cc._LOWER, t + lower[0], t + lower[1],
                   f"jit({name})"))
    ev.append(("enter", cc._BACKEND, t + backend[0]))
    ev.extend(cache)
    ev.append(("exit", cc._BACKEND, t + backend[0], t + backend[1],
               f"jit({name})"))
    return ev


HIT = (("event", cc._CACHE_ASKED), ("event", cc._CACHE_HIT),
       ("duration", cc._SAVED, 9.25), ("duration", cc._RETRIEVAL, 0.75))


class TestAssembly:
    def test_miss_with_inner_traces(self):
        (r,) = _feed(_build("pb.fed_miss", 1000.0,
                            inner=[(0.1, 0.3), (0.4, 0.9)],
                            cache=[("event", cc._CACHE_ASKED)]))
        assert r["program"] == "pb.fed_miss" and r["thread"] == "pb-fed"
        assert r["t0"] == 1000.0 and r["wall_s"] == 4.0
        assert (r["trace_s"], r["lower_s"], r["backend_s"]) == (1.0, 0.5, 1.5)
        assert r["cache"] == "miss" and r["retrieval_s"] is None
        assert r["compiled_s"] == 1.5
        _check_record(r)

    def test_hit_reports_what_compiling_cost(self):
        (r,) = _feed(_build("pb.fed_hit", 2000.0, cache=HIT))
        assert r["cache"] == "hit" and r["retrieval_s"] == 0.75
        assert r["compiled_s"] == 10.0 and r["backend_s"] == 1.5
        _check_record(r)

    def test_no_cache_directory_reads_off(self):
        # jax asks its cache of every compile; with no directory nothing
        # can answer, and that is "off", not a miss
        was = jax.config.jax_compilation_cache_dir
        jax.config.update("jax_compilation_cache_dir", None)
        try:
            (r,) = _feed(_build("pb.fed_off", 3000.0,
                                cache=[("event", cc._CACHE_ASKED)]))
        finally:
            jax.config.update("jax_compilation_cache_dir", was)
        assert r["cache"] == "off" and r["compiled_s"] == r["backend_s"]

    def test_stale_trace_is_another_programs(self):
        stale = [("enter", cc._TRACE, 3990.0),
                 ("exit", cc._TRACE, 3990.0, 3995.0, "pb.fed_shape_only")]
        (r,) = _feed(stale + _build("pb.fed_fresh", 4000.0))
        assert r["t0"] == 4000.0 and r["trace_s"] == 1.0
        # and a build with no trace of its own (lowered again for another
        # placement) starts at its lowering
        (r,) = _feed(stale + _build("pb.fed_relower", 5000.0, trace=None))
        assert r["t0"] == 5001.5 and r["trace_s"] == 0.0
        assert r["wall_s"] == 2.5
        # an eagerly compiled executable (AOT) has neither
        (r,) = _feed(_build("pb.fed_aot", 6000.0, trace=None, lower=None))
        assert r["t0"] == 6002.5 and r["wall_s"] == r["backend_s"] == 1.5

    def test_two_builds_share_nothing(self):
        a, b = _feed(_build("pb.fed_one", 7000.0, cache=HIT)
                     + _build("pb.fed_two", 7010.0,
                              cache=[("event", cc._CACHE_ASKED)]))
        assert (a["cache"], b["cache"]) == ("hit", "miss")
        assert b["t0"] == 7010.0 and b["retrieval_s"] is None
        assert b["compiled_s"] == b["backend_s"]

    def test_thread_builds_counts_the_calling_thread(self):
        got = {}

        def run():
            n0, s0 = cc.thread_builds()
            for e in _build("pb.fed_mine", 8000.0):
                if e[0] == "enter":
                    cc._on_scalar(e[1], e[2])
                elif e[0] == "exit":
                    cc._on_time_span(e[1], e[2], e[3], fun_name=e[4])
            n1, s1 = cc.thread_builds()
            got.update(n=n1 - n0, s=s1 - s0)

        mine = cc.thread_builds()
        t = threading.Thread(target=run)
        t.start()
        t.join()
        assert got == {"n": 1, "s": 4.0}
        assert cc.thread_builds() == mine  # another thread's, not this one's


    def test_threads_lose_no_record(self):
        """More building threads than cores, a short switch interval: every
        thread's builds are in the log, whole and in its own order."""
        n_threads, each = 24, 8
        start = threading.Barrier(n_threads)

        def run(k):
            start.wait(timeout=30)
            for j in range(each):
                for e in _build(f"pb.stress_{k}", 9000.0 + 100 * k + 10 * j,
                                cache=HIT if j % 2 else ()):
                    if e[0] == "enter":
                        cc._on_scalar(e[1], e[2])
                    elif e[0] == "exit":
                        cc._on_time_span(e[1], e[2], e[3], fun_name=e[4])
                    elif e[0] == "event":
                        cc._on_event(e[1])
                    else:
                        cc._on_duration(e[1], e[2])

        was = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            threads = [threading.Thread(target=run, args=(k,))
                       for k in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(was)
        assert not any(t.is_alive() for t in threads)
        log = cc.builds()
        for k in range(n_threads):
            mine = [r for r in log if r["program"] == f"pb.stress_{k}"]
            assert [r["t0"] for r in mine] == [
                9000.0 + 100 * k + 10 * j for j in range(each)]
            assert [r["cache"] for r in mine] == ["off", "hit"] * (each // 2)
            assert all(r["wall_s"] == 4.0 for r in mine)


# ---------------------------------------------------------------------------
# the benchmark's six readers, on fakes of ``run``
# ---------------------------------------------------------------------------


def _line(t0, wall_s, thread="MainThread", cache="hit", trace_s=0.25,
          lower_s=0.5, retrieval_s=1.0, compiled_s=8.0, program="p"):
    hit = cache == "hit"
    return {"kind": "span", "name": "program.build", "t0": t0,
            "wall_s": wall_s, "depth": 0, "id": 1, "parent": None,
            "attrs": {"program": program, "thread": thread,
                      "trace_s": trace_s, "lower_s": lower_s,
                      "backend_s": wall_s - trace_s - lower_s,
                      "cache": cache,
                      "retrieval_s": retrieval_s if hit else None,
                      "compiled_s": compiled_s}}


def _run(spans, mark=100.0, setup_s=20.0):
    return types.SimpleNamespace(spans=spans, device_mark_t=mark,
                                 setup_s=setup_s, state={}, result=None)


def _reader(name):
    return mf.load_plugin(mf.load_manifest(), _ROOT, "layer_metrics", name)


FAKE = [
    _line(90.0, 5.0),                                # before the mark: out
    _line(101.0, 4.0),                               # 101-105
    _line(103.0, 4.0, thread="committer"),           # 103-107: overlaps
    _line(110.0, 2.0, cache="miss", compiled_s=1.25),  # 110-112, compiled
    _line(119.0, 3.0),                               # 119-122: clipped at 120
    _line(120.5, 1.0),                               # the window: out
    {"kind": "span", "name": "chunk", "t0": 105.0, "wall_s": 9.0,
     "attrs": {}},
]
EXPECT = {
    "setup_programs_built": 4,
    "setup_cache_misses": 1,
    "setup_trace_lower_s": 4 * 0.75,
    "setup_cache_read_s": 3 * 1.0,
    "setup_cold_compile_s": 3 * 8.0 + 1.25,
    # 101-107 once, 110-112, 119-120
    "setup_build_share": (6.0 + 2.0 + 1.0) / 20.0,
}


@pytest.mark.parametrize("name", READERS)
class TestSetupReaders:
    def test_reads_setups_builds(self, name):
        assert _reader(name).read(_run(FAKE)) == pytest.approx(EXPECT[name])

    def test_parents_stream_reads_nothing(self, name):
        parent = [s for s in FAKE if s["name"] != "program.build"]
        assert _reader(name).read(_run(parent)) is None
        assert _reader(name).read(_run([])) is None

    def test_manifest_entry(self, name):
        manifest = mf.load_manifest()
        (m,) = [e for e in manifest["per_layer"] if e["name"] == name]
        cells = [w["name"] for w in manifest["workloads"]]
        assert len(cells) == 8 and m["workloads"] == cells
        assert (m["layer"], m["moves"], m["better"]) == (
            "walk_driver", "setup_s", "lower")
        counts = name in ("setup_programs_built", "setup_cache_misses")
        assert m["source"] == ("program_counter" if counts
                               else "program_span")
        assert m["unit"] == ("programs" if counts else
                             "share" if name.endswith("_share") else "s")
        # the six readers were appended together, in this order
        names = [e["name"] for e in manifest["per_layer"]]
        at = names.index(READERS[0])
        assert names[at:at + 6] == READERS


def test_union_counts_overlaps_once():
    assert setup_builds.union_s([]) == 0.0
    assert setup_builds.union_s([(0, 2), (1, 3), (5, 6), (5.5, 5.75)]) == 4.0
    assert setup_builds.union_s([(3, 4), (0, 10)]) == 10.0


def test_build_share_of_one_long_build_is_at_most_one():
    run = _run([_line(99.0, 30.0)], mark=100.0, setup_s=20.0)
    assert _reader("setup_build_share").read(run) is not None
    # started before the mark: not one of set-up's
    assert _reader("setup_programs_built").read(run) == 0
    run = _run([_line(100.0, 30.0)], mark=100.0, setup_s=20.0)
    assert _reader("setup_build_share").read(run) == 1.0
