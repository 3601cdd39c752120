"""The split of device-idle time by the driver's innermost span
(``benchmark/span_idle.py``): its arithmetic on a hand-made trace, its sums
on the chunk recorded on the chip (``data/``), and the seven readers over
it — on fakes of what a run hands them, and in a CPU rehearsal of each
cell, where every reader gives a number or nothing and never raises."""

import gzip
import json
import os
import types

import pytest

from benchmark import manifest as mf
from benchmark import span_idle
from benchmark import trace_reduce as tr
from benchmark.tests.test_rehearse import check_line, rehearse

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "arima111_walk_dense_chunk.json.gz")
NEW = ["idle_unnamed_share", "handoff_exposed_s_per_chunk",
       "sanitize_exposed_s_per_chunk", "readback_exposed_s_per_chunk",
       "dispatch_exposed_s_per_chunk", "optimizer_iters_per_chunk",
       "lockstep_useful_share"]


def hand_made():
    """One chip, a 1000 ns window holding one chunk of a walk as the
    program traces it now; the device is busy in [200, 500) and [650, 700).
    """
    ops = [["fusion.1", 200, 300, 0], ["copy.2", 650, 50, 0]]
    driver = [[tr.WINDOW_SPAN, 0, 1000], ["bench.walk", 20, 960],
              ["walk", 30, 940], ["walk.open", 30, 70],
              ["chunk.plan", 100, 20],
              ["chunk", 120, 480], ["sanitize", 130, 50],
              ["fit.primary", 180, 340], ["fit.stage1", 190, 320],
              ["fit.readback", 520, 70],
              ["chunk.submit", 600, 40], ["chunk.plan", 640, 110],
              ["walk.close", 750, 200]]
    host = [{"thread": "main", "spans": driver},
            {"thread": "committer", "spans": [["commit.overlap", 620, 300]]}]
    return {"devices": [{"plane": "/device:TPU:0", "ordinal": 0,
                         "ops": ops}], "host": host}


def test_split_by_overlap_on_a_hand_made_trace():
    t = tr.Trace(hand_made())
    parts = span_idle.split(t)
    want = {"no span": 20 + 20, "bench.walk": 10 + 10, "walk": 20,
            "walk.open": 70, "chunk.plan": 20 + 10 + 50, "chunk": 10 + 10,
            "sanitize": 50, "fit.primary": 10 + 10,
            "fit.stage1": 10 + 10, "fit.readback": 70, "chunk.submit": 40,
            "walk.close": 200}
    assert parts == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert sum(parts.values()) == pytest.approx(t.window_s - t.busy_s())
    assert sum(parts.values()) == pytest.approx(
        t.idle_share_worst() * t.window_s)
    # one chunk in the window: per chunk is the sum itself
    assert span_idle.per_chunk(t, ("chunk.plan", "chunk.submit")) \
        == pytest.approx(120e-9)
    assert span_idle.per_chunk(t, ("no.such.span",)) is None
    # the commit's thread is not the driver's: its span names nothing
    assert "commit.overlap" not in parts


def test_sharded_or_untraced_walks_split_into_nothing():
    assert span_idle.split(None) is None
    two_chips = hand_made()
    two_chips["devices"].append({"plane": "/device:TPU:1", "ordinal": 1,
                                 "ops": [["copy.1", 0, 100, 0]]})
    assert span_idle.split(tr.Trace(two_chips)) is None
    two_lanes = hand_made()
    two_lanes["host"].append({"thread": "lane-1",
                              "spans": [["chunk", 100, 500]]})
    assert span_idle.split(tr.Trace(two_lanes)) is None
    no_walk = hand_made()
    no_walk["host"] = no_walk["host"][1:]
    assert span_idle.split(tr.Trace(no_walk)) is None
    cpu = hand_made()
    cpu["devices"] = []  # a rehearsal's trace has no device plane
    assert span_idle.per_chunk(tr.Trace(cpu), ("sanitize",)) is None


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt", encoding="utf-8") as f:
        rec = json.load(f)
    return tr.Trace(rec["trace"]), rec["expect"]


def test_recorded_chunk_sums_to_the_traces_idle_time(recorded):
    """One chunk of ``arima111.walk-dense`` as the parent commit traced it
    (my chip run 4, PR 23): the spans it had were ``chunk``, ``sanitize``
    and ``fit.primary``, and those hold its idle time, as ``top_gaps``
    (named by midpoint) says; the 2 ms before and the 1.4 ms after the
    chunk are under no span."""
    t, want = recorded
    parts = span_idle.split(t)
    idle_s = want["window_s"] - want["busy_s"]
    assert sum(parts.values()) == pytest.approx(idle_s)
    assert sum(parts.values()) == pytest.approx(
        want["idle_share_worst"] * want["window_s"])
    assert set(parts) == {"chunk", "sanitize", "fit.primary", "no span"}
    assert {n.split("+")[-1] for n in want["top_gaps"]} \
        == set(parts) - {"no span"}
    assert parts["chunk"] > parts["fit.primary"] > parts["sanitize"] > 0
    assert parts["no span"] == pytest.approx(0.003388147)
    # by overlap the sanitize span holds 3.2 ms, not the 5.4 ms gap whose
    # midpoint falls in it (the gap starts 2 ms before the span does)
    assert parts["sanitize"] == pytest.approx(0.00321084)


def fake_run(trace, spans=(), traced=(1, 2)):
    return types.SimpleNamespace(
        trace=trace, spans=list(spans),
        result={"traced_walks": list(traced),
                "walks": [{"n_chunks": 1}] * 3})


def readers():
    m = mf.load_manifest()
    return {n: mf.load_plugin(m, mf.ROOT, "layer_metrics", n) for n in NEW}


def test_readers_on_the_parents_trace_and_on_none(recorded):
    """A program without the new spans (the parent commit, whose traced
    runs the driver makes with these readers too): the exposed times of
    spans it has are numbers, those of spans it lacks are left out."""
    t, _ = recorded
    got = {n: r.read(fake_run(t)) for n, r in readers().items()}
    assert got["idle_unnamed_share"] == pytest.approx(
        0.003388147 / 0.019317644)
    assert got["sanitize_exposed_s_per_chunk"] == pytest.approx(0.00321084)
    assert got["dispatch_exposed_s_per_chunk"] == pytest.approx(0.00499176)
    for n in ("handoff_exposed_s_per_chunk", "readback_exposed_s_per_chunk",
              "optimizer_iters_per_chunk", "lockstep_useful_share"):
        assert got[n] is None
    for n, r in readers().items():
        assert r.read(fake_run(None)) is None, n


def test_attribute_readers_take_the_traced_walks_only():
    def line(name, walk, **attrs):
        return {"kind": "span", "name": name, "walk": walk, "attrs": attrs}

    spans = [line("walk", w) for w in (1, 2, 3, 4)]
    for w, iters in zip((1, 2, 3, 4), (50, 6, 8, 50)):
        spans += [line("fit.stage1", w, rows=8, iters=iters, undone=1),
                  line("fit.readback", w, rows=8, iters_max=iters,
                       iters_sum=4 * iters, failed=0)]
    spans.append(line("fit.stage1", 2, rows=8))  # obs went off mid-span
    run = fake_run(None, spans, traced=(1, 2))  # the second and third walk
    got = {n: r.read(run) for n, r in readers().items()}
    assert got["optimizer_iters_per_chunk"] == pytest.approx(7.0)
    assert got["lockstep_useful_share"] == pytest.approx(0.5)
    assert span_idle.window_spans(fake_run(None, spans, traced=(7,)),
                                  "fit.stage1") == []


# minutes each on the CPU, whose profiler is slow
@pytest.mark.parametrize("cell", ["arima111.walk-dense",
                                  "hw-add24.walk-dense"])
def test_new_readers_in_a_traced_rehearsal(cell, tmp_path):
    line = rehearse(cell, 1, tmp_path)
    resolved = mf.resolve_cell(mf.load_manifest(), cell)
    assert set(NEW) <= {m["name"] for m in resolved.per_layer}
    check_line(line, resolved, 1)  # declared names, units, no CPU time
    assert line["correct"]
