"""The split of device-busy time by program span and loop nesting
(``benchmark/device_phases.py``, ISSUE 38), in tier-1 so the driver runs it:
its arithmetic on hand-made traces (several starts, no stage 2, a bracket
without anchors, a ladder rung, a device clock ahead of the host's, several
lanes), and on the chunk recorded on
the chip (``benchmark/tests/data/``) its sums, the trace's line-search event
counts against the program's own ``trials``, and the twelve readers against
the values written down when it was recorded."""

import gzip
import json
import os
import types

import pytest

from benchmark import device_phases as dp
from benchmark import manifest as mf
from benchmark import trace_reduce as tr
from tools.record_trace import as_run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(ROOT, "benchmark", "tests", "data",
                        "arima111_walk_dense_stage2_chunk.json.gz")
SCOPE = "pallas.k"
NEW = ["stage1_trials_per_iter", "stage2_trials_per_iter",
       "stage2_iters_per_chunk", "objective_passes_per_chunk",
       "sanitize_device_share", "ladder_device_share",
       "stage1_prep_device_share", "stage1_linesearch_device_share",
       "stage1_gradient_device_share", "stage1_update_device_share",
       "stage2_device_share", "phase_unattributed_share"]
SHARES = [n for n in NEW if n.endswith("_share")]


@pytest.fixture(autouse=True)
def nanosecond_bursts(request, monkeypatch):
    # the hand-made traces are in ns: 15 of them idle divide two programs
    if "recorded" not in request.fixturenames:
        monkeypatch.setattr(dp, "BURST_GAP_NS", 15)


def hand_made(stage2=True, starts=2, anchor="jvp_pallas.k_", rung=False,
              early=0):
    """One chip, a 2000 ns window holding one chunk of a walk: the
    sanitizer's probe, a stage 1 of ``starts`` lockstep loops, a stage 2
    that runs on under the read-back (its events ``early`` ns ahead of the
    host's spans), and a ladder rung whose program outlives its span."""
    ops = [["fusion.0", 30, 8, 0],            # under chunk.plan
           ["fusion.1", 60, 40, 0],           # the sanitizer's probe
           ["fusion.2", 170, 20, 0],          # fit.primary before stage 1
           ["copy.3", 210, 40, 0],            # stage 1 ahead of its loops
           ["while.10", 300, 300, 0],         # lockstep loop of start 1
           ["fusion.11", 310, 10, 0],
           ["while.12", 330, 100, 0],         # its line search: 2 trials
           ["pallas.k.5", 340, 30, 0], ["fusion.13", 370, 10, 0],
           ["pallas.k.5", 380, 30, 0],
           [anchor + ".6", 440, 40, 0],
           ["transpose_" + anchor + "_.7", 480, 60, 0],
           ["while.14", 545, 20, 0], ["fusion.15", 550, 10, 0],
           ["fusion.30", 900, 50, 0],         # the gather, finalize
           ["fusion.31", 1020, 10, 0],        # between the two stages
           ["fusion.60", 1810, 10, 0]]        # under chunk.submit
    if starts == 2:
        ops += [["while.20", 650, 150, 0], ["while.22", 660, 40, 0],
                ["pallas.k.5", 665, 30, 0], [anchor + ".6", 700, 50, 0]]
    driver = [["walk", 10, 1980], ["chunk.plan", 20, 20],
              ["chunk", 40, 1760], ["sanitize", 50, 100],
              ["fit.primary", 160, 1040], ["fit.stage1", 200, 800],
              ["fit.readback", 1210, 290], ["chunk.submit", 1800, 50]]
    program2 = [["fusion.43", 1410, 30, 0]]   # under fit.readback
    if stage2:
        driver += [["fit.stage2", 1100, 50]]
        program2 += [["copy.40", 1110, 10, 0],
                     ["while.41", 1130, 270, 0],  # on under fit.readback
                     ["while.42", 1140, 60, 0], ["pallas.k.9", 1150, 40, 0],
                     [anchor + ".10", 1220, 80, 0]]
    ops += [[n, s - early, d, b] for n, s, d, b in program2]
    if rung:
        driver += [["fit.rung.scan", 1520, 40]]
        ops += [["while.50", 1530, 170, 0], ["fusion.51", 1600, 50, 0],
                ["fusion.52", 1710, 10, 0]]   # after the rung's span
    host = [{"thread": "main", "spans": driver},
            {"thread": "recorded", "spans": [[tr.WINDOW_SPAN, 0, 2000]]},
            {"thread": "committer", "spans": [["commit.overlap", 1820, 100]]}]
    return {"devices": [{"plane": "/device:TPU:0", "ordinal": 0,
                         "ops": ops}], "host": host}


def fake_run(data, spans=(), traced=(0,), scope=SCOPE):
    cell = types.SimpleNamespace(config={"objective": {"kernel": scope}})
    return as_run(data, spans, cell, traced)


def ns(parts):
    return {k: round(v * 1e9) for k, v in parts.items() if v}


def test_two_starts_a_stage2_and_a_rung_split_as_the_loops_nest():
    run = fake_run(hand_made(rung=True))
    parts = dp.split(run)
    assert set(parts) == set(dp.PARTS)
    assert ns(parts) == {
        "sanitize": 40,
        # fusion.2 + copy.3 + fusion.30
        "stage1_prep": 20 + 40 + 50,
        # while.12 whole, while.22 whole
        "stage1_linesearch": 100 + 40,
        "stage1_gradient": 40 + 60 + 50,
        # loop 1: fusion.11, while.14 whole, its own 70; loop 2: its own 60
        "stage1_update": 10 + 20 + 70 + 60,
        "stage2_linesearch": 60,
        # copy.40, while.41's own 130, the jvp, fusion.43 under fit.readback
        "stage2_rest": 10 + 130 + 80 + 30,
        # the rung's loop outlives its span; the chunk's rest after a rung
        "ladder": 170 + 10,
        # chunk.plan, between the stages, chunk.submit
        "unattributed": 8 + 10 + 10}
    assert sum(parts.values()) == pytest.approx(run.trace.busy_s())
    assert dp.trial_events(run) == {"stage1": 3, "stage2": 1}
    assert dp.share(run, "sanitize") == pytest.approx(40 / 2000)
    assert dp.share(run, *dp.STAGE2) == pytest.approx(310 / 2000)


def test_a_burst_goes_whole_where_most_of_it_ran():
    # the device's clock 70 ns ahead: stage 2's program starts "before" the
    # span that dispatches it opens, between the stages, and the operation
    # queued there (fusion.31) runs into it — one burst, 270 of its 350 ns
    # inside the stage-2 bracket
    run = fake_run(hand_made(early=70))
    parts, on_time = ns(dp.split(run)), ns(dp.split(fake_run(hand_made())))
    assert parts["stage2_linesearch"] == on_time["stage2_linesearch"] == 60
    assert parts["stage2_rest"] == on_time["stage2_rest"] + 10
    assert parts["unattributed"] == on_time["unattributed"] - 10
    assert dp.trial_events(run) == {"stage1": 3, "stage2": 1}


def test_no_stage2_leaves_the_readback_unattributed():
    run = fake_run(hand_made(stage2=False, starts=1))
    parts = ns(dp.split(run))
    assert "stage2_rest" not in parts and "stage2_linesearch" not in parts
    assert "ladder" not in parts
    assert parts["unattributed"] == 8 + 10 + 10 + 30  # + fusion.43
    assert parts["stage1_update"] == 10 + 20 + 70
    assert dp.trial_events(run) == {"stage1": 2, "stage2": 0}
    assert dp.share(run, *dp.STAGE2) == 0
    assert dp.share(run, "ladder") == 0


def test_a_bracket_without_anchors_is_unattributed_whole():
    # the gradient's kernel events under another name: no lockstep loop is
    # recognised, and nothing of either bracket is given a phase
    run = fake_run(hand_made(anchor="jvp_pallas.other_"))
    parts = ns(dp.split(run))
    assert set(parts) == {"sanitize", "unattributed"}
    assert parts["unattributed"] == round(run.trace.busy_s() * 1e9) - 40
    assert dp.trial_events(run) == {"stage1": 0, "stage2": 0}
    # and the inline path (a fit.primary with no stage span) likewise
    inline = hand_made(stage2=False)
    inline["host"][0]["spans"] = [
        s for s in inline["host"][0]["spans"] if s[0] != "fit.stage1"]
    assert set(ns(dp.split(fake_run(inline)))) == {"sanitize",
                                                   "unattributed"}


def test_nothing_to_split_where_span_idle_has_nothing():
    assert dp.split(fake_run(None)) is None
    assert dp.share(fake_run(None), "sanitize") is None
    assert dp.trial_events(fake_run(None)) is None
    two_chips = hand_made()
    two_chips["devices"].append({"plane": "/device:TPU:1", "ordinal": 1,
                                 "ops": [["copy.1", 0, 100, 0]]})
    assert dp.split(fake_run(two_chips)) is None
    two_lanes = hand_made()
    two_lanes["host"].append({"thread": "lane-1",
                              "spans": [["chunk", 100, 500]]})
    assert dp.split(fake_run(two_lanes)) is None
    cpu = hand_made()
    cpu["devices"] = []  # a rehearsal's trace has no device plane
    assert dp.share(fake_run(cpu), "unattributed") is None


def readers():
    manifest = mf.load_manifest()
    return {n: mf.load_plugin(manifest, mf.ROOT, "layer_metrics", n).read
            for n in NEW}


def line(name, walk, **attrs):
    return {"kind": "span", "name": name, "walk": walk, "attrs": attrs}


def test_counter_readers_on_the_span_lines():
    # two chunks of one traced walk; a line of another walk is not read
    spans = [line("walk", 1), line("walk", 2),
             line("chunk", 2), line("chunk", 2), line("chunk", 1),
             line("fit.stage1", 2, iters=4, iter_passes=4, trials=24,
                  starts=1),
             line("fit.stage1", 2, iters=6, iter_passes=10, trials=40,
                  starts=2),
             line("fit.stage1", 1, iters=9, iter_passes=9, trials=90,
                  starts=1),
             line("fit.readback", 2, rows=8, stage2_iters=3,
                  stage2_trials=12),
             line("fit.readback", 2, rows=8, stage2_iters=0,
                  stage2_trials=0)]
    run = fake_run(hand_made(), spans, traced=(1,))
    read = readers()
    assert read["stage1_trials_per_iter"](run) == pytest.approx(64 / 14)
    assert read["stage2_trials_per_iter"](run) == pytest.approx(4.0)
    assert read["stage2_iters_per_chunk"](run) == pytest.approx(1.5)
    # trials 64 + 12, gradient passes 14 + 3 starts + 3
    assert read["objective_passes_per_chunk"](run) == pytest.approx(96 / 2)
    # the parent's lines carry none of the counters: nothing to read
    old = [line("walk", 1), line("chunk", 1),
           line("fit.stage1", 1, iters=4, undone=3),
           line("fit.readback", 1, rows=8, iters_max=5, iters_sum=20)]
    run = fake_run(hand_made(), old)
    assert all(read[n](run) is None for n in NEW if not n.endswith("share"))
    # and no stage 2 in the window: no trials an iteration of it
    none = spans[:8] + [line("fit.readback", 2, rows=8, stage2_iters=0,
                             stage2_trials=0)]
    run = fake_run(hand_made(), none, traced=(1,))
    assert read["stage2_trials_per_iter"](run) is None
    assert read["stage2_iters_per_chunk"](run) == 0


def test_share_readers_sum_to_the_busy_share():
    run = fake_run(hand_made(rung=True))
    read = readers()
    shares = {n: read[n](run) for n in SHARES}
    assert shares["ladder_device_share"] == pytest.approx(180 / 2000)
    assert shares["stage2_device_share"] == pytest.approx(310 / 2000)
    assert sum(shares.values()) == pytest.approx(
        1 - run.trace.idle_share_worst())
    assert all(read[n](fake_run(None)) is None for n in NEW)


def test_manifest_lists_the_twelve_for_every_walk_cell():
    manifest = mf.load_manifest()
    entries = {m["name"]: m for m in manifest["per_layer"]}
    walk_cells = [w["name"] for w in manifest["workloads"]
                  if w["traffic"] == "walk-dense"]
    for n in NEW:
        assert entries[n]["workloads"] == walk_cells
        assert entries[n]["moves"] == "series_per_s_chip"
        assert entries[n]["better"] == "lower"
    # new entries go last, in the issue's order
    assert [m["name"] for m in manifest["per_layer"]][-12:] == NEW


# -- the chunk recorded on the chip -------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt", encoding="utf-8") as f:
        rec = json.load(f)
    cell = mf.resolve_cell(mf.load_manifest(), rec["workload"])
    return as_run(rec["trace"], rec["spans"], cell), rec


def test_recorded_chunk_parts_are_disjoint_and_sum_to_busy(recorded):
    run, rec = recorded
    parts = dp.split(run)
    assert sum(parts.values()) == pytest.approx(run.trace.busy_s(), abs=1e-9)
    assert sum(parts.values()) == pytest.approx(
        (1 - run.trace.idle_share_worst()) * run.trace.window_s)
    assert parts == pytest.approx(rec["expect"]["parts_s"], abs=1e-9)
    # one chunk of a dense panel: no rung; every phase of both stages ran
    assert parts["ladder"] == 0
    assert all(parts[p] > 0 for p in dp.PARTS if p != "ladder")


def test_recorded_trace_counts_the_programs_trials(recorded):
    # the loop's own counter against the device's events: what makes the
    # split checkable (a whole trace can lose events; this chunk lost none)
    run, _ = recorded
    by_name = {s["name"]: s["attrs"] for s in run.spans}
    assert dp.trial_events(run) == {
        "stage1": by_name["fit.stage1"]["trials"],
        "stage2": by_name["fit.readback"]["stage2_trials"]}
    assert by_name["fit.readback"]["stage2_iters"] > 0
    assert "fit.stage2" in by_name


def test_recorded_chunk_readers_return_what_was_written_down(recorded):
    run, rec = recorded
    read = readers()
    got = {n: read[n](run) for n in NEW}
    assert got == pytest.approx(rec["expect"]["metrics"], abs=1e-9)
    assert got["ladder_device_share"] == 0
    assert sum(got[n] for n in SHARES) == pytest.approx(
        1 - run.trace.idle_share_worst())
