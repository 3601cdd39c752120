"""The trace -> metrics reduction: its arithmetic on a hand-made trace, and
its numbers on the small trace recorded on the chip (``data/``)."""

import gzip
import json
import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def hand_made():
    """One chip, a 1000 ns window: a ``while`` over two kernel events and a
    fusion, then a gap the host spends in ``commit``, then one more op."""
    ops = [
        ["while.1", 100, 500, 0],
        ["jvp_pallas.k_.2", 100, 200, 4096],
        ["fusion.7", 300, 50, 0],
        ["pallas.k.3", 400, 200, 4096],
        ["copy.3", 900, 50, 0],
        ["copy.3", 1500, 50, 0],  # outside the window
    ]
    host = [{"thread": "main", "spans": [[tr.WINDOW_SPAN, 0, 1000],
                                         ["chunk", 0, 650],
                                         ["fit.primary", 60, 550]]},
            {"thread": "committer", "spans": [["commit.overlap", 620, 250]]}]
    return {"devices": [{"plane": "/device:TPU:0", "ordinal": 0,
                         "ops": ops}], "host": host}


def test_arithmetic_on_a_hand_made_trace():
    t = tr.Trace(hand_made())
    assert t.window == (0, 1000) and t.window_s == pytest.approx(1e-6)
    # busy: [100, 600) and [900, 950)
    assert t.busy_s() == pytest.approx(550e-9)
    assert t.idle_share_worst() == pytest.approx(0.45)
    # the while keeps only what its children leave: 500 - 200 - 50 - 200
    ops = dict((n, s) for n, s in t.device_ops())
    assert ops["pallas.k"] == pytest.approx(200e-9)
    assert ops["jvp_pallas.k_"] == pytest.approx(200e-9)
    assert ops["while"] == pytest.approx(50e-9)
    assert ops["fusion"] == pytest.approx(50e-9)
    assert ops["copy"] == pytest.approx(50e-9)
    k = t.scope("pallas.k")
    assert k == {"events": 2, "seconds": pytest.approx(400e-9),
                 "bytes": 8192}
    assert t.busy_outside(("pallas.k",)) == pytest.approx(150e-9)
    gaps = t.idle_gaps()
    assert gaps[0] == ["commit.overlap", pytest.approx(300e-9)]
    assert gaps[1] == ["chunk", pytest.approx(100e-9)]
    assert gaps[2] == ["no span", pytest.approx(50e-9)]
    assert t.host_spans("chunk") == [(0, 650)]


def test_two_chips_report_the_worse():
    data = hand_made()
    data["devices"].append({"plane": "/device:TPU:1", "ordinal": 1,
                            "ops": [["copy.1", 0, 100, 0]]})
    t = tr.Trace(data)
    assert t.busy_s_per_device() == pytest.approx([550e-9, 100e-9])
    assert t.busy_s() == pytest.approx(325e-9)
    assert t.idle_share_worst() == pytest.approx(0.9)
    assert t.idle_gaps()[0][1] == pytest.approx(900e-9)


def test_parse_hlo():
    """Results and operands once each; layouts and attributes, which repeat
    the shapes, not at all (texts as the v5e trace has them)."""
    text = ("%jvp_pallas.css_neg_loglik_.24 = (f32[1000,1024,128]{2,1,0:"
            "T(8,128)}, f32[1,1024,128]{2,1,0:T(8,128)S(1)}) custom-call("
            "f32[1000,1024,128]{2,1,0:T(8,128)} %get-tuple-element.1996, "
            "f32[3,1024,128]{2,1,0:T(8,128)S(1)} %copy_bitcast_fusion.6), "
            "custom_call_target=\"tpu_custom_call\", operand_layout_constr"
            "aints={f32[1000,1024,128]{2,1,0}, f32[3,1024,128]{2,1,0}}, "
            "frontend_attributes={kernel_metadata={}}")
    assert tr.parse_hlo(text) == ("jvp_pallas.css_neg_loglik_.24",
                                  4 * (2 * 1000 + 1 + 3) * 1024 * 128)
    assert tr.parse_hlo("%iota.1 = s32[256]{0:T(256)} iota(), "
                        "iota_dimension=0") == ("iota.1", 1024)
    assert tr.parse_hlo("%copy.76 = f32[120,8]{1,0} copy(f32[120,8]{0,1} "
                        "%bitcast.580)") == ("copy.76", 2 * 4 * 960)
    assert tr.parse_hlo("no-equals-sign") == ("no-equals-sign", 0)


def test_cut_keeps_whole_events_and_rebases():
    small = tr.cut(hand_made(), 100, 700)
    assert [op[1] for op in small["devices"][0]["ops"]] == [0, 0, 200, 300]
    assert small["host"][0]["spans"] == []
    assert small["host"][1]["spans"] == []


RECORDED = os.path.join(DATA, "arima111_walk_dense_chunk.json.gz")


def test_recorded_chip_trace():
    """One chunk of ``arima111.walk-dense`` as a v5e traced it: 45 objective
    kernel events, 74 ms of the chunk's 132 ms, 361 GB/s."""
    with gzip.open(RECORDED, "rt", encoding="utf-8") as f:
        rec = json.load(f)
    t = tr.Trace(rec["trace"])
    want = rec["expect"]
    assert t.window_s == pytest.approx(want["window_s"])
    assert t.busy_s() == pytest.approx(want["busy_s"])
    assert t.idle_share_worst() == pytest.approx(want["idle_share_worst"])
    for scope, exp in want["scopes"].items():
        got = t.scope(scope)
        assert got["events"] == exp["events"]
        assert got["bytes"] == exp["bytes"]
        assert got["seconds"] == pytest.approx(exp["seconds"])
    assert t.busy_outside(list(want["scopes"])) == pytest.approx(
        want["busy_outside_s"])
    assert [n for n, _ in t.device_ops(3)] == want["top_ops"]
    assert [n for n, _ in t.idle_gaps(3)] == want["top_gaps"]
    kernel = want["scopes"]["pallas.css_neg_loglik"]
    assert kernel["events"] == 45
    assert kernel["bytes"] / kernel["seconds"] == pytest.approx(361e9,
                                                                rel=0.01)
    assert len(t.host_spans("chunk")) == 1
