"""Every cell runs end to end at tiny sizes on the CPU, traced and not, and
its last line has the contract's keys and no device number."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest as mf

CELLS = [w["name"] for w in mf.load_manifest()["workloads"]]
KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}
PROCESS_METRICS = {"process_start_s", "process_start_cpu_s"}
SETUP_METRICS = {"setup_panel_s", "setup_first_chunk_s",
                 "setup_warm_walk_rest_s"}


def rehearse(cell, trace, tmp_path, *more, detail=False):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(mf.BENCH_DIR, "run.py"), "--workload",
         cell, "--seed", "3", "--seconds", "2", "--trace", str(trace),
         "--rehearse", "--out", str(tmp_path), *more],
        cwd=mf.ROOT, env=env, capture_output=True, text=True, timeout=1500)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    assert all(ln.get("rehearsal") is True for ln in lines)
    if detail:
        return lines[-1], {ln["what"]: ln for ln in lines[:-1]}, proc.stderr
    return lines[-1]


PLANNED = {
    # the cells PERF.md keeps for later, as the data they will arrive as
    "walk-ragged-host": {
        "kind": "walk", "residency": "host", "gap_frac": 0.1,
        "pad_side": "both",
        "lengths": {"dist": "log-uniform", "min": 64, "max": 128},
        "process_mix": {"matched": 0.6, "near_unit_root": 0.2,
                        "white_noise": 0.1, "level_shift": 0.1}},
    "serve-bursty": {
        "kind": "serve-open-loop", "rate": 6.0,
        "arrival": {"kind": "onoff", "factor": 5.0, "on_s": 0.5,
                    "period_s": 1.0},
        "rows": {"dist": "log-uniform", "min": 8, "max": 128},
        "tenants": 4, "skew": 1.1, "pool_rows": 1024,
        "warmup_rows": [128, 8], "drain_s": 120.0,
        "server": {"cell_rows": 64, "max_batch_rows": 256}},
}
SERVE_LAYERS = {
    "batch_wall_p50_s": ("s", "program_span", "request_p50_s"),
    "rows_per_batch_p50": ("rows", "program_span", "request_p50_s"),
    "padding_useful_share": ("share", "program_counter", "request_p50_s"),
    "loadgen_late_p95_s": ("s", "host_clock", "request_p95_s"),
    "serve_device_idle_share": ("share", "device_trace", "request_p50_s"),
}


@pytest.fixture(scope="module")
def later(tmp_path_factory):
    """BENCHMARK.json as a later PR could leave it: a new directory of mixes
    and nothing but new entries — the four-chip sharded walk and the
    serving cell (their files are here already; ``PERF.md`` says why they
    are not cells yet), a ragged, gappy, mixed-process, host-resident walk
    and a bursty serving mix with its own server arguments."""
    root = tmp_path_factory.mktemp("later")
    (root / "later" / "traffic").mkdir(parents=True)
    os.symlink(mf.BENCH_DIR, root / "benchmark")
    for name, mix in PLANNED.items():
        (root / "later" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    m = mf.load_manifest()
    m["paths"].append("later")
    m["configs"].append({
        "name": "arima111-x4", "source": "BASELINE.json north_star",
        "file": "benchmark/configs/arima111-x4.json", "reduced": [],
        "why": "example"})
    walks = ["arima111-x4.walk-sharded", "arima111.walk-ragged-host"]
    serves = ["arima111.serve-small", "arima111.serve-bursty"]
    m["workloads"] += [
        {"name": "arima111-x4.walk-sharded", "config": "arima111-x4",
         "traffic": "walk-sharded", "chips": 4, "why": "example"},
        *({"name": f"arima111.{mix}", "config": "arima111", "traffic": mix,
           "chips": 1, "why": "example"}
          for mix in ("walk-ragged-host", "serve-small", "serve-bursty"))]
    for e in m["end_to_end"] + m["per_layer"]:
        if "arima111.walk-dense" in e.get("workloads", ()):
            e["workloads"] += walks
    m["end_to_end"] += [
        {"name": n, "unit": "s", "better": "lower", "bound": 0.1,
         "source": "host_clock", "workloads": serves}
        for n in ("request_p50_s", "request_p95_s")]
    m["per_layer"] += [
        {"name": n, "unit": unit, "better": "lower", "source": source,
         "layer": "admission_batching", "moves": moves,
         "workloads": serves}
        for n, (unit, source, moves) in SERVE_LAYERS.items()]
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return m, ("--manifest", str(root / "BENCHMARK.json"))


def check_line(line, resolved, trace):
    assert set(line) - {"rehearsal"} == KEYS
    assert line["attempted"] > 0
    assert line["failed"] <= 0.01 * line["attempted"]  # tiny rows, CPU
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    declared = {m["name"]: m for m in
                (resolved.per_layer if trace else resolved.end_to_end)}
    assert set(line["metrics"]) <= set(declared)
    if not trace:
        assert set(line["metrics"]) == set(declared)
    for name, got in line["metrics"].items():
        assert got["unit"] == declared[name]["unit"]
        if declared[name]["source"] != "program_counter":
            assert got["value"] is None  # no CPU time under a device name


# every cell untraced, and one traced (minutes on the CPU, whose profiler
# is slow)
@pytest.mark.parametrize("cell,trace", [(c, 0) for c in CELLS]
                         + [("arima111.walk-dense", 1)])
def test_last_line(cell, trace, tmp_path):
    line = rehearse(cell, trace, tmp_path)
    check_line(line, mf.resolve_cell(mf.load_manifest(), cell), trace)
    if trace:
        # what PR 33 took out of setup_s and the three stretches it is made
        # of: read in every cell, nulled here like every measured metric
        for name in (PROCESS_METRICS | SETUP_METRICS
                     | {"process_start_cpu_user_s"}):
            assert line["metrics"][name] == {"value": None, "unit": "s"}


@pytest.mark.parametrize("cell,trace", [
    ("arima111-x4.walk-sharded", 0), ("arima111.serve-small", 0),
    ("arima111.serve-small", 1), ("arima111.walk-ragged-host", 1),
    ("arima111.serve-bursty", 0)])
def test_cells_kept_for_later_arrive_as_data(later, cell, trace, tmp_path):
    manifest, more = later
    line = rehearse(cell, trace, tmp_path, *more)
    check_line(line, mf.resolve_cell(manifest, cell,
                                     root=os.path.dirname(more[1])), trace)
    if cell == "arima111-x4.walk-sharded":
        assert line["device"]["count"] == 4 and line["correct"]
    if cell == "arima111.walk-ragged-host":
        # a share of the ragged rows needs the ladder or is lost: the
        # metric of a later ragged cell reads above 0 here, 0 in the dense
        assert line["metrics"]["rescued_row_share"]["value"] > 0
    if cell == "arima111.serve-bursty":
        assert line["attempted"] == 12


def test_setup_counts_from_the_device_mark(tmp_path):
    """``setup_s`` is the wall from the device mark to the window's
    opening, and the stretch before the mark is on the ``start`` and
    ``done`` lines of an untraced run too."""
    line, detail, stderr = rehearse("hw-add24.walk-dense", 0, tmp_path,
                                    detail=True)
    start, done = detail["start"], detail["done"]
    for name in PROCESS_METRICS:
        assert start[name] == done[name] > 0
    assert 0 < start["process_start_cpu_user_s"] \
        <= start["process_start_cpu_s"]
    # the start line is written right after the mark
    assert 0 <= start["at_s"] - start["process_start_s"] < 1.0
    assert done["setup_s"] > 0
    assert abs(done["setup_end_at_s"] - done["process_start_s"]
               - done["setup_s"]) < 0.005
    # the three stretches in order, inside setup_s
    panel, warm = detail["panel"], detail["warmup_walk"]
    assert 0 < panel["since_device_s"] < warm["since_device_s"] \
        <= done["setup_s"]
    assert panel["since_device_s"] + warm["wall_s"] \
        <= warm["since_device_s"]
    assert line["metrics"]["setup_s"] == {"value": None, "unit": "s"}
    # every number compared stands beside its limit: last in the line,
    # and the last lines of stderr
    assert list(line)[-1] == "checks" and line["correct"]
    assert {"walks_unsound", "resume_arrays_differing",
            "compiles_in_window"} <= set(line["checks"])
    assert all(set(c) == {"value", "rule", "limit", "ok"} and c["ok"]
               for c in line["checks"].values())
    tail = [ln for ln in stderr.splitlines() if ln.startswith("check ")]
    assert len(tail) == len(line["checks"])
    assert stderr.splitlines()[-len(tail):] == tail


def test_broken_timed_path_is_not_correct(tmp_path):
    """The whole of a run but the look for a chip, over a fit whose
    answers are altered where they are produced: ``correct`` is false, and
    the number that failed stands beside its limit."""
    root = tmp_path / "root"
    (root / "broken" / "configs").mkdir(parents=True)
    os.symlink(mf.BENCH_DIR, root / "benchmark")
    m = mf.load_manifest()
    cfg = mf.resolve_cell(m, "arima111.walk-dense").config
    cfg["model"] = {**cfg["model"],
                    "fit": "benchmark.tests.broken_fits:arima_params_shifted"}
    (root / "broken" / "configs" / "arima111-broken.json").write_text(
        json.dumps({**cfg, "name": "arima111-broken"}))
    m["paths"].append("broken")
    m["configs"].append({
        "name": "arima111-broken", "source": "a test", "reduced": [],
        "file": "broken/configs/arima111-broken.json", "why": "example"})
    m["workloads"].append({
        "name": "arima111-broken.walk-dense", "config": "arima111-broken",
        "traffic": "walk-dense", "chips": 1, "why": "example"})
    for e in m["end_to_end"] + m["per_layer"]:
        if "arima111.walk-dense" in e.get("workloads", ()):
            e["workloads"].append("arima111-broken.walk-dense")
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    line, _, stderr = rehearse(
        "arima111-broken.walk-dense", 0, tmp_path / "out", "--manifest",
        str(root / "BENCHMARK.json"), detail=True)
    assert line["correct"] is False and line["failed"] == 0
    failed = {k for k, c in line["checks"].items() if not c["ok"]}
    assert failed == {"reference_share_within_gap_0.1"}
    assert "NOT MET" in stderr.splitlines()[-2]


def test_no_tpu_no_result(tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(mf.BENCH_DIR, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0", "--out",
         str(tmp_path)], cwd=mf.ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert proc.returncode != 0
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert "takes no CPU path" in proc.stderr
