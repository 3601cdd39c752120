"""Every cell runs end to end at tiny sizes on the CPU, traced and not, and
its last line has the contract's keys and no device number."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest as mf

CELLS = [w["name"] for w in mf.load_manifest()["workloads"]]
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(cell, trace, tmp_path, *more):
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
