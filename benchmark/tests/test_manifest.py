"""BENCHMARK.json keeps the builder's contract, every name in it resolves
to a file, and a cell, a configuration, a mix and a layer metric can each
be added by new files plus new entries."""

import copy
import json
import os
import re

import pytest

from benchmark import manifest as mf

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return mf.load_manifest()


def test_contract_shape(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(mf.MANIFEST_PATH) <= 64 * 1024
    assert 1 <= len(manifest["paths"]) <= 16
    assert manifest["command"][-1].startswith(manifest["paths"][0] + "/")
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names += [e["name"] for e in manifest[group]]
    assert all(NAME.match(n) for n in names)
    # a layer is a plain name too, and PERF.md names it so (section 3)
    layers = {m["layer"] for m in manifest["per_layer"]}
    assert all(LAYER.match(name) for name in layers), layers
    with open(os.path.join(mf.ROOT, "PERF.md"), encoding="utf-8") as f:
        perf = f.read()
    assert all(f"`{name}`" in perf for name in layers), layers
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [e["name"] for e in manifest[group]]
        assert len(got) == len(set(got)), group
    assert all(len(e["why"]) <= 200
               for e in manifest["configs"] + manifest["workloads"])
    assert all(m["source"] in SOURCES
               for m in manifest["end_to_end"] + manifest["per_layer"])
    assert all(m["source"] in ("host_clock", "device_trace")
               and 0.01 <= m["bound"] <= 0.1 for m in manifest["end_to_end"])
    assert any(m["name"] == "setup_s" and m["bound"] == 0.1
               for m in manifest["end_to_end"])
    assert all(m["unit"] == "%" for m in manifest["per_layer"]
               if m["name"].endswith("_roofline"))


SETUP_SPLIT = {"process_start_s": "process", "process_start_cpu_s": "process",
               "process_start_cpu_user_s": "process",
               "setup_panel_s": "walk_driver",
               "setup_first_chunk_s": "walk_driver",
               "setup_warm_walk_rest_s": "walk_driver"}


@pytest.mark.parametrize("name,layer", sorted(SETUP_SPLIT.items()))
def test_setup_split_entries(manifest, name, layer):
    """PR 33: ``setup_s`` counts from the device mark; what left it and the
    three stretches it is made of are per-layer metrics of every cell."""
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": "s", "better": "lower",
                     "source": "host_clock", "layer": layer,
                     "moves": "setup_s", "workloads": entry["workloads"]}
    assert entry["moves"] in e2e
    assert {"arima111.walk-dense", "hw-add24.walk-dense",
            "garch11.walk-dense"} <= set(entry["workloads"]) <= cells
    reader = mf.load_plugin(manifest, mf.ROOT, "layer_metrics", name)
    assert callable(reader.read) and "setup_s" in reader.__doc__
    # the metric was re-pointed, not loosened: every cell, the same bound
    assert e2e["setup_s"] == {"name": "setup_s", "unit": "s",
                              "better": "lower", "bound": 0.1,
                              "source": "host_clock"}


def test_setup_readers_find_nothing_without_a_warm_up_walk():
    """A kind that records no panel mark and no warm-up walk: the readers
    return ``None`` and the metrics are left out of the line."""
    manifest = mf.load_manifest()

    class Run:
        state = {"server": object()}

    for name, layer in SETUP_SPLIT.items():
        if layer == "walk_driver":
            reader = mf.load_plugin(manifest, mf.ROOT, "layer_metrics", name)
            assert reader.read(Run) is None
    Run.state = {"setup_panel_s": 0.75, "setup_first_chunk_s": 7.0,
                 "setup_warm_walk_rest_s": 1.0}
    got = {name: mf.load_plugin(manifest, mf.ROOT, "layer_metrics",
                                name).read(Run)
           for name, layer in SETUP_SPLIT.items() if layer == "walk_driver"}
    assert got == Run.state


def test_cells_and_budget(manifest):
    cells = manifest["workloads"]
    assert 2 <= len(cells) <= 24
    pairs = [(c["config"], c["traffic"]) for c in cells]
    assert len(pairs) == len(set(pairs))
    assert {c["config"] for c in cells} == {c["name"]
                                            for c in manifest["configs"]}
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)
    # the check of a PR with the full 24 cells must fit into 43200 s
    s = manifest["run_seconds"]
    assert 1 <= s <= 51
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_every_name_resolves(manifest):
    for w in manifest["workloads"]:
        cell = mf.resolve_cell(manifest, w["name"])
        assert cell.plugin("kinds", cell.traffic["kind"]).measure
        assert cell.plugin("processes", cell.config["process"]["name"]).rows
        assert cell.plugin("reference",
                           cell.config["reference"]["module"]).optimum
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in e2e
            assert callable(cell.plugin("layer_metrics", m["name"]).read)
        rehearsal = mf.resolve_cell(manifest, w["name"], rehearse=True)
        assert rehearsal.config["rows"] < cell.config["rows"]
    for m in manifest["per_layer"]:
        mf.load_plugin(manifest, mf.ROOT, "layer_metrics", m["name"])
    for c in manifest["configs"]:
        with open(os.path.join(mf.ROOT, c["file"]), encoding="utf-8") as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert cfg["source"] and cfg["guarantees"] and cfg["assumed"]


def test_added_by_files_alone(manifest, tmp_path):
    """A later PR's cell: a new directory with one file each and four
    manifest entries; nothing that exists is edited."""
    os.symlink(mf.BENCH_DIR, tmp_path / "benchmark")
    extra = tmp_path / "extra"
    for d in ("configs", "traffic", "layer_metrics"):
        (extra / d).mkdir(parents=True)
    cfg = mf.resolve_cell(manifest, "arima111.walk-dense").config
    (extra / "configs" / "ar2.json").write_text(json.dumps(
        {**cfg, "name": "ar2", "model": {**cfg["model"],
                                         "kwargs": {"order": [2, 0, 0]}}}))
    (extra / "traffic" / "walk-ragged.json").write_text(json.dumps(
        {"kind": "walk", "gap_frac": 0.1,
         "lengths": {"dist": "log-uniform", "min": 250, "max": 1000}}))
    (extra / "layer_metrics" / "walks_in_window.py").write_text(
        "def read(run):\n    return len(run.result['walks'])\n")
    later = copy.deepcopy(manifest)
    later["paths"].append("extra")
    later["configs"].append({"name": "ar2", "source": "a paper",
                             "file": "extra/configs/ar2.json",
                             "reduced": [], "why": "example"})
    later["workloads"].append({"name": "ar2.walk-ragged", "config": "ar2",
                               "traffic": "walk-ragged", "chips": 1,
                               "why": "example"})
    later["end_to_end"][0]["workloads"].append("ar2.walk-ragged")
    later["per_layer"].append({
        "name": "walks_in_window", "unit": "walks", "better": "higher",
        "source": "program_counter", "layer": "walk_driver",
        "moves": "series_per_s_chip", "workloads": ["ar2.walk-ragged"]})
    cell = mf.resolve_cell(later, "ar2.walk-ragged", root=str(tmp_path))
    assert cell.config["model"]["kwargs"]["order"] == [2, 0, 0]
    assert cell.traffic["gap_frac"] == 0.1
    assert [m["name"] for m in cell.per_layer] == ["walks_in_window"]

    class Run:
        result = {"walks": [1, 2, 3]}

    assert cell.plugin("layer_metrics", "walks_in_window").read(Run) == 3
    # the kind, the process and the reference it names are the shared ones
    assert cell.plugin("kinds", cell.traffic["kind"]).setup


def test_unknown_names_are_errors(manifest):
    with pytest.raises(mf.ManifestError):
        mf.resolve_cell(manifest, "no-such-cell")
    broken = copy.deepcopy(manifest)
    broken["workloads"][0]["traffic"] = "no-such-mix"
    with pytest.raises(mf.ManifestError):
        mf.resolve_cell(broken, broken["workloads"][0]["name"])
