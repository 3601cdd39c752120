"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

Whatever belongs to one configuration, one traffic mix or one per-layer
metric sits in a file of its own under one of the manifest's ``paths``; a
later PR adds files and manifest entries and edits nothing that exists.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
MANIFEST_PATH = os.path.join(ROOT, "BENCHMARK.json")


class ManifestError(ValueError):
    """The manifest names something that is not there, or twice."""


def load_manifest(path: str = MANIFEST_PATH) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _find(manifest: dict, root: str, *rel: str) -> str:
    """First ``<path>/<rel...>`` that exists over the manifest's ``paths``."""
    tried = []
    for p in manifest["paths"]:
        cand = os.path.join(root, p, *rel)
        if os.path.exists(cand):
            return cand
        tried.append(cand)
    raise ManifestError(f"no file {os.path.join(*rel)!r} under paths "
                        f"{manifest['paths']} (tried {tried})")


def _load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def module_name(name: str) -> str:
    """File stem of a python module named in data (``serve-open-loop`` ->
    ``serve_open_loop``)."""
    return name.replace("-", "_").replace(".", "_")


def load_plugin(manifest: dict, root: str, group: str, name: str):
    """``<path>/<group>/<name>.py`` as a module: a traffic kind, a process,
    a plain reference or a layer-metric reader."""
    stem = module_name(name)
    return _load_module(_find(manifest, root, group, stem + ".py"),
                        f"_bench_{group}_{stem}")


def _by_name(entries: list, what: str) -> dict:
    out = {}
    for e in entries:
        if e["name"] in out:
            raise ManifestError(f"{what} {e['name']!r} appears twice")
        out[e["name"]] = e
    return out


def _for_cell(entries: list, cell: str) -> list:
    """Metric entries this cell reports: those with no ``workloads`` key,
    or with the cell in it."""
    return [e for e in entries
            if "workloads" not in e or cell in e["workloads"]]


@dataclass
class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list
    per_layer: list
    manifest: dict = field(repr=False)
    root: str = ROOT

    def plugin(self, group: str, name: str):
        return load_plugin(self.manifest, self.root, group, name)


def _apply_rehearsal(d: dict) -> dict:
    """Tiny sizes for a CPU rehearsal: the file's own ``rehearse`` block
    overrides its top-level keys."""
    out = {k: v for k, v in d.items() if k != "rehearse"}
    out.update(d.get("rehearse", {}))
    return out


def resolve_cell(manifest: dict, name: str, *, root: str = ROOT,
                 rehearse: bool = False) -> Cell:
    cells = _by_name(manifest["workloads"], "workload")
    if name not in cells:
        raise ManifestError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = _by_name(manifest["configs"], "config")
    if w["config"] not in configs:
        raise ManifestError(f"workload {name!r} names config "
                            f"{w['config']!r}, which is not in configs")
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(_find(manifest, root, "traffic",
                               w["traffic"] + ".json"))
    if rehearse:
        config, traffic = _apply_rehearsal(config), _apply_rehearsal(traffic)
    e2e = _by_name(manifest["end_to_end"], "end_to_end metric")
    per_layer = _for_cell(manifest["per_layer"], name)
    for m in per_layer:
        if m["moves"] not in e2e:
            raise ManifestError(f"per_layer {m['name']!r} moves "
                                f"{m['moves']!r}, not an end_to_end metric")
    mine = {m["name"] for m in _for_cell(manifest["end_to_end"], name)}
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"], config=config,
        traffic_name=w["traffic"], traffic=traffic,
        end_to_end=_for_cell(manifest["end_to_end"], name),
        # a per-layer metric is reported only where the metric it moves is
        per_layer=[m for m in per_layer if m["moves"] in mine],
        manifest=manifest, root=root)
