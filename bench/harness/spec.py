"""Find a cell's files by the names that BENCHMARK.json gives.

A cell names a configuration and a traffic mix; the configuration's entry
names its file, the mix is `bench/traffic/<traffic>.json`, the mix names
its loop `bench/loops/<loop>.py`, and each per-layer metric is read
by `bench/metrics/<metric>.py`. Adding a cell, a mix or a metric adds files
and entries; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(RuntimeError):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict           # the configuration file's contents
    config_entry: dict     # its entry in BENCHMARK.json
    traffic_name: str
    traffic: dict          # the traffic file's contents
    end_to_end: list       # metric entries that this cell reports untraced
    per_layer: list        # metric entries that this cell reports traced
    spec: dict = field(repr=False, default_factory=dict)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise SpecError(f"no BENCHMARK.json in {root}")
    spec = load_json(path)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SpecError(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = load_json(os.path.join(root, entry["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     w["traffic"] + ".json"))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                config_entry=entry, traffic_name=w["traffic"],
                traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
                per_layer=[m for m in spec["per_layer"] if _reports(m, name)],
                spec=spec)


def load_module(kind: str, name: str, root: str = ROOT):
    """Import `bench/<kind>/<name>.py` by path (names may hold dots)."""
    path = os.path.join(root, "bench", kind, name + ".py")
    if not os.path.exists(path):
        raise SpecError(f"missing {os.path.relpath(path, root)}")
    mod_name = f"bench_{kind}_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
