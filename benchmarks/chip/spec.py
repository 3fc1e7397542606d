"""A cell of ``BENCHMARK.json`` and the data files it names.

A cell names a configuration and a traffic.  The traffic's name is
``<placement>.<mix>``: ``placements/<placement>.json`` says how much of the
KV the plan keeps in HBM, ``traffic/<mix>.json`` holds the mix's parameters.
The configuration's file is the one ``BENCHMARK.json`` lists for it.  Each
metric is read by ``metrics/<name>.py``.  A new cell, mix, placement or
metric is new files and new ``BENCHMARK.json`` entries; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    placement: dict
    mix_name: str
    mix: dict
    end_to_end: list        # the cell's end-to-end metric entries
    per_layer: list         # the cell's per-layer metric entries


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _load(ROOT / "BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    bench = benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    placement, mix = w["traffic"].split(".", 1)
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=_load(ROOT / conf["file"]),
        placement=_load(HERE / "placements" / f"{placement}.json"),
        mix_name=mix, mix=_load(HERE / "traffic" / f"{mix}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def reader(entry: dict):
    """The module ``metrics/<name>.py`` that reads the metric ``entry``,
    checked against what ``BENCHMARK.json`` declares for it."""
    name = entry["name"]
    path = HERE / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    for key in ("unit", "better", "source", "layer", "moves"):
        declared = getattr(mod, key.upper(), None)
        if key in entry and declared != entry[key]:
            raise SystemExit(f"metrics/{name}.py declares {key}={declared!r}, "
                             f"BENCHMARK.json {entry[key]!r}")
    return mod
