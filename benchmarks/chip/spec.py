"""A cell of ``BENCHMARK.json`` and the data files it names.

A cell names a configuration and a traffic.  The traffic's name is
``<placement>.<mix>``: ``placements/<placement>.json`` says how much of the
KV the plan keeps in HBM, ``traffic/<mix>.json`` holds the mix's parameters.
The configuration's file is the one ``BENCHMARK.json`` lists for it, and it
names its architecture's family, ``families/<family>.py``.  Each metric is
read by ``metrics/<name>.py``.  A new cell, mix, placement or metric is new
files and new ``BENCHMARK.json`` entries; so is a configuration of another
architecture: a family module, a configuration file, and the entries.
Nothing here changes.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import re
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

from benchmarks.chip.families import INTERFACE

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    family: ModuleType      # families/<family>.py, as the config names it
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
    config = _load(ROOT / conf["file"])
    if sorted(config.get("reduced", [])) != sorted(conf["reduced"]):
        raise SystemExit(f"{conf['file']}: reduced "
                         f"{config.get('reduced', [])!r} but BENCHMARK.json "
                         f"lists {conf['reduced']!r}")
    placement, mix = w["traffic"].split(".", 1)
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=config, family=family(config.get("family"), conf["file"]),
        placement=_load(HERE / "placements" / f"{placement}.json"),
        mix_name=mix, mix=_load(HERE / "traffic" / f"{mix}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def _module(name: str, path: Path) -> ModuleType:
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[name] = mod         # dataclasses look their module up there
    mod_spec.loader.exec_module(mod)
    return mod


@functools.cache
def _family_at(path: Path) -> ModuleType:
    # once per process: its jitted weights and reference keep their
    # compiled programs from one run to the next
    return _module(f"bench_family_{path.stem}", path)


def family(name, config_file: str) -> ModuleType:
    """The module ``families/<name>.py`` that the configuration file
    ``config_file`` names as its family, with the interface that
    ``families/__init__.py`` states."""
    path = HERE / "families" / f"{name}.py"
    if not (isinstance(name, str) and re.fullmatch(r"[A-Za-z]\w*", name)
            and path.is_file()):
        have = sorted(p.stem for p in (HERE / "families").glob("*.py")
                      if not p.stem.startswith("_"))
        raise SystemExit(f"{config_file}: unknown family {name!r}; have "
                         f"{have}")
    mod = _family_at(path)
    missing = [n for n in INTERFACE if not hasattr(mod, n)]
    if missing:
        raise SystemExit(f"{config_file}: families/{name}.py lacks "
                         f"{missing}")
    return mod


def reader(entry: dict):
    """The module ``metrics/<name>.py`` that reads the metric ``entry``,
    checked against what ``BENCHMARK.json`` declares for it."""
    name = entry["name"]
    mod = _module(f"bench_metric_{name}", HERE / "metrics" / f"{name}.py")
    for key in ("unit", "better", "source", "layer", "moves"):
        declared = getattr(mod, key.upper(), None)
        if key in entry and declared != entry[key]:
            raise SystemExit(f"metrics/{name}.py declares {key}={declared!r}, "
                             f"BENCHMARK.json {entry[key]!r}")
    return mod
