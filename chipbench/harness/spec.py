"""What ``BENCHMARK.json`` names, found by name on disk.

- a cell (``workloads`` entry) names a configuration and a traffic mix;
- a configuration's sizes are in the file its ``configs`` entry names;
- a traffic mix is ``chipbench/traffic/<traffic>.json``: data, which
  names its ``loop`` and its ``entry``;
- a loop, the generator that sends a mix's frames and times them, is
  ``chipbench/loops/<loop>.py``;
- an entry, the way frames reach the system under test, is
  ``chipbench/entries/<entry>.py``;
- an app's plain reference is ``chipbench/references/<app>.py``;
- a metric's reader is ``chipbench/metrics/<metric>.py``; a metric
  ``<name>.<suffix>`` without a file of its own is read by
  ``<name>.py``, so one quantity split by the end-to-end metric it
  moves has one reader.

A later cell, mix, loop, entry, app or metric is a new file and a new
entry; nothing here changes for it.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
from types import ModuleType
from typing import Any

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
BENCHMARK_JSON = os.path.join(ROOT, "BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict[str, Any]
    traffic: dict[str, Any]
    #: the entries of ``end_to_end`` / ``per_layer`` this cell reports
    end_to_end: list[dict[str, Any]]
    per_layer: list[dict[str, Any]]


def _load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def reports(metric: dict[str, Any], cell: str,
            end_to_end_names: set[str]) -> bool:
    """Whether ``cell`` reports ``metric``: the cells its ``workloads``
    lists, or, without the key, every cell that reports what the metric
    ``moves`` (end-to-end metrics without the key: every cell)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in end_to_end_names


def load_cell(name: str, bench_path: str = BENCHMARK_JSON) -> Cell:
    bench = _load_json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_path}; "
                       f"choose from {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(ROOT, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(BENCH_DIR, "traffic",
                                      w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if reports(m, name, set())]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if reports(m, name, e2e_names)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, layer)


@functools.lru_cache(maxsize=None)
def _module(kind: str, name: str) -> ModuleType:
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _exists(kind: str, name: str) -> bool:
    return os.path.exists(os.path.join(BENCH_DIR, kind, name + ".py"))


def reference(app: str) -> ModuleType:
    """The app's plain reference module (``references/<app>.py``)."""
    return _module("references", app)


def loop(name: str) -> ModuleType:
    """The generator ``loops/<name>.py``: ``run(mix, entry, seconds,
    rng, annotate) -> drive.Window``."""
    return _module("loops", name)


def entry(name: str) -> ModuleType:
    """The entry ``entries/<name>.py``: ``start(cell, app, pool_dev,
    devices) -> drive.Entry`` and ``programs(cell, app, plane, devices)``
    for the rehearsal without the chip."""
    return _module("entries", name)


def metric_reader(metric: str):
    """The ``read(run)`` function of ``metrics/<metric>.py``, or of
    ``metrics/<name>.py`` for a metric ``<name>.<suffix>`` that has no
    file of its own."""
    name = metric
    while not _exists("metrics", name) and "." in name:
        name = name.rsplit(".", 1)[0]
    return _module("metrics", name).read
