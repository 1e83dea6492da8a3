"""Resolve a cell of ``BENCHMARK.json`` to the files that define it.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by name:

  chipbench/configs/<config>.json    the deployment: scale, data, chips
  chipbench/traffic/<traffic>.json   the job: query, executor, limits
  chipbench/metrics/<metric>.py      a reader with ``read(run)``

A new cell, configuration, mix or metric is a new file and a new entry in
``BENCHMARK.json``; no code here names one.  The query operations
(``query.py``) and the data recipe (``data.py``) hold only what the
committed cells run; a cell that needs another brings it along.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Optional[Callable] = None   # per-layer metrics only


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(name: str) -> Callable:
    """``read`` of ``chipbench/metrics/<name>.py``."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, bench: Optional[dict] = None) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics."""
    bench = bench if bench is not None else load_benchmark()
    cells: Dict[str, dict] = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    (cfg,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    config = _json(os.path.join(ROOT, cfg["file"]))
    traffic = _json(os.path.join(HERE, "traffic", f"{w['traffic']}.json"))
    e2e = [Metric(m["name"], m["unit"]) for m in bench["end_to_end"]
           if _applies(m, name)]
    layer = [Metric(m["name"], m["unit"], load_reader(m["name"]))
             for m in bench["per_layer"] if _applies(m, name)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, layer)
