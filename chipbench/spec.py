"""Resolve a cell of ``BENCHMARK.json`` to the files that define it.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by name:

  chipbench/configs/<config>.json    the deployment: scale, data, chips,
                                     and the ``recipe`` that makes its data
  chipbench/traffic/<traffic>.json   the job: ``query`` (its ``module``
                                     and that module's spec), limits
  chipbench/recipes/<recipe>.py      ``make_tables(config, seed, chips,
                                     tables)`` -> {table: {column: ndarray}},
                                     the ``tables`` named and no others
  chipbench/queries/<module>.py      ``tables_read(spec)``,
                                     ``build_frame(frames, spec, config)``,
                                     ``reference(tables, spec[, dtype])``,
                                     ``compare(got, ref)`` -> {reading: value}
  chipbench/metrics/<metric>.py      a reader with ``read(run)``

A new cell, configuration, data recipe, query, mix or metric is a new file
and a new entry in ``BENCHMARK.json``; no code here or in ``bench.py``
names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import types
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
    recipe: types.ModuleType          # chipbench/recipes/<config's recipe>
    query: types.ModuleType           # chipbench/queries/<query's module>


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, root: str = ROOT) -> types.ModuleType:
    """``chipbench/<kind>/<name>.py`` under ``root``, loaded from its file."""
    path = os.path.join(root, os.path.basename(HERE), kind, f"{name}.py")
    mod_name = f"chipbench_{kind}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, root: str = ROOT) -> Callable:
    """``read`` of ``chipbench/metrics/<name>.py``."""
    return load_module("metrics", name, root).read


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``root``'s ``BENCHMARK.json`` with its
    configuration, traffic, data recipe, query module and metrics."""
    bench = load_benchmark(root)
    cells: Dict[str, dict] = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    (cfg,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    config = _json(os.path.join(root, cfg["file"]))
    traffic = _json(os.path.join(root, os.path.basename(HERE), "traffic",
                                 f"{w['traffic']}.json"))
    e2e = [Metric(m["name"], m["unit"]) for m in bench["end_to_end"]
           if _applies(m, name)]
    layer = [Metric(m["name"], m["unit"], load_reader(m["name"], root))
             for m in bench["per_layer"] if _applies(m, name)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, layer,
                recipe=load_module("recipes", config["recipe"], root),
                query=load_module("queries", traffic["query"]["module"],
                                  root))
