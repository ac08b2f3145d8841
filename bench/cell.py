"""Find everything a cell needs by the names in ``BENCHMARK.json``.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix. Each lives in a file of its own:

- the configuration: the ``file`` its ``configs`` entry gives;
- the traffic mix: ``bench/traffic/<traffic>.json``;
- the limits of the numbers ``correct`` compares:
  ``bench/limits/<cell>.json``;
- each per-layer metric: ``bench/metrics/<metric>.py``, a module with
  ``read(run) -> float | None``;
- the configuration's plain reference: ``bench/reference/<reference>.py``.

A later cell or metric is added as files and entries; nothing here names
one.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: tuple
    per_layer: tuple


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(bench: dict, name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` with its files read. Raises KeyError for a name
    the benchmark does not hold and OSError for a missing file."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = configs[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=_read_json(os.path.join(root, conf["file"])),
        traffic_name=w["traffic"],
        traffic=_read_json(os.path.join(root, "bench", "traffic",
                                        w["traffic"] + ".json")),
        limits=_read_json(os.path.join(root, "bench", "limits",
                                       name + ".json")),
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if applies(m, name)))


def _module(path: str):
    spec = importlib.util.spec_from_file_location(
        "bench_file_" + os.path.relpath(path, ROOT).replace(os.sep, "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: str = ROOT):
    """``read`` of ``bench/metrics/<name>.py``."""
    return _module(os.path.join(root, "bench", "metrics", name + ".py")).read


def reference_module(config: dict):
    """The configuration's reference model, ``bench/reference/<name>.py``."""
    return importlib.import_module(f"bench.reference.{config['reference']}")
