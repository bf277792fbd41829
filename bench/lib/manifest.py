"""The benchmark's data, found by name: the manifest ``BENCHMARK.json``, a
cell's configuration, traffic mix and limits, and each metric's reader.

- configuration ``<c>``: the file the manifest names (``bench/configs``);
- traffic mix ``<t>``: ``bench/traffic/<t>.json``;
- limits of cell ``<w>``: ``bench/limits/<w>.json``;
- metric ``<m>``: ``bench/metrics/<m>.py``, whose ``read(run)`` returns the
  number, or None where the run holds nothing to read.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic: str
    mix: dict
    limits: dict
    end_to_end: list          # manifest entries of the cell's metrics
    per_layer: list


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: str = ROOT) -> Cell:
    m = manifest(root)
    work = {w["name"]: w for w in m["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"there are {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in m["configs"]}[w["config"]]
    return Cell(
        name=name, chips=w["chips"], config_name=conf["name"],
        config=_json(os.path.join(root, conf["file"])),
        traffic=w["traffic"],
        mix=_json(os.path.join(root, "bench", "traffic",
                               w["traffic"] + ".json")),
        limits=_json(os.path.join(root, "bench", "limits", name + ".json")),
        end_to_end=[e for e in m["end_to_end"] if _applies(e, name)],
        per_layer=[e for e in m["per_layer"] if _applies(e, name)])


def reader(metric: str, root: str = ROOT):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
