"""Find a cell's parts by the names in ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration, whose file
``BENCHMARK.json`` gives, and a traffic mix, read from
``chipbench/traffic/<traffic>.json``.  Its end-to-end metrics are those
whose ``workloads`` list names it (or that list none); its per-layer
metrics likewise, each read by ``chipbench/metrics/<metric>.py``.  Adding
a cell, a mix or a metric is adding files and entries, never editing one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

from harness import env

BENCHMARK = os.path.join(env.ROOT, "BENCHMARK.json")
TRAFFIC_DIR = os.path.join(env.BENCH_DIR, "traffic")
METRICS_DIR = os.path.join(env.BENCH_DIR, "metrics")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list   # metric entries of BENCHMARK.json
    per_layer: list

    @property
    def kind(self) -> str:
        return self.config["kind"]


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in reported if "moves" in metric else True


def load_cell(name: str, benchmark: str = BENCHMARK) -> Cell:
    with open(benchmark) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {benchmark}; "
                       f"have {sorted(cells)}")
    w = cells[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    with open(os.path.join(env.ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(TRAFFIC_DIR, w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, set())]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, reported)]
    return Cell(name, int(w["chips"]), w["config"], config, w["traffic"],
                traffic, e2e, per_layer)


def reader(metric: str):
    """The ``read(run)`` function of ``chipbench/metrics/<metric>.py``."""
    path = os.path.join(METRICS_DIR, metric + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read
