"""Find a cell's files by the names in ``BENCHMARK.json``.

* ``perfbench/configs/<config>.json``: the model configuration as run; its
  ``arch`` names the module pair ``perfbench/arch/<arch>.py`` (weights,
  plain reference, counts) and ``<arch>_serve.py`` (the program's side);
* ``perfbench/traffic/<traffic>.json``: the traffic mix (:mod:`.traffic`);
* ``perfbench/cells/<workload>.json``: the serving deployment of the cell
  (lanes, context, page size, rate) and its correctness limits;
* ``perfbench/metrics/<metric>.py``: one reader per metric.

A later cell, mix or metric is new files and new entries, with no edit to
any of these.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(*parts) -> Dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def benchmark() -> Dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    cell: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]

    def arch(self):
        return importlib.import_module(f"perfbench.arch.{self.config['arch']}")

    def serve(self):
        return importlib.import_module(
            f"perfbench.arch.{self.config['arch']}_serve")


def _applies(metric: Dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def cell(workload: str, bench: Optional[Dict] = None) -> Cell:
    bench = bench or benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config=_load("configs", entry["config"] + ".json"),
        traffic=_load("traffic", entry["traffic"] + ".json"),
        cell=_load("cells", workload + ".json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
    )


def reader(metric: str):
    """The ``read(run)`` function of ``perfbench/metrics/<metric>.py``."""
    path = os.path.join(HERE, "metrics", metric + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
