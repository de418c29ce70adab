"""Find every piece of a cell by its name.

``BENCHMARK.json`` at the checkout's root names the cells, their
configurations and traffic mixes, and the metrics.  Each piece sits in a
file of its own under ``benchmark/``:

- a configuration at the ``file`` its entry gives;
- a traffic mix at ``traffic/<traffic>.json``;
- how to build a family's index at ``families/<family>.py``;
- the work of a family's request at ``work/<family>.py``;
- a per-layer metric's reader at ``layer_metrics/<metric>.py``;
- the chip's peaks in ``peaks.json``.

Adding a configuration, a mix, a family or a metric is adding files.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclasses.dataclass
class Cell:
    root: str
    workload: dict
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def name(self) -> str:
        return self.workload["name"]

    def module(self, kind: str, name: str):
        return load_module(self.root, kind, name)


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, workload: str, e2e_names) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(workload: str, root: str = ROOT, bench: dict = None) -> Cell:
    """The cell ``workload`` of ``bench`` (by default the checkout's
    ``BENCHMARK.json``), with every piece it names read from ``root``."""
    if bench is None:
        bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    found = [w for w in bench["workloads"] if w["name"] == workload]
    if not found:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = found[0]
    conf = [c for c in bench["configs"] if c["name"] == w["config"]][0]
    config = _read_json(os.path.join(root, conf["file"]))
    traffic = _read_json(os.path.join(root, "benchmark", "traffic",
                                      w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, workload, names)]
    return Cell(root, w, config, traffic, e2e, per_layer)


def load_module(root: str, kind: str, name: str):
    """The module at ``benchmark/<kind>/<name>.py``; names may hold dots."""
    path = os.path.join(root, "benchmark", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str, root: str = ROOT) -> dict:
    table = _read_json(os.path.join(root, "benchmark", "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmark/peaks.json")
    return table[device_kind]
