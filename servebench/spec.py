"""Where the benchmark's pieces live, and how one cell is put together.

Every piece is found by its name in ``BENCHMARK.json``, so a later change
adds files and entries without editing any that exist:

    configs/<config>.json       model sizes as published, serving sizes
    traffic/<traffic>.json      parameters of one traffic mix
    references/<name>.py        a plain float32 reference the config names
    limits/<workload>.json      the correctness limits of one cell
    metrics/<metric>.py         the reader of one per-layer metric
    peaks.json                  the chip's published peaks by device kind
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class SpecError(Exception):
    """The benchmark's files do not describe a runnable cell."""


def _load_json(path: str) -> Dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}") from None


def load_module(path: str, name: str):
    """Import one file of the benchmark by its path (names may hold dots)."""
    if not os.path.exists(path):
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]     # the end-to-end metrics this cell reports
    per_layer: List[Dict]      # the per-layer metrics this cell reports

    @property
    def max_len(self) -> int:
        """Longest prompt plus longest output of the traffic mix."""
        return int(self.traffic["prompt"]["max"] + self.traffic["output"]["max"])


def _applies(metric: Dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json; "
                        f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    centry = configs[w["config"]]
    config = _load_json(os.path.join(root, centry["file"]))
    traffic = _load_json(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    limits = _load_json(os.path.join(HERE, "limits", workload + ".json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    per = [m for m in bench["per_layer"] if _applies(m, workload)]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per)


def load_peaks(device_kind: str) -> Dict:
    """Published peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    table = _load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in table:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        f"servebench/peaks.json ({sorted(table)})")
    return table[device_kind]


def reference_module(config: Dict):
    name = config["reference"]
    return load_module(os.path.join(HERE, "references", name + ".py"),
                       f"servebench_reference_{name}")


def metric_reader(metric_name: str):
    return load_module(os.path.join(HERE, "metrics", metric_name + ".py"),
                       "servebench_metric_" + metric_name.replace(".", "_"))

