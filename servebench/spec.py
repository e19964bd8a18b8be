"""Where the benchmark's pieces live, and how one cell is put together.

Every piece is found by its name in ``BENCHMARK.json``, so a later change
adds files and entries without editing any that exist:

    configs/<config>.json       model sizes as published, serving sizes,
                                the ``reference`` it names and the weight
                                ``layout``
    traffic/<traffic>.json      parameters of one traffic mix
    references/<name>.py        a plain float32 reference the config names
    work/<name>.py              that reference's architecture's step work
    limits/<workload>.json      the correctness limits of one cell
    metrics/<metric>.py         the reader of one per-layer metric
    peaks.json                  the chip's published peaks by device kind

A configuration of a new architecture brings ``configs/<config>.json``,
``references/<name>.py``, ``work/<name>.py``, ``limits/<workload>.json``
and the readers of any metric it adds, and appends its entries to
``BENCHMARK.json``. What each file must export:

    references/<name>.py   program_sizes(config) -> {ModelConfig attribute
                             (dotted into nested ones): value it must hold}
                           weight_specs(config) -> [weights.WeightSpec]
                           Reference(config, key, storage, fp8=False) with
                             hidden(tokens, rows) and logits(h)
    work/<name>.py         cache_bytes_per_token(config): bytes a token
                             holds in the paged pool (paged caches only)
                           token_flops(config, keys, emits),
                           weight_bytes(config),
                           decode_least_bytes(config, rows, context): for
                             ``mfu.*`` and ``step_bw_share.*``
    metrics/<metric>.py    read(run) -> float | None

The ``layout`` maps each reference weight to the program's parameter path
that holds it: one path (its leading axis holds layers 0, 1, ...), or
``{path: first layer}`` where the program splits its layers over segments
(``weights.program_params``).
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


def work_module(config: Dict):
    """The step work of the configuration's architecture, found by the
    name of its reference."""
    name = config["reference"]
    return load_module(os.path.join(HERE, "work", name + ".py"),
                       f"servebench_work_{name}")


def metric_reader(metric_name: str):
    return load_module(os.path.join(HERE, "metrics", metric_name + ".py"),
                       "servebench_metric_" + metric_name.replace(".", "_"))

