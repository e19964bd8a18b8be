"""Shared pieces of the servebench CPU tests: cells at reduced widths."""
import json
import os

import pytest

from servebench import spec

FIXTURES = os.path.join(spec.HERE, "fixtures")


def tiny_cell(config: str, mix: str, like: str, limits=None) -> spec.Cell:
    """A cell of a fixture configuration (registry.reduced widths) under a
    traffic mix cut to a few tokens, reporting the metrics of the cell
    ``like`` of ``BENCHMARK.json`` (metrics that name no cells included;
    the open loop adds its two tails)."""
    with open(os.path.join(FIXTURES, config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(spec.HERE, "traffic", mix + ".json")) as f:
        t = json.load(f)
    t = dict(t, prompt=dict(t["prompt"], median=24, min=4, max=40),
             output=dict(t["output"], median=12, min=4, max=24))
    if t["loop"] == "open":
        t.update(rate_per_s=4.0, horizon_s=5)
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    pick = lambda ms: [m for m in ms  # noqa: E731
                       if like in m.get("workloads", [like])]
    e2e = pick(bench["end_to_end"])
    if t["loop"] == "open":     # the open loop's tails (``sweep.py``)
        e2e += [{"name": "ttft_p90_ms", "unit": "ms"},
                {"name": "itl_p95_ms", "unit": "ms"}]
    return spec.Cell(name=f"{config}.{mix}", chips=1, config=cfg, traffic=t,
                     limits=limits or {"logit_gap": 0.03, "short_answers": 0},
                     end_to_end=e2e, per_layer=pick(bench["per_layer"]))


@pytest.fixture
def cell_factory():
    return tiny_cell
