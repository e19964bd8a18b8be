"""The one traffic generator: the same seed gives the same inputs, every
seed the same sizes and arrivals, and lengths stay inside their clips."""
import json
import os

import numpy as np
import pytest

from servebench import spec, traffic

MIXES = ["decode_long", "chat"]


def _mix(name):
    with open(os.path.join(spec.HERE, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_inputs(name):
    a = traffic.make_plan(_mix(name), 2 ** 33 + 5, 8, 151936)
    b = traffic.make_plan(_mix(name), 2 ** 33 + 5, 8, 151936)
    for x, y in zip(a.first + a.queue, b.first + b.queue):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.due) == (y.max_new, y.due)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_offers_the_same_work(name):
    a = traffic.make_plan(_mix(name), 1, 8, 151936)
    b = traffic.make_plan(_mix(name), 2, 8, 151936)
    sizes = lambda p: [(len(r.prompt), r.max_new, r.due)  # noqa: E731
                       for r in p.first + p.queue]
    assert sizes(a) == sizes(b)
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a.queue, b.queue))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_are_clipped(name):
    mix = _mix(name)
    plan = traffic.make_plan(mix, 7, 32, 1000)
    for r in plan.queue:
        assert mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= r.max_new <= mix["output"]["max"]
    longest = mix["prompt"]["max"] + mix["output"]["max"]
    for r in plan.first + plan.queue:
        assert 0 <= r.prompt.min() and r.prompt.max() < 1000
        assert len(r.prompt) + r.max_new <= longest


def test_closed_loop_first_requests_are_residual():
    mix = _mix("decode_long")
    plan = traffic.make_plan(mix, 3, 32, 1000)
    assert plan.loop == "closed" and len(plan.first) == 32
    firsts = [r.max_new for r in plan.first]
    assert all(1 <= o <= mix["output"]["max"] for o in firsts)
    assert min(firsts) < mix["output"]["min"]     # caught mid-flight
    # the prompt holds what was already emitted: the window opens with
    # contexts of the size a running deployment holds
    ctx = [len(r.prompt) for r in plan.first]
    assert max(ctx) > mix["prompt"]["max"]
    assert np.mean(ctx) > 2 * mix["prompt"]["median"]
    assert len(plan.queue) == 32 * mix["requests_per_client"]


def test_open_loop_arrivals_follow_the_rate():
    mix = dict(_mix("chat"), rate_per_s=2.0, horizon_s=500)
    plan = traffic.make_plan(mix, 3, 8, 1000)
    due = [r.due for r in plan.queue]
    assert due == sorted(due) and len(due) == 1000
    assert abs(due[-1] / len(due) - 0.5) < 0.05
