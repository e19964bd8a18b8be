"""A reduced-width rehearsal of a whole run for each traffic kind and each
attention family: set-up, window, metrics and the reference check, on the
CPU (the look for a chip is skipped)."""
import math

import pytest

from servebench import run

SEED = 2 ** 32 + 17          # wider than 32 bits, as seeds may be
# the benchmark configuration whose metrics each fixture reports
LIKE = {"tiny-qwen3": "qwen3-4b", "tiny-qwen3-srf": "qwen3-4b-srf"}


@pytest.mark.parametrize("config", ["tiny-qwen3", "tiny-qwen3-srf"])
@pytest.mark.parametrize("mix", ["decode_long", "chat"])
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal(cell_factory, config, mix, trace):
    cell = cell_factory(config, mix, f"{LIKE[config]}.{mix}")
    res = run.run_cell(cell, SEED, 2.0, bool(trace), allow_cpu=True)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "check"
    assert set(res["check"]) == {"logit_gap", "short_answers"}
    names = {m["name"] for m in (cell.per_layer if trace else cell.end_to_end)}
    got = set(res["metrics"])
    if trace:
        # no device trace on the CPU: only the span and clock readers read
        assert got <= names and (got or mix == "chat")
    else:
        assert got == names
        assert all(math.isfinite(v["value"]) and v["value"] > 0
                   for v in res["metrics"].values())


@pytest.mark.parametrize("config", ["tiny-qwen3", "tiny-qwen3-srf"])
def test_fill_opens_the_window_with_every_client_prefilled(cell_factory,
                                                          config):
    from servebench import drive, stats, traffic
    cell = cell_factory(config, "decode_long",
                        f"{LIKE[config]}.decode_long")
    s = run.build(cell, SEED, False)
    run.warm_up(s)
    plan = traffic.make_plan(cell.traffic, SEED, s.sched.max_batch,
                             s.cfg.vocab)
    d = drive.Driver(s.eng, plan)
    d.fill()
    firsts = d.recs[:len(plan.first)]
    assert len(firsts) == s.sched.max_batch and d.steps == []
    assert all(r.token_t for r in firsts)
    assert max(len(r.prompt) for r in firsts) > cell.traffic["prompt"]["max"]
    win = d.window(1.0)
    assert win.steps and all(st.t0 >= win.t0 for st in win.steps)
    # tokens of the fill are not the window's
    assert stats.tokens_in(win.recs, win.t0, win.t1) == sum(
        1 for r in win.recs for t in r.token_t if win.t0 <= t <= win.t1)
    assert all(t < win.t0 for r in firsts for t in r.token_t[:1])
    if d.paged:
        assert 0 < stats.kv_used_share(win, s.sched.num_pages) <= 100
