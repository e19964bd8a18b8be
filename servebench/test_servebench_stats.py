"""Tail and rate arithmetic of the end-to-end metrics, on requests built by
hand; a stall injected into the token times must move every metric."""
import math

import numpy as np

from servebench import run, spec, stats
from servebench.drive import Rec, StepRec, Window


def test_percentile_nearest_rank_and_missing():
    assert stats.percentile([5, 1, 4, 2, 3], 90) == 5
    assert stats.percentile(list(range(1, 11)), 90) == 9
    assert stats.percentile([1.0, stats.INF], 50) == 1.0
    assert math.isinf(stats.percentile([1.0, stats.INF], 90))
    assert stats.percentile([], 90) is None
    assert stats.median([3, 1, 2, 4]) == 2.5


def test_spread_is_iqr_over_median():
    v = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q = __import__("statistics").quantiles(v, n=4)
    assert stats.spread(v) == (q[2] - q[0]) / q[1]


def _recs(stalls=(), stall=0.0):
    """Ten requests due every 0.5 s, each getting a first token 0.2 s after
    it is due and then one token every 0.05 s for 20 tokens; every token
    after each time in ``stalls`` comes ``stall`` seconds later."""
    out = []
    for i in range(10):
        due = 0.5 * i
        ts = [due + 0.2 + 0.05 * k for k in range(20)]
        for at in stalls:
            ts = [t + stall if t >= at else t for t in ts]
        r = Rec(i, np.zeros(4, np.int32), 20, due)
        r.submit_t, r.token_t, r.done_t = due, ts, ts[-1]
        out.append(r)
    return out


def _cell():
    return spec.Cell("x", 1, {}, {}, {}, [
        {"name": "out_tok_s"}, {"name": "ttft_p90_ms"},
        {"name": "itl_p95_ms"}], [])


def test_e2e_metrics_by_hand():
    m = run.e2e_metrics(_cell(), Window(0.0, 10.0, _recs(), []))
    assert m["out_tok_s"] == 200 / 10.0
    assert abs(m["ttft_p90_ms"] - 200.0) < 1e-6
    assert abs(m["itl_p95_ms"] - 50.0) < 1e-6


def test_stall_moves_every_end_to_end_metric():
    stalls = [0.75 + 0.5 * k for k in range(9)]    # a prefill burst every 0.5 s
    base = run.e2e_metrics(_cell(), Window(0.0, 6.0, _recs(), []))
    hit = run.e2e_metrics(_cell(), Window(0.0, 6.0, _recs(stalls, 0.3), []))
    assert hit["out_tok_s"] < base["out_tok_s"]
    assert hit["ttft_p90_ms"] > base["ttft_p90_ms"]
    assert hit["itl_p95_ms"] > base["itl_p95_ms"]


def test_missing_first_token_counts_its_wait_so_far():
    # a request with no first token by the close is missing: infinitely
    # late, never its wait so far
    recs = _recs()
    recs[-1].token_t = []                     # due at 4.5, never served
    m = run.e2e_metrics(_cell(), Window(0.0, 5.0, recs, []))
    assert abs(m["ttft_p90_ms"] - 1e3 * 0.2) < 1e-6
    recs[-2].token_t = []                     # due at 4.0, never served
    m = run.e2e_metrics(_cell(), Window(0.0, 5.0, recs, []))
    assert math.isinf(m["ttft_p90_ms"])
    assert m["ttft_p90_ms"] == 1e3 * stats.percentile(
        stats.ttft_s(recs, 0.0, 5.0), 90)


def test_a_starved_request_adds_its_wait_so_far():
    recs = _recs()
    recs[0].token_t, recs[0].done_t = [0.2], None   # never decodes again
    gaps = stats.token_gaps_s(recs, 0.0, 10.0)
    assert max(gaps) == 10.0 - 0.2
    m = run.e2e_metrics(_cell(), Window(0.0, 10.0, recs[:1], []))
    assert m["itl_p95_ms"] == 1e3 * (10.0 - 0.2)


def test_kv_pool_in_use_is_time_weighted():
    steps = [StepRec(0.0, 1.0, "decode", 4, 0, 100),
             StepRec(1.0, 4.0, "prefill", 4, 0, 200),
             StepRec(-2.0, -1.0, "prefill", 4, 0, 900)]   # before the window
    w = Window(0.0, 5.0, [], steps)
    assert abs(stats.kv_used_share(w, 401) - 100.0 * 175 / 400) < 1e-9


def test_tokens_outside_the_window_do_not_count():
    recs = _recs()
    assert stats.tokens_in(recs, 0.0, 1.0) == sum(
        1 for r in recs for t in r.token_t if t <= 1.0)
    gaps = stats.token_gaps_s(recs, 0.0, 1.0)
    # whole gaps of 0.05 s, and the waits of the two requests still
    # decoding at the close, which are shorter
    assert sum(1 for g in gaps if abs(g - 0.05) < 1e-9) >= len(gaps) - 2
    assert all(g <= 0.05 + 1e-9 for g in gaps)
