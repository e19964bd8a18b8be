"""Tail and rate arithmetic shared by the end-to-end and per-layer metrics.

A percentile here is the nearest rank over every sample, with missing
samples counted as infinitely late: p90 of n samples is the
ceil(0.9 n)-th smallest.
"""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence

INF = float("inf")


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    v = sorted(values)
    if not v:
        return None
    k = max(1, math.ceil(q / 100.0 * len(v)))
    return v[k - 1]


def median(values: Iterable[float]) -> Optional[float]:
    v = sorted(values)
    if not v:
        return None
    n = len(v)
    return v[n // 2] if n % 2 else 0.5 * (v[n // 2 - 1] + v[n // 2])


def spread(values: Sequence[float]) -> float:
    """Inter-quartile distance as a share of the median, with the
    quartiles of ``statistics.quantiles(values, n=4)``."""
    import statistics
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def ttft_s(requests, t0: float, t1: float) -> List[float]:
    """Time to first token of every request due in [t0, t1), from when it
    was due; one with no first token by t1 is missing (infinite)."""
    out = []
    for r in requests:
        if not t0 <= r.due_t < t1:
            continue
        f = r.token_t[0] if r.token_t else None
        out.append(f - r.due_t if f is not None and f <= t1 else INF)
    return out


def token_gaps_s(requests, t0: float, t1: float) -> List[float]:
    """Every gap between consecutive output tokens of one request, both
    emitted inside [t0, t1]. A request still running at t1 adds the wait
    for its next token so far, a lower bound of that gap, so a decode that
    starves shows."""
    out = []
    for r in requests:
        ts = [t for t in r.token_t if t0 <= t <= t1]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
        if ts and (r.done_t is None or r.done_t > t1):
            out.append(t1 - ts[-1])
    return out


def tokens_in(requests, t0: float, t1: float) -> int:
    return sum(1 for r in requests for t in r.token_t if t0 <= t <= t1)


def kv_used_share(window, num_pages: int) -> float:
    """Pages of the paged KV pool held, as a % of the pages it reserves
    (page 0 is the allocator's null page), averaged over the window's
    steps weighted by their time."""
    steps = [s for s in window.steps if s.t0 >= window.t0]
    busy = sum(s.t1 - s.t0 for s in steps)
    held = sum(s.pages_used * (s.t1 - s.t0) for s in steps)
    return 100.0 * held / busy / (num_pages - 1)
