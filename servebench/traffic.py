"""One general generator for every traffic mix.

A mix is a data file (``traffic/<name>.json``) of parameters:

    loop            "closed": one client per engine slot, each sends its next
                    request when the last one finished; "open": requests are
                    due on a fixed schedule whether or not earlier ones are done
    prompt, output  {"median", "sigma", "min", "max"}: lognormal lengths,
                    rounded and clipped
    sizes_seed      fixes every length and arrival time; ``--seed`` draws
                    the token ids (and, elsewhere, the weights), so every
                    seed offers the same work in the same order and runs
                    differ by noise, not by load
    first_output    "residual" (closed loop): each client's first request
                    is caught mid-flight: its prompt holds the request's own
                    prompt and the output it has already emitted (set-up
                    prefills it, so the window opens at steady state), and
                    it emits only the rest, so completions are staggered
    requests_per_client   (closed loop) requests queued per client
    rate_per_s      (open loop) mean arrival rate of a Poisson process
    horizon_s       (open loop) seconds of arrivals generated

All decoding is greedy and no prompt shares a prefix with another.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np


@dataclass
class Planned:
    index: int
    prompt: np.ndarray        # (P,) int32 token ids
    max_new: int
    due: float                # open loop: seconds after the window opens


@dataclass
class Plan:
    loop: str
    first: List[Planned]      # closed loop: one per client, in client order,
    #                           prefilled before the window opens
    queue: List[Planned]      # closed loop: later requests, taken in order;
    #                           open loop: every request, by due time


def lognormal_lengths(rng: np.random.Generator, dist: Dict, n: int
                      ) -> np.ndarray:
    z = rng.standard_normal(n)
    x = np.exp(np.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def _residual(rng: np.random.Generator, dist: Dict, n: int
              ) -> Tuple[np.ndarray, np.ndarray]:
    """Requests caught mid-flight: an output length drawn with probability
    proportional to itself (a long request is running more of the time),
    split at a uniform point. Returns (emitted so far, left to emit)."""
    pool = lognormal_lengths(rng, dist, 64 * n).astype(np.float64)
    full = rng.choice(pool, size=n, p=pool / pool.sum()).astype(np.int64)
    left = np.maximum(1, np.ceil(full * rng.uniform(size=n))).astype(np.int64)
    return full - left, left


def make_plan(traffic: Dict, seed: int, clients: int, vocab: int) -> Plan:
    base = np.random.default_rng(int(traffic["sizes_seed"]))
    toks = np.random.default_rng([int(seed), 0x70C])

    def request(i: int, plen: int, out: int, due: float) -> Planned:
        prompt = toks.integers(0, vocab, int(plen), dtype=np.int64)
        return Planned(i, prompt.astype(np.int32), int(out), float(due))

    if traffic["loop"] == "closed":
        n = clients * int(traffic["requests_per_client"])
        p_first = lognormal_lengths(base, traffic["prompt"], clients)
        if traffic.get("first_output") == "residual":
            emitted, o_first = _residual(base, traffic["output"], clients)
            p_first = p_first + emitted
        else:
            o_first = lognormal_lengths(base, traffic["output"], clients)
        p = lognormal_lengths(base, traffic["prompt"], n)
        o = lognormal_lengths(base, traffic["output"], n)
        first = [request(c, p_first[c], o_first[c], 0.0)
                 for c in range(clients)]
        queue = [request(clients + i, p[i], o[i], 0.0) for i in range(n)]
        return Plan("closed", first, queue)
    if traffic["loop"] == "open":
        rate = float(traffic["rate_per_s"])
        n = int(np.ceil(rate * float(traffic["horizon_s"])))
        gaps = base.exponential(1.0 / rate, n)
        p = lognormal_lengths(base, traffic["prompt"], n)
        o = lognormal_lengths(base, traffic["output"], n)
        due = np.cumsum(gaps)
        queue = [request(i, p[i], o[i], due[i]) for i in range(n)]
        return Plan("open", [], queue)
    raise ValueError(f"unknown loop {traffic['loop']!r}")
