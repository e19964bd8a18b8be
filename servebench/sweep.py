#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, by a sweep of fixed rates on
the chip (not a benchmark run; the cell's traffic file then fixes its
rate at about four fifths of the knee).

    python3 servebench/sweep.py --workload qwen3-4b.chat \
        --rates 0.2,0.4,0.6 --seconds 51 --seed 5 --out sweep.jsonl

One process: the cell's set-up once, then for each rate a fresh engine on
the same weights, the cell's traffic at that rate for ``--seconds``, and
one JSON line: offered and admitted requests, the queue left at the
close, time to first token (median and p90) of the first and last third
of the arrivals, the p95 token gap and the output rate. Past the knee the
queue left at the close and the late arrivals' wait grow with the rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)
from servebench import run  # noqa: E402


def one_rate(cell, s, rate: float, seconds: float, seed: int) -> dict:
    from repro.serving import Engine
    from servebench import check, drive, stats, traffic
    check.free(s.eng.pools)
    s.eng = Engine(s.cfg, s.eng.params, sched=s.sched, seed=seed)
    run.warm_up(s)
    mix = dict(cell.traffic, rate_per_s=rate, horizon_s=seconds)
    plan = traffic.make_plan(mix, seed, s.sched.max_batch, s.cfg.vocab)
    win = drive.run_window(s.eng, plan, seconds)
    due = [r for r in win.recs if r.due_t < win.t1]
    third = len(due) // 3
    ttft = stats.ttft_s(win.recs, win.t0, win.t1)
    early, late = ttft[:third], ttft[-third:] if third else []
    vals = run.e2e_metrics(cell, win)
    return {"rate": rate, "offered": len(due),
            "admitted": sum(1 for r in due if r.req is not None and
                            r.req.trace is not None and
                            r.req.trace.first("admitted") is not None),
            "queue_at_close": len(s.eng.sched.waiting),
            "ttft_p50_early_s": stats.median(early),
            "ttft_p50_late_s": stats.median(late),
            "out_tok_s": stats.tokens_in(win.recs, win.t0, win.t1) / seconds,
            **vals}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    from servebench import spec
    cell = spec.load_cell(args.workload)
    try:
        run.check_devices(cell.chips)
    except run.DeviceError as e:
        run.log(f"[sweep] {e}")
        return run.EXIT_DEVICE
    run.enable_cache()
    s = run.build(cell, args.seed, False)
    run.warm_up(s)
    for rate in [float(x) for x in args.rates.split(",")]:
        line = json.dumps(one_rate(cell, s, rate, args.seconds, args.seed))
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
