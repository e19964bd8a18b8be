#!/usr/bin/env python3
"""Run one cell of the on-chip serving benchmark.

    python3 servebench/run.py --workload qwen3-4b.decode_long --seed 7 \
        --seconds 51 --trace 0

One process holds the chip and starts no other. Set-up makes the weights
on the device from ``--seed``, builds ``repro.serving.Engine`` with the
scheduler the program would choose for the traffic's longest request
(only the slot count and the pool's size come from the configuration),
and warms every shape the traffic uses. The window then drives the engine
for ``--seconds``; with ``--trace 1`` the program's spans are on and a few
seconds in the middle are traced on the device. Afterwards the program's
state is freed and a sample of the requests it served is compared with the
plain float32 reference (``check.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` [, ``breakdown``] and,
last, ``check``: each compared number with its limit (also the last lines
of standard error). Without a TPU, with fewer chips than the cell asks
for, or on a device kind missing from ``peaks.json``, it exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Callable, Dict, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

EXIT_SPEC, EXIT_DEVICE = 2, 3
#: Fixed in-checkout compilation cache (listed in .gitignore); the
#: environment's JAX_COMPILATION_CACHE_DIR wins when it is set.
CACHE_DIR = os.path.join(ROOT, ".servebench_cache", "jax")
#: Where in the window the device trace starts (share of the window), and
#: for how long it runs.
TRACE_AT, TRACE_SECONDS = 0.5, 6.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class DeviceError(Exception):
    """No accelerator this cell can run on."""


def check_devices(chips: int, allow_cpu: bool = False):
    import jax
    from servebench import spec
    devs = jax.devices()
    if not allow_cpu:
        if devs[0].platform != "tpu":
            raise DeviceError(f"JAX finds no TPU (first device is "
                              f"{devs[0].platform}); nothing was run")
        if len(devs) < chips:
            raise DeviceError(f"the cell asks for {chips} chips, JAX sees "
                              f"{len(devs)}")
    try:
        peaks = spec.load_peaks(devs[0].device_kind)
    except spec.SpecError as e:
        if not allow_cpu:
            raise DeviceError(str(e)) from None
        peaks = spec.load_peaks("TPU v5 lite")
    return devs[:chips], peaks


def enable_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def program_config(config: Dict):
    """The program's ModelConfig for a configuration file, checked against
    the sizes its reference says it must keep (``program_sizes``)."""
    from repro.configs import registry
    from servebench import spec
    prog = config["program"]
    get = registry.reduced if prog.get("reduced") else registry.get
    cfg = get(prog["arch"], **prog.get("overrides", {}))
    want = spec.reference_module(config).program_sizes(config)
    for k, v in want.items():
        got = cfg
        for part in k.split("."):
            got = getattr(got, part)
        if got != v:
            raise ValueError(f"program config {k}={got!r}, published {v!r}")
    return cfg


def sched_config(cfg, cell):
    """The program's own scheduler for the traffic's longest request, with
    the configuration's slot count and pool size."""
    from repro.serving import paged_cache
    from repro.serving.engine import _default_sched
    from servebench import spec
    serving = cell.config["serving"]
    plan = paged_cache.plan_for(cfg)
    sched = _default_sched(cfg, serving["slots"], cell.max_len, plan, "fcfs")
    if plan.has_paged:
        work = spec.work_module(cell.config)
        page_bytes = sched.page_size * work.cache_bytes_per_token(cell.config)
        sched = dataclasses.replace(
            sched, num_pages=int(serving["pool_bytes"] // page_bytes))
    return sched


@dataclasses.dataclass
class Setup:
    cfg: object
    sched: object
    eng: object
    key: object
    spans: object
    param_bytes: int


def build(cell, seed: int, trace: bool,
          engine_hook: Optional[Callable] = None) -> Setup:
    import jax
    from repro.models import transformer as model_lib
    from repro.obs.spans import NOOP, SpanRecorder
    from repro.serving import Engine
    from servebench import spec, weights

    cfg = program_config(cell.config)
    sched = sched_config(cfg, cell)
    ref = spec.reference_module(cell.config)
    key = weights.seed_key(seed)
    shapes = jax.eval_shape(
        lambda: model_lib.init(jax.random.PRNGKey(0), cfg))
    params = weights.program_params(
        ref.weight_specs(cell.config), cfg.n_layers, shapes,
        cell.config["layout"], key, cell.config["dtype"])
    jax.block_until_ready(params)
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    spans = SpanRecorder(maxlen=1 << 18) if trace else NOOP
    eng = Engine(cfg, params, sched=sched, seed=seed & 0x7FFFFFFF,
                 spans=spans)
    if engine_hook is not None:
        engine_hook(eng)
    return Setup(cfg, sched, eng, key, spans, param_bytes)


def warm_up(s: Setup) -> None:
    """Compile and run every shape the window will use: full prefill and
    decode batches, a prefill that finishes mid-chunk, and, where slots
    hold state, the slot reset for every admission count."""
    import numpy as np
    from repro.serving import Request, paged_cache
    eng, sched = s.eng, s.sched
    rng = np.random.default_rng(0)
    reqs = [Request(uid=-1 - i, max_new=3,
                    prompt=rng.integers(0, s.cfg.vocab, sched.prefill_chunk
                                        + 1).astype(np.int32))
            for i in range(sched.max_batch)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    if eng.plan.needs_slot:
        for k in range(1, sched.max_batch + 1):
            eng.pools = paged_cache.zero_slot_rows(eng.pools,
                                                   list(range(1, k + 1)))
    import jax
    jax.block_until_ready(eng.pools)
    s.spans.clear()


def e2e_metrics(cell, win) -> Dict[str, float]:
    from servebench import stats
    t0, t1 = win.t0, win.t1
    out = {}
    names = {m["name"] for m in cell.end_to_end}
    if "out_tok_s" in names:
        out["out_tok_s"] = stats.tokens_in(win.recs, t0, t1) / (t1 - t0)
    if "ttft_p90_ms" in names:
        out["ttft_p90_ms"] = 1e3 * stats.percentile(
            stats.ttft_s(win.recs, t0, t1), 90)
    if "itl_p95_ms" in names:
        out["itl_p95_ms"] = 1e3 * stats.percentile(
            stats.token_gaps_s(win.recs, t0, t1), 95)
    return out


@dataclasses.dataclass
class RunData:
    """What a per-layer metric reader may read (``metrics/<name>.py``)."""
    cell: object
    setup: Setup
    window: object
    peaks: Dict
    profile: object


def run_cell(cell, seed: int, seconds: float, trace: bool,
             allow_cpu: bool = False,
             engine_hook: Optional[Callable] = None) -> Dict:
    """Set-up, window, check; returns the result object (not printed)."""
    import jax
    from servebench import (check, drive, profile, spec, stats, traffic,
                            weights)

    devs, peaks = check_devices(cell.chips, allow_cpu)
    cache = "off" if allow_cpu else enable_cache()
    log(f"[servebench] {cell.name} seed={seed} seconds={seconds} "
        f"trace={int(trace)} device={devs[0].device_kind} cache={cache}")
    s = build(cell, seed, trace, engine_hook)
    log(f"[servebench] sched {s.sched}; params {s.param_bytes:,} B")
    warm_up(s)
    plan = traffic.make_plan(cell.traffic, seed, s.sched.max_batch,
                             s.cfg.vocab)
    tracer = profile.WindowTracer(TRACE_AT * seconds,
                                  min(TRACE_SECONDS, 0.25 * seconds)) \
        if trace else None
    driver = drive.Driver(s.eng, plan)
    fill_s = driver.fill()
    setup_s = time.perf_counter() - _T_START
    log(f"[servebench] set-up {setup_s:.3f} s, of which {fill_s:.3f} s "
        f"prefilled the {len(plan.first)} requests running at the open"
        + (f"; KV pool {s.eng.sched.alloc.used_pages} of "
           f"{s.sched.num_pages - 1} pages in use" if driver.paged else ""))
    win = driver.window(seconds, tick=tracer.tick if tracer else None)
    if tracer is not None:
        tracer.stop()
        win.first_traced_step = tracer.first_step
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes":
              max(check.peak_bytes(d) or 0 for d in devs)}
    if driver.paged and win.steps:
        log(f"[servebench] KV pool in use over the window: "
            f"{stats.kv_used_share(win, s.sched.num_pages):.3f} % "
            f"(peak {max(st.pages_used for st in win.steps)} of "
            f"{s.sched.num_pages - 1} pages); preemptions "
            f"{s.eng.stats['preemptions']}")
    late = win.lateness_s()
    log(f"[servebench] generator lateness: max {max(late, default=0):.6f} s,"
        f" p99 {stats.percentile(late, 99) or 0:.6f} s over {len(late)} "
        "submissions")
    metrics: Dict[str, Dict] = {}
    breakdown = None
    if trace:
        prof = tracer.reduce()
        run = RunData(cell, s, win, peaks, prof)
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if prof is not None:
            device["busy_s"] = prof.busy_s
            device["window_s"] = prof.window_s
            breakdown = prof.breakdown()
    else:
        vals = e2e_metrics(cell, win)
        vals["setup_s"] = setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": vals[m["name"]], "unit": m["unit"]}
    attempted = sum(1 for r in win.recs if r.submit_t is not None)
    failed = sum(1 for r in win.recs if r.done_t is not None
                 and r.finish_reason not in ("length", "eos"))
    finished, running = win.finished(), win.running()
    storage = s.cfg.dtype
    check.free(s.eng.pools, s.eng.params)
    s.eng = None
    del s
    gc.collect()
    jax.clear_caches()          # drop the program's executables too
    samples = check.sample(finished, running, seed)
    ref = spec.reference_module(cell.config)
    t_ref = time.perf_counter()
    vals = check.readings(ref, cell.config, weights.seed_key(seed), storage,
                          samples)
    vals["short_answers"] = check.short_answers(samples)
    numbers = check.judge(vals, cell.limits)
    log(f"[servebench] reference over {vals['tokens']} served tokens of "
        f"{len(samples)} requests ({len(finished)} finished in the window): "
        f"{time.perf_counter() - t_ref:.1f} s")
    result = {"correct": check.passed(numbers) and failed == 0,
              "attempted": attempted, "failed": failed, "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = numbers
    return result


def print_result(result: Dict) -> None:
    for name, n in result["check"].items():
        log(f"[check] {name}: {n['value']!r} limit {n['limit']!r}")
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from servebench import spec
    try:
        cell = spec.load_cell(args.workload)
    except spec.SpecError as e:
        log(f"[servebench] {e}")
        return EXIT_SPEC
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except DeviceError as e:
        log(f"[servebench] {e}")
        return EXIT_DEVICE
    print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
