#!/usr/bin/env python3
"""Readings that the correctness limits are set from (not a benchmark run).

    python3 servebench/calibrate.py --workload qwen3-4b.decode_long \
        --seeds 11,12,13 --control-seeds 11,12,13 --seconds 51 \
        --out calib.jsonl

In one process, for each seed: the cell's own set-up, window and sample,
then the widest logit gap of the served tokens against the float32
reference (the program's reading) and, for the control seeds, the widest
gap of the tokens that the float8 reference puts first (the control's
reading). ``--int8-kv`` serves the window with the program's own int8 KV
cache instead, as a further lower-precision control. ``--witness`` (SRF
cells) also reads the served tokens against each of the reference's two
bfloat16 witnesses (its SRF state stored in bfloat16 where the program
stores it; its SRF features in bfloat16), and the gap of the tokens that
each witness puts first. ``--fault d0_block`` serves the
window with a fault planted in the program: the signs of the first quarter
of the spinner's D0 diagonal flipped in every layer and head. One JSON line
per seed goes to standard output and to ``--out``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import jax
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)
from servebench import run  # noqa: E402


def flip_d0_block(cell):
    """An engine hook that negates the first quarter of the spinner's D0
    signs in the program's parameters (every layer and kv head)."""
    from servebench import weights
    path = cell.config["layout"]["srf_d0"]

    def hook(eng):
        def f(p, x):
            if weights._path_str(p) != path:
                return x
            q = x.shape[-1] // 4
            return x.at[..., :q].multiply(-1)
        eng.params = jax.tree_util.tree_map_with_path(f, eng.params)
    return hook


def witness_readings(ref_mod, config, key, storage, samples, chunk: int
                     ) -> dict:
    """For each SRF witness (the reference with its state stored in
    bfloat16 after every prefill chunk of ``chunk`` tokens from position 0
    and every decoded token; the reference with bfloat16 features): the
    served tokens' widest gap against it, and the widest gap, under the
    float32 reference, of the tokens that it puts first."""
    import jax.numpy as jnp
    from servebench import check
    tokens, rows, served = check._batch(samples)
    stored = np.zeros(tokens.shape, bool)
    for i, r in enumerate(samples):
        p = len(r.prompt)
        stored[i, min(chunk, p) - 1:p:chunk] = True
        stored[i, p - 1:] = True
    f32 = ref_mod.Reference(config, key, storage)
    h = f32.hidden(tokens, rows)
    out = {}
    for name, kw in (("state", {"state_dtype": jnp.bfloat16}),
                     ("features", {"feature_dtype": jnp.bfloat16})):
        wit = ref_mod.Reference(config, key, storage, **kw)
        hw = wit.hidden(tokens, rows, stored if name == "state" else None)
        gap = pick_gap = 0.0
        for c in range(0, len(rows), check.ROW_CHUNK):
            lf, lw = f32.logits(h[c:c + check.ROW_CHUNK]), \
                wit.logits(hw[c:c + check.ROW_CHUNK])
            srv = jnp.asarray(served[c:c + check.ROW_CHUNK])
            g, _ = check._gaps(lw, srv, srv)
            _, gp = check._gaps(lf, srv, jnp.argmax(lw, -1).astype(jnp.int32))
            gap, pick_gap = max(gap, float(jnp.max(g))), \
                max(pick_gap, float(jnp.max(gp)))
        out[f"witness_{name}_gap"] = gap
        out[f"witness_{name}_pick_gap"] = pick_gap
    return out


def readings_for_seed(cell, seed: int, seconds: float, control: bool,
                      int8_kv: bool, witness: bool = False,
                      fault: str = "") -> dict:
    from servebench import check, drive, spec, traffic, weights

    t0 = time.perf_counter()
    hook = flip_d0_block(cell) if fault == "d0_block" else None
    s = run.build(cell, seed, False, engine_hook=hook)
    if int8_kv:
        from repro.serving import Engine, paged_cache
        params = s.eng.params
        check.free(s.eng.pools)
        s.eng = Engine(s.cfg, params, sched=s.sched, seed=seed & 0x7FFFFFFF,
                       paged=paged_cache.PagedConfig(quantize_kv=True))
    run.warm_up(s)
    plan = traffic.make_plan(cell.traffic, seed, s.sched.max_batch,
                             s.cfg.vocab)
    driver = drive.Driver(s.eng, plan)
    fill = driver.fill()
    setup = time.perf_counter() - t0
    win = driver.window(seconds)
    vals = run.e2e_metrics(cell, win)
    finished, running = win.finished(), win.running()
    storage, chunk = s.cfg.dtype, s.sched.prefill_chunk
    check.free(s.eng.pools, s.eng.params)
    s.eng = None
    del s
    gc.collect()
    jax.clear_caches()          # drop the program's executables too
    samples = check.sample(finished, running, seed)
    t1 = time.perf_counter()
    ref = spec.reference_module(cell.config)
    r = check.readings(ref, cell.config, weights.seed_key(seed), storage,
                       samples, control)
    if witness:
        r.update(witness_readings(ref, cell.config, weights.seed_key(seed),
                                  storage, samples, chunk))
    out = {"workload": cell.name, "seed": seed, "int8_kv": int8_kv,
           "fault": fault, "setup_s": setup, "fill_s": fill,
           "finished": len(finished), "sampled": len(samples),
           "sampled_context_max": max((len(x.prompt) + len(x.tokens)
                                       for x in samples), default=0),
           "short_answers": check.short_answers(samples),
           "reference_s": time.perf_counter() - t1}
    out.update(r)
    out.update(vals)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--int8-kv", action="store_true")
    ap.add_argument("--witness", action="store_true")
    ap.add_argument("--fault", choices=("", "d0_block"), default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    from servebench import spec
    cell = spec.load_cell(args.workload)
    try:
        run.check_devices(cell.chips)
    except run.DeviceError as e:
        run.log(f"[calibrate] {e}")
        return run.EXIT_DEVICE
    run.enable_cache()
    ctl = {int(x) for x in args.control_seeds.split(",") if x}
    for seed in [int(x) for x in args.seeds.split(",")]:
        line = json.dumps(readings_for_seed(
            cell, seed, args.seconds, seed in ctl, args.int8_kv,
            args.witness, args.fault))
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
