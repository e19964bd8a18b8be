#!/usr/bin/env python3
"""Chip smoke test: serve qwen3-4b at its published widths on a TPU.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # router over 2 x TP=2 replicas vs one chip

One chip runs these phases, in this order, in one process:

  device   stop unless JAX's first device is a TPU
  kernels  every Pallas kernel of the serving path, compiled for the chip,
           against its jnp reference (``use_pallas=False``) on the same
           chip; and the lowered serving steps (kv, int8 kv, srf, seeded
           srf) must each hold a ``tpu_custom_call`` for its kernels
  kv       ``Engine`` with full attention serves 8 greedy requests (prompts
           of 64-256 tokens, 16 new tokens each); the first generated
           token's logits are compared with ``transformer.forward``
  srf      the same with the paper's SRF attention (``attn_impl="srf"``),
           on the same parameters once the kv engine and its pools are freed

``--chips 4`` runs only the cross-chip path: the same requests on one chip
(``mesh=None``), then through ``Router`` over ``make_serving_meshes(2, 2)``,
for kv and srf; greedy tokens must be bit-identical.

Parameters are random (``--seed``). Compile and wall seconds are printed as
smoke timings: one cold run, compilation included — not benchmark numbers.
The last stdout line is one JSON object, ``{"ok": true, "device": ...}``;
any failed phase raises, so a failing run exits non-zero with no such line.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import re
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "qwen3-4b"
N_REQUESTS = 8
MAX_NEW = 16
PROMPT_LENS = (64, 256)
# Bounds of the kernel phase, set from the readings of one v5e run
# (seed 0). The spinner runs bf16 in and out against its jnp reference in
# bf16; its exp output is compared in log space (the pre-activation,
# |y| < ~20), where the readings were 5.3e-2 (materialized) and 4.8e-2
# (seeded); a wrong tile is off by O(1). srf_decode runs f32 against a
# reference at full f32 matmul precision: the state update is elementwise
# (reading 0), and the two readouts agree to ~1e-7 (CPU) where a bf16
# state or readout pass is off by ~4e-4 (the chip's reading against a
# reference left at the default one-pass bf16 precision). The gathers copy,
# or dequantize with the same f32 multiply and bf16 cast on both sides:
# readings 0, bounds 0.
KERNEL_BOUNDS = {"spinner_project": 0.15, "spinner_project_seeded": 0.15,
                 "srf_decode.state": 1e-6, "srf_decode.out": 1e-4,
                 "paged_gather": 0.0, "paged_gather_dequant": 0.0}
# First-token logits of the engine against transformer.forward on the same
# prompt, as max |a - b| / max |b| over the vocabulary. Both run bf16
# weights and activations, the engine in 32-token prefill chunks over
# pages or the SRF state, the forward over the whole prompt at once; in
# f32 the two agree to ~1e-5. bf16 rounding through 36 random layers left
# 1.72e-2 (kv) and 8.24e-2 (srf: the exp feature maps and the normalizer
# amplify it) on one v5e (seed 0); a wrong kernel or layout is off by O(1).
LOGIT_BOUND = {"kv": 0.05, "srf": 0.12}
STEP_KERNELS = {"kv": {"_gather_kernel"},
                "kv_int8": {"_gather_dequant_kernel"},
                "srf": {"_spinner_kernel", "_srf_decode_kernel"},
                "srf_seeded": {"_seeded_spinner_kernel", "_srf_decode_kernel"}}


def log(msg: str) -> None:
    print(msg, flush=True)


class Timer:
    """Wall seconds of a phase and the trace + lower + compile seconds
    JAX reports inside it (its ``/jax/core/compile/*`` duration events)."""
    _compile_s = 0.0
    _listening = False

    @classmethod
    def _listen(cls, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            cls._compile_s += duration

    def __init__(self, name: str):
        import jax
        if not Timer._listening:
            jax.monitoring.register_event_duration_secs_listener(Timer._listen)
            Timer._listening = True
        self.name = name

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), Timer._compile_s
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            log(f"[smoke timing] {self.name}: compile_s="
                f"{Timer._compile_s - self.c0:.1f} "
                f"wall_s={time.perf_counter() - self.t0:.1f}")


def device_phase():
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX finds no TPU (first device is "
                         f"{devs[0].platform}); nothing was run")
    log(f"[device] platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}")
    return devs


def check(name: str, err: float, bound: float) -> None:
    ok = err <= bound
    log(f"[kernel] {name}: max_err={err:.3e} bound={bound:.1e} "
        f"{'ok' if ok else 'FAIL'}")
    if not ok:
        raise AssertionError(f"{name}: error {err} over bound {bound}")


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(float(np.max(np.abs(b))), 1e-30))


def kernel_phase(cfg_srf, seed: int) -> None:
    """Each native kernel at the serving widths against its reference."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.models.attention import srf_cfg

    sc = srf_cfg(cfg_srf)
    g, n, m = cfg_srf.n_kv_heads, sc.head_dim, sc.n_features
    k = jax.random.split(jax.random.PRNGKey(seed), 12)
    bf = jnp.bfloat16
    x = (jax.random.normal(k[0], (g, 512, n)) * n ** -0.25).astype(bf)
    params = {"g": jax.random.normal(k[1], (g, -(-m // n), n)).astype(bf),
              "d0": jnp.sign(jax.random.normal(k[2], (g, n))).astype(bf),
              "d1": jnp.sign(jax.random.normal(k[3], (g, n))).astype(bf)}

    def log_feat(y):
        return np.log(np.maximum(np.asarray(y, np.float32), 1e-30))

    def spin(up):
        return ops.spinner_project(sc.kind, params, x, m, epilogue="exp",
                                   grouped=True, use_pallas=up)
    check("spinner_project",
          float(np.max(np.abs(log_feat(spin(True)) - log_feat(spin(False))))),
          KERNEL_BOUNDS["spinner_project"])

    seeds = jax.random.randint(k[4], (g,), 1, 2 ** 31 - 1).astype(jnp.uint32)

    def spin_seeded(up):
        return ops.spinner_project_seeded(sc.kind, seeds, x, m,
                                          epilogue="exp", grouped=True,
                                          use_pallas=up)
    check("spinner_project_seeded",
          float(np.max(np.abs(log_feat(spin_seeded(True))
                              - log_feat(spin_seeded(False))))),
          KERNEL_BOUNDS["spinner_project_seeded"])

    b, h, dv = 8, cfg_srf.n_heads, cfg_srf.head_dim
    s = jax.random.normal(k[5], (b, h, m, dv))
    z = jax.random.uniform(k[6], (b, h, m)) * 10.0
    pq = jax.random.uniform(k[7], (b, h, m))
    pk = jax.random.uniform(k[8], (b, h, m))
    v = jax.random.normal(k[9], (b, h, dv))
    got = ops.srf_decode(s, z, pq, pk, v, use_pallas=True)
    with jax.default_matmul_precision("highest"):
        want = ops.srf_decode(s, z, pq, pk, v, use_pallas=False)
    check("srf_decode.state", max(_rel(got[0], want[0]), _rel(got[1], want[1])),
          KERNEL_BOUNDS["srf_decode.state"])
    check("srf_decode.out", _rel(got[2], want[2]),
          KERNEL_BOUNDS["srf_decode.out"])

    page, width, n_pages = 16, 17, 273        # the kv engine's geometry
    d = cfg_srf.n_kv_heads * cfg_srf.head_dim
    pool = jax.random.normal(k[10], (n_pages, page, d)).astype(bf)
    tables = jax.random.randint(k[11], (8, width), 1, n_pages)
    check("paged_gather",
          _rel(ops.paged_gather(pool, tables, use_pallas=True),
               ops.paged_gather(pool, tables, use_pallas=False)),
          KERNEL_BOUNDS["paged_gather"])
    q8 = jnp.clip(jnp.round(pool.astype(jnp.float32) * 40), -127,
                  127).astype(jnp.int8)
    scales = jax.random.uniform(k[0], (n_pages, page, 1)) / 40
    check("paged_gather_dequant",
          _rel(ops.paged_gather_dequant(q8, scales, tables, bf,
                                        use_pallas=True),
               ops.paged_gather_dequant(q8, scales, tables, bf,
                                        use_pallas=False)),
          KERNEL_BOUNDS["paged_gather_dequant"])


def step_kernel_names(cfg, paged=None) -> set:
    """Pallas kernels (``tpu_custom_call`` ops) in the lowered decode step
    the engine would run for ``cfg``; lowered from shapes, not compiled."""
    import jax
    import jax.numpy as jnp
    from repro.launch import steps
    from repro.models import transformer as model_lib
    from repro.serving import paged_cache

    b, width, page = N_REQUESTS, 17, 16
    params = jax.eval_shape(lambda: model_lib.init(jax.random.PRNGKey(0), cfg))
    pools = jax.eval_shape(lambda: paged_cache.init_pools(
        cfg, 2 * b * width + 1, page, num_slots=b + 1, paged=paged))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    args = [params, pools, i32(b, 1), i32(b, 1),
            jax.ShapeDtypeStruct((b, 1), jnp.bool_), i32(b, width), i32(b)]
    if getattr(cfg.srf, "seeded", False) and cfg.attn_impl == "srf":
        args.append(jax.ShapeDtypeStruct((b,), jnp.uint32))
    txt = jax.jit(steps.make_paged_step(cfg, paged=paged)).lower(*args).as_text()
    return set(re.findall(r'tpu_custom_call.*?kernel_name = "(\w+)"', txt))


def step_kernel_phase(cfg_kv, cfg_srf) -> None:
    import dataclasses
    from repro.serving import PagedConfig
    variants = {
        "kv": (cfg_kv, None),
        "kv_int8": (cfg_kv, PagedConfig(quantize_kv=True)),
        "srf": (cfg_srf, None),
        "srf_seeded": (dataclasses.replace(
            cfg_srf, srf=dataclasses.replace(cfg_srf.srf, seeded=True)), None),
    }
    for name, (cfg, paged) in variants.items():
        found = step_kernel_names(cfg, paged)
        missing = STEP_KERNELS[name] - found
        log(f"[step kernels] {name} decode step: tpu_custom_call "
            f"{sorted(found)}{' MISSING ' + str(sorted(missing)) if missing else ''}")
        if missing:
            raise AssertionError(f"{name} step lacks kernels {sorted(missing)}")


def make_requests(vocab: int, seed: int):
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    return [Request(uid=i, max_new=MAX_NEW,
                    prompt=rng.integers(0, vocab, int(n)).astype(np.int32))
            for i, n in enumerate(lens)]


def free(*trees) -> None:
    import jax
    for t in trees:
        for leaf in jax.tree.leaves(t):
            if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                leaf.delete()
    gc.collect()


def drive(eng, reqs):
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    bad = [(r.uid, len(r.out_tokens), r.finish_reason) for r in done
           if len(r.out_tokens) != MAX_NEW or r.finish_reason != "length"]
    if len(done) != len(reqs) or bad:
        raise AssertionError(f"{len(done)}/{len(reqs)} requests done; "
                             f"short or failed: {bad}")
    return {r.uid: list(r.out_tokens) for r in done}


def max_len() -> int:
    return PROMPT_LENS[1] + MAX_NEW


def serve_phase(fam: str, cfg, params, seed: int, check_logits: bool = True):
    """Serve the requests through the engine and check the first-token
    logits against the model's forward pass. Returns the greedy tokens."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as model_lib

    from repro.serving import Engine

    reqs = make_requests(cfg.vocab, seed)
    first_rows = {}
    eng = Engine(cfg, params, batch_slots=N_REQUESTS, max_len=max_len(),
                 seed=seed, on_first_logits=lambda req, row:
                 first_rows.__setitem__(req.uid, row))
    tokens = drive(eng, reqs)
    log(f"[serve {fam}] {ARCH} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"vocab={cfg.vocab} dtype={cfg.dtype}: {len(tokens)}/{N_REQUESTS} "
        f"requests finished with {MAX_NEW} tokens each; cache "
        f"{eng.cache_report()}")
    free(eng.pools)
    del eng
    gc.collect()
    if not check_logits:
        return tokens

    lens = np.array([len(r.prompt) for r in reqs])
    batch = np.zeros((len(reqs), int(lens.max())), np.int32)
    for i, r in enumerate(reqs):
        batch[i, :len(r.prompt)] = r.prompt          # causal: the tail is moot

    @jax.jit
    def last_logits(p, toks, last):
        logits, _ = model_lib.forward(p, cfg, {"tokens": toks})
        return jnp.take_along_axis(logits, last[:, None, None], axis=1)[:, 0]

    ref = np.asarray(last_logits(params, jnp.asarray(batch),
                                 jnp.asarray(lens - 1)), np.float32)
    errs, agree = [], 0
    for i, r in enumerate(reqs):
        got, want = first_rows[r.uid], ref[i, :cfg.vocab]
        errs.append(_rel(got, want))
        if np.argmax(got) == np.argmax(want):
            agree += 1
            continue
        # where the argmax differs, the reference's top-1/top-2 margin says
        # whether it was a near-tie that bf16 rounding may flip
        top2 = np.sort(want)[-2:]
        log(f"[serve {fam}] request {r.uid}: argmax engine={np.argmax(got)} "
            f"forward={np.argmax(want)}; forward top1-top2 margin="
            f"{top2[1] - top2[0]:.3e} (max |logit| {np.max(np.abs(want)):.3e}, "
            f"engine-forward max |diff| {np.max(np.abs(got - want)):.3e})")
    err, bound = max(errs), LOGIT_BOUND[fam]
    log(f"[serve {fam}] first-token logits vs transformer.forward: "
        f"max_rel_err={err:.3e} bound={bound:.1e} argmax_agree={agree}/"
        f"{len(reqs)} {'ok' if err <= bound else 'FAIL'}")
    if err > bound:
        raise AssertionError(f"{fam} logits off the forward pass: {err}")
    return tokens


def router_phase(fam: str, cfg, host_params, want, seed: int) -> None:
    """The same requests through a Router over 2 replicas x TP=2."""
    import jax
    from repro.launch import mesh as mesh_lib
    from repro.serving import Engine, Router

    meshes = mesh_lib.make_serving_meshes(2, 2)
    engines = [Engine(cfg, host_params, batch_slots=N_REQUESTS,
                      max_len=max_len(), seed=seed, mesh=m) for m in meshes]
    router = Router(engines)
    got = drive(router, make_requests(cfg.vocab, seed))
    for d in jax.devices():
        log(f"[router {fam}] device {d.id} bytes_in_use="
            f"{(d.memory_stats() or {}).get('bytes_in_use')}")
    same = got == want
    log(f"[router {fam}] 2 replicas x TP=2 greedy tokens "
        f"{'bit-identical to' if same else 'DIFFER from'} one chip "
        f"({len(got)} requests); router {router.describe()}")
    for e in engines:
        free(e.pools, e.params)
    if not same:
        raise AssertionError(f"{fam}: router tokens differ from one chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    with Timer("device"):
        devs = device_phase()
    if len(devs) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} devices, "
                         f"JAX sees {len(devs)}")
    import jax
    from repro.configs import registry
    from repro.launch import compile_cache
    from repro.models import transformer as model_lib
    log(f"[cache] compilation cache at {compile_cache.enable_compile_cache()}")

    cfg_kv = registry.get(ARCH)
    cfg_srf = registry.get(ARCH, attn_impl="srf")
    if args.chips == 1:
        with Timer("kernels"):
            kernel_phase(cfg_srf, args.seed)
            step_kernel_phase(cfg_kv, cfg_srf)
    with Timer("init"):
        # one parameter set serves both families: the srf tree is the kv
        # tree plus the per-head projections the kv path never reads
        params = jax.jit(model_lib.init, static_argnums=1)(
            jax.random.PRNGKey(args.seed), cfg_srf)
        jax.block_until_ready(params)
        log(f"[init] {ARCH} params={cfg_kv.param_count():,} "
            f"bytes={sum(x.nbytes for x in jax.tree.leaves(params)):,}")
    tokens = {}
    for fam, cfg in (("kv", cfg_kv), ("srf", cfg_srf)):
        with Timer(f"serve {fam}"):
            tokens[fam] = serve_phase(fam, cfg, params, args.seed,
                                      check_logits=args.chips == 1)
    if args.chips == 4:
        host_params = jax.device_get(params)   # device 0 holds one copy only
        free(params)
        for fam, cfg in (("kv", cfg_kv), ("srf", cfg_srf)):
            with Timer(f"router {fam}"):
                router_phase(fam, cfg, host_params, tokens[fam], args.seed)
    d = devs[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform,
                                             "kind": d.device_kind,
                                             "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
