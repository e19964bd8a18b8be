import os
import sys as _sys
# MUST precede any jax import/init: jax locks the device count on first use.
# Set here (and only here) so tests/benches still see 1 real device.
# REPRO_DRYRUN_DEVICES is the single programmatic override (set it before
# importing this module); without it, the CLI serve-mesh/serve-chaos paths
# force a realistic 8-device host instead of 512 to keep startup down. The
# smokes themselves only need 4 devices and are correct (just slower) under
# 512, and the grid cells are lower/compile-only, so a mesh wider than the
# forced count still partitions — the argv sniff is a speed knob, not
# semantics.
_FORCED = os.environ.get("REPRO_DRYRUN_DEVICES") or \
    ("8" if ("--serve-mesh" in _sys.argv or "--serve-chaos" in _sys.argv
             or "--serve-prefix" in _sys.argv or "--serve-seeded" in _sys.argv)
     else "512")
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"),
    f"--xla_force_host_platform_device_count={_FORCED}")))

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
extract memory/cost/collective evidence for EXPERIMENTS.md.

    PYTHONPATH=src python -m repro.launch.dryrun --arch mistral-nemo-12b \
        --shape train_4k [--multi-pod] [--attn srf] [--remat dots]
    PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun.jsonl

Per cell this proves: the sharding config is coherent (SPMD partitioning
succeeds), the per-device footprint fits HBM (memory_analysis), and yields
the roofline terms (trip-count-aware HLO walk; see hlo_analysis.py).
"""
import argparse
import dataclasses
import importlib.util
import json
import sys
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import registry, shapes as shp
from repro.obs.report import Reporter
from repro.distributed import sharding as S
from repro.launch import hlo_analysis as H
from repro.launch import mesh as M
from repro.launch import steps
from repro.models import hooks
from repro.models import transformer as T
from repro.optim import adamw

HBM_PER_CHIP = 16 * 1024 ** 3   # v5e


def check_bench(bench_dir: Optional[str] = None, reporter=None) -> int:
    """``--check-bench``: run the perf-regression gate
    (``benchmarks/regress.py``) over the committed ``BENCH_*.json``
    payloads vs ``BENCH_history.jsonl``. The benchmarks tree is not a
    package on ``PYTHONPATH=src``, so the module is loaded by file path;
    ``REPRO_BENCH_DIR`` overrides the default (cwd = repo root)."""
    rep = reporter or Reporter()
    bench_dir = bench_dir or os.environ.get("REPRO_BENCH_DIR", ".")
    mod_path = os.path.join(bench_dir, "benchmarks", "regress.py")
    if not os.path.exists(mod_path):
        mod_path = os.path.join(bench_dir, "regress.py")
    if not os.path.exists(mod_path):
        rep.line(f"[regress] no regress.py under {bench_dir}")
        return 1
    spec = importlib.util.spec_from_file_location("_bench_regress", mod_path)
    regress = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(regress)
    paths = regress.discover(bench_dir)
    history = os.path.join(bench_dir, regress.HISTORY)
    bad = regress.check_files(paths, history, reporter=rep)
    for msg in bad:
        rep.line(f"[regress] REGRESSION {msg}")
    rep.line(f"[regress] {'FAIL' if bad else 'PASS'}: {len(bad)} "
             f"violation(s) across {len(paths)} payload(s)")
    return 1 if bad else 0


def _mem_summary(compiled) -> Dict[str, float]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    return {
        "arg_bytes": float(ma.argument_size_in_bytes),
        "out_bytes": float(ma.output_size_in_bytes),
        "alias_bytes": float(ma.alias_size_in_bytes),
        "temp_bytes": float(ma.temp_size_in_bytes),
        "peak_bytes": float(ma.argument_size_in_bytes
                            + ma.output_size_in_bytes
                            - ma.alias_size_in_bytes
                            + ma.temp_size_in_bytes),
    }


def run_cell(arch: str, shape: str, multi_pod: bool = False,
             use_reduced: bool = False, overrides: Optional[Dict] = None,
             hlo_dir: Optional[str] = None) -> Dict:
    t0 = time.time()
    cfg, note = shp.cell_config(arch, shape, use_reduced, **(overrides or {}))
    ss = shp.SHAPES[shape]
    mesh = M.make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    hooks.set_constrainer(S.make_constrainer(mesh, cfg))
    rec: Dict = {
        "arch": arch, "shape": shape, "mesh": M.describe(mesh),
        "chips": chips, "step": ss.step, "attn_impl": cfg.attn_impl,
        "note": note, "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    try:
        params_sds = jax.eval_shape(lambda: T.init(jax.random.PRNGKey(0), cfg))
        pspecs = S.param_specs(params_sds, mesh)
        ins = shp.input_specs(cfg, shape)
        with mesh:
            if ss.step == "train":
                opt_sds = jax.eval_shape(lambda: adamw.init(params_sds))
                ospecs = S.opt_state_specs(opt_sds, params_sds, pspecs, mesh)
                bspecs = S.batch_specs_tree(ins["batch"], mesh)
                gshard = S.named(mesh, S.zero1_specs(params_sds, pspecs,
                                                     mesh))
                fn = steps.make_train_step(cfg, grad_shardings=gshard)
                jitted = jax.jit(
                    fn,
                    in_shardings=(S.named(mesh, pspecs), S.named(mesh, ospecs),
                                  None, S.named(mesh, bspecs)),
                    out_shardings=(S.named(mesh, pspecs),
                                   S.named(mesh, ospecs), None),
                    donate_argnums=(0, 1))
                lowered = jitted.lower(params_sds, opt_sds,
                                       jax.ShapeDtypeStruct((), jnp.int32),
                                       ins["batch"])
            elif ss.step == "prefill":
                cspecs = S.cache_specs_tree(ins["cache"], cfg, mesh)
                bspecs = S.batch_specs_tree(ins["batch"], mesh)
                fn = steps.make_prefill_step(cfg)
                jitted = jax.jit(
                    fn,
                    in_shardings=(S.named(mesh, pspecs),
                                  S.named(mesh, bspecs),
                                  S.named(mesh, cspecs)),
                    out_shardings=(None, S.named(mesh, cspecs)),
                    donate_argnums=(2,))
                lowered = jitted.lower(params_sds, ins["batch"], ins["cache"])
            else:  # decode
                cspecs = S.cache_specs_tree(ins["cache"], cfg, mesh)
                tspec = S.batch_specs_tree({"t": ins["tokens"]}, mesh)["t"]
                fn = steps.make_serve_step(cfg)
                jitted = jax.jit(
                    fn,
                    in_shardings=(S.named(mesh, pspecs),
                                  S.named(mesh, cspecs),
                                  S.named(mesh, {"t": tspec})["t"]),
                    out_shardings=(None, None, S.named(mesh, cspecs)),
                    donate_argnums=(1,))
                lowered = jitted.lower(params_sds, ins["cache"],
                                       ins["tokens"])
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
            rec.update(_mem_summary(compiled))
            ca = compiled.cost_analysis() or {}
            rec["xla_cost_flops_once"] = float(ca.get("flops", 0.0))
            hlo = compiled.as_text()
            if hlo_dir:
                os.makedirs(hlo_dir, exist_ok=True)
                tag = f"{arch}_{shape}_{'mp' if multi_pod else 'sp'}"
                with open(os.path.join(hlo_dir, tag + ".hlo"), "w") as f:
                    f.write(hlo)
            an = H.analyze(hlo)
            rec.update({f"hlo_{k.replace('/', '_')}": v for k, v in an.items()})
            rec.update(H.roofline_terms(an))
            rec["fits_hbm"] = bool(rec.get("peak_bytes", 0) < HBM_PER_CHIP)
            rec["lower_s"] = round(t1 - t0, 2)
            rec["compile_s"] = round(t2 - t1, 2)
            rec["ok"] = True
    except Exception as e:  # failures here are bugs in the system
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    finally:
        hooks.reset()
    return rec


def pipeline_smoke() -> Dict:
    """``--pipeline``: spinner-pipeline serialization round-trip smoke.

    Builds a mixed-kind 3-block SpinnerPipeline, round-trips it through
    ``spinner.dumps``/``loads`` (the checkpointable config form), and
    proves the reloaded pipeline is spec-equal AND bit-identical under
    ``apply`` with the same params — the invariant checkpoint restore
    relies on.
    """
    from repro.core import spinner
    t0 = time.time()
    pipe = spinner.chain(
        [spinner.SpinnerBlock("circulant", 128, 128),
         spinner.SpinnerBlock("toeplitz", 128, 128),
         spinner.SpinnerBlock("skew_circulant", 256, 128)], f="relu")
    rec: Dict = {"cell": "pipeline_smoke", "depth": pipe.depth,
                 "n_in": pipe.n_in, "out_dim": pipe.out_dim,
                 "budget_t": pipe.budget, "storage_floats": pipe.storage}
    try:
        blob = spinner.dumps(pipe)
        pipe2 = spinner.loads(blob)
        params = pipe.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (8, pipe.n_in)) * 0.3
        y1 = pipe.apply(params, x)
        y2 = pipe2.apply(params, x)
        rec["config_bytes"] = len(blob)
        rec["roundtrip_spec_equal"] = bool(pipe2 == pipe)
        rec["roundtrip_apply_identical"] = bool(jnp.all(y1 == y2))
        rec["apply_finite"] = bool(jnp.all(jnp.isfinite(y1)))
        rec["ok"] = (rec["roundtrip_spec_equal"]
                     and rec["roundtrip_apply_identical"]
                     and rec["apply_finite"])
    except Exception as e:
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


def serve_mesh_smoke(arch: str = "qwen3-4b") -> Dict:
    """``--serve-mesh``: mesh-serving end-to-end smoke on the fake
    8-device host platform.

    Builds 2 router-managed engine replicas with model-axis-sharded page
    pools (TP=2 each), serves 4 mixed-length requests end to end, and
    checks (a) every request completes with greedy tokens identical to
    the single-host paged engine, (b) per-device pool bytes are
    1/model_axis of the single-host layout.
    """
    import numpy as np
    from repro.launch import mesh as mesh_lib
    from repro.serving import Engine, Request, Router
    from repro.serving.mesh import shard as mesh_shard

    t0 = time.time()
    cfg = registry.reduced(arch, n_layers=2)
    rec: Dict = {"cell": "serve_mesh_smoke", "arch": arch,
                 "devices": len(jax.devices())}
    try:
        params = T.init(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(0)
        lens = [3, 9, 17, 6]
        prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
                   for n in lens]

        single = Engine(cfg, params, batch_slots=4, max_len=64)
        for i, p in enumerate(prompts):
            single.submit(Request(uid=i, prompt=p, max_new=6))
        want = {r.uid: r.out_tokens for r in single.run()}

        meshes = mesh_lib.make_serving_meshes(replicas=2, model_parallel=2)
        router = Router([Engine(cfg, params, batch_slots=4, max_len=64,
                                mesh=m) for m in meshes])
        for i, p in enumerate(prompts):
            router.submit(Request(uid=i, prompt=p.copy(), max_new=6))
        got = {r.uid: r.out_tokens for r in router.run()}

        rep = router.engines[0].cache_report()
        tp = mesh_shard.paged_tp(cfg, meshes[0])
        rec.update({
            "replicas": 2, "model_parallel": 2, "paged_tp": tp,
            "requests_done": len(got),
            "tokens_match_single_host": bool(got == want),
            "pool_bytes_single": single.cache_report()["pool_bytes"],
            "pool_bytes_per_device": rep["pool_bytes_per_device"],
            "router": router.describe(),
        })
        rec["ok"] = (got == want and len(got) == len(prompts)
                     and tp == 2
                     and rep["pool_bytes_per_device"] * tp
                     == single.cache_report()["pool_bytes"])
    except Exception as e:
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


def serve_chaos_smoke(arch: str = "qwen3-4b") -> Dict:
    """``--serve-chaos``: fault-tolerant mesh-serving smoke on the fake
    8-device host platform.

    Builds 2 router-managed TP=2 replicas sharing one metrics registry,
    arms the FT watchdog, and kills replica 1 mid-decode with the
    TEST-ONLY chaos harness (``raise`` at its 4th step). Checks (a) every
    request still completes with greedy tokens bit-identical to an
    undisturbed single-host run (exactly-once rescue), (b) exactly one
    quarantine and zero rescue failures, (c) after ``heal`` + ``revive``
    the pool leaks no pages/slots and fresh requests bit-match too.
    """
    import numpy as np
    from repro.launch import mesh as mesh_lib
    from repro.obs.metrics import MetricsRegistry
    from repro.serving import Engine, FTConfig, Request, Router
    from repro.serving.chaos import ChaosEngine, ChaosPlan

    t0 = time.time()
    cfg = registry.reduced(arch, n_layers=2)
    rec: Dict = {"cell": "serve_chaos_smoke", "arch": arch,
                 "devices": len(jax.devices())}
    try:
        params = T.init(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(0)
        lens = [3, 9, 17, 6, 11, 5]
        prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
                   for n in lens]

        single = Engine(cfg, params, batch_slots=4, max_len=64)
        for i, p in enumerate(prompts):
            single.submit(Request(uid=i, prompt=p.copy(), max_new=6))
        want = {r.uid: r.out_tokens for r in single.run()}

        reg = MetricsRegistry()
        meshes = mesh_lib.make_serving_meshes(replicas=2, model_parallel=2)
        engines = [Engine(cfg, params, batch_slots=2, max_len=64, seed=i,
                          mesh=m, metrics=reg)
                   for i, m in enumerate(meshes)]
        chaos = ChaosEngine(engines[1], ChaosPlan("raise", at_step=4))
        engines[1] = chaos
        router = Router(engines, metrics=reg, ft=FTConfig())
        for i, p in enumerate(prompts):
            router.submit(Request(uid=i, prompt=p.copy(), max_new=6))
        got = {r.uid: r.out_tokens for r in router.run()}

        v = reg.value_sum
        quarantined = int(router.metrics.value_sum(
            "router_quarantined_total"))
        rec.update({
            "replicas": 2, "model_parallel": 2,
            "requests_done": len(got),
            "tokens_match_undisturbed": bool(got == want),
            "quarantined": quarantined,
            "dead_after_fault": sorted(router.dead),
            "rescued": int(router.metrics.value_sum("router_rescued_total")),
            "replayed": int(router.metrics.value_sum(
                "router_replayed_total")),
            "failed": int(router.metrics.value_sum("router_failed_total")),
        })

        chaos.heal()
        revived = router.revive(1)
        extra = [Request(uid=100 + i, prompt=p.copy(), max_new=6)
                 for i, p in enumerate(prompts[:2])]
        for r in extra:
            router.submit(r)
        router.run()
        used = sum(e.sched.alloc.used_pages for e in router.engines)
        slots = sum(e.sched.slot_alloc.used_pages for e in router.engines
                    if e.sched.slot_alloc is not None)
        conserved = (v("sched_submitted_total") + v("sched_adopted_total")
                     == v("sched_finished_total")
                     + v("sched_released_total"))
        rec.update({
            "revived": bool(revived),
            "extra_after_revive_match": bool(
                all(np.array_equal(r.out_tokens, want[r.uid - 100])
                    for r in extra)),
            "used_pages_after": used, "used_slots_after": slots,
            "conservation_holds": bool(conserved),
            "router": router.describe(),
        })
        rec["ok"] = (got == want and len(got) == len(prompts)
                     and quarantined == 1 and rec["failed"] == 0
                     and rec["dead_after_fault"] == [1]
                     and revived and rec["extra_after_revive_match"]
                     and used == 0 and slots == 0 and conserved)
    except Exception as e:
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


def serve_prefix_smoke(arch: str = "qwen3-4b") -> Dict:
    """``--serve-prefix``: prefix-sharing serving smoke.

    Serves 8 requests sharing a 32-token prompt prefix through one
    paged engine with the radix prefix cache + chunked prefill armed
    (small slot count so admission staggers into waves and later waves
    can hit the donor wave's cached pages). Checks (a) the cache
    actually hit (hit-rate > 0 and strictly fewer tokens prefilled than
    the cold engine), (b) greedy tokens are bit-identical to a cold-cache
    run, (c) after the drain + ``drop_all`` not a single page or slot is
    leaked.
    """
    import numpy as np
    from repro.obs.metrics import MetricsRegistry
    from repro.serving import (ChunkConfig, Engine, PrefixConfig, Request)

    t0 = time.time()
    cfg = registry.reduced(arch, n_layers=2)
    rec: Dict = {"cell": "serve_prefix_smoke", "arch": arch}
    try:
        params = T.init(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(0)
        shared = rng.integers(0, cfg.vocab, 32).astype(np.int32)
        prompts = [np.concatenate([shared, rng.integers(
            0, cfg.vocab, 3 + i).astype(np.int32)]) for i in range(8)]

        def serve(prefix, reg):
            eng = Engine(cfg, params, batch_slots=2, max_len=64,
                         metrics=reg, prefix=prefix)
            for i, p in enumerate(prompts):
                eng.submit(Request(uid=i, prompt=p.copy(), max_new=6))
            return eng, {r.uid: r.out_tokens for r in eng.run()}

        cold_reg = MetricsRegistry()
        _, want = serve(None, cold_reg)
        warm_reg = MetricsRegistry()
        eng, got = serve(PrefixConfig(chunk=ChunkConfig(chunk_tokens=16)),
                         warm_reg)

        hits = int(warm_reg.value_sum("prefix_hits_total"))
        rec.update({
            "requests_done": len(got),
            "hit_rate": round(hits / len(prompts), 3),
            "hit_tokens": int(warm_reg.value_sum("prefix_hit_tokens_total")),
            "cow_forks": int(warm_reg.value_sum("prefix_cow_forks_total")),
            "prefill_tokens_cold": int(cold_reg.value_sum(
                "engine_prefill_tokens_total")),
            "prefill_tokens_warm": int(warm_reg.value_sum(
                "engine_prefill_tokens_total")),
            "tokens_match_cold": bool(got == want),
        })
        cache_pages = eng.prefix.pages
        eng.prefix.drop_all()
        rec.update({
            "cache_pages_at_drain": cache_pages,
            "used_pages_after_drop": eng.sched.alloc.used_pages,
        })
        rec["ok"] = (got == want and len(got) == len(prompts)
                     and hits > 0
                     and rec["prefill_tokens_warm"]
                     < rec["prefill_tokens_cold"]
                     and eng.sched.alloc.used_pages == 0
                     and eng.sched.alloc.total_refs == 0)
    except Exception as e:
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


def serve_seeded_smoke(arch: str = "qwen3-4b") -> Dict:
    """``--serve-seeded``: zero-storage seeded-projection serving smoke.

    Builds the SRF variant of ``arch`` with ``srf.seeded=True`` (every
    projection regenerated in-kernel from one uint32 seed per head) and
    serves one base request plus two requests with DISTINCT per-request
    ``embed_seed``s through one paged engine. Checks (a) zero
    materialized projection bytes — the params hold one uint32 per
    (layer, head, block), orders of magnitude under the materialized
    twin's float storage, (b) personalization — the seeded requests
    decode different streams than the base one from the SAME prompt, and
    differ from each other, (c) determinism — a rerun is bit-identical.
    """
    import numpy as np
    from repro.models.attention import srf_cfg
    from repro.serving import Engine, Request

    t0 = time.time()
    cfg = registry.reduced(arch, n_layers=2, attn_impl="srf")
    cfg = dataclasses.replace(
        cfg, srf=dataclasses.replace(cfg.srf, seeded=True))
    rec: Dict = {"cell": "serve_seeded_smoke", "arch": arch}
    try:
        params = T.init(jax.random.PRNGKey(0), cfg)
        seed_leaves = [l for l in jax.tree_util.tree_leaves(params)
                       if l.dtype == jnp.uint32]
        seed_bytes = sum(int(l.size) * 4 for l in seed_leaves)
        pipe = srf_cfg(cfg).pipeline
        twin = dataclasses.replace(pipe, blocks=tuple(
            dataclasses.replace(b, seeded=False) for b in pipe.blocks))
        head_pipes = sum(int(l.size) for l in seed_leaves) // len(pipe.blocks)
        mat_bytes = int(twin.storage) * 4 * head_pipes

        prompt = np.arange(9, dtype=np.int32)

        def serve():
            eng = Engine(cfg, params, batch_slots=4, max_len=64)
            for uid, es in ((0, 0), (1, 1234), (2, 98765)):
                eng.submit(Request(uid=uid, prompt=prompt.copy(), max_new=6,
                                   embed_seed=es))
            return {r.uid: list(r.out_tokens) for r in eng.run()}

        got, again = serve(), serve()
        rec.update({
            "requests_done": len(got),
            "projection_seed_bytes": seed_bytes,
            "materialized_equiv_bytes": mat_bytes,
            "projection_bytes_reduction_x":
                round(mat_bytes / max(seed_bytes, 1), 1),
            "personalized": bool(got[1] != got[0] and got[2] != got[0]
                                 and got[2] != got[1]),
            "deterministic": bool(got == again),
        })
        rec["ok"] = (len(got) == 3
                     and rec["personalized"] and rec["deterministic"]
                     and seed_bytes == 4 * sum(int(l.size)
                                               for l in seed_leaves)
                     and mat_bytes > 10 * seed_bytes)
    except Exception as e:
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=registry.ARCHS + [None])
    ap.add_argument("--shape", default=None,
                    choices=list(shp.SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true", help="all 40 cells")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--attn", default=None, choices=[None, "full", "srf"])
    ap.add_argument("--remat", default=None,
                    choices=[None, "none", "dots", "full"])
    ap.add_argument("--srf-kind", default=None)
    ap.add_argument("--srf-features", type=int, default=None)
    ap.add_argument("--out", default=None, help="append-jsonl results path")
    ap.add_argument("--hlo-dir", default=None, help="dump compiled HLO here")
    ap.add_argument("--pipeline", action="store_true",
                    help="spinner-pipeline serialization round-trip smoke "
                         "(no mesh/arch needed)")
    ap.add_argument("--serve-mesh", action="store_true",
                    help="mesh-serving smoke: router + sharded pools on a "
                         "fake 8-device mesh, 4 mixed-length requests e2e")
    ap.add_argument("--serve-chaos", action="store_true",
                    help="fault-tolerance smoke: FT router + chaos-killed "
                         "replica mid-decode, rescue must be bit-identical")
    ap.add_argument("--serve-prefix", action="store_true",
                    help="prefix-sharing smoke: 8 shared-prefix requests, "
                         "hit-rate > 0, bit-match vs cold cache, zero "
                         "leaked pages")
    ap.add_argument("--serve-seeded", action="store_true",
                    help="seeded-projection smoke: requests with distinct "
                         "embed_seeds personalize deterministically with "
                         "zero materialized projection bytes")
    ap.add_argument("--check-bench", action="store_true",
                    help="perf-regression gate: check the committed "
                         "BENCH_*.json payloads against "
                         "BENCH_history.jsonl (benchmarks/regress.py); "
                         "REPRO_BENCH_DIR overrides the repo-root default")
    ap.add_argument("--bench-dir", default=None,
                    help="bench payload/history dir for --check-bench")
    args = ap.parse_args(argv)

    rep = Reporter()
    if args.check_bench:
        return check_bench(args.bench_dir, reporter=rep)

    if (args.pipeline or args.serve_mesh or args.serve_chaos
            or args.serve_prefix or args.serve_seeded):
        rec = (pipeline_smoke() if args.pipeline
               else serve_mesh_smoke(args.arch or "qwen3-4b")
               if args.serve_mesh
               else serve_chaos_smoke(args.arch or "qwen3-4b")
               if args.serve_chaos
               else serve_prefix_smoke(args.arch or "qwen3-4b")
               if args.serve_prefix
               else serve_seeded_smoke(args.arch or "qwen3-4b"))
        line = json.dumps(rec, default=float)
        rep.line(line)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
        return 0 if rec["ok"] else 1

    overrides = {}
    if args.attn:
        overrides["attn_impl"] = args.attn
    if args.remat:
        overrides["remat"] = args.remat
    if args.srf_kind or args.srf_features:
        base = registry.get(args.arch or registry.ARCHS[0]).srf
        overrides["srf"] = dataclasses.replace(
            base, **({"kind": args.srf_kind} if args.srf_kind else {}),
            **({"n_features": args.srf_features} if args.srf_features else {}))

    cells = []
    archs = [args.arch] if args.arch else registry.ARCHS
    shapes_ = [args.shape] if args.shape else list(shp.SHAPES)
    if not (args.all or args.arch or args.shape):
        ap.error("pass --arch/--shape or --all")
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes_:
            for mp in meshes:
                cells.append((a, s, mp))

    ok = True
    for a, s, mp in cells:
        rec = run_cell(a, s, multi_pod=mp, use_reduced=args.reduced,
                       overrides=overrides, hlo_dir=args.hlo_dir)
        ok = ok and rec["ok"]
        line = json.dumps(rec, default=float)
        rep.line(line)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
