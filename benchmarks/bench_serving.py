"""Serving benchmark: paged continuous-batching engine vs the legacy
per-slot engine, and single-host vs mesh-sharded serving — tokens/s and
time-to-first-token across cache families and concurrency levels.

Suite mode (``python -m benchmarks.run --only serving``) runs a fast
smoke (kv/srf plus the mixed-geometry hybrid and enc-dec plans, 8
requests, one mesh cell) so the tier-1 flow exercises the serving path;
the full sweep (8–64 concurrent requests x all six families, hybrid and
enc-dec included) runs via

    PYTHONPATH=src python -m benchmarks.bench_serving --full

Emits machine-readable ``BENCH_serving.json`` (``BENCH_serving_smoke.json``
in smoke mode): paged-vs-legacy per family/concurrency, a 1-host vs
simulated 8-device-mesh comparison (2 router replicas x TP=2, run in a
subprocess so the forced host-platform device count cannot leak into
this process), a failover-cost cell (2-replica FT router, replica 1
chaos-killed mid-decode: requests/s dip vs the undisturbed run plus the
rescue latency read from the registry event stream), a shared-prefix
cell (64 requests at ~90% prompt overlap served cold vs with the radix
prefix cache + COW + chunked prefill: prefill-token reduction, TPOT-p95
ratio, bit-identity, leak check), and the ``launch/dryrun
--serve-chaos`` smoke verdict (subprocess, same device-count
isolation). ``--failover`` / ``--prefix`` re-measure ONLY that cell and
read-modify-write it into the committed ``BENCH_serving.json`` without
re-running the full sweep. CSV columns: name, us_per_call (wall us per
generated token), derived (tokens/s | mean ttft ms | preemptions).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict, List

import jax
import numpy as np

FAMILIES = [
    ("kv", "qwen3-4b", {}),
    ("srf", "qwen3-4b", {"attn_impl": "srf"}),
    ("mla", "deepseek-v2-lite-16b", {}),
    ("ssd", "mamba2-2.7b", {}),
    ("hybrid", "hymba-1.5b", {}),
    ("encdec", "seamless-m4t-large-v2", {}),
]

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _requests(cfg, n, seed=0):
    from repro.models import frontends
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        enc = (frontends.synthetic_audio_features(rng, cfg)
               if cfg.is_encdec else None)
        out.append(Request(uid=i,
                           prompt=rng.integers(0, cfg.vocab,
                                               int(rng.integers(4, 20))
                                               ).astype(np.int32),
                           max_new=12, enc_emb=enc))
    return out


def _drive(eng, reqs):
    from repro.obs import latency_summary
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    toks = sum(len(r.out_tokens) for r in done)
    ttft = np.mean([r.t_first - r.t_submit for r in done]) * 1e3
    return wall, toks, ttft, latency_summary(done)


def _pct_fields(summ) -> Dict:
    """Flatten a latency_summary into ttft_ms_p50/.../tpot_ms_p99 JSON
    fields (ms, rounded; None for empty samples so the JSON stays
    standard — json NaN is an extension)."""
    out = {}
    for kind in ("ttft", "tpot"):
        for pk, v in summ[f"{kind}_s"].items():
            out[f"{kind}_ms_{pk}"] = (round(v * 1e3, 2)
                                      if v == v else None)
    return out


def _bench_pair(fam, arch, over, concurrency, seed=0) -> Dict:
    """Paged vs legacy at one concurrency level -> one JSON record."""
    import warnings
    from repro.configs import registry
    from repro.models import transformer as T
    from repro.serving import Engine
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        from repro.serving import legacy
    cfg = registry.reduced(arch, **over)
    params = T.init(jax.random.PRNGKey(0), cfg)
    slots = min(concurrency, 16)

    eng = Engine(cfg, params, batch_slots=slots, max_len=64, seed=seed)
    wall_p, toks_p, ttft_p, summ_p = _drive(eng,
                                            _requests(cfg, concurrency, seed))

    leg = legacy.Engine(cfg, params, batch_slots=slots, max_len=64)
    wall_l, toks_l, ttft_l, summ_l = _drive(leg,
                                            _requests(cfg, concurrency, seed))

    return {"family": fam, "arch": arch, "concurrency": concurrency,
            "paged": {"tok_s": round(toks_p / wall_p, 2),
                      "ttft_ms": round(float(ttft_p), 1),
                      "us_per_tok": round(wall_p / max(toks_p, 1) * 1e6),
                      "preemptions": eng.sched.stats["preemptions"],
                      **_pct_fields(summ_p)},
            "legacy": {"tok_s": round(toks_l / wall_l, 2),
                       "ttft_ms": round(float(ttft_l), 1),
                       "us_per_tok": round(wall_l / max(toks_l, 1) * 1e6),
                       **_pct_fields(summ_l)},
            "speedup": round((toks_p / wall_p) / (toks_l / wall_l), 3)}


def _pair_rows(rec: Dict) -> List[str]:
    fam, c = rec["family"], rec["concurrency"]
    p, l = rec["paged"], rec["legacy"]
    return [
        f"serving/{fam}/paged/c{c},{p['us_per_tok']},"
        f"tok_s={p['tok_s']}|ttft_ms={p['ttft_ms']:.0f}"
        f"|preempt={p['preemptions']}",
        f"serving/{fam}/legacy/c{c},{l['us_per_tok']},"
        f"tok_s={l['tok_s']}|ttft_ms={l['ttft_ms']:.0f}|preempt=0",
        f"serving/{fam}/speedup/c{c},0,x{rec['speedup']:.2f}",
    ]


# ---------------------------------------------------------------------------
# failover cost: FT router with a chaos-killed replica vs undisturbed
# ---------------------------------------------------------------------------


def _bench_failover(concurrency: int = 16, seed: int = 0) -> Dict:
    """Serve the SAME request set twice through a 2-replica FT router —
    once undisturbed, once with replica 1 chaos-killed mid-decode
    (``raise`` at its 6th step) — and price the failover: requests/s
    dip, rescue latency (quarantine event -> last request re-homed,
    from the shared registry's event stream), the extra prefill/decode
    steps the forced-prefix replays cost, and whether the rescued
    greedy tokens stayed bit-identical (the exactly-once guarantee).

    Note the replicas step serially in this process (no real device
    parallelism), so the dip measures replay overhead, not the halved
    fleet capacity a production deployment would also see.

    The killed run also records span timelines on both replicas and the
    router, exports them as one merged Chrome-trace JSON
    (``TRACE_failover.json``; load in Perfetto), and verifies the
    quarantine -> rescue -> replay chain is present and uid-correlated
    in the exported events."""
    from repro.configs import registry
    from repro.models import transformer as T
    from repro.obs import MetricsRegistry, SpanRecorder, chrome_trace
    from repro.serving import Engine, FTConfig, Router
    from repro.serving.chaos import ChaosEngine, ChaosPlan

    cfg = registry.reduced("qwen3-4b", n_layers=2)
    params = T.init(jax.random.PRNGKey(0), cfg)
    slots = max(2, min(concurrency, 16) // 2)   # per replica

    def serve(kill: bool, n: int = concurrency, recorders=None) -> Dict:
        reg = MetricsRegistry()
        spans = recorders or [None] * 3
        engines = [Engine(cfg, params, batch_slots=slots, max_len=64,
                          seed=seed + i, metrics=reg, spans=spans[i])
                   for i in range(2)]
        if kill:
            engines[1] = ChaosEngine(engines[1],
                                     ChaosPlan("raise", at_step=6))
        router = Router(engines, metrics=reg, ft=FTConfig(),
                        spans=spans[2])
        reqs = _requests(cfg, n, seed)
        wall, toks, _, _ = _drive(router, reqs)
        return {"reg": reg, "wall": wall, "toks": toks,
                "steps": int(reg.value_sum("engine_prefill_steps_total")
                             + reg.value_sum("engine_decode_steps_total")),
                "out": {r.uid: r.out_tokens for r in reqs}}

    serve(kill=False, n=4)      # warm the jit caches: without this the
    clean = serve(kill=False)   # clean run eats compile time and the
                                # "dip" comes out negative
    recorders = [SpanRecorder(replica=i) for i in range(3)]
    killed = serve(kill=True, recorders=recorders)
    trace = chrome_trace(recorders)
    trace_path = os.environ.get("REPRO_BENCH_TRACE_JSON",
                                "TRACE_failover.json")
    with open(trace_path, "w") as f:
        json.dump(trace, f)
        f.write("\n")
    evs = killed["reg"].events
    t_q = next((e["t"] for e in evs if e["event"] == "quarantined"), None)
    t_home = [e["t"] for e in evs
              if e["event"] in ("rescued", "replayed")]
    rescue_s = (round(max(t_home) - t_q, 4)
                if t_q is not None and t_home else None)
    req_s_clean = concurrency / clean["wall"]
    req_s_killed = concurrency / killed["wall"]
    kv = killed["reg"].value_sum
    return {
        "concurrency": concurrency, "replicas": 2, "fault": "raise@6:1",
        "clean": {"req_s": round(req_s_clean, 2),
                  "tok_s": round(clean["toks"] / clean["wall"], 2),
                  "engine_steps": clean["steps"]},
        "killed": {"req_s": round(req_s_killed, 2),
                   "tok_s": round(killed["toks"] / killed["wall"], 2),
                   "engine_steps": killed["steps"],
                   "quarantined": int(kv("router_quarantined_total")),
                   "rescued": int(kv("router_rescued_total")),
                   "replayed": int(kv("router_replayed_total")),
                   "failed": int(kv("router_failed_total"))},
        "req_s_dip_pct": round(100.0 * (1.0 - req_s_killed / req_s_clean),
                               1),
        "replay_extra_steps": killed["steps"] - clean["steps"],
        "rescue_latency_s": rescue_s,
        "tokens_match_clean": bool(killed["out"] == clean["out"]),
        "trace": _verify_failover_trace(trace, trace_path),
    }


def _verify_failover_trace(trace: Dict, path: str) -> Dict:
    """Check the exported chaos-kill Chrome trace actually tells the
    failover story: a quarantine instant on the router timeline followed
    by per-request rescue (waiting seq adopted) or replay (running seq
    re-prefilled) instants, every one uid-tagged and timestamped at or
    after the quarantine — i.e. the recovery of each request can be
    followed through the merged timeline by its uid."""
    evs = trace["traceEvents"]
    inst = [e for e in evs if e.get("ph") == "i"]
    t_q = min((e["ts"] for e in inst if e["name"] == "quarantine"),
              default=None)
    rescue = {e["args"]["uid"]: e["ts"] for e in inst
              if e["name"] == "rescue"}
    replay = {e["args"]["uid"]: e["ts"] for e in inst
              if e["name"] == "replay"}
    moved = {**rescue, **replay}
    correlated = (t_q is not None and len(moved) > 0
                  and all(u is not None for u in moved)
                  and all(t >= t_q for t in moved.values()))
    return {"path": path, "events": len(evs),
            "timelines": len({e.get("pid") for e in evs}),
            "quarantine": sum(e["name"] == "quarantine" for e in inst),
            "rescue_uids": sorted(rescue), "replay_uids": sorted(replay),
            "chain_uid_correlated": bool(correlated)}


def _failover_rows(rec: Dict) -> List[str]:
    c = rec["concurrency"]
    cl, kd = rec["clean"], rec["killed"]
    return [
        f"serving/failover/clean/c{c},0,"
        f"req_s={cl['req_s']}|tok_s={cl['tok_s']}",
        f"serving/failover/killed/c{c},0,"
        f"req_s={kd['req_s']}|tok_s={kd['tok_s']}"
        f"|dip_pct={rec['req_s_dip_pct']}",
        f"serving/failover/rescue/c{c},0,"
        f"latency_s={rec['rescue_latency_s']}"
        f"|extra_steps={rec['replay_extra_steps']}"
        f"|match={rec['tokens_match_clean']}|failed={kd['failed']}",
        f"serving/failover/trace/c{c},0,"
        f"events={rec['trace']['events']}"
        f"|timelines={rec['trace']['timelines']}"
        f"|chain_uid_correlated={rec['trace']['chain_uid_correlated']}",
    ]


# ---------------------------------------------------------------------------
# shared-prefix serving: prefix cache + COW + chunked prefill vs cold
# ---------------------------------------------------------------------------


def _bench_prefix(concurrency: int = 64, slots: int = 16,
                  seed: int = 0) -> Dict:
    """Serve ``concurrency`` requests sharing a 36-token prompt prefix
    (~90% of the prompt) twice through one paged engine — cold, and
    with the radix prefix cache + chunked prefill armed — after an
    identical 4-request donor warm-up in both runs (which also warms
    the jit caches). Prices the subsystem: prefill-token reduction
    (admission throughput — a hit skips its matched tokens), end-to-end
    tokens/s, decode-p95-TPOT ratio under chunked prefill (must stay
    ~1x: interleaving bounds decode starvation), greedy bit-identity,
    and zero leaked pages after dropping the cache."""
    from repro.configs import registry
    from repro.models import transformer as T
    from repro.obs import MetricsRegistry
    from repro.serving import ChunkConfig, Engine, PrefixConfig, Request

    cfg = registry.reduced("qwen3-4b", n_layers=2)
    params = T.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab, 36).astype(np.int32)
    tails = [rng.integers(0, cfg.vocab,
                          3 + int(rng.integers(0, 3))).astype(np.int32)
             for _ in range(concurrency)]
    mean_len = 36 + float(np.mean([len(t) for t in tails]))

    def serve(prefix) -> Dict:
        reg = MetricsRegistry()
        eng = Engine(cfg, params, batch_slots=slots, max_len=64,
                     seed=seed, metrics=reg, prefix=prefix)
        for i in range(4):                      # donor warm-up (+ jit)
            eng.submit(Request(uid=1000 + i, prompt=shared.copy(),
                               max_new=4))
        eng.run()
        pre0 = reg.value_sum("engine_prefill_tokens_total")
        reqs = [Request(uid=i, prompt=np.concatenate([shared, t]),
                        max_new=12) for i, t in enumerate(tails)]
        wall, toks, _, summ = _drive(eng, reqs)
        rec = {"wall": wall, "toks": toks,
               "prefill_tokens": int(reg.value_sum(
                   "engine_prefill_tokens_total") - pre0),
               "tpot_p95_s": summ["tpot_s"]["p95"],
               "out": {r.uid: r.out_tokens for r in reqs}}
        if eng.prefix is not None:
            v = reg.value_sum
            rec.update({
                "hit_rate": round(v("prefix_hits_total")
                                  / v("prefix_lookups_total"), 3),
                "hit_tokens": int(v("prefix_hit_tokens_total")),
                "cow_forks": int(v("prefix_cow_forks_total")),
                "evictions": int(v("prefix_evictions_total")),
                "cache_pages": eng.prefix.pages,
            })
            eng.prefix.drop_all()
            rec["leaked_pages_after_drop"] = eng.sched.alloc.used_pages
        return rec

    cold = serve(None)
    warm = serve(PrefixConfig(chunk=ChunkConfig(chunk_tokens=32)))
    out_cold = cold.pop("out")
    out_warm = warm.pop("out")
    return {
        "concurrency": concurrency, "slots": slots, "arch": "qwen3-4b",
        "overlap_pct": round(100.0 * 36 / mean_len, 1),
        "cold": {"tok_s": round(cold["toks"] / cold["wall"], 2),
                 "prefill_tokens": cold["prefill_tokens"],
                 "tpot_ms_p95": round(cold["tpot_p95_s"] * 1e3, 2)},
        "warm": {"tok_s": round(warm["toks"] / warm["wall"], 2),
                 "prefill_tokens": warm["prefill_tokens"],
                 "tpot_ms_p95": round(warm["tpot_p95_s"] * 1e3, 2),
                 "hit_rate": warm["hit_rate"],
                 "hit_tokens": warm["hit_tokens"],
                 "cow_forks": warm["cow_forks"],
                 "evictions": warm["evictions"],
                 "cache_pages": warm["cache_pages"]},
        "prefill_reduction_x": round(cold["prefill_tokens"]
                                     / max(warm["prefill_tokens"], 1), 2),
        "tpot_p95_ratio": round(warm["tpot_p95_s"]
                                / max(cold["tpot_p95_s"], 1e-9), 3),
        "tokens_match_cold": bool(out_warm == out_cold),
        "leaked_pages_after_drop": warm["leaked_pages_after_drop"],
    }


def _prefix_rows(rec: Dict) -> List[str]:
    c = rec["concurrency"]
    cl, wm = rec["cold"], rec["warm"]
    return [
        f"serving/prefix/cold/c{c},0,"
        f"tok_s={cl['tok_s']}|prefill_toks={cl['prefill_tokens']}"
        f"|tpot_ms_p95={cl['tpot_ms_p95']}",
        f"serving/prefix/warm/c{c},0,"
        f"tok_s={wm['tok_s']}|prefill_toks={wm['prefill_tokens']}"
        f"|hit_rate={wm['hit_rate']}|forks={wm['cow_forks']}",
        f"serving/prefix/quality/c{c},0,"
        f"prefill_x={rec['prefill_reduction_x']}"
        f"|tpot_p95_ratio={rec['tpot_p95_ratio']}"
        f"|match={rec['tokens_match_cold']}"
        f"|leaked={rec['leaked_pages_after_drop']}",
    ]


# ---------------------------------------------------------------------------
# chaos smoke: launch/dryrun --serve-chaos (a CPU-rehearsal subprocess:
# the forced 8-device host platform must not leak into this process)
# ---------------------------------------------------------------------------


def _cpu_child_env() -> Dict[str, str]:
    """Environment of the CPU-rehearsal children below. They start after
    this process has imported JAX, which holds any accelerator, so they
    are pinned to virtual CPU devices (``JAX_PLATFORMS=cpu``) and never
    contend for the chip."""
    env = dict(os.environ, PYTHONPATH=_SRC + os.pathsep
               + os.environ.get("PYTHONPATH", ""), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env


def _chaos_smoke() -> Dict:
    env = _cpu_child_env()
    try:
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--serve-chaos"],
            env=env, capture_output=True, text=True, timeout=900)
        for line in out.stdout.splitlines():
            if line.startswith("{"):
                return json.loads(line)
        return {"ok": False, "error": "no JSON line",
                "stderr": out.stderr[-1500:]}
    except Exception as e:                      # keep the suite alive
        return {"ok": False, "error": f"{type(e).__name__}: {e}"}


def _chaos_rows(rec: Dict) -> List[str]:
    if not rec.get("ok"):
        return [f"serving/chaos_smoke/error,0,"
                f"{str(rec.get('error', 'failed'))[:60]}"]
    return [
        f"serving/chaos_smoke,0,ok={rec['ok']}"
        f"|quarantined={rec['quarantined']}"
        f"|match={rec['tokens_match_undisturbed']}"
        f"|revived={rec['revived']}|total_s={rec['total_s']}",
    ]


# ---------------------------------------------------------------------------
# 1-host vs simulated 8-device mesh (a CPU-rehearsal subprocess: the
# forced device count must not leak into the calling process)
# ---------------------------------------------------------------------------

_MESH_SCRIPT = r"""
import os, json, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, numpy as np
from repro.configs import registry
from repro.launch import mesh as mesh_lib
from repro.models import transformer as T
from repro.serving import Engine, Request, Router

cfg = registry.reduced("qwen3-4b", n_layers=2)
params = T.init(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
def reqs(n):
    return [Request(uid=i, prompt=rng.integers(0, cfg.vocab,
                    int(rng.integers(4, 20))).astype(np.int32), max_new=12)
            for i in range(n)]

def drive(eng, rs):
    for r in rs: eng.submit(r)
    t0 = time.perf_counter(); done = eng.run()
    wall = time.perf_counter() - t0
    return wall, sum(len(r.out_tokens) for r in done), {r.uid: r.out_tokens
                                                        for r in done}

N = 16
rng = np.random.default_rng(0)
single = Engine(cfg, params, batch_slots=8, max_len=64)
w1, t1, out1 = drive(single, reqs(N))
rng = np.random.default_rng(0)
meshes = mesh_lib.make_serving_meshes(replicas=2, model_parallel=2)
router = Router([Engine(cfg, params, batch_slots=8, max_len=64, mesh=m)
                 for m in meshes])
w2, t2, out2 = drive(router, reqs(N))
rep = router.engines[0].cache_report()
print("MESHJSON " + json.dumps({
    "requests": N, "replicas": 2, "model_parallel": 2,
    "single_host": {"tok_s": round(t1 / w1, 2), "pool_bytes":
                    single.cache_report()["pool_bytes"]},
    "mesh": {"tok_s": round(t2 / w2, 2),
             "pool_bytes_per_device": rep["pool_bytes_per_device"],
             "migrations": router.stats["migrations"]},
    "tokens_match": out1 == out2,
}))
"""


def _bench_mesh() -> Dict:
    env = _cpu_child_env()
    try:
        out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                             capture_output=True, text=True, timeout=900)
        for line in out.stdout.splitlines():
            if line.startswith("MESHJSON "):
                return json.loads(line[len("MESHJSON "):])
        return {"error": "no MESHJSON line",
                "stderr": out.stderr[-1500:]}
    except Exception as e:                      # keep the suite alive
        return {"error": f"{type(e).__name__}: {e}"}


def _mesh_rows(rec: Dict) -> List[str]:
    if "error" in rec:
        return [f"serving/mesh/error,0,{rec['error'][:60]}"]
    s, m = rec["single_host"], rec["mesh"]
    return [
        f"serving/mesh/single_host/c{rec['requests']},0,"
        f"tok_s={s['tok_s']}|pool_bytes={s['pool_bytes']}",
        f"serving/mesh/router2xTP2/c{rec['requests']},0,"
        f"tok_s={m['tok_s']}|pool_bytes_dev={m['pool_bytes_per_device']}"
        f"|match={rec['tokens_match']}",
    ]


def run(full: bool = False):
    """Suite entry point: fast smoke by default. Streams CSV rows as each
    cell finishes (the mesh subprocess runs LAST so paged-vs-legacy
    progress is visible while it compiles) and writes the collected JSON
    payload at the end."""
    if full:
        plan = [(fam, arch, over, c) for fam, arch, over in FAMILIES
                for c in (8, 16, 32, 64)]
    else:
        # smoke covers the structured-feature family plus one mixed-
        # geometry plan each: hybrid (kv pages + ssd slots) and enc-dec
        # (kv pages + encoder-memory slots)
        plan = [("kv", "qwen3-4b", {}, 8),
                ("srf", "qwen3-4b", {"attn_impl": "srf"}, 8),
                ("hybrid", "hymba-1.5b", {}, 8),
                ("encdec", "seamless-m4t-large-v2", {}, 8)]
    pairs = []
    for fam, arch, over, c in plan:
        rec = _bench_pair(fam, arch, over, c)
        pairs.append(rec)
        yield from _pair_rows(rec)
    failover = _bench_failover(16)
    yield from _failover_rows(failover)
    shared_prefix = _bench_prefix(64 if full else 16)
    yield from _prefix_rows(shared_prefix)
    mesh = _bench_mesh()
    yield from _mesh_rows(mesh)
    chaos = _chaos_smoke()
    yield from _chaos_rows(chaos)
    payload = {
        "bench": "serving",
        "smoke": not full,
        "backend": jax.default_backend(),
        "paged_vs_legacy": pairs,
        "failover": failover,
        "shared_prefix": shared_prefix,
        "mesh_vs_single_host": mesh,
        "chaos_smoke": chaos,
    }
    default = "BENCH_serving.json" if full else "BENCH_serving_smoke.json"
    path = os.environ.get("REPRO_BENCH_SERVING_JSON", default)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")


def main(argv=None):
    args = list(argv if argv is not None else sys.argv[1:])
    if "--failover" in args:
        # re-measure ONLY the failover cell and splice it into the
        # committed full-sweep JSON (the sweep itself takes far longer)
        print("name,us_per_call,derived")
        rec = _bench_failover(16)
        for row in _failover_rows(rec):
            print(row, flush=True)
        path = os.environ.get("REPRO_BENCH_SERVING_JSON",
                              "BENCH_serving.json")
        payload = {"bench": "serving"}
        if os.path.exists(path):
            with open(path) as f:
                payload = json.load(f)
        payload["failover"] = rec
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        return 0
    if "--prefix" in args:
        # re-measure ONLY the shared-prefix cell and splice it into the
        # committed full-sweep JSON (same pattern as --failover)
        print("name,us_per_call,derived")
        rec = _bench_prefix(64)
        for row in _prefix_rows(rec):
            print(row, flush=True)
        path = os.environ.get("REPRO_BENCH_SERVING_JSON",
                              "BENCH_serving.json")
        payload = {"bench": "serving"}
        if os.path.exists(path):
            with open(path) as f:
                payload = json.load(f)
        payload["shared_prefix"] = rec
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        return 0
    full = "--full" in args
    print("name,us_per_call,derived")
    for row in run(full=full):
        print(row, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
