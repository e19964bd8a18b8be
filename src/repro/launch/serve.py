"""Serving launcher: paged continuous-batching engine, optionally
mesh-sharded and router-replicated.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b [--reduced] \
        --requests 16 --prompt-len 16 --max-new 24 [--attn srf] \
        [--policy priority] [--temperature 0.8 --top-k 40] [--legacy] \
        [--replicas 2] [--model-parallel 2] [--quantize-kv]

The arch serves at its published widths (``registry.get``); ``--reduced``
opts in to the toy-width config (``registry.reduced``) for CPU runs.
Every registry family serves through the paged engine — dense/moe/mla,
ssm (constant-state slots), hybrid (kv pages + ssd slots), enc-dec
(synthetic frontend features are generated per request and encoded once
at admission) and the vlm/audio frontend archs.

``--attn srf`` serves with the paper's SRF attention: the per-request
cache is one constant-size O(m d) state page instead of O(L) KV pages.
``--legacy`` runs the old per-slot lock-step engine (the test oracle)
for comparison.
``--replicas``/``--model-parallel`` route requests across engine
replicas whose page pools are model-axis sharded (``serving/mesh``);
``--quantize-kv`` stores KV pages as int8 with per-page-row scales.
``--prefix-cache`` arms the prefix-sharing subsystem (radix cache +
copy-on-write paged KV, ``serving/prefix``); ``--cache-bytes`` bounds
its footprint and ``--chunk-tokens`` budgets chunked prefill so long
cold prompts interleave with decode. ``--shared-prefix N`` makes the
synthetic prompts share their first N tokens, so hit rates are visible.
``--ft`` arms the fault-tolerant router (replica watchdog + failover
with request rescue, ``serving/ft.py``), ``--deadline S`` gives every
request an S-second deadline (overdue waiting requests finish as
``timeout``), and ``--chaos KIND@STEP[:REPLICA]`` injects a scripted
fault through the TEST-ONLY harness (``serving/chaos.py``) to
demonstrate the recovery path end to end.

Telemetry: every engine replica and the router share ONE
``obs.MetricsRegistry``; ``--metrics`` prints a live one-line report
every ``--metrics-every`` seconds plus a final latency-percentile dump,
``--metrics-out FILE`` additionally writes the Prometheus text
exposition (+ ``FILE.events.jsonl``). All output routes through
``obs.report.Reporter`` — this module is lint-pinned print-free
(``tests/test_obs.py``).
"""
from __future__ import annotations

import argparse
import contextlib
import glob
import os
import sys
import time

import jax
import numpy as np

from repro import obs
from repro.configs import registry
from repro.launch import compile_cache
from repro.launch import mesh as mesh_lib
from repro.models import transformer as model_lib
from repro.obs import devtrace
from repro.obs import export as trace_export
from repro.obs import quality as quality_lib
from repro.obs import spans as spans_lib
from repro.obs.report import Reporter
from repro.serving import Engine, PagedConfig, Request, Router


def _report_device_trace(rep: Reporter, trace_dir: str) -> None:
    """The newest trace under ``trace_dir``, read against the engine's
    spans: each decode step's device ms by named scope (medians) and the
    five longest device-idle gaps inside engine steps."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        rep.line(f"[device-trace] no trace under {trace_dir}")
        return
    tr = devtrace.read(max(found, key=os.path.getmtime))
    parts = devtrace.step_parts(tr, "decode_step")
    rep.line("[device-trace] decode step ms (median): " + " ".join(
        f"{k}={v:.3f}" for k, v in parts.items()))
    for g in devtrace.idle_gaps(tr, "engine_step", top=5):
        rep.line(f"[device-trace] idle {g['ms']:.3f} ms in {g['leaf']}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=registry.ARCHS)
    ap.add_argument("--reduced", action="store_true",
                    help="toy-width config (registry.reduced) for CPU runs")
    ap.add_argument("--attn", default=None, choices=[None, "full", "srf"])
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--policy", default="fcfs", choices=["fcfs", "priority"])
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--legacy", action="store_true",
                    help="old per-slot engine (baseline)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="router-managed engine replicas")
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="model-axis TP width per replica (shards pools)")
    ap.add_argument("--quantize-kv", action="store_true",
                    help="int8 KV pages + per-page-row scales (kv family)")
    ap.add_argument("--ft", action="store_true",
                    help="fault-tolerant router: replica health watchdog "
                         "+ failover with request rescue (multi-replica)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline in seconds; overdue waiting "
                         "requests finish with reason 'timeout'")
    ap.add_argument("--chaos", default=None, metavar="KIND@STEP[:REPLICA]",
                    help="TEST-ONLY fault injection (kinds: raise|hang|"
                         "reject|oom), e.g. raise@6:1; needs --ft and "
                         "--replicas >= 2 to demonstrate recovery")
    ap.add_argument("--metrics", action="store_true",
                    help="periodic one-line metrics report + final "
                         "latency-percentile dump from the shared registry")
    ap.add_argument("--metrics-every", type=float, default=2.0,
                    help="seconds between periodic metrics lines")
    ap.add_argument("--metrics-out", default=None,
                    help="write Prometheus text exposition here "
                         "(+ .events.jsonl) at exit")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache: requests sharing a cached "
                         "prompt prefix reuse its KV pages (COW) instead "
                         "of re-prefilling (serving/prefix)")
    ap.add_argument("--cache-bytes", type=int, default=0,
                    help="prefix-cache byte budget (0 = unbounded; LRU "
                         "eviction above the budget)")
    ap.add_argument("--chunk-tokens", type=int, default=0,
                    help="chunked-prefill token budget per step (0 = full "
                         "jit budget); long cold prompts admit in chunks "
                         "interleaved with decode")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="synthetic prompts share their first N tokens "
                         "(workload shaping for --prefix-cache demos)")
    ap.add_argument("--quality-every", type=int, default=64,
                    help="decode steps between SRF row-gaussianity quality "
                         "probes (srf_row_* gauges; 0 disables)")
    ap.add_argument("--quality-tol", type=float,
                    default=quality_lib.DRIFT_TOL,
                    help="row-moment drift tolerance; past it the engine "
                         "emits a quality_drift registry event")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="record span timelines on every replica and the "
                         "router, write a merged Chrome-trace JSON here "
                         "at exit (load in Perfetto / chrome://tracing)")
    ap.add_argument("--device-trace", default=None, metavar="DIR",
                    help="run the serving loop under jax.profiler, writing "
                         "the trace under DIR, and print the decode step's "
                         "device time by named scope and the longest "
                         "device-idle gaps by span (obs/devtrace.py)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    rep = Reporter()
    metrics = obs.MetricsRegistry()
    tracing = (args.trace_out or args.device_trace) and not args.legacy
    recorders = [spans_lib.SpanRecorder(replica=i)
                 for i in range(max(args.replicas, 1))] if tracing else []

    def _spans(i):
        return recorders[i] if tracing else None
    compile_cache.enable_compile_cache()
    overrides = {"attn_impl": args.attn} if args.attn else {}
    cfg = (registry.reduced if args.reduced else registry.get)(
        args.arch, **overrides)
    params = model_lib.init(jax.random.PRNGKey(args.seed), cfg)
    paged = PagedConfig(quantize_kv=args.quantize_kv)
    prefix = None
    if args.prefix_cache or args.cache_bytes or args.chunk_tokens:
        from repro.serving import ChunkConfig, PrefixConfig
        prefix = PrefixConfig(
            cache_bytes=args.cache_bytes,
            chunk=ChunkConfig(chunk_tokens=args.chunk_tokens))
    if args.legacy:
        from repro.serving import legacy
        eng = legacy.Engine(cfg, params, batch_slots=args.slots,
                            max_len=args.max_len)
    elif args.replicas > 1 or args.model_parallel > 1:
        from repro.serving import FTConfig
        meshes = mesh_lib.make_serving_meshes(args.replicas,
                                              args.model_parallel)
        engines = [Engine(cfg, params, batch_slots=args.slots,
                          max_len=args.max_len, policy=args.policy,
                          seed=args.seed + i, mesh=m, paged=paged,
                          metrics=metrics, prefix=prefix,
                          quality_every=args.quality_every,
                          quality_tol=args.quality_tol, spans=_spans(i))
                   for i, m in enumerate(meshes)]
        if args.chaos:
            from repro.serving.chaos import ChaosEngine, ChaosPlan
            spec, _, rep_s = args.chaos.partition(":")
            kind, _, step_s = spec.partition("@")
            rep_i = int(rep_s or (len(engines) - 1))
            engines[rep_i] = ChaosEngine(
                engines[rep_i], ChaosPlan(kind, at_step=int(step_s or 5)))
            rep.line(f"[chaos] replica {rep_i}: {kind}@{step_s or 5} "
                     "(test-only fault injection)")
        if tracing:
            # the router's own spans (scoring, quarantine/rescue/replay)
            # merge as one extra timeline row past the replica rows
            recorders.append(spans_lib.SpanRecorder(replica=len(engines)))
        eng = Router(engines, metrics=metrics,
                     ft=FTConfig() if args.ft else None,
                     spans=recorders[-1] if tracing else None)
    else:
        eng = Engine(cfg, params, batch_slots=args.slots,
                     max_len=args.max_len, policy=args.policy,
                     seed=args.seed, paged=paged, metrics=metrics,
                     prefix=prefix, quality_every=args.quality_every,
                     quality_tol=args.quality_tol, spans=_spans(0))
    rng = np.random.default_rng(args.seed)
    common = rng.integers(0, cfg.vocab, max(args.shared_prefix, 0)
                          ).astype(np.int32)
    t0 = time.perf_counter()
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab,
                              args.prompt_len).astype(np.int32)
        if len(common):
            prompt = np.concatenate([common, prompt[len(common):]]) \
                if args.prompt_len > len(common) else common.copy()
        enc = None
        if cfg.is_encdec:
            from repro.models import frontends
            enc = frontends.synthetic_audio_features(rng, cfg)
        eng.submit(Request(uid=i, prompt=prompt, max_new=args.max_new,
                           priority=int(rng.integers(0, 3)),
                           temperature=args.temperature,
                           top_k=args.top_k, top_p=args.top_p,
                           enc_emb=enc, deadline=args.deadline))
    on_step = (rep.periodic(metrics, every_s=args.metrics_every)
               if args.metrics and not args.legacy else None)
    with (jax.profiler.trace(args.device_trace) if args.device_trace
          else contextlib.nullcontext()):
        done = (eng.run() if args.legacy else eng.run(on_step=on_step))
    dt = time.perf_counter() - t0
    tok = sum(len(r.out_tokens) for r in done)
    engine = ("legacy" if args.legacy else
              "router" if isinstance(eng, Router) else "paged")
    rep.line(f"arch={args.arch} attn={cfg.attn_impl} engine={engine} "
             f"requests={len(done)} tokens={tok} wall={dt:.2f}s "
             f"tok/s={tok/dt:.1f}")
    if isinstance(eng, Router):
        rep.line(f"  router: {eng.describe()}")
        rep.line(f"  replica0 report: {eng.engines[0].cache_report()}")
    elif not args.legacy:
        rep.line(f"  sched: {dict(eng.sched.stats)}  "
                 f"report: {eng.cache_report()}")
    if prefix is not None and not args.legacy:
        v = metrics.value_sum
        rep.line(f"  prefix: hits={int(v('prefix_hits_total'))} "
                 f"hit_tokens={int(v('prefix_hit_tokens_total'))} "
                 f"cow_forks={int(v('prefix_cow_forks_total'))} "
                 f"evictions={int(v('prefix_evictions_total'))} "
                 f"cache_bytes={int(v('prefix_cache_bytes'))}")
    for r in done[:3]:
        ttft = (f"{r.t_first - r.t_submit:.3f}s" if r.t_first
                else f"n/a ({r.finish_reason})")   # expired/shed: no token
        rep.line(f"  req{r.uid}: ttft={ttft} out={r.out_tokens[:8]}...")
    if args.metrics or args.metrics_out:
        rep.final(metrics, done, dump_path=args.metrics_out)
    if tracing and args.device_trace:
        _report_device_trace(rep, args.device_trace)
    if tracing and args.trace_out:
        n = trace_export.dump_chrome_trace(args.trace_out, recorders)
        spans = sum(len(r) for r in recorders)
        dropped = sum(r.dropped for r in recorders)
        rep.line(f"[trace] {args.trace_out}: {n} events from {spans} "
                 f"spans across {len(recorders)} timelines"
                 + (f" ({dropped} dropped)" if dropped else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
