"""Step-function factories shared by the trainer, the server and the
multi-pod dry-run. Pure functions of (params, state, batch) — jit/sharding
is applied by the caller.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import transformer as model
from repro.optim import adamw, schedule


def _prewarm_srf_spinner(cfg) -> None:
    """Populate the fused-spinner block-size plan cache for every block of
    the SRF feature pipeline this config will serve. The sweep itself is
    cheap (a pure-Python candidate scan); the point is to pin the plan at
    factory time so every step dispatch sees a warm, deterministic cache
    and the chosen blocks are inspectable before the first request."""
    if getattr(cfg, "attn_impl", None) != "srf":
        return
    import jax.numpy as _jnp
    from repro.core import spinner
    from repro.kernels import ops as kops
    from repro.models.attention import srf_cfg
    sc = srf_cfg(cfg)
    pipe = sc.pipeline
    dtype = _jnp.dtype(getattr(cfg, "dtype", "float32"))
    # softmax_pos: keys use the fused 'exp' epilogue; the stabilized query
    # path projects with 'identity' (overflow-safe shift applied outside).
    # Nonlinearities with needs_input (exp's subtrahend is the pipeline
    # input norm) fuse in-kernel only at depth 1 — same rule as
    # SpinnerPipeline.apply — so deeper pipelines warm 'identity' instead.
    last = {"softmax_pos": ("exp", "identity"), "trig": ("cos_sin",),
            "relu": ("relu",)}[sc.feature]
    if pipe.depth > 1:
        last = tuple(dict.fromkeys(
            "identity" if spinner.nonlinearity(e).needs_input else e
            for e in last))
    for i, blk in enumerate(pipe.blocks):
        epis = last if i == pipe.depth - 1 else ("identity",)
        for epi in epis:
            kops.spinner_plan(blk.kind, blk.n, blk.m, use_hd=blk.use_hd,
                              epilogue=epi, dtype=dtype, seeded=blk.seeded)


@dataclass(frozen=True)
class TrainHyper:
    lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 10000
    adam: adamw.AdamWConfig = adamw.AdamWConfig()
    aux_weight: float = 0.01


def make_train_step(cfg, hyper: TrainHyper = TrainHyper(),
                    grad_shardings=None):
    """``grad_shardings``: optional NamedSharding tree = the ZeRO-1 moment
    shardings. Constraining the bf16 grads to it BEFORE the optimizer's
    f32 upcast makes XLA reduce-scatter bf16 gradients to the moment
    shards instead of all-gathering f32 ones (2x collective bytes on the
    MoE cells, measured — EXPERIMENTS.md §Perf-hillclimb A4)."""
    def train_step(params, opt_state, step_idx, batch):
        def loss(p):
            return model.loss_fn(p, cfg, batch, hyper.aux_weight)
        (l, metrics), grads = jax.value_and_grad(loss, has_aux=True)(params)
        if grad_shardings is not None:
            grads = jax.lax.with_sharding_constraint(grads, grad_shardings)
        lr = schedule.warmup_cosine(step_idx, hyper.lr, hyper.warmup,
                                    hyper.total_steps)
        params, opt_state, stats = adamw.update(grads, opt_state, params,
                                                lr, hyper.adam)
        out = {"loss": l, "lr": lr, **metrics, **stats}
        return params, opt_state, out
    return train_step


def make_grad_step(cfg, aux_weight: float = 0.01):
    """Gradients only (used by the compressed-DP trainer, which applies the
    optimizer after the explicit cross-pod reduction)."""
    def grad_step(params, batch):
        def loss(p):
            return model.loss_fn(p, cfg, batch, aux_weight)
        (l, metrics), grads = jax.value_and_grad(loss, has_aux=True)(params)
        return grads, {"loss": l, **metrics}
    return grad_step


def make_prefill_step(cfg):
    _prewarm_srf_spinner(cfg)
    def prefill_step(params, batch, cache):
        return model.prefill(params, cfg, batch, cache)
    return prefill_step


def make_encode_step(cfg):
    """Enc-dec encoder pass: (params, enc_emb (B, E, feat)) -> memory
    (B, E, d_model). The paged engine runs this once per request at
    admission (batch 1 — bit-identical to the legacy per-slot prefill)
    and caches the result in the read-only encoder-memory pool."""
    def encode_step(params, enc_emb):
        return model.encode_memory(params, cfg, enc_emb)
    return encode_step


def make_paged_step(cfg, mesh=None, paged=None, params_sds=None):
    """Batched paged serving step (decode: C = 1; chunked prefill: C = chunk).

    (params, pools, tokens (B, C), positions (B, C), q_valid (B, C),
    tables (B, M), slots (B,)) -> (logits (B, C, V_padded), pools').
    ``pools`` is the full container from ``serving.paged_cache``
    (paged-domain pages + constant-state slots + optional enc-dec
    memory); ``slots`` indexes the slot-domain pools and the memory pool
    (0 = null slot for padded rows) and threads the per-request encoder
    memory through to the cross-attending decoder layers. One jit cache
    entry per (B, C) shape — the engine keeps those fixed. With SRF
    attention the phi(q)/phi(k) feature maps inside run as single fused
    spinner passes; the factory pre-warms their block-size plan.

    ``mesh``: mesh-sharded serving. When the family's head dims divide
    the mesh's model axis (``serving.mesh.shard.paged_tp``), the step is
    wrapped in a manual shard_map: q/k/v projections arrive column-
    parallel sliced, pools arrive as the local head block, the body runs
    ``model.paged_step`` under the shard-local config, and attention
    stitches the per-shard head outputs with a model-axis all-gather
    (``distributed.collectives.stitch_heads``) before contracting the
    deliberately REPLICATED wo — that keeps the d_model reduction in
    single-host order, so greedy tokens are bit-identical to the
    unsharded engine (a row-parallel wo + psum re-associates the sum).
    The paged-gather kernel then runs per-shard on the local pool slice.
    Families that degrade to replication (mla / ssd / indivisible heads)
    fall back to the plain body — identical work on every device, pools
    replicated.

    ``paged`` (``serving.paged_cache.PagedConfig``) only changes the
    pool *structure* the specs are derived from (int8 scale leaves);
    ``params_sds`` (any tree of arrays or ShapeDtypeStructs, e.g. the
    engine's real params) supplies the parameter shapes the in_specs are
    derived from, avoiding an abstract re-trace of ``model.init``.

    Seeded-SRF configs (``cfg.srf.seeded``) get an EIGHTH positional
    argument ``embed_seeds (B,) uint32`` — per-request projection seeds
    (0 = base projection); non-seeded configs keep the 7-arg signature
    so existing call sites and jit caches are untouched.
    """
    _prewarm_srf_spinner(cfg)
    seeded_srf = (getattr(cfg, "attn_impl", None) == "srf"
                  and getattr(cfg.srf, "seeded", False))

    if seeded_srf:
        def paged_step(params, pools, tokens, positions, q_valid, tables,
                       slots, embed_seeds):
            return model.paged_step(params, cfg, pools, tokens, positions,
                                    q_valid, tables, slots,
                                    embed_seeds=embed_seeds)
    else:
        def paged_step(params, pools, tokens, positions, q_valid, tables,
                       slots):
            return model.paged_step(params, cfg, pools, tokens, positions,
                                    q_valid, tables, slots)

    if mesh is None:
        return paged_step
    from jax.sharding import PartitionSpec as P
    from repro.serving.mesh import shard as mesh_shard

    tp = mesh_shard.paged_tp(cfg, mesh)
    if tp <= 1:
        return paged_step               # replication degradation: plain body

    cfg_local = mesh_shard.local_cfg(cfg, tp)
    if params_sds is None:
        params_sds = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), cfg))
    pspecs = mesh_shard.serving_param_specs(params_sds, cfg, mesh)
    poolspecs = mesh_shard.pool_specs(cfg, mesh, paged)
    rep = P()

    if seeded_srf:
        def body(params, pools, tokens, positions, q_valid, tables, slots,
                 embed_seeds):
            return model.paged_step(params, cfg_local, pools, tokens,
                                    positions, q_valid, tables, slots,
                                    tp_axis="model",
                                    embed_seeds=embed_seeds)
        in_specs = (pspecs, poolspecs, rep, rep, rep, rep, rep, rep)
    else:
        def body(params, pools, tokens, positions, q_valid, tables, slots):
            return model.paged_step(params, cfg_local, pools, tokens,
                                    positions, q_valid, tables, slots,
                                    tp_axis="model")
        in_specs = (pspecs, poolspecs, rep, rep, rep, rep, rep)

    # Manual over every mesh axis, with varying-axes checking off: the
    # Pallas kernels in the body declare plain output shapes (no vma),
    # and the layer scan's carry would then mix varying and invariant
    # types. The body is explicit per-shard compute plus stitch_heads.
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=(rep, poolspecs), check_vma=False)


def make_serve_step(cfg, greedy: bool = True, temperature: float = 1.0):
    """One decode step: (params, cache, tokens(B,1)) -> (next(B,1), cache)."""
    def serve_step(params, cache, tokens):
        logits, cache = model.decode_step(params, cfg, cache, tokens)
        logits = logits[:, -1, : cfg.vocab]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return nxt, logits, cache
    return serve_step
