"""Unified model: decoder LMs (dense/MoE/MLA), SSM, hybrid, enc-dec, VLM.

One config-driven implementation covering all ten assigned architectures.
Layers are grouped into SEGMENTS of identical structure and executed with
``lax.scan`` over stacked parameters (constant-size HLO at any depth —
what makes 512-device compiles fast) with selectable remat.

Public API (pure functions):
    init(rng, cfg)                       -> params
    forward(params, cfg, batch)          -> (logits, aux)     train mode
    loss_fn(params, cfg, batch)          -> (loss, metrics)
    init_serve_cache(cfg, batch, maxlen) -> cache
    prefill(params, cfg, batch, cache)   -> (last_logits, cache)
    decode_step(params, cfg, cache, tok) -> (logits, cache)
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention, frontends, hooks, layers, moe, ssm


# ---------------------------------------------------------------------------
# segments
# ---------------------------------------------------------------------------

def segments(cfg) -> List[Tuple[str, int]]:
    """[(layer_kind, count)] for the decoder stack."""
    if cfg.family == "ssm":
        return [("ssm", cfg.n_layers)]
    if cfg.family == "hybrid":
        return [("hybrid", cfg.n_layers)]
    if cfg.is_encdec:
        return [("dense_cross", cfg.n_layers)]
    if cfg.is_moe:
        segs = []
        if cfg.moe_first_dense:
            segs.append(("dense", cfg.moe_first_dense))
        segs.append(("moe", cfg.n_layers - cfg.moe_first_dense))
        return segs
    return [("dense", cfg.n_layers)]


def _layer_plan(cfg) -> List[Tuple[str, int, Tuple[str, ...]]]:
    """Serving-state plan: per segment ``(kind, count, components)``.

    ``components`` names the decode-state objects EVERY layer of the
    segment owns — ``"attn"`` (kv / mla pages or the srf constant state,
    resolved by ``serving.paged_cache.attn_family_for``) and/or ``"ssm"``
    (the ssd constant state). Hybrid layers own both; the enc-dec
    encoder memory is model-level (one pool, not per layer) and is keyed
    off ``cfg.is_encdec`` by the pool plan instead."""
    plan = []
    for kind, count in segments(cfg):
        if kind == "ssm":
            comps: Tuple[str, ...] = ("ssm",)
        elif kind == "hybrid":
            comps = ("attn", "ssm")
        else:
            comps = ("attn",)
        plan.append((kind, count, comps))
    return plan


def layer_init(rng, cfg, kind: str, dtype) -> Dict:
    keys = jax.random.split(rng, 8)
    d = cfg.d_model
    p: Dict = {"ln1": layers.rmsnorm_init(d, dtype)}
    if kind == "ssm":
        p["ssm"] = ssm.ssm_init(keys[0], cfg, dtype)
        return p
    if kind == "hybrid":
        p["attn"] = attention.attn_init(keys[0], cfg, dtype)
        p["ssm"] = ssm.ssm_init(keys[1], cfg, dtype)
        p["fuse_na"] = layers.rmsnorm_init(d, dtype)
        p["fuse_ns"] = layers.rmsnorm_init(d, dtype)
        p["ln2"] = layers.rmsnorm_init(d, dtype)
        p["mlp"] = layers.mlp_init(keys[2], d, cfg.d_ff, dtype)
        return p
    p["attn"] = attention.attn_init(keys[0], cfg, dtype)
    p["ln2"] = layers.rmsnorm_init(d, dtype)
    if kind == "dense_cross":
        p["ln_x"] = layers.rmsnorm_init(d, dtype)
        p["cross"] = attention.cross_attn_init(keys[1], cfg, dtype)
        p["mlp"] = layers.mlp_init(keys[2], d, cfg.d_ff, dtype)
    elif kind == "moe":
        p["moe"] = moe.moe_init(keys[1], cfg, dtype)
    else:  # dense
        p["mlp"] = layers.mlp_init(keys[1], d, cfg.d_ff, dtype)
    return p


def layer_apply(p, cfg, kind: str, x, positions, mode: str,
                cache: Optional[Dict], pos3=None, memory=None
                ) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
    """-> (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "ssm":
        h, new_cache = ssm.ssm_apply(p["ssm"], cfg, layers.rmsnorm(p["ln1"], x, cfg.norm_eps),
                                     mode, cache)
        return x + h, new_cache, aux
    if kind == "hybrid":
        h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
        a, cache_a = attention.attention(p["attn"], cfg, h, positions, mode,
                                         None if cache is None else cache["attn"], pos3)
        s, cache_s = ssm.ssm_apply(p["ssm"], cfg, h, mode,
                                   None if cache is None else cache["ssm"])
        fused = 0.5 * (layers.rmsnorm(p["fuse_na"], a, cfg.norm_eps)
                       + layers.rmsnorm(p["fuse_ns"], s, cfg.norm_eps))
        x = x + fused
        x = x + layers.mlp(p["mlp"], layers.rmsnorm(p["ln2"], x, cfg.norm_eps))
        new_cache = None
        if cache_a is not None or cache_s is not None:
            new_cache = {"attn": cache_a, "ssm": cache_s}
        return x, new_cache, aux
    # attention families
    h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
    a, new_cache = attention.attention(p["attn"], cfg, h, positions, mode,
                                       cache, pos3)
    x = x + a
    if kind == "dense_cross" and memory is not None:
        x = x + attention.cross_attention(
            p["cross"], cfg, layers.rmsnorm(p["ln_x"], x, cfg.norm_eps), memory)
    h2 = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if kind == "moe":
        y, aux = moe.moe_apply(p["moe"], cfg, h2)
    else:
        y = layers.mlp(p["mlp"], h2)
    return x + y, new_cache, aux


# ---------------------------------------------------------------------------
# model init
# ---------------------------------------------------------------------------

def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


def init(rng, cfg) -> Dict:
    dt = _dtype(cfg)
    keys = jax.random.split(rng, 8)
    params: Dict = {"embed": layers.embed_init(keys[0], cfg.padded_vocab,
                                               cfg.d_model, dt)}
    segs = segments(cfg)
    params["segments"] = []
    for i, (kind, count) in enumerate(segs):
        lkeys = jax.random.split(jax.random.fold_in(keys[1], i), count)
        stacked = jax.vmap(lambda k: layer_init(k, cfg, kind, dt))(lkeys)
        params["segments"].append(stacked)
    if cfg.is_encdec:
        ekeys = jax.random.split(keys[2], cfg.enc_layers)
        params["encoder"] = jax.vmap(
            lambda k: layer_init(k, cfg, "dense", dt))(ekeys)
        params["enc_norm"] = layers.rmsnorm_init(cfg.d_model, dt)
    if cfg.frontend != "none":
        params["frontend"] = frontends.frontend_init(keys[3], cfg, dt)
    params["final_norm"] = layers.rmsnorm_init(cfg.d_model, dt)
    if not cfg.tie_embeddings:
        params["head"] = layers.dense_init(keys[4], cfg.d_model,
                                           cfg.padded_vocab, dt)
    return params


# ---------------------------------------------------------------------------
# segment runners (scan over stacked layers)
# ---------------------------------------------------------------------------

def _remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)




def run_segment(stacked, cfg, kind: str, x, positions, mode: str,
                caches=None, pos3=None, memory=None):
    """scan over layers of one segment. Returns (x, new_caches, aux_sum)."""
    if mode in ("train", "encoder"):
        count = jax.tree.leaves(stacked)[0].shape[0]
        g = cfg.scan_group if (cfg.scan_group > 1 and
                               count % cfg.scan_group == 0) else 1

        def one_layer(x, lp):
            y, _, aux = layer_apply(lp, cfg, kind, x, positions, mode,
                                    None, pos3, memory)
            return y, aux

        # NESTED remat when g > 1: the outer checkpoint makes the scan save
        # the residual only every g layers ((L/g, B, T, d) stack — XLA
        # widens it to f32, so size matters); the inner per-layer
        # checkpoints make the group backward recompute ONE layer's
        # internals at a time instead of g at once. Both measured in
        # EXPERIMENTS.md §Perf.
        inner = _remat(cfg, one_layer) if g > 1 else one_layer

        def body(x, lp_group):
            # sequence-parallel residual: between layers x is sharded over
            # ('data' x batch, 'model' x sequence) — Megatron SP. The scan's
            # saved-for-backward residual stack inherits this sharding, so
            # its per-device footprint drops by the TP width. XLA inserts
            # the all-gather (pre-attention) / reduce-scatter (post-wo)
            # pair automatically from the sharding constraint.
            x = hooks.constrain(jax.lax.optimization_barrier(x), "residual")
            aux = jnp.zeros((), jnp.float32)
            for i in range(g):
                lp = jax.tree.map(lambda a: a[i], lp_group) if g > 1 \
                    else lp_group
                x, a = inner(x, lp)
                aux = aux + a
            return jax.lax.optimization_barrier(x), aux

        body = _remat(cfg, body)
        grouped = stacked if g == 1 else jax.tree.map(
            lambda a: a.reshape(count // g, g, *a.shape[1:]), stacked)
        x, auxs = jax.lax.scan(body, x, grouped)
        return x, None, jnp.sum(auxs)
    if mode == "prefill":
        def body(x, inp):
            lp, cproto = inp           # cproto: pre-allocated cache buffers
            y, cache, _ = layer_apply(lp, cfg, kind, x, positions, "prefill",
                                      cproto, pos3, memory)
            return y, cache
        x, new_caches = jax.lax.scan(body, x, (stacked, caches))
        return x, new_caches, jnp.zeros(())
    if mode == "decode":
        def body(x, inp):
            lp, cache = inp
            y, new_cache, _ = layer_apply(lp, cfg, kind, x, positions,
                                          "decode", cache, pos3, memory)
            return y, new_cache
        x, new_caches = jax.lax.scan(body, x, (stacked, caches))
        return x, new_caches, jnp.zeros(())
    raise ValueError(mode)


# ---------------------------------------------------------------------------
# embedding / inputs
# ---------------------------------------------------------------------------

def encode_memory(params, cfg, enc_emb: jax.Array) -> jax.Array:
    """Run the encoder once: (B, enc_len, feat) -> (B, enc_len, d_model).
    Shared by training/prefill (``embed_inputs``) and the paged engine,
    which encodes per request at admission and caches the result in the
    read-only encoder-memory pool — the computation (and its bits) is the
    same either way."""
    dt = _dtype(cfg)
    enc_x = frontends.frontend_apply(params["frontend"], cfg,
                                     enc_emb).astype(dt)
    b, s, _ = enc_x.shape
    enc_pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    enc_x, _, _ = run_segment(params["encoder"], cfg, "dense", enc_x,
                              enc_pos, "encoder")
    return layers.rmsnorm(params["enc_norm"], enc_x, cfg.norm_eps)


def embed_inputs(params, cfg, batch: Dict, decode: bool = False):
    """-> (x, positions, pos3, memory). Handles vlm/audio stubs + encdec."""
    dt = _dtype(cfg)
    pos3 = batch.get("pos3")
    memory = None
    if cfg.is_encdec:
        memory = encode_memory(params, cfg, batch["enc_emb"])
    tokens = batch["tokens"]
    x = layers.embed(params["embed"], tokens).astype(dt)
    if cfg.frontend == "vision_stub" and not decode and "vision_emb" in batch:
        v = frontends.frontend_apply(params["frontend"], cfg,
                                     batch["vision_emb"]).astype(dt)
        x = jnp.concatenate([v, x], axis=1)
    b, l, _ = x.shape
    if "positions" in batch:
        positions = batch["positions"]
    else:
        positions = jnp.broadcast_to(jnp.arange(l)[None], (b, l))
    x = hooks.constrain(x, "activation")
    return x, positions, pos3, memory


def _logits(params, cfg, x):
    x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    w = params["embed"]["tok"].T if cfg.tie_embeddings else params["head"]
    logits = x @ w
    return hooks.constrain(logits, "logits")


# ---------------------------------------------------------------------------
# training entry points
# ---------------------------------------------------------------------------

def forward(params, cfg, batch: Dict) -> Tuple[jax.Array, jax.Array]:
    x, positions, pos3, memory = embed_inputs(params, cfg, batch)
    aux_total = jnp.zeros((), jnp.float32)
    for seg_params, (kind, _) in zip(params["segments"], segments(cfg)):
        x, _, aux = run_segment(seg_params, cfg, kind, x, positions, "train",
                                pos3=pos3, memory=memory)
        aux_total = aux_total + aux
    return _logits(params, cfg, x), aux_total


def loss_fn(params, cfg, batch: Dict, aux_weight: float = 0.01
            ) -> Tuple[jax.Array, Dict]:
    logits, aux = forward(params, cfg, batch)
    labels = batch["labels"]
    if logits.shape[1] != labels.shape[1]:      # vlm: vision prefix unlabeled
        logits = logits[:, -labels.shape[1]:]
    xent = layers.cross_entropy(logits, labels, cfg.vocab)
    loss = xent + aux_weight * aux
    return loss, {"xent": xent, "aux": aux}


# ---------------------------------------------------------------------------
# serving entry points
# ---------------------------------------------------------------------------

def init_serve_cache(cfg, batch_size: int, max_len: int) -> Dict:
    dt = _dtype(cfg)
    segs = segments(cfg)
    caches = []
    for kind, count in segs:
        def one(_):
            if kind == "ssm":
                return ssm.init_ssm_cache(cfg, batch_size, dt)
            if kind == "hybrid":
                return {"attn": attention.init_cache(cfg, batch_size, max_len, dt),
                        "ssm": ssm.init_ssm_cache(cfg, batch_size, dt)}
            return attention.init_cache(cfg, batch_size, max_len, dt)
        caches.append(jax.tree.map(
            lambda *xs: jnp.stack(xs), *[one(i) for i in range(count)]))
    out = {"segments": caches, "pos": jnp.zeros((), jnp.int32)}
    if cfg.is_encdec:
        out["memory"] = jnp.zeros((batch_size, cfg.enc_len, cfg.d_model), dt)
    return out


def prefill(params, cfg, batch: Dict, cache: Dict) -> Tuple[jax.Array, Dict]:
    x, positions, pos3, memory = embed_inputs(params, cfg, batch)
    new_segs = []
    for seg_params, seg_cache, (kind, _) in zip(params["segments"],
                                                cache["segments"], segments(cfg)):
        x, new_c, _ = run_segment(seg_params, cfg, kind, x, positions,
                                  "prefill", caches=seg_cache, pos3=pos3,
                                  memory=memory)
        new_segs.append(new_c)
    logits = _logits(params, cfg, x[:, -1:])
    out = {"segments": new_segs, "pos": jnp.asarray(x.shape[1], jnp.int32)}
    if cfg.is_encdec:
        out["memory"] = memory
    return logits, out


def paged_step(params, cfg, pools: Dict, tokens: jax.Array,
               positions: jax.Array, q_valid: jax.Array,
               tables: jax.Array, slots: jax.Array,
               tp_axis: Optional[str] = None,
               embed_seeds: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, Dict]:
    """One batched step against pooled paged caches (serving hot path).

    tokens: (B, C) int32 — C = 1 for batched decode, C = prefill chunk
    for chunked prefill; both run through the same code. positions: (B, C)
    absolute positions; q_valid: (B, C) validity (False rows/tails are
    padding); tables: (B, M) page ids into the paged-domain pools;
    slots: (B,) slot ids into the constant-state pools and the enc-dec
    memory pool (0 = null slot for padded rows). ``pools`` is the full
    container from ``serving.paged_cache.init_pools`` ({"paged", "slot"}
    per-segment lists + optional "memory"). Returns
    (logits (B, C, V_padded), pools').

    Layers scan over (stacked params, stacked per-layer pools of BOTH
    domains — hybrid layers carry a kv sub-pool and an ssd sub-pool
    side by side); tables / positions are loop constants, so the whole
    step stays one jit'd program regardless of batch composition. For
    enc-dec the per-request encoder memory is gathered ONCE from the
    memory pool (paged-gather with a width-1 table of slot ids) and
    cross-attended by every decoder layer.

    ``tp_axis``: set when running per-shard inside the mesh-serving
    shard_map (``launch.steps.make_paged_step(mesh=...)``): ``cfg`` is
    then the shard-local view (head counts divided), the pools hold the
    local head block, and attention all-gathers its per-shard head
    outputs over the named mesh axis (``collectives.stitch_heads``)
    before the replicated-wo contraction. Everything outside (self and
    cross) attention — including the ssd half of hybrid layers — is
    replicated: each shard repeats the identical constant-state update.

    ``embed_seeds``: optional (B,) uint32 per-request projection seeds
    for seeded-SRF configs (0 = base projection); forwarded into every
    SRF attention layer's feature maps (zero-storage personalization).
    """
    dt = _dtype(cfg)
    x = layers.embed(params["embed"], tokens).astype(dt)
    x = hooks.constrain(x, "activation")
    memory = None
    mem_pool = pools.get("memory")
    if mem_pool is not None:
        memory = attention._paged_hist(mem_pool, slots[:, None]).astype(dt)
    new_paged, new_slot = [], []
    for seg_params, pseg, sseg, (kind, _) in zip(
            params["segments"], pools["paged"], pools["slot"], segments(cfg)):
        def body(x, inp):
            lp, lpp, lsp = inp
            y, npp, nsp = _paged_layer(lp, cfg, kind, x, positions, q_valid,
                                       lpp, lsp, tables, slots, memory,
                                       tp_axis, embed_seeds)
            return y, (npp, nsp)
        with jax.named_scope("layers"):
            x, (np_, ns_) = jax.lax.scan(body, x, (seg_params, pseg, sseg))
        new_paged.append(np_)
        new_slot.append(ns_)
    out_pools = {"paged": new_paged, "slot": new_slot}
    if mem_pool is not None:
        out_pools["memory"] = mem_pool        # read-only: pass through
    with jax.named_scope("head"):
        logits = _logits(params, cfg, x)
    return logits, out_pools


def _paged_layer(p, cfg, kind: str, x, positions, q_valid, lpaged, lslot,
                 tables, slots, memory=None, tp_axis: Optional[str] = None,
                 embed_seeds: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, Optional[Dict], Optional[Dict]]:
    """Single-layer paged step (mirrors ``layer_apply`` for serving).
    -> (x, new_paged_pools, new_slot_pools), each keyed by component.

    Named scopes (``attn``, ``ssm``, ``mlp``; each with its norm and
    residual) let a device trace split the layer's time by part."""
    if kind == "ssm":
        if tp_axis is not None:     # ssd pools always replicate (shard.py)
            raise ValueError("tp_axis is not supported for pure ssm stacks")
        with jax.named_scope("ssm"):
            h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
            y, new_ssm = ssm.paged_ssm_step(p["ssm"], cfg, h, q_valid,
                                            lslot["ssm"], slots)
            return x + y, None, {"ssm": new_ssm}
    attn_in_slot = cfg.attn_impl == "srf"   # srf state is a constant slot
    ctx = {"pool": (lslot if attn_in_slot else lpaged)["attn"],
           "tables": tables, "slots": slots, "q_valid": q_valid,
           "tp_axis": tp_axis}
    if embed_seeds is not None:
        ctx["embed_seeds"] = embed_seeds
    with jax.named_scope("attn"):
        h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
        a, new_attn = attention.attention(p["attn"], cfg, h, positions,
                                          "paged", ctx)
    if kind == "hybrid":
        with jax.named_scope("ssm"):
            s, new_ssm = ssm.paged_ssm_step(p["ssm"], cfg, h, q_valid,
                                            lslot["ssm"], slots)
            fused = 0.5 * (layers.rmsnorm(p["fuse_na"], a, cfg.norm_eps)
                           + layers.rmsnorm(p["fuse_ns"], s, cfg.norm_eps))
            x = x + fused
        with jax.named_scope("mlp"):
            x = x + layers.mlp(p["mlp"],
                               layers.rmsnorm(p["ln2"], x, cfg.norm_eps))
        new_s = {"ssm": new_ssm}
        if attn_in_slot:
            new_s["attn"] = new_attn
            return x, None, new_s
        return x, {"attn": new_attn}, new_s
    with jax.named_scope("attn"):
        x = x + a
        if kind == "dense_cross" and memory is not None:
            x = x + attention.paged_cross_attention(
                p["cross"], cfg, layers.rmsnorm(p["ln_x"], x, cfg.norm_eps),
                memory, tp_axis)
    with jax.named_scope("mlp"):
        h2 = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
        if kind == "moe":
            # q_valid keeps padded chunk-tail tokens out of expert
            # capacity: without it real tokens' slot positions (and thus
            # drops) depend on batch padding, breaking cross-replica
            # determinism
            y, _ = moe.moe_apply(p["moe"], cfg, h2, valid=q_valid)
        else:
            y = layers.mlp(p["mlp"], h2)
        x = x + y
    if attn_in_slot:
        return x, None, {"attn": new_attn}
    return x, {"attn": new_attn}, None


def decode_step(params, cfg, cache: Dict, tokens: jax.Array,
                pos3: Optional[jax.Array] = None) -> Tuple[jax.Array, Dict]:
    """tokens: (B, 1) int32. Returns logits (B, 1, V)."""
    dt = _dtype(cfg)
    b = tokens.shape[0]
    pos = cache["pos"]
    positions = jnp.broadcast_to(pos[None, None], (b, 1))
    x = layers.embed(params["embed"], tokens).astype(dt)
    x = hooks.constrain(x, "activation")
    memory = cache.get("memory")
    new_segs = []
    for seg_params, seg_cache, (kind, _) in zip(params["segments"],
                                                cache["segments"], segments(cfg)):
        x, new_c, _ = run_segment(seg_params, cfg, kind, x, positions,
                                  "decode", caches=seg_cache, pos3=pos3,
                                  memory=memory)
        new_segs.append(new_c)
    logits = _logits(params, cfg, x)
    out = {"segments": new_segs, "pos": pos + 1}
    if memory is not None:
        out["memory"] = memory
    return logits, out
