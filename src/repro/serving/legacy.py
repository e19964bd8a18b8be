"""Legacy per-slot serving engine — TEST ORACLE ONLY (and the benchmark
baseline ``bench_serving`` measures the paged engine against).

The paged engine in ``serving.engine`` serves every registry family;
nothing routes here in production (``launch/serve.py`` keeps a
``--legacy`` flag purely for A/B runs). The per-slot loop survives
because its simplicity makes it a trustworthy independent
implementation: the cross-engine parity matrix
(``tests/test_engine_parity.py``) pins the paged engine's greedy decode
bit-exactly to this one for every config family.

Requests enter a queue; free slots are filled by prefilling the prompt
into that slot's cache region. All active slots decode in lock-step with
one jit'd serve_step per token (the standard continuous-batching loop,
single-host flavor). Works with every cache family — full KV, MLA latent,
SRF state (the paper's O(m d) cache), SSD state, hybrid, enc-dec (each
:class:`Request` may carry its own ``enc_emb`` frontend features).

For simplicity slots share a common max_len; prefill runs per-request
(batch-1) and writes into the slot. Sampling uses the SAME stateless
per-request keys as the paged engine (``sampler.sample_tokens``:
noise from ``(base_key, uid, token index)``, never from engine state) —
that is what lets the parity matrix pin sampled decode bit-exactly
paged-vs-legacy, not just greedy. EOS or max_new stops.
"""
from __future__ import annotations

import time
import warnings
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import steps as step_lib
from repro.models import transformer as model_lib
from .engine import Request
from .sampler import sample_tokens as _sample_tokens

warnings.warn(
    "repro.serving.legacy is deprecated; use the paged engine "
    "(repro.serving.Engine — continuous batching over pooled paged "
    "caches, mesh-shardable via Engine(mesh=...)). The per-slot "
    "lock-step engine is kept only as the benchmark baseline.",
    DeprecationWarning, stacklevel=2)


class Engine:
    def __init__(self, cfg, params, batch_slots: int = 4,
                 max_len: int = 512, seed: int = 0):
        self.cfg = cfg
        self.params = params
        self.slots = batch_slots
        self.max_len = max_len
        self._prefill = jax.jit(step_lib.make_prefill_step(cfg))
        self._step = jax.jit(step_lib.make_serve_step(cfg))
        # per-slot independent caches (batch=1) stacked lazily
        self.caches = [model_lib.init_serve_cache(cfg, 1, max_len)
                       for _ in range(batch_slots)]
        self.active: List[Optional[Request]] = [None] * batch_slots
        self.queue: List[Request] = []
        self.stats: Dict[str, float] = {"tokens": 0, "requests": 0}
        # stateless sampling keys: identical derivation to the paged
        # engine (fold_in(fold_in(base, uid), position)), so a request
        # sampled here and there draws the same noise at every token
        self._base_key = jax.random.PRNGKey(seed)

    def submit(self, req: Request):
        req.t_submit = time.perf_counter()
        self.queue.append(req)

    def _pick(self, req: Request, logits: jax.Array) -> int:
        """Sample one token for ``req`` from (V,) logits; batch-1 call of
        the shared stateless sampler (bit-identical to any batched call
        with the same (uid, position) — that is the whole point)."""
        toks, _ = _sample_tokens(
            self._base_key,
            np.asarray([req.uid & 0xFFFFFFFF], np.uint32),
            np.asarray([len(req.out_tokens)], np.int32),
            logits[None, :],
            np.asarray([req.temperature], np.float32),
            np.asarray([req.top_k], np.int32),
            np.asarray([req.top_p], np.float32))
        return int(np.asarray(toks)[0])

    def _fill_slots(self, extra_batch: Optional[Dict] = None):
        for i in range(self.slots):
            # loop: a request whose FIRST token already satisfies
            # eos/max_new finishes at prefill and never occupies the slot
            # (matches the paged engine's finish-at-prefill path, so the
            # parity matrix holds at max_new=1 too)
            while self.active[i] is None and self.queue:
                req = self.queue.pop(0)
                batch = {"tokens": jnp.asarray(req.prompt[None, :])}
                if getattr(req, "enc_emb", None) is not None:
                    batch["enc_emb"] = jnp.asarray(req.enc_emb)[None]
                if extra_batch:
                    batch.update(extra_batch)
                cache = model_lib.init_serve_cache(self.cfg, 1, self.max_len)
                logits, cache = self._prefill(self.params, batch, cache)
                nxt = self._pick(req, logits[0, -1, : self.cfg.vocab])
                req.out_tokens.append(nxt)
                now = time.perf_counter()
                req.t_first = now
                self.stats["tokens"] += 1
                if nxt == req.eos_id or len(req.out_tokens) >= req.max_new:
                    req.done = True
                    req.t_done = now
                    self.stats["requests"] += 1
                    continue
                self.caches[i] = cache
                self.active[i] = req

    def _decode_once(self):
        for i, req in enumerate(self.active):
            if req is None:
                continue
            tok = jnp.asarray([[req.out_tokens[-1]]], jnp.int32)
            _, logits, cache = self._step(self.params, self.caches[i], tok)
            self.caches[i] = cache
            t = self._pick(req, logits[0])
            req.out_tokens.append(t)
            self.stats["tokens"] += 1
            if t == req.eos_id or len(req.out_tokens) >= req.max_new:
                req.done = True
                req.t_done = time.perf_counter()
                self.stats["requests"] += 1
                self.active[i] = None

    def run(self, extra_batch: Optional[Dict] = None) -> List[Request]:
        """Drain the queue; returns completed requests."""
        done: List[Request] = []
        pending = lambda: self.queue or any(a is not None for a in self.active)
        tracked: List[Request] = list(self.queue)
        while pending():
            self._fill_slots(extra_batch)
            self._decode_once()
        return [r for r in tracked if r.done]
