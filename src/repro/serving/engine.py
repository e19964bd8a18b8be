"""Paged continuous-batching serving engine.

Replaces the per-slot lock-step engine (now ``serving.legacy``, kept as
a test oracle / benchmark baseline): all requests share pooled,
pre-allocated caches (``paged_cache``) indexed through per-request block
tables and constant-state slots (``blocks``), a scheduler handles
admission / chunked prefill / preemption (``scheduler``), prefill and
decode both run as single batched jit steps (``transformer.paged_step``),
and sampling is temperature / top-k / top-p (``sampler``) with greedy as
the deterministic default.

Why paged: full-KV and MLA caches grow O(L) and are pooled in fixed-size
pages; the paper's SRF attention state (and the SSD state) is O(m d) —
one constant-size slot per request. EVERY registry family serves through
this engine: dense/moe (kv or mla pages), ssm (ssd slots), hybrid (kv
pages AND ssd slots per layer), enc-dec (kv pages + a read-only
encoder-memory slot written once at admission), and the vlm/audio
frontend archs (their decode path is plain kv).

Step shapes are fixed (max_batch x 1 decode, prefill_batch x chunk
prefill), so the engine compiles exactly two programs regardless of
traffic; inactive batch rows are masked and their writes land in the
reserved null page / null slot.
"""
from __future__ import annotations

import hashlib
import itertools
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import steps as step_lib
from repro.obs import metrics as obs_metrics
from repro.obs import quality as obs_quality
from repro.obs import spans as obs_spans
from repro.obs import trace as obs_trace

from . import paged_cache
from .prefix import ChunkPolicy, PrefixCache, PrefixConfig, cow
from .sampler import sample_tokens as _sample_tokens
from .scheduler import SchedConfig, Scheduler, Sequence, tenant_of


@dataclass
class Request:
    uid: int
    prompt: np.ndarray               # (P,) int32
    max_new: int = 32
    eos_id: int = -1                 # -1: never
    priority: int = 0                # higher first (policy="priority")
    temperature: float = 0.0         # 0 = greedy (deterministic)
    top_k: int = 0                   # 0 = disabled
    top_p: float = 1.0
    embed_seed: int = 0              # seeded-SRF configs: personalized
    #                                  zero-storage projection seed (0 =
    #                                  the model's base projection); costs
    #                                  no pool pages and no weight bytes
    enc_emb: Optional[np.ndarray] = None  # (enc_len, feat) enc-dec input
    deadline: Optional[float] = None # seconds after submit; overdue WAITING
    #                                  requests finish as 'timeout' instead
    #                                  of serving late (running ones finish)
    max_retries: int = 2             # replica-failure rescue budget
    namespace: str = ""              # tenant id: per-tenant accounting labels
    #                                  + prefix-cache partition ("" = default
    #                                  tenant, labelled "-")
    out_tokens: List[int] = field(default_factory=list)
    done: bool = False
    finish_reason: str = ""          # eos | length | timeout | shed | failed
    retries: int = 0                 # rescues consumed (ft router)
    deadline_at: Optional[float] = None  # absolute stamp, set at 1st submit
    # monotonic (perf_counter) stamps — wall-clock time.time() steps
    # corrupt TTFT/TPOT; trace carries the full lifecycle
    t_submit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0
    trace: Optional[obs_trace.Trace] = None


def _default_sched(cfg, batch_slots: int, max_len: int, plan,
                   policy: str) -> SchedConfig:
    page = 16 if max_len >= 64 else 8
    if not plan.has_paged:
        # constant-state only: the slot domain is the whole geometry
        return SchedConfig(max_batch=batch_slots, prefill_batch=batch_slots,
                           prefill_chunk=min(32, max(8, page)),
                           page_size=page, num_pages=2, table_width=1,
                           num_slots=batch_slots + 1, policy=policy)
    width = max(1, -(-max_len // page))
    return SchedConfig(max_batch=batch_slots, prefill_batch=batch_slots,
                       prefill_chunk=min(32, 2 * page), page_size=page,
                       num_pages=2 * batch_slots * width + 1,
                       table_width=width, num_slots=batch_slots + 1,
                       policy=policy)


def _enc_namespace(enc_emb) -> int:
    """Prefix-cache namespace for an enc-dec request: a content hash of
    the encoder features (identical features -> identical memory rows ->
    identical decoder KV, so sharing is sound; different features must
    partition the trie)."""
    h = hashlib.blake2b(np.ascontiguousarray(enc_emb).tobytes(),
                        digest_size=8)
    return int.from_bytes(h.digest(), "big")


def _cache_namespace(req, seeded_srf: bool = False) -> int:
    """Prefix-cache trie namespace for a request: partitioned by tenant
    (requests from different namespaces must never share cache state —
    isolation beats reuse across trust boundaries) and, for enc-dec, by
    encoder-content hash. A default-tenant text-only request keeps
    ``ns=0``, bit-identical to the pre-tenant trie layout.

    ``seeded_srf`` engines additionally partition by ``embed_seed``:
    personalized projections produce different attention states for the
    same token prefix, so sharing across seeds would be unsound. Non-
    seeded engines ignore the field (no needless sharing reduction)."""
    ns = _enc_namespace(req.enc_emb) if req.enc_emb is not None else 0
    tenant = getattr(req, "namespace", "")
    if tenant:
        h = hashlib.blake2b(tenant.encode("utf-8"), digest_size=8)
        ns ^= int.from_bytes(h.digest(), "big")
    if seeded_srf:
        es = getattr(req, "embed_seed", 0)
        if es:
            h = hashlib.blake2b(int(es).to_bytes(8, "big", signed=False),
                                digest_size=8)
            ns ^= int.from_bytes(h.digest(), "big")
    return ns


# distinct label value per engine instance: replicas sharing one registry
# must not share counter children (``router.describe`` reads per-engine)
_ENGINE_IDS = itertools.count()


class Engine:
    """Continuous batching over paged cache pools.

    ``batch_slots`` and ``max_len`` keep the old engine's constructor
    contract (tests, examples); pass ``sched=SchedConfig(...)`` to size
    the pools explicitly (e.g. tight pools to exercise preemption).

    ``mesh``: mesh-sharded serving — pools laid out with model-axis
    NamedSharding on the head/feature dim, attention params sliced to
    match, and the step shard_map-wrapped (``serving/mesh/shard.py`` owns
    the layout contract; ``launch.steps.make_paged_step`` builds the
    step). ``paged=PagedConfig(quantize_kv=True)`` stores KV pages as
    int8 with per-page-row scales (kv family only).

    ``on_first_logits(req, row)``: called once per request when its
    prefill finishes, with the f32 logits row (``vocab`` wide) its first
    generated token is sampled from — for checks against a reference
    forward pass.

    Enc-dec: every :class:`Request` must carry ``enc_emb`` (the frontend
    features); the engine runs the encoder exactly once per request at
    admission (batch-1, bit-identical to the legacy per-slot prefill) and
    caches the result in the read-only encoder-memory pool at the
    request's slot — decode steps gather it and cross-attend.

    Copy-on-preempt snapshots are asynchronous: eviction enqueues the
    device-side page+slot slice and the non-blocking host transfer, the
    next decode step overlaps the copy (the step donates its pool
    buffers, so the engine fences pending slices with
    ``block_until_ready`` first), and the transfer is only awaited when
    the victim swaps back in.
    """

    def __init__(self, cfg, params, batch_slots: int = 4,
                 max_len: int = 512, sched: Optional[SchedConfig] = None,
                 policy: str = "fcfs", seed: int = 0, mesh=None,
                 paged: Optional[paged_cache.PagedConfig] = None,
                 metrics: Optional[obs_metrics.MetricsRegistry] = None,
                 quality_every: int = 64,
                 quality_tol: float = obs_quality.DRIFT_TOL,
                 prefix: Optional[PrefixConfig] = None,
                 spans: Optional[obs_spans.SpanRecorder] = None,
                 on_first_logits: Optional[Callable] = None):
        self.cfg = cfg
        self.on_first_logits = on_first_logits
        self.plan = paged_cache.plan_for(cfg)
        self.mesh = mesh
        self.paged = paged or paged_cache.PagedConfig()
        self.metrics = metrics if metrics is not None \
            else obs_metrics.MetricsRegistry()
        self.spans = spans if spans is not None else obs_spans.NOOP
        self.engine_id = str(next(_ENGINE_IDS))
        if sched is None:
            sched = _default_sched(cfg, batch_slots, max_len, self.plan,
                                   policy)
        self.sched_cfg = sched
        self.sched = Scheduler(sched, self.plan, metrics=self.metrics,
                               labels={"engine": self.engine_id},
                               spans=self.spans)
        self.pools = paged_cache.init_pools(cfg, sched.num_pages,
                                            sched.page_size,
                                            num_slots=self.sched.num_slots,
                                            mesh=mesh, paged=self.paged)
        if mesh is not None:
            from .mesh import shard as mesh_shard
            params = mesh_shard.place_params(params, cfg, mesh)
        self.params = params
        self._step = jax.jit(
            step_lib.make_paged_step(cfg, mesh=mesh, paged=self.paged,
                                     params_sds=params),
            donate_argnums=(1,))
        self._encode = (jax.jit(step_lib.make_encode_step(cfg))
                        if cfg.is_encdec else None)
        # stateless sampling: the base key never advances — per-token
        # noise is derived as fold_in(fold_in(base, uid), position), so a
        # request's sampled stream is independent of batch composition,
        # admission order and replica (FT replay of sampled requests is
        # bit-identical)
        self._base_key = jax.random.PRNGKey(seed)
        self._seeded_srf = (getattr(cfg, "attn_impl", None) == "srf"
                            and getattr(cfg.srf, "seeded", False))
        # injectable step-time clock, read exactly twice per step() — the
        # replica watchdog consumes the recorded engine_step_seconds, and
        # the chaos harness simulates stalls by swapping this clock
        self.clock = time.perf_counter
        self._pending_snaps: List[paged_cache.PendingSnapshot] = []
        # (src, dst) tail-page copies owed to the prefix cache, flushed
        # as one batched device copy at the end of the prefill step so
        # donors keep exclusive tail ownership (no mid-decode forks)
        self._cache_copies: List[Tuple[int, int]] = []
        # prefix sharing (serving/prefix): pure-constant-state plans have
        # no pages to share, so the cache is paged-domain only
        self.prefix: Optional[PrefixCache] = None
        self._chunk: Optional[ChunkPolicy] = None
        if prefix is not None and prefix.enabled and self.plan.has_paged:
            self.prefix = PrefixCache(
                self.sched.alloc, self.sched_cfg.page_size,
                paged_cache.page_bytes(self.pools), prefix,
                metrics=self.metrics, labels={"engine": self.engine_id},
                spans=self.spans)
            self.sched.attach_prefix(self.prefix)
            self._chunk = ChunkPolicy(prefix.chunk, spans=self.spans)
        self._init_metrics()
        self._quality_every = (quality_every
                               if getattr(cfg, "attn_impl", None) == "srf"
                               else 0)
        self._quality_tol = quality_tol
        # primed so the FIRST decode step publishes a sample — short runs
        # (fewer than quality_every steps) still see the live gauge
        self._steps_since_quality = max(0, self._quality_every - 1)

    # -- metrics -------------------------------------------------------------

    def _init_metrics(self) -> None:
        """Bind this engine's children in the (possibly shared) registry;
        ``self.stats`` stays API-compatible with the old ad-hoc dict as
        a read-only view over the registry."""
        lab = {"engine": self.engine_id}
        m = self.metrics
        c = lambda name, help: m.counter(name, help,  # noqa: E731
                                         ("engine",)).labels(**lab)
        h = lambda name, help: m.histogram(           # noqa: E731
            name, help, ("engine",)).labels(**lab)
        self._c_tokens = c("engine_tokens_total", "tokens generated")
        self._c_requests = c("engine_requests_total", "requests finished")
        self._c_prefill_steps = c("engine_prefill_steps_total",
                                  "batched prefill-chunk steps")
        self._c_prefill_tokens = c("engine_prefill_tokens_total",
                                   "prompt tokens actually prefilled "
                                   "(prefix-cache hits skip theirs)")
        self._c_decode_steps = c("engine_decode_steps_total",
                                 "batched decode steps")
        self._c_sample_calls = m.counter(
            "engine_sample_calls_total", "sampler calls by program: argmax "
            "(no row samples) or full (top-k / top-p sort)",
            ("engine", "path"))
        self._c_preemptions = c("engine_preemptions_total",
                                "copy-on-preempt evictions")
        self._c_expired = c("engine_expired_total",
                            "waiting requests expired past deadline")
        self._c_cow_forks = c("prefix_cow_forks_total",
                              "copy-on-write page forks applied (admission "
                              "boundary + decode divergence)")
        self._h_step = h("engine_step_seconds", "wall time of one engine "
                         "step (the replica-health watchdog reads this)")
        self._h_ttft = h("request_ttft_seconds", "time to first token")
        self._h_tpot = h("request_tpot_seconds", "per-output-token time "
                         "after the first")
        self._h_queue = h("request_queue_seconds", "submit -> admission")
        self._h_e2e = h("request_e2e_seconds", "submit -> done")
        # per-tenant accounting (fairness substrate): same registry,
        # {engine, tenant} labels; children bound lazily per namespace
        tl = ("engine", "tenant")
        self._ct_prefill = m.counter(
            "tenant_prefill_tokens_total",
            "prompt tokens prefilled, by tenant namespace", tl)
        self._ct_decode = m.counter(
            "tenant_decode_tokens_total",
            "decode tokens generated, by tenant namespace", tl)
        self._ct_requests = m.counter(
            "tenant_requests_total",
            "requests finished, by tenant namespace", tl)
        self._ct_expired = m.counter(
            "tenant_expired_total",
            "requests expired past deadline, by tenant namespace", tl)
        self._tenant_children: Dict[str, Dict[str, object]] = {}
        self.stats = obs_metrics.StatsView({
            "tokens": self._c_tokens.value,
            "requests": self._c_requests.value,
            "prefill_steps": self._c_prefill_steps.value,
            "decode_steps": self._c_decode_steps.value,
            "preemptions": self._c_preemptions.value,
        })
        self._sample_memory_gauges()

    def _tenant(self, req) -> Dict[str, object]:
        """Bound per-tenant counter children for a request's namespace
        (cached — binding is a dict insert, incrementing is one add)."""
        t = tenant_of(req)
        ch = self._tenant_children.get(t)
        if ch is None:
            lab = {"engine": self.engine_id, "tenant": t}
            ch = {"prefill": self._ct_prefill.labels(**lab),
                  "decode": self._ct_decode.labels(**lab),
                  "requests": self._ct_requests.labels(**lab),
                  "expired": self._ct_expired.labels(**lab)}
            self._tenant_children[t] = ch
        return ch

    def _sample_memory_gauges(self) -> None:
        """Device-memory gauges from the pool container (pools are
        preallocated, so bytes are constant per engine; free/used page
        and slot gauges track live via the scheduler)."""
        lab = {"engine": self.engine_id}
        g = self.metrics.gauge("pool_bytes", "total pool bytes (all "
                               "devices)", ("engine",)).labels(**lab)
        g.set(paged_cache.pool_bytes(self.pools))
        gd = self.metrics.gauge("pool_bytes_per_device",
                                "pool bytes resident per device",
                                ("engine",)).labels(**lab)
        gd.set(paged_cache.pool_bytes_per_device(self.pools))

    def _maybe_sample_quality(self) -> None:
        """Every ``quality_every`` decode steps, publish the paper's row
        statistics (Def. 1 calibration) of the live SRF params as gauges
        — the live counterpart of ``bench_coherence``'s offline report."""
        if not self._quality_every or not self.metrics.enabled:
            return
        self._steps_since_quality += 1
        if self._steps_since_quality < self._quality_every:
            return
        self._steps_since_quality = 0
        with self.spans.span("quality_probe"):
            stats = obs_quality.srf_quality_probe(self.cfg, self.params)
        if not stats:
            return
        gq = self.metrics.gauge("srf_quality", "live embedding row "
                                "statistics (Def. 1)", ("engine", "stat"))
        for k, v in stats.items():
            gq.labels(engine=self.engine_id, stat=k).set(v)
        if obs_quality.moments_drifted(stats, self._quality_tol):
            self.metrics.event("quality_drift", engine=self.engine_id,
                               tol=self._quality_tol, **stats)

    # -- public API ---------------------------------------------------------

    def submit(self, req: Request) -> None:
        if self.cfg.is_encdec and req.enc_emb is None:
            raise ValueError(
                "enc-dec serving needs Request.enc_emb (frontend features "
                f"({self.cfg.enc_len}, feat)); request uid={req.uid} has none")
        now = time.perf_counter()
        req.t_submit = now
        if req.deadline is not None and req.deadline_at is None:
            # absolute stamp survives rescue re-submission: the deadline
            # clock keeps running across replica failures
            req.deadline_at = now + req.deadline
        if req.trace is None:
            req.trace = obs_trace.Trace(uid=req.uid)
        req.trace.stamp("queued", now)
        self.metrics.event("queued", uid=req.uid, engine=self.engine_id)
        seq = self.sched.submit(req)
        if self.prefix is not None:
            # decoder KV depends on the encoder memory, and tenants must
            # not share cache state: token-equal prompts under different
            # encoder inputs or namespaces (or, when projections are
            # personalized, embed seeds) never cross-match
            seq.ns = _cache_namespace(req, self._seeded_srf)

    def prefix_peek(self, req: Request) -> int:
        """Tokens of ``req``'s prompt this engine could serve from its
        prefix cache right now — non-pinning, non-LRU-touching (the
        router's affinity probe)."""
        if self.prefix is None:
            return 0
        return self.prefix.peek(_cache_namespace(req, self._seeded_srf),
                                req.prompt,
                                want_state=bool(self.plan.slot_families))

    def run(self, on_step=None) -> List[Request]:
        """Drain all submitted requests; returns the completed ones.
        ``on_step(engine)`` is called after every scheduler iteration
        (the reporter's periodic-metrics hook)."""
        tracked = [s.req for s in self.sched.waiting + self.sched.running]
        stall = 0
        while self.sched.has_work:
            progressed = self.step()
            if on_step is not None:
                on_step(self)
            stall = 0 if progressed else stall + 1
            if stall > 2:
                raise RuntimeError(
                    "scheduler stalled: pool too small for the remaining "
                    f"requests (free={self.sched.alloc.free_pages} pages, "
                    f"{self.sched.free_slots} slots)")
        return [r for r in tracked if r.done]

    def step(self) -> bool:
        """One scheduler iteration: admit, then one prefill-chunk step if
        any sequence is still prefilling, else one batched decode step.
        Returns False when nothing could run (allocator exhausted).

        Timed through ``self.clock`` (exactly two reads per step) into
        ``engine_step_seconds`` — the replica-health signal. Spans use
        ``perf_counter`` directly and never touch ``self.clock`` (the
        chaos harness's stall clock counts its reads)."""
        t0 = self.clock()
        tok = self.spans.begin("engine_step")
        try:
            return self._step_once()
        finally:
            self.spans.end(tok)
            self._h_step.observe(self.clock() - t0)

    def _step_once(self) -> bool:
        # deadline expiry first: an overdue waiting request holds no
        # device capacity, so dropping it is pure bookkeeping — and doing
        # it before admission means a backlogged pool never wastes pages
        # on work that is already late
        expired = self.sched.expire_overdue(time.perf_counter())
        for seq in expired:
            self._expire(seq)
        admitted = self.sched.admit()
        now = time.perf_counter() if admitted else 0.0
        fresh: List[Sequence] = []
        for seq in admitted:
            if seq.req.trace is not None:
                seq.req.trace.stamp("admitted", now)
            if seq.snapshot is not None:
                with self.spans.span("restore", uid=seq.req.uid):
                    self.pools = paged_cache.restore_page_rows(
                        self.pools, seq.table.pages, self._slot_ids(seq),
                        seq.snapshot)
                self.sched.restored(seq)
                if seq.req.trace is not None:
                    seq.req.trace.stamp("restored", now)
                self.metrics.event("restored", uid=seq.req.uid,
                                   engine=self.engine_id)
            else:
                if seq.hit_tokens > 0:
                    if seq.req.trace is not None:
                        seq.req.trace.stamp("prefix_hit", now)
                    self.metrics.event("prefix_hit", uid=seq.req.uid,
                                       engine=self.engine_id,
                                       tokens=seq.hit_tokens)
                if seq.slot is not None:
                    # constant-state slots are accumulators: a reused slot
                    # must start from zero, not the previous request's
                    # state
                    fresh.append(seq)
        if fresh:
            # the enc-dec memory rows are fully overwritten by the encoder
            # below, so their zeroing is skipped (one whole-pool write
            # saved per admission burst)
            with self.spans.span("zero_slot_rows", rows=len(fresh)):
                self.pools = paged_cache.zero_slot_rows(
                    self.pools, [s.slot for s in fresh],
                    zero_memory=self._encode is None)
            if self._encode is not None:
                self._write_memories(fresh)
        self._apply_forks(admitted)
        for seq in admitted:
            if seq.state_payload is not None:
                # donor's constant-state snapshot at the matched token
                # count: restoring it is what makes the shared KV pages
                # resumable for slot-bearing plans
                with self.spans.span("restore", uid=seq.req.uid):
                    self.pools = paged_cache.restore_page_rows(
                        self.pools, [], self._slot_ids(seq),
                        seq.state_payload)
                seq.state_payload = None
        work = self.sched.prefill_work()
        sc = self.sched_cfg
        if work and self._chunk is not None \
                and self.sched.decode_ready() \
                and self._chunk.spans_steps(work, sc.prefill_chunk,
                                            sc.prefill_batch) \
                and self._chunk.decode_turn():
            # chunked-prefill interleave: yield this step to decode so a
            # long cold prompt cannot starve running requests' TPOT
            if self._decode_step(self.sched.decode_ready()):
                return True
            work = self.sched.prefill_work()    # decode may have evicted
        if work:
            self._prefill_step(work)
            return True
        ready = self.sched.decode_ready()
        if ready:
            return self._decode_step(ready) or bool(expired)
        return bool(admitted) or bool(expired)

    def _apply_forks(self, seqs: List[Sequence]) -> None:
        """Apply pending COW forks as ONE batched gather-then-scatter
        copy (``copy_page_rows`` reads every source from the pre-copy
        pools, so a page freed and recycled as another fork's destination
        in the same round can never clobber a source). Admission forks
        pin their source in the cache until the copy is issued — released
        here."""
        forks = [s.fork for s in seqs if s.fork is not None]
        if not forks:
            return
        with self.spans.span("fork", pages=len(forks)):
            self.pools = paged_cache.copy_page_rows(
                self.pools, [f.src for f in forks], [f.dst for f in forks])
        self._c_cow_forks.inc(len(forks))
        self.spans.instant("cow_fork", pages=len(forks))
        for s in seqs:
            if s.fork is not None:
                if s.fork.pinned_src:
                    self.prefix.release_fork(s.fork.src)
                s.fork = None

    def _expire(self, seq: Sequence) -> None:
        """Terminal ``timeout``: the request went past its deadline while
        waiting (it holds no pages/slots — the scheduler already dropped
        it from the queue)."""
        req = seq.req
        req.done = True
        req.finish_reason = "timeout"
        now = time.perf_counter()
        req.t_done = now
        if req.trace is not None:
            req.trace.stamp("done", now)
            if req.trace.e2e is not None:
                self._h_e2e.observe(req.trace.e2e)
        self._c_expired.inc()
        self._tenant(req)["expired"].inc()
        self.metrics.event("expired", uid=req.uid, engine=self.engine_id)

    @staticmethod
    def _slot_ids(seq: Sequence) -> List[int]:
        return [seq.slot] if seq.slot is not None else []

    # -- enc-dec memory ------------------------------------------------------

    def _write_memories(self, seqs: List[Sequence]) -> None:
        """Run the encoder once per freshly admitted request and cache the
        results in the read-only memory pool. Encoding stays batch-1 per
        request (bit-identical to the legacy per-slot prefill); the row
        writes are batched into ONE whole-pool update per admission."""
        mems = [self._encode(self.params, jnp.asarray(s.req.enc_emb)[None])[0]
                for s in seqs]
        idx = jnp.asarray([s.slot for s in seqs], jnp.int32)
        new = self.pools["memory"].at[idx].set(
            jnp.stack(mems).astype(self.pools["memory"].dtype))
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            new = jax.device_put(
                new, NamedSharding(self.mesh, PartitionSpec()))
        self.pools["memory"] = new

    # -- snapshot fencing ----------------------------------------------------

    def _fence_snapshots(self) -> None:
        """The jit'd step donates the pool buffers; make sure every pending
        copy-on-preempt slice has executed before they are reused. This
        waits on the *device* compute only — the device->host transfer
        keeps streaming underneath the next step."""
        if self._pending_snaps:
            for snap in self._pending_snaps:
                snap.fence()
            self._pending_snaps.clear()

    def _run_step(self, tokens, pos, qv, tables, slots, embed_seeds=None):
        self._fence_snapshots()
        if self._seeded_srf:
            return self._step(self.params, self.pools, jnp.asarray(tokens),
                              jnp.asarray(pos), jnp.asarray(qv),
                              jnp.asarray(tables), jnp.asarray(slots),
                              jnp.asarray(embed_seeds))
        return self._step(self.params, self.pools, jnp.asarray(tokens),
                          jnp.asarray(pos), jnp.asarray(qv),
                          jnp.asarray(tables), jnp.asarray(slots))

    def _embed_seeds(self, seqs: List[Sequence], n_pad: int) -> np.ndarray:
        """(B,) uint32 per-row projection seeds for seeded-SRF steps
        (0 = base projection; padded rows are base)."""
        es = np.zeros((n_pad,), np.uint32)
        for i, s in enumerate(seqs):
            es[i] = getattr(s.req, "embed_seed", 0) & 0xFFFFFFFF
        return es

    # -- sampling -----------------------------------------------------------

    def _sample_rows(self, rows: jax.Array, seqs: List[Optional[Sequence]],
                     n_pad: int) -> np.ndarray:
        """Stateless per-request sampling: row i's noise is keyed by
        (base_key, uid, emitted-token index), never by engine RNG state —
        the token a request samples at position p is the same whatever
        batch it lands in (and on whatever replica; FT replay re-derives
        the identical keys from the forced-prefix high-water mark). A
        call in which no row samples takes the argmax program
        (``sampler.sample_tokens``); ``engine_sample_calls_total`` counts
        the calls by path. Rows past ``seqs``, or whose entry is None,
        are padding and draw greedily; their tokens are discarded."""
        temps = np.zeros((n_pad,), np.float32)
        ks = np.zeros((n_pad,), np.int32)
        ps = np.ones((n_pad,), np.float32)
        uids = np.zeros((n_pad,), np.uint32)
        poss = np.zeros((n_pad,), np.int32)
        for i, s in enumerate(seqs):
            if s is None:
                continue
            temps[i] = s.req.temperature
            ks[i] = s.req.top_k
            ps[i] = s.req.top_p
            uids[i] = s.req.uid & 0xFFFFFFFF    # negative uids (probes) wrap
            poss[i] = len(s.req.out_tokens)     # index of the token drawn
        toks, path = _sample_tokens(self._base_key, uids, poss, rows, temps,
                                    ks, ps)
        self._c_sample_calls.labels(engine=self.engine_id, path=path).inc()
        with self.spans.span("sync"):
            return np.asarray(toks)

    # -- prefill ------------------------------------------------------------

    def _prefill_step(self, work: List[Sequence]) -> None:
        stok = self.spans.begin("prefill_step")
        sc = self.sched_cfg
        b, c, m = sc.prefill_batch, sc.prefill_chunk, sc.table_width
        with self.spans.span("build"):
            tokens = np.zeros((b, c), np.int32)
            pos = np.zeros((b, c), np.int32)
            qv = np.zeros((b, c), bool)
            tables = np.zeros((b, m), np.int32)
            slots = np.zeros((b,), np.int32)
            last_row = np.zeros((b,), np.int32)
            finishing: List[Optional[Sequence]] = [None] * b
            if self._chunk is not None:
                planned = self._chunk.plan(work, c, b)
            else:
                planned = [(s, min(s.prompt_len - s.prefill_pos, c))
                           for s in work]
            self._c_prefill_tokens.inc(sum(t for _, t in planned))
            for i, (seq, take) in enumerate(planned):
                self._tenant(seq.req)["prefill"].inc(take)
                self.spans.instant("prefill_chunk", uid=seq.req.uid,
                                   tokens=take)
                start = seq.prefill_pos
                tr = seq.req.trace
                if tr is not None:
                    # first chunk stamps "prefill" whether it starts at 0
                    # or at a prefix-cache match boundary; continuations
                    # under a chunk policy stamp "chunked_prefill"
                    if tr.count("prefill") == 0:
                        tr.stamp("prefill")
                    elif self._chunk is not None:
                        tr.stamp("chunked_prefill")
                if self.prefix is not None:
                    # host invariant: prefill writes only land in pages
                    # this request exclusively owns (shared prefixes are
                    # read-only)
                    cow.assert_writable(self.sched.alloc, seq.table.pages,
                                        start, take, sc.page_size)
                chunk = np.asarray(seq.req.prompt[start:start + take],
                                   np.int32)
                n = len(chunk)
                tokens[i, :n] = chunk
                # true absolute positions (rope); the invalid tail rows
                # are masked by q_valid, and page lookups clamp harmlessly
                pos[i] = start + np.arange(c)
                qv[i, :n] = True
                tables[i] = seq.table.padded(m)
                slots[i] = seq.slot or 0
                seq.prefill_pos += n
                seq.table.length = seq.prefill_pos
                if seq.prefill_done:
                    finishing[i] = seq
                    last_row[i] = n - 1
            es = (self._embed_seeds([s for s, _ in planned], b)
                  if self._seeded_srf else None)
        with self.spans.span("dispatch"):
            logits, self.pools = self._run_step(tokens, pos, qv, tables,
                                                slots, es)
        with self.spans.span("sample"):
            rows = jnp.take_along_axis(
                logits[:, :, : self.cfg.vocab],
                jnp.asarray(last_row)[:, None, None], axis=1)[:, 0]
            toks = self._sample_rows(rows, finishing, b)
        with self.spans.span("emit"):
            if self.on_first_logits is not None:
                host_rows = np.asarray(rows, np.float32)
                for i, seq in enumerate(finishing):
                    if seq is not None and not seq.req.out_tokens:
                        self.on_first_logits(seq.req, host_rows[i])
            now = time.perf_counter()
            for i, seq in enumerate(finishing):
                if seq is None:
                    continue
                if self.prefix is not None:
                    # cache the fully prefilled prompt BEFORE any finish
                    # path frees its pages — the cache's references keep
                    # them alive
                    self._prefix_insert(seq)
                tok = int(toks[i])
                seq.req.out_tokens.append(tok)
                seq.req.t_first = now
                if seq.req.trace is not None:
                    seq.req.trace.stamp("first_token", now)
                self._c_tokens.inc()
                self._tenant(seq.req)["decode"].inc()
                # the first token can already satisfy eos/max_new —
                # finishing here keeps max_new=1 at exactly one emitted
                # token and frees the pages/slot a step earlier
                # (previously such a request took one extra decode step
                # and emitted max_new+1 tokens)
                if tok == seq.req.eos_id or \
                        len(seq.req.out_tokens) >= seq.req.max_new:
                    self._finish(seq, now)
            self._flush_cache_copies()
        self._c_prefill_steps.inc()
        stok.args["rows"] = len(planned)
        self.spans.end(stok)

    def _prefix_insert(self, seq: Sequence) -> None:
        """Donate a fully prefilled prompt to the prefix cache. Slot-
        bearing plans attach the donor's constant-state snapshot (taken
        async NOW, before any decode step mutates the slot) so a later
        hit can resume the SSM exactly at the prompt boundary.

        An unaligned prompt's tail page would become shared the moment
        it is cached — and the donor's very next decode write would have
        to COW-fork it, a whole-pool copy landing in a decode token gap
        (measurably inflating TPOT p95 at high hit rates). So the CACHE
        takes a private copy of the tail page instead: the copy batches
        into this prefill-completion step (which already pauses decode)
        and the donor keeps exclusive ownership of its own tail. Under
        pool exhaustion the copy page may be unavailable; then the tail
        is shared as-is and the scheduler's decode-fork site covers the
        donor's next write."""
        payload, ptoks = None, 0
        if self.plan.slot_families and seq.slot is not None:
            payload = paged_cache.snapshot_page_rows_async(
                self.pools, [], [seq.slot])
            self._pending_snaps.append(payload)
            ptoks = seq.prompt_len
        pages = list(seq.table.pages)
        tail_src, cp = None, None
        if seq.prompt_len % self.sched_cfg.page_size:
            got = self.sched.alloc.alloc(1)
            if got is not None:
                tail_src, cp = pages[-1], got[0]
                pages[-1] = cp
        newly = self.prefix.insert(seq.ns, seq.req.prompt, pages, payload,
                                   payload_tokens=ptoks)
        if cp is not None:
            if cp in newly:
                # our alloc ref on cp is held until the flush so the
                # page cannot be recycled into another copy's dst first
                self._cache_copies.append((tail_src, cp))
            else:                       # tail node existed: copy unused
                self.sched.alloc.free([cp])
                self.sched._sync_gauges()

    def _flush_cache_copies(self) -> None:
        """One batched device copy for every tail page the cache
        adopted this step (see ``_prefix_insert``), then drop the
        engine's transient allocation refs (the cache's remain)."""
        if not self._cache_copies:
            return
        self.pools = paged_cache.copy_page_rows(
            self.pools, [s for s, _ in self._cache_copies],
            [d for _, d in self._cache_copies])
        self._c_cow_forks.inc(len(self._cache_copies))
        self.spans.instant("cache_tail_copy", pages=len(self._cache_copies))
        self.sched.alloc.free([d for _, d in self._cache_copies])
        self._cache_copies.clear()
        self.sched._sync_gauges()

    # -- completion ----------------------------------------------------------

    def _finish(self, seq: Sequence, now: float) -> None:
        """Mark one sequence done (from prefill or decode): latency
        histograms from its trace, pages/slot back to the scheduler."""
        req = seq.req
        req.done = True
        req.finish_reason = ("eos" if req.out_tokens
                             and req.out_tokens[-1] == req.eos_id
                             else "length")
        req.t_done = now
        tr = req.trace
        if tr is not None:
            tr.stamp("done", now)
            q, ttft, e2e = tr.queue_time, tr.ttft, tr.e2e
            tpot = tr.tpot(len(req.out_tokens))
        else:                             # externally built request
            q, ttft = 0.0, req.t_first - req.t_submit
            e2e, tpot = now - req.t_submit, None
        if q is not None:
            self._h_queue.observe(q)
        if ttft is not None:
            self._h_ttft.observe(ttft)
        if e2e is not None:
            self._h_e2e.observe(e2e)
        if tpot is not None:
            self._h_tpot.observe(tpot)
        self._c_requests.inc()
        self._tenant(req)["requests"].inc()
        self.metrics.event("done", uid=req.uid, engine=self.engine_id,
                           tokens=len(req.out_tokens))
        self.sched.finished(seq)

    # -- decode -------------------------------------------------------------

    def _evict(self, victim: Sequence) -> None:
        if victim.fork is not None:
            # a decode fork planned earlier in this same grow loop: its
            # table already points at the (not-yet-copied) destination, so
            # the copy must land before the snapshot reads it
            self._apply_forks([victim])
        snap = paged_cache.snapshot_page_rows_async(
            self.pools, victim.table.pages, self._slot_ids(victim))
        self._pending_snaps.append(snap)
        self.sched.evicted(victim, snap)
        self.spans.instant("preempt", uid=victim.req.uid)
        if victim.req.trace is not None:
            victim.req.trace.stamp("preempted")
        self.metrics.event("preempted", uid=victim.req.uid,
                           engine=self.engine_id)
        self._c_preemptions.inc()

    def _decode_step(self, ready: List[Sequence]) -> bool:
        stok = self.spans.begin("decode_step")
        try:
            return self._decode_once(ready, stok)
        finally:
            self.spans.end(stok)

    def _decode_once(self, ready: List[Sequence], stok) -> bool:
        sc = self.sched_cfg
        batch: List[Sequence] = []
        with self.spans.span("grow"):
            for seq in ready:
                if seq not in self.sched.running:
                    continue                   # evicted below us this step
                ok, victim = self.sched.grow_for_decode(seq)
                while not ok and victim is not None:
                    self._evict(victim)
                    batch = [s for s in batch if s is not victim]
                    ok, victim = self.sched.grow_for_decode(seq)
                if ok:
                    batch.append(seq)
            if batch:
                self._apply_forks(batch)   # COW: diverging writes into
                #                            shared pages fork first
        if not batch:
            return False
        b, m = sc.max_batch, sc.table_width
        with self.spans.span("build"):
            tokens = np.zeros((b, 1), np.int32)
            pos = np.zeros((b, 1), np.int32)
            qv = np.zeros((b, 1), bool)
            tables = np.zeros((b, m), np.int32)
            slots = np.zeros((b,), np.int32)
            for i, seq in enumerate(batch):
                if self.prefix is not None:
                    cow.assert_writable(self.sched.alloc, seq.table.pages,
                                        seq.table.length, 1, sc.page_size)
                tokens[i, 0] = seq.req.out_tokens[-1]
                pos[i, 0] = seq.table.length
                qv[i, 0] = True
                tables[i] = seq.table.padded(m)
                slots[i] = seq.slot or 0
            es = self._embed_seeds(batch, b) if self._seeded_srf else None
        with self.spans.span("dispatch"):
            logits, self.pools = self._run_step(tokens, pos, qv, tables,
                                                slots, es)
        with self.spans.span("sample"):
            toks = self._sample_rows(logits[:, 0, : self.cfg.vocab], batch,
                                     b)
        with self.spans.span("emit"):
            now = time.perf_counter()
            for i, seq in enumerate(batch):
                seq.table.length += 1
                tok = int(toks[i])
                seq.req.out_tokens.append(tok)
                if seq.req.trace is not None and \
                        seq.req.trace.count("decode") == 0:
                    seq.req.trace.stamp("decode", now)
                self._c_tokens.inc()
                self._tenant(seq.req)["decode"].inc()
                if tok == seq.req.eos_id or \
                        len(seq.req.out_tokens) >= seq.req.max_new:
                    self._finish(seq, now)
        self._c_decode_steps.inc()
        stok.args["rows"] = len(batch)
        self._maybe_sample_quality()
        return True

    def defrag(self) -> None:
        """Compact live pages to the low pool indices. Paging never needs
        this for correctness (any free page serves any request); it is an
        idle-time locality optimization, so it is NOT run on the decode
        hot path."""
        moves = self.sched.defrag()
        self.pools = paged_cache.apply_moves(self.pools, moves)

    # -- introspection ------------------------------------------------------

    @property
    def free_pages(self) -> int:
        return self.sched.alloc.free_pages

    @property
    def free_slots(self) -> int:
        return self.sched.free_slots

    @property
    def usable_pages(self) -> int:
        """Paged-domain pages available to requests (page 0 is null)."""
        return max(self.sched_cfg.num_pages - 1, 1)

    @property
    def usable_slots(self) -> int:
        """Slot-domain slots available to requests (slot 0 is null)."""
        return max(self.sched.num_slots - 1, 1)

    @property
    def free_fraction(self) -> float:
        """Fraction of the BINDING pool currently free (router pressure):
        the minimum over the domains this plan actually allocates from."""
        fr = []
        if self.plan.has_paged:
            fr.append(self.free_pages / self.usable_pages)
        if self.sched.slot_alloc is not None:
            fr.append(self.free_slots / self.usable_slots)
        return min(fr) if fr else 1.0

    def cache_report(self, max_len: Optional[int] = None) -> Dict[str, float]:
        ml = max_len or (self.sched_cfg.table_width * self.sched_cfg.page_size)
        return {"family": self.plan.name,
                "bytes_per_token_per_layer":
                    self.plan.bytes_per_token(self.cfg, ml, self.paged),
                "pool_bytes": paged_cache.pool_bytes(self.pools),
                "pool_bytes_per_device":
                    paged_cache.pool_bytes_per_device(self.pools),
                "free_pages": self.sched.alloc.free_pages,
                "free_slots": self.sched.free_slots}
