"""Paged serving subsystem: allocator invariants, paged-gather kernel vs
jnp reference, scheduler policies, sampler semantics, and end-to-end
engine runs with mixed-length concurrent requests per cache family."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.kernels import ops, ref
from repro.models import transformer as T
from repro.serving import (BlockAllocator, BlockTable, Engine, Request,
                           SchedConfig)
from repro.serving.blocks import NULL_PAGE


def _legacy():
    """Import the legacy oracle without tripping the deprecation-as-error
    filter (its import warns by design; see pytest.ini)."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        from repro.serving import legacy
    return legacy


# ---------------------------------------------------------------------------
# block allocator
# ---------------------------------------------------------------------------

def test_allocator_no_double_alloc_and_free_returns():
    a = BlockAllocator(num_pages=8, page_size=4)
    seen = set()
    p1 = a.alloc(3)
    p2 = a.alloc(4)
    assert p1 is not None and p2 is not None
    for p in p1 + p2:
        assert p not in seen, "page handed out twice"
        assert p != NULL_PAGE
        seen.add(p)
    assert a.alloc(1) is None                 # exhausted (7 usable)
    a.free(p1)
    assert a.free_pages == 3
    p3 = a.alloc(3)
    assert p3 is not None and set(p3) == set(p1)


def test_allocator_double_free_raises():
    a = BlockAllocator(num_pages=4, page_size=4)
    p = a.alloc(1)
    a.free(p)
    with pytest.raises(ValueError):
        a.free(p)


def test_defrag_compacts_live_pages():
    a = BlockAllocator(num_pages=16, page_size=4)
    p1 = a.alloc(3)
    p2 = a.alloc(3)
    a.free(p1)
    moves = a.defrag_plan()
    # surviving pages now occupy 1..3
    live_after = set(moves.get(p, p) for p in p2)
    assert live_after == {1, 2, 3}
    assert a.alloc(12) is not None            # whole pool reusable


def test_block_table_pages_needed():
    t = BlockTable(pages=[5], length=4)
    assert t.pages_needed(4, page_size=4) == 0
    assert t.pages_needed(5, page_size=4) == 1
    assert t.pages_needed(9, page_size=4) == 2
    assert t.padded(3) == [5, NULL_PAGE, NULL_PAGE]


# ---------------------------------------------------------------------------
# paged-gather kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(6, 4, 8), (10, 8, 16)])
def test_paged_gather_kernel_matches_ref(shape):
    n, p, d = shape
    pool = jax.random.normal(jax.random.PRNGKey(0), (n, p, d))
    tables = jax.random.randint(jax.random.PRNGKey(1), (3, 4), 0, n)
    want = ref.paged_gather_ref(pool, tables)
    got = ops.paged_gather(pool, tables, use_pallas=True)     # interpret
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))
    got_ref = ops.paged_gather(pool, tables, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want))


@pytest.mark.parametrize("shape", [(6, 4, 8), (10, 8, 16)])
def test_paged_gather_dequant_kernel_matches_ref(shape):
    n, p, d = shape
    pool = jax.random.randint(jax.random.PRNGKey(0), (n, p, d), -127, 128,
                              jnp.int8)
    scales = jax.random.uniform(jax.random.PRNGKey(1), (n, p, 1),
                                jnp.float32, 0.01, 0.1)
    tables = jax.random.randint(jax.random.PRNGKey(2), (3, 4), 0, n)
    want = ref.paged_gather_dequant_ref(pool, scales, tables)
    got = ops.paged_gather_dequant(pool, scales, tables, use_pallas=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))
    got_ref = ops.paged_gather_dequant(pool, scales, tables,
                                       use_pallas=False)
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want))
    # manual dequant oracle
    idx = np.asarray(tables)
    oracle = np.asarray(pool).astype(np.float32)[idx] * \
        np.asarray(scales)[idx]
    np.testing.assert_allclose(
        np.asarray(want), oracle.reshape(3, 4 * p, d))


# ---------------------------------------------------------------------------
# engine end-to-end per family
# ---------------------------------------------------------------------------

FAMILY_CASES = [
    ("kv", "qwen3-4b", {}),
    ("srf", "qwen3-4b", {"attn_impl": "srf"}),
    ("mla", "deepseek-v2-lite-16b", {}),
    ("ssd", "mamba2-2.7b", {}),
]


@pytest.mark.parametrize("fam,arch,over", FAMILY_CASES,
                         ids=[c[0] for c in FAMILY_CASES])
def test_engine_mixed_lengths_per_family(fam, arch, over):
    cfg = registry.reduced(arch, n_layers=2, **over)
    params = T.init(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, batch_slots=8, max_len=64)
    rng = np.random.default_rng(0)
    n = 16
    for i in range(n):
        plen = int(rng.integers(2, 24))
        eng.submit(Request(uid=i,
                           prompt=rng.integers(0, cfg.vocab, plen)
                           .astype(np.int32),
                           max_new=int(rng.integers(3, 8))))
    done = eng.run()
    assert len(done) == n
    assert all(len(r.out_tokens) == r.max_new for r in done)
    assert eng.stats["requests"] == n
    # every page returned to the pool
    assert eng.sched.alloc.used_pages == 0


def test_paged_matches_legacy_greedy():
    """Same params, same prompt: the paged engine's greedy output equals
    the legacy contiguous-cache engine's."""
    legacy = _legacy()
    cfg = registry.reduced("qwen3-4b", n_layers=2)
    params = T.init(jax.random.PRNGKey(0), cfg)
    prompt = np.arange(11, dtype=np.int32)

    eng = Engine(cfg, params, batch_slots=2, max_len=64)
    eng.submit(Request(uid=0, prompt=prompt, max_new=8))
    paged = eng.run()[0].out_tokens

    leg = legacy.Engine(cfg, params, batch_slots=1, max_len=64)
    leg.submit(Request(uid=0, prompt=prompt, max_new=8))
    old = leg.run()[0].out_tokens
    assert paged == old


def test_preemption_restores_state():
    """Tight pool forces eviction mid-decode; copy-on-preempt + swap-in
    must reproduce the unconstrained outputs exactly."""
    cfg = registry.reduced("qwen3-4b", n_layers=2)
    params = T.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, 3).astype(np.int32)
               for _ in range(4)]

    def drive(sched):
        eng = Engine(cfg, params, batch_slots=4, max_len=16, sched=sched)
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p, max_new=10))
        done = eng.run()
        return {r.uid: r.out_tokens for r in done}, eng.stats["preemptions"]

    tight = SchedConfig(max_batch=4, prefill_batch=2, prefill_chunk=4,
                        page_size=4, num_pages=9, table_width=4)
    roomy = SchedConfig(max_batch=4, prefill_batch=2, prefill_chunk=4,
                        page_size=4, num_pages=33, table_width=4)
    out_tight, n_pre = drive(tight)
    out_roomy, _ = drive(roomy)
    assert n_pre > 0, "pool was not tight enough to force preemption"
    assert out_tight == out_roomy


def test_priority_policy_orders_admission():
    cfg = registry.reduced("qwen3-4b", n_layers=2)
    params = T.init(jax.random.PRNGKey(0), cfg)
    # pool with room for a single active request at a time
    sched = SchedConfig(max_batch=1, prefill_batch=1, prefill_chunk=8,
                        page_size=8, num_pages=3, table_width=2,
                        policy="priority")
    eng = Engine(cfg, params, sched=sched)
    prompt = np.arange(6, dtype=np.int32)
    eng.submit(Request(uid=0, prompt=prompt, max_new=4, priority=0))
    eng.submit(Request(uid=1, prompt=prompt, max_new=4, priority=5))
    done = eng.run()
    assert len(done) == 2
    by_uid = {r.uid: r for r in done}
    assert by_uid[1].t_done <= by_uid[0].t_done   # high priority first


@pytest.mark.parametrize("attn", ["full", "srf"])
def test_chunked_prefill_long_prompt(attn):
    """Prompt much longer than the chunk: result equals one-shot legacy
    (for SRF this also covers rope positions past the single state page
    and the carried-state chunk boundary)."""
    legacy = _legacy()
    cfg = registry.reduced("qwen3-4b", n_layers=2, attn_impl=attn)
    params = T.init(jax.random.PRNGKey(0), cfg)
    prompt = (np.arange(50, dtype=np.int32) * 7) % cfg.vocab
    sched = SchedConfig(max_batch=2, prefill_batch=2, prefill_chunk=8,
                        page_size=8, num_pages=33, table_width=8)
    eng = Engine(cfg, params, sched=sched)
    eng.submit(Request(uid=0, prompt=prompt, max_new=6))
    paged = eng.run()[0].out_tokens
    leg = legacy.Engine(cfg, params, batch_slots=1, max_len=128)
    leg.submit(Request(uid=0, prompt=prompt, max_new=6))
    assert paged == leg.run()[0].out_tokens


def test_max_new_one_emits_exactly_one_token():
    """Regression: a max_new=1 request finishes AT PREFILL with exactly
    one output token. Previously the prefill step appended the first
    token without checking eos/max_new, so such a request took an extra
    decode step and emitted max_new+1 tokens (both engines had the bug)."""
    legacy = _legacy()
    cfg = registry.reduced("qwen3-4b", n_layers=2)
    params = T.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab,
                            int(rng.integers(2, 12))).astype(np.int32)
               for _ in range(6)]

    eng = Engine(cfg, params, batch_slots=4, max_len=64)
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p.copy(), max_new=1))
    done = eng.run()
    assert len(done) == 6
    assert all(len(r.out_tokens) == 1 for r in done)
    # finished at prefill: no decode step ran, everything returned
    assert eng.metrics.value_sum("engine_decode_steps_total") == 0
    assert eng.sched.alloc.used_pages == 0

    leg = legacy.Engine(cfg, params, batch_slots=4, max_len=64)
    for i, p in enumerate(prompts):
        leg.submit(Request(uid=i, prompt=p.copy(), max_new=1))
    ldone = leg.run()
    assert all(len(r.out_tokens) == 1 for r in ldone)
    assert {r.uid: r.out_tokens for r in done} == \
        {r.uid: r.out_tokens for r in ldone}


def test_eos_on_first_token_finishes_at_prefill():
    """A request whose FIRST sampled token is eos stops with one token
    and a closed trace: learn the greedy first token, resubmit with it
    as eos_id."""
    cfg = registry.reduced("qwen3-4b", n_layers=2)
    params = T.init(jax.random.PRNGKey(0), cfg)
    prompt = np.arange(9, dtype=np.int32)
    eng = Engine(cfg, params, batch_slots=2, max_len=64)
    eng.submit(Request(uid=0, prompt=prompt.copy(), max_new=8))
    first = eng.run()[0].out_tokens[0]

    eng2 = Engine(cfg, params, batch_slots=2, max_len=64)
    eng2.submit(Request(uid=0, prompt=prompt.copy(), max_new=8,
                        eos_id=int(first)))
    done = eng2.run()
    assert len(done) == 1
    r = done[0]
    assert r.out_tokens == [first]
    assert r.t_submit <= r.t_first <= r.t_done
    assert r.trace.count("done") == 1 and r.trace.monotonic()
    assert eng2.metrics.value_sum("engine_decode_steps_total") == 0


def test_on_first_logits_reports_the_first_token_row():
    """The hook sees each request's first-token logits row exactly once;
    its greedy argmax is the first token the engine emits."""
    cfg = registry.reduced("qwen3-4b", n_layers=2)
    params = T.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    rows = {}

    def keep(req, row):
        assert req.uid not in rows
        rows[req.uid] = row
    eng = Engine(cfg, params, batch_slots=2, max_len=64, on_first_logits=keep)
    for i in range(4):
        eng.submit(Request(uid=i, max_new=4, prompt=rng.integers(
            0, cfg.vocab, int(rng.integers(3, 40))).astype(np.int32)))
    done = eng.run()
    assert sorted(rows) == [0, 1, 2, 3]
    for r in done:
        assert rows[r.uid].shape == (cfg.vocab,)
        assert rows[r.uid].dtype == np.float32
        assert int(np.argmax(rows[r.uid])) == r.out_tokens[0]


# ---------------------------------------------------------------------------
# sampler
# ---------------------------------------------------------------------------

def test_sampler_greedy_topk_topp():
    from repro.serving.sampler import sample
    logits = jnp.log(jnp.asarray([[0.05, 0.15, 0.5, 0.3]] * 3))
    out = sample(jax.random.PRNGKey(0), logits,
                 jnp.asarray([0.0, 1.0, 1.0]),      # greedy / k=1 / tiny p
                 jnp.asarray([0, 1, 0]),
                 jnp.asarray([1.0, 1.0, 1e-6]))
    assert list(np.asarray(out)) == [2, 2, 2]
    # top-k=2 support is exactly {2, 3}
    hits = set()
    for i in range(64):
        o = sample(jax.random.PRNGKey(i), logits, jnp.asarray([1.0] * 3),
                   jnp.asarray([2] * 3), jnp.asarray([1.0] * 3))
        hits.update(int(x) for x in np.asarray(o))
    assert hits == {2, 3}


def test_engine_sampled_run_completes():
    cfg = registry.reduced("qwen3-4b", n_layers=2)
    params = T.init(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, batch_slots=4, max_len=64, seed=7)
    for i in range(4):
        eng.submit(Request(uid=i, prompt=np.arange(5, dtype=np.int32),
                           max_new=6, temperature=0.9, top_k=50, top_p=0.95))
    done = eng.run()
    assert len(done) == 4
    assert all(len(r.out_tokens) == 6 for r in done)
    assert all(0 <= t < cfg.vocab for r in done for t in r.out_tokens)


# ---------------------------------------------------------------------------
# seeded SRF: per-request zero-storage personalized projections
# ---------------------------------------------------------------------------

def _seeded_srf_cfg():
    import dataclasses
    cfg = registry.reduced("qwen3-4b", n_layers=2, attn_impl="srf")
    return dataclasses.replace(
        cfg, srf=dataclasses.replace(cfg.srf, seeded=True))


def test_seeded_srf_engine_personalizes_per_request():
    """Requests carry ``embed_seed``: same prompt, different seeds →
    different (personalized) greedy streams; same seed → bit-identical
    regardless of which other requests share the batch. embed_seed=0 is
    the shared base projection. No per-request projection weights exist
    anywhere — the kernel regenerates them from the folded seed."""
    cfg = _seeded_srf_cfg()
    params = T.init(jax.random.PRNGKey(0), cfg)
    # the SRF projection params really are seeds — one uint32 per
    # (layer, head), no float matrices (zero storage in n_features)
    seeds = [l for l in jax.tree_util.tree_leaves(params)
             if l.dtype == jnp.uint32]
    assert seeds and all(l.size <= cfg.n_layers * cfg.n_heads
                         for l in seeds)
    prompt = np.arange(9, dtype=np.int32)

    def run(seeds):
        eng = Engine(cfg, params, batch_slots=4, max_len=64)
        for i, es in enumerate(seeds):
            eng.submit(Request(uid=i, prompt=prompt.copy(), max_new=6,
                               embed_seed=es))
        return {r.uid: list(r.out_tokens) for r in eng.run()}

    mixed = run([0, 123, 777])
    assert mixed[1] != mixed[0], "embed_seed=123 did not personalize"
    assert mixed[2] != mixed[1]
    # batch-composition invariance: each stream reproduces solo
    assert run([123])[0] == mixed[1]
    assert run([0])[0] == mixed[0]
    # determinism: rerun bit-identical
    assert run([0, 123, 777]) == mixed


def test_seeded_srf_zero_embed_matches_unseeded_semantics():
    """The base (embed_seed=0) projection is one fixed per-head seed set:
    an all-base batch equals a batch submitted without touching
    embed_seed at all (the default)."""
    cfg = _seeded_srf_cfg()
    params = T.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, int(rng.integers(3, 14)))
               .astype(np.int32) for _ in range(5)]

    def run(with_field):
        eng = Engine(cfg, params, batch_slots=4, max_len=64)
        for i, p in enumerate(prompts):
            kw = {"embed_seed": 0} if with_field else {}
            eng.submit(Request(uid=i, prompt=p.copy(), max_new=5, **kw))
        return {r.uid: list(r.out_tokens) for r in eng.run()}

    assert run(True) == run(False)
