"""The sampler's two programs: a call in which no row samples takes
``greedy_tokens`` (one argmax per row), any other call takes
``sample_stateless``. Each path must give every row the token
``sample_stateless`` gives it, and the engines must count the path taken."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import transformer as T
from repro.serving import Engine, Request, engine as engine_mod, sampler

V = 1000


def _knobs(temps):
    b = len(temps)
    return (np.asarray(temps, np.float32), np.zeros((b,), np.int32),
            np.ones((b,), np.float32))


def _keys(b):
    return np.arange(7, 7 + b, dtype=np.uint32), np.arange(b, dtype=np.int32)


def _stateless(logits, temps, uids, poss, top_k=None, top_p=None):
    t, k, p = _knobs(temps)
    k = k if top_k is None else top_k
    p = p if top_p is None else top_p
    return np.asarray(sampler.sample_stateless(
        jax.random.PRNGKey(3), jnp.asarray(uids), jnp.asarray(poss), logits,
        jnp.asarray(t), jnp.asarray(k), jnp.asarray(p)))


def _logits(b, dtype, seed=0):
    """Random rows; every third row gets an exact tie for its maximum at
    two columns, so the first index has to win."""
    x = np.random.default_rng(seed).normal(size=(b, V)).astype(np.float32)
    for i in range(0, b, 3):
        lo, hi = sorted(np.random.default_rng(seed + i).choice(V, 2, False))
        x[i, lo] = x[i, hi] = x[i].max() + 1.0
    return jnp.asarray(x, dtype)


@pytest.mark.parametrize("b", [1, 14, 24])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_greedy_tokens_match_sample_stateless(b, dtype):
    logits = _logits(b, dtype, seed=b)
    uids, poss = _keys(b)
    want = _stateless(logits, [0.0] * b, uids, poss)
    got = sampler.greedy_tokens(logits)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), want)
    for i in range(0, b, 3):        # the tied maximum resolves to its first
        row = np.asarray(logits[i].astype(jnp.float32))
        assert want[i] == np.flatnonzero(row == row.max())[0]
    toks, path = sampler.sample_tokens(jax.random.PRNGKey(3), uids, poss,
                                       logits, *_knobs([0.0] * b))
    assert path == "argmax"
    np.testing.assert_array_equal(np.asarray(toks), want)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_one_sampled_row_takes_the_full_path(dtype):
    b = 14
    logits = _logits(b, dtype, seed=5)
    uids, poss = _keys(b)
    temps = [0.0] * b
    temps[4] = 0.8
    top_k = np.full((b,), 50, np.int32)
    top_p = np.full((b,), 0.95, np.float32)
    toks, path = sampler.sample_tokens(jax.random.PRNGKey(3), uids, poss,
                                       logits, np.asarray(temps, np.float32),
                                       top_k, top_p)
    assert path == "full"
    want = _stateless(logits, temps, uids, poss, top_k, top_p)
    np.testing.assert_array_equal(np.asarray(toks), want)
    greedy = np.asarray(sampler.greedy_tokens(logits))
    np.testing.assert_array_equal(np.delete(want, 4), np.delete(greedy, 4))


@pytest.mark.parametrize("temps,path", [
    ([0.0], "argmax"),
    ([0.0] * 24, "argmax"),
    ([-1.0, 0.0, 0.0], "argmax"),
    ([0.0, 0.0, 1e-6], "full"),
    ([0.8], "full"),
    ([0.0] * 23 + [0.7], "full"),
])
def test_path_is_argmax_exactly_when_no_row_samples(temps, path):
    b = len(temps)
    uids, poss = _keys(b)
    _, got = sampler.sample_tokens(jax.random.PRNGKey(0), uids, poss,
                                   _logits(b, jnp.float32), *_knobs(temps))
    assert got == path


# ---------------------------------------------------------------------------
# engines
# ---------------------------------------------------------------------------

def _engine_run(reqs, **kw):
    cfg = registry.reduced("qwen3-4b", n_layers=2)
    params = T.init(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, batch_slots=4, max_len=64, seed=11, **kw)
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    return eng, {r.uid: list(r.out_tokens) for r in done}


def _requests(temps, max_new=6):
    rng = np.random.default_rng(2)
    return [Request(uid=i, max_new=max_new, temperature=t, top_k=50,
                    top_p=0.95, prompt=rng.integers(
                        0, 512, int(rng.integers(3, 30))).astype(np.int32))
            for i, t in enumerate(temps)]


def _calls(eng, path):
    return eng.metrics.counter("engine_sample_calls_total",
                               labelnames=("engine", "path")).value(
        engine=eng.engine_id, path=path)


def test_engine_counts_sampler_calls_by_path():
    """Greedy traffic counts only ``argmax``. A request at temperature 0.8
    is in one sampler call per token it draws (its last prefill chunk, then
    each decode step): those calls, and only those, count ``full``."""
    eng, _ = _engine_run(_requests([0.0] * 6))
    steps = (eng.metrics.value_sum("engine_prefill_steps_total")
             + eng.metrics.value_sum("engine_decode_steps_total"))
    assert _calls(eng, "full") == 0
    assert _calls(eng, "argmax") == steps > 0

    reqs = _requests([0.0] * 5 + [0.8])
    eng, out = _engine_run(reqs)
    steps = (eng.metrics.value_sum("engine_prefill_steps_total")
             + eng.metrics.value_sum("engine_decode_steps_total"))
    assert len(out[5]) == reqs[5].max_new
    assert _calls(eng, "full") == reqs[5].max_new
    assert _calls(eng, "argmax") == steps - reqs[5].max_new


@pytest.mark.parametrize("temps", [[0.0] * 6, [0.0] * 5 + [0.8]],
                         ids=["greedy", "mixed"])
def test_engine_streams_do_not_depend_on_the_path(monkeypatch, temps):
    """The engine serves the same tokens when every call is forced through
    ``sample_stateless``."""
    reqs = lambda: _requests(temps)  # noqa: E731
    _, chosen = _engine_run(reqs())

    def full_only(key, uids, poss, logits, temps, ks, ps):
        return sampler.sample_stateless(
            key, jnp.asarray(uids), jnp.asarray(poss), logits,
            jnp.asarray(temps), jnp.asarray(ks), jnp.asarray(ps)), "full"
    monkeypatch.setattr(engine_mod, "_sample_tokens", full_only)
    eng, forced = _engine_run(reqs())
    assert _calls(eng, "argmax") == 0
    assert forced == chosen
