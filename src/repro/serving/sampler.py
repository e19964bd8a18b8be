"""Token sampling for the serving engine: temperature / top-k / top-p.

One jit'd, fully batched sampler: every request carries its own
(temperature, top_k, top_p) vector entry, so mixed sampling configs run
in a single call with no per-request branching. ``temperature <= 0``
selects greedy argmax for that row (the engine's default, which keeps
decoding deterministic for tests).

Engines draw through :func:`sample_tokens`, which picks the program on
the host from the batch's temperatures: a batch with no sampled row takes
:func:`greedy_tokens`, a plain argmax, and any other batch takes
:func:`sample_stateless`, which sorts every row for top-k / top-p. Both
give a greedy row the same token, ``argmax(logits.astype(f32))``.

In :func:`sample_stateless` the noise for row ``i``
is a pure function of ``(base_key, uid[i], position[i])`` — NOT of any
engine-side RNG state, batch composition, admission order, or replica.
That is the sampling-key contract fault-tolerant replay relies on: a
rescued request replays the exact keys its killed replica would have
used, so temperature-sampled streams are bit-identical across rescue
(``serving/README.md`` §sampling determinism).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _scoped(fn):
    """Trace ``fn`` under ``jax.named_scope("sample")``, so the sampler's
    device operations carry ``sample`` in their HLO ``op_name``."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.named_scope("sample"):
            return fn(*args, **kwargs)
    return wrapped


@functools.partial(jax.jit, static_argnames=())
@_scoped
def sample(rng: jax.Array, logits: jax.Array, temperature: jax.Array,
           top_k: jax.Array, top_p: jax.Array) -> jax.Array:
    """logits: (B, V); temperature/top_p: (B,) f32; top_k: (B,) int32
    (0 = disabled) -> (B,) int32 sampled token ids.

    Implementation: sort once descending, build the combined top-k
    (rank < k) and top-p (cumulative prob below p, first always kept)
    masks in sorted order, then Gumbel-max over the surviving logits —
    equivalent to renormalized categorical sampling, no second pass.
    """
    b, v = logits.shape
    lf = logits.astype(jnp.float32)
    greedy = temperature <= 0.0
    temp = jnp.where(greedy, 1.0, jnp.maximum(temperature, 1e-6))
    scaled = lf / temp[:, None]

    order = jnp.argsort(-scaled, axis=-1)                  # (B, V) desc
    sorted_logits = jnp.take_along_axis(scaled, order, axis=-1)
    ranks = jnp.arange(v)[None, :]
    k_eff = jnp.where(top_k <= 0, v, top_k)[:, None]
    keep = ranks < k_eff                                   # top-k in sorted order

    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # keep tokens whose cumulative mass BEFORE them is < top_p; the
    # argmax token (rank 0) always survives
    keep &= (cum - probs) < top_p[:, None]
    keep |= ranks == 0

    masked = jnp.where(keep, sorted_logits, -jnp.inf)
    g = jax.random.gumbel(rng, (b, v), jnp.float32)
    pick_sorted = jnp.argmax(masked + g, axis=-1)          # (B,)
    sampled = jnp.take_along_axis(order, pick_sorted[:, None], axis=-1)[:, 0]
    argmax = jnp.argmax(lf, axis=-1)
    return jnp.where(greedy, argmax, sampled).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=())
@_scoped
def sample_stateless(base_key: jax.Array, uids: jax.Array,
                     positions: jax.Array, logits: jax.Array,
                     temperature: jax.Array, top_k: jax.Array,
                     top_p: jax.Array) -> jax.Array:
    """Per-request stateless sampling: same masking math as
    :func:`sample`, but row ``i``'s Gumbel noise comes from the derived
    key ``fold_in(fold_in(base_key, uids[i]), positions[i])`` instead of
    one batch-wide key. uids/positions: (B,) int32 (padded rows may carry
    anything — their key is drawn but their token is discarded).

    Because each row's draw depends only on its own (uid, position), the
    sampled stream of a request is invariant to batch composition and
    batch slot — a batch-1 replay (e.g. the legacy engine, or a rescue
    replica re-running a lone request) reproduces it bit for bit.
    """
    b, v = logits.shape
    lf = logits.astype(jnp.float32)
    greedy = temperature <= 0.0
    temp = jnp.where(greedy, 1.0, jnp.maximum(temperature, 1e-6))
    scaled = lf / temp[:, None]

    order = jnp.argsort(-scaled, axis=-1)                  # (B, V) desc
    sorted_logits = jnp.take_along_axis(scaled, order, axis=-1)
    ranks = jnp.arange(v)[None, :]
    k_eff = jnp.where(top_k <= 0, v, top_k)[:, None]
    keep = ranks < k_eff

    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep &= (cum - probs) < top_p[:, None]
    keep |= ranks == 0

    masked = jnp.where(keep, sorted_logits, -jnp.inf)

    def row_gumbel(uid, position):
        k = jax.random.fold_in(jax.random.fold_in(base_key, uid), position)
        return jax.random.gumbel(k, (v,), jnp.float32)

    g = jax.vmap(row_gumbel)(uids, positions)              # (B, V)
    pick_sorted = jnp.argmax(masked + g, axis=-1)          # (B,)
    sampled = jnp.take_along_axis(order, pick_sorted[:, None], axis=-1)[:, 0]
    argmax = jnp.argmax(lf, axis=-1)
    return jnp.where(greedy, argmax, sampled).astype(jnp.int32)


@jax.jit
@_scoped
def greedy_tokens(logits: jax.Array) -> jax.Array:
    """logits: (B, V) -> (B,) int32 argmax per row: the token
    :func:`sample_stateless` gives a row with ``temperature <= 0`` (the
    same cast and the same first-index tie-break), without its sort."""
    return jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)


def sample_tokens(base_key: jax.Array, uids: np.ndarray,
                  positions: np.ndarray, logits: jax.Array,
                  temperature: np.ndarray, top_k: np.ndarray,
                  top_p: np.ndarray) -> Tuple[jax.Array, str]:
    """Draw one token per row; the sampling parameters are host (numpy)
    arrays of shape (B,), as :func:`sample_stateless` takes them.

    Returns the (B,) int32 tokens and the path taken: ``"argmax"`` when
    no row samples (every ``temperature <= 0``, padded zero rows
    included), else ``"full"``. The choice reads only the host arrays, so
    it costs no device work, and each path gives every row the token
    :func:`sample_stateless` would."""
    if np.all(temperature <= 0.0):
        return greedy_tokens(logits), "argmax"
    return sample_stateless(base_key, jnp.asarray(uids),
                            jnp.asarray(positions), logits,
                            jnp.asarray(temperature), jnp.asarray(top_k),
                            jnp.asarray(top_p)), "full"
