"""The SRF witnesses: the reference's recurrent form, which rounds the
state where a server stores it, is the causal form when nothing is
rounded, and moves away from it when the state is stored in bfloat16; the
bfloat16-feature form moves away from the float32 reference too."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from servebench import spec, weights

FIX = os.path.join(spec.HERE, "fixtures")


def _hidden(state_dtype, stored_every, feature_dtype=None):
    with open(os.path.join(FIX, "tiny-qwen3-srf.json")) as f:
        config = json.load(f)
    ref = spec.reference_module(config)
    key = weights.seed_key(2 ** 34 + 21)
    rng = np.random.default_rng(5)
    tokens = rng.integers(0, config["published"]["vocab_size"],
                          (2, 96)).astype(np.int32)
    rows = np.array([(i, t) for i in range(2) for t in range(96)], np.int32)
    stored = np.zeros(tokens.shape, bool)
    stored[:, stored_every - 1::stored_every] = True
    r = ref.Reference(config, key, "bfloat16", state_dtype=state_dtype,
                      feature_dtype=feature_dtype)
    return np.asarray(r.hidden(tokens, rows,
                               None if state_dtype is None else stored))


@pytest.mark.parametrize("stored_every", [1, 16])
def test_recurrent_form_is_the_causal_form_unrounded(stored_every):
    causal = _hidden(None, stored_every)
    recurrent = _hidden(jnp.float32, stored_every)
    assert np.max(np.abs(recurrent - causal)) < 1e-3 * np.max(np.abs(causal))


def test_bfloat16_state_moves_the_reference():
    exact = _hidden(jnp.float32, 1)
    narrow = _hidden(jnp.bfloat16, 1)
    err = np.max(np.abs(narrow - exact)) / np.max(np.abs(exact))
    assert 1e-5 < err < 0.5


def test_bfloat16_features_move_the_reference():
    exact = _hidden(None, 1)
    narrow = _hidden(None, 1, jnp.bfloat16)
    err = np.max(np.abs(narrow - exact)) / np.max(np.abs(exact))
    assert 1e-5 < err < 0.5
