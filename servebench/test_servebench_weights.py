"""Weights from the seed: the program's tree, made in one call, holds
bit for bit what the reference makes one layer at a time."""
import json
import os

import jax
import numpy as np

from servebench import run, spec, weights

FIX = os.path.join(spec.HERE, "fixtures")


def _config(name):
    with open(os.path.join(FIX, name + ".json")) as f:
        return json.load(f)


def test_seed_key_takes_wide_seeds():
    a = weights.seed_key(2 ** 40 + 1)
    assert np.array_equal(a, weights.seed_key(2 ** 40 + 1))
    assert not np.array_equal(a, weights.seed_key(2 ** 40 + 2))
    assert a.shape == (2,)


def test_program_tree_matches_reference_layers():
    from repro.models import transformer as model_lib
    for name in ("tiny-qwen3", "tiny-qwen3-srf"):
        config = _config(name)
        cfg = run.program_config(config)
        ref = spec.reference_module(config)
        specs = ref.weight_specs(config)
        key = weights.seed_key(2 ** 35 + 9)
        shapes = jax.eval_shape(
            lambda: model_lib.init(jax.random.PRNGKey(0), cfg))
        params = weights.program_params(specs, cfg.n_layers, shapes,
                                        config["layout"], key, "bfloat16")
        layer = weights.layer_maker(specs, "bfloat16")(key, np.int32(1))
        seg = params["segments"][0]
        assert np.array_equal(seg["attn"]["wq"][1], layer["wq"])
        assert np.array_equal(seg["mlp"]["wg"][1], layer["w_gate"])
        assert np.array_equal(seg["ln2"]["w"][1], layer["ln2"])
        glob = weights.global_maker(specs, "bfloat16")(key)
        tok = params["embed"]["tok"]
        v = config["published"]["vocab_size"]
        assert np.array_equal(tok[:v], glob["embed"])
        assert not np.any(np.asarray(tok[v:], np.float32))   # padded rows
        if "srf" in seg["attn"]:
            assert np.array_equal(seg["attn"]["srf"][0]["d1"][1],
                                  layer["srf_d1"])


def test_layout_must_cover_the_program():
    config = _config("tiny-qwen3")
    cfg = run.program_config(config)
    from repro.models import transformer as model_lib
    shapes = jax.eval_shape(lambda: model_lib.init(jax.random.PRNGKey(0), cfg))
    ref = spec.reference_module(config)
    layout = dict(config["layout"])
    del layout["wo"]
    try:
        weights.program_params(ref.weight_specs(config), cfg.n_layers,
                               shapes, layout, weights.seed_key(1), "bfloat16")
    except ValueError as e:
        assert "unmapped" in str(e)
    else:
        raise AssertionError("a parameter left out of the layout passed")
