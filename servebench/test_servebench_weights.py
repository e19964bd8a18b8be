"""Weights from the seed: the program's tree, made in one call, holds
bit for bit what the reference makes one layer at a time."""
import json
import os

import jax
import numpy as np
import pytest

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


def _deepseek():
    """registry.reduced('deepseek-v2-lite-16b')'s tree, split as the
    program splits it: one dense layer, then MoE layers. The spec list and
    the layout are written here, not taken from a configuration."""
    from repro.configs import registry
    from repro.models import transformer as model_lib
    cfg = registry.reduced("deepseek-v2-lite-16b", dtype="bfloat16")
    assert model_lib.segments(cfg) == [("dense", 1), ("moe", 2)]
    shapes = jax.eval_shape(lambda: model_lib.init(jax.random.PRNGKey(0), cfg))
    d, h, e, v = cfg.d_model, cfg.n_heads, cfg.moe_experts, cfg.vocab
    lora, nope, rope = cfg.mla_kv_lora, cfg.mla_qk_nope, cfg.mla_qk_rope
    vd, ff, fe = cfg.mla_v_dim, cfg.d_ff, cfg.moe_d_ff
    sh = cfg.moe_shared * fe
    W = weights.WeightSpec

    def mat(name, shape):
        return W(name, shape, True, "normal", shape[-2] ** -0.5)
    specs = [W("embed", (v, d), False, "normal", 0.02),
             W("final_norm", (d,), False, "norm", 0.05),
             W("lm_head", (d, v), False, "normal", d ** -0.5),
             W("ln1", (d,), True, "norm", 0.05),
             W("ln2", (d,), True, "norm", 0.05),
             mat("wq", (d, h * (nope + rope))), mat("wdkv", (d, lora)),
             mat("wkpe", (d, rope)), mat("wuk", (lora, h * nope)),
             mat("wuv", (lora, h * vd)), mat("wo", (h * vd, d)),
             mat("w_gate", (d, ff)), mat("w_up", (d, ff)),
             mat("w_down", (ff, d)), mat("router", (d, e)),
             mat("expert_gate", (e, d, fe)), mat("expert_up", (e, d, fe)),
             mat("expert_down", (e, fe, d)), mat("shared_gate", (d, sh)),
             mat("shared_up", (d, sh)), mat("shared_down", (sh, d))]

    def both(leaf):
        return {f"segments/0/{leaf}": 0, f"segments/1/{leaf}": 1}

    def moe(leaf):
        return {f"segments/1/moe/{leaf}": 1}
    layout = {"embed": "embed/tok", "final_norm": "final_norm/w",
              "lm_head": "head", "ln1": both("ln1/w"), "ln2": both("ln2/w"),
              **{n: both(f"attn/{n}")
                 for n in ("wq", "wdkv", "wkpe", "wuk", "wuv", "wo")},
              "w_gate": "segments/0/mlp/wg", "w_up": "segments/0/mlp/wi",
              "w_down": "segments/0/mlp/wo", "router": moe("router"),
              "expert_gate": moe("wg"), "expert_up": moe("wi"),
              "expert_down": moe("wo"), "shared_gate": moe("shared/wg"),
              "shared_up": moe("shared/wi"), "shared_down": moe("shared/wo")}
    return cfg, shapes, specs, layout


def test_two_segment_tree_holds_every_layer_as_the_reference_makes_it():
    cfg, shapes, specs, layout = _deepseek()
    key = weights.seed_key(2 ** 36 + 5)
    params = weights.program_params(specs, cfg.n_layers, shapes, layout,
                                    key, "bfloat16")
    flat = {weights._path_str(p): np.asarray(x, np.float32) for p, x in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    made = weights.layer_maker(specs, "bfloat16")
    layers = [{k: np.asarray(x, np.float32)
               for k, x in made(key, np.int32(l)).items()}
              for l in range(cfg.n_layers)]
    per = {s.name for s in specs if s.per_layer}
    seen = set()
    for name in per:
        where = layout[name]
        for path, first in ({where: 0} if isinstance(where, str)
                            else where).items():
            for i, x in enumerate(flat[path]):
                assert np.array_equal(x, layers[first + i][name]), (path, i)
                seen.add((name, first + i))
    # wq names a path in each segment, and the two hold every layer
    assert {l for n, l in seen if n == "wq"} == set(range(cfg.n_layers))
    assert flat["segments/0/attn/wq"].shape[0] == 1
    assert flat["segments/1/attn/wq"].shape[0] == cfg.n_layers - 1
    assert {l for n, l in seen if n == "router"} == {1, 2}
    glob = weights.global_maker(specs, "bfloat16")(key)
    assert np.array_equal(flat["head"], np.asarray(glob["lm_head"],
                                                   np.float32))


@pytest.mark.parametrize("shift", [-1, 1])
@pytest.mark.parametrize("scope", ["segment", "one_name"])
def test_first_layer_off_by_one_fails(shift, scope):
    cfg, shapes, specs, layout = _deepseek()
    for name, where in layout.items():
        if isinstance(where, dict) and (scope == "segment" or name == "wq"):
            layout[name] = {p: f + shift if p.startswith("segments/1/")
                            else f for p, f in where.items()}
    with pytest.raises(ValueError, match="do not tile"):
        weights.program_params(specs, cfg.n_layers, shapes, layout,
                               weights.seed_key(1), "bfloat16")


def test_one_path_named_twice_fails():
    cfg, shapes, specs, layout = _deepseek()
    layout["wq"] = dict(layout["wq"], **{"segments/1/attn/wo": 1})
    with pytest.raises(ValueError, match="named by both"):
        weights.program_params(specs, cfg.n_layers, shapes, layout,
                               weights.seed_key(1), "bfloat16")
