"""A configuration of another architecture joins with files of its own.

A DeepSeek-V2-Lite configuration (published sizes as in its config.json:
MLA attention, routed and shared experts, a dense first layer) names a
reference and a work module that are stubbed here, in place of
``references/<name>.py`` and ``work/<name>.py``. Through them, and no
harness file written for it, the program's sizes are checked, the paged
pool is sized by the latent cache a token holds, and the model-step
metrics read that architecture's work."""
import json
import os
import types

import pytest

from servebench import run, spec
from servebench.drive import StepRec, Window

PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10944,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "greedy", "v_head_dim": 128,
    "vocab_size": 102400}
CONFIG = {
    "name": "deepseek-v2-lite", "published": PUBLISHED,
    "attention": {"impl": "full"}, "dtype": "bfloat16",
    "reference": "deepseek_v2",
    "program": {"arch": "deepseek-v2-lite-16b",
                "overrides": {"norm_eps": 1e-06}},
    "serving": {"slots": 16, "pool_bytes": 3.0e9}}


def program_sizes(config):
    p = config["published"]
    return {"n_layers": p["num_hidden_layers"], "d_model": p["hidden_size"],
            "n_heads": p["num_attention_heads"],
            "n_kv_heads": p["num_key_value_heads"],
            "d_ff": p["intermediate_size"], "vocab": p["vocab_size"],
            "rope_theta": float(p["rope_theta"]),
            "norm_eps": p["rms_norm_eps"],
            "tie_embeddings": p["tie_word_embeddings"],
            "qkv_bias": p["attention_bias"],
            "mla_kv_lora": p["kv_lora_rank"],
            "mla_qk_nope": p["qk_nope_head_dim"],
            "mla_qk_rope": p["qk_rope_head_dim"],
            "mla_v_dim": p["v_head_dim"],
            "moe_experts": p["n_routed_experts"],
            "moe_top_k": p["num_experts_per_tok"],
            "moe_shared": p["n_shared_experts"],
            "moe_d_ff": p["moe_intermediate_size"],
            "moe_first_dense": p["first_k_dense_replace"],
            "dtype": config["dtype"], "attn_impl": config["attention"]["impl"]}


def cache_bytes_per_token(config, itemsize=2):
    """MLA keeps the latent and the rope key of every layer."""
    p = config["published"]
    return p["num_hidden_layers"] * (p["kv_lora_rank"]
                                     + p["qk_rope_head_dim"]) * itemsize


def token_flops(config, keys, emits):
    return 1e9 * keys + (5e8 if emits else 0.0)


def decode_least_bytes(config, rows, context):
    return 1e6 * rows + 1e3 * context


@pytest.fixture
def stubs(monkeypatch):
    ref = types.SimpleNamespace(program_sizes=program_sizes)
    work = types.SimpleNamespace(cache_bytes_per_token=cache_bytes_per_token,
                                 token_flops=token_flops,
                                 decode_least_bytes=decode_least_bytes)
    monkeypatch.setattr(spec, "reference_module",
                        lambda c: {"deepseek_v2": ref}[c["reference"]])
    monkeypatch.setattr(spec, "work_module",
                        lambda c: {"deepseek_v2": work}[c["reference"]])


def test_published_sizes_pass(stubs):
    cfg = run.program_config(CONFIG)
    assert (cfg.mla_kv_lora, cfg.moe_experts, cfg.moe_top_k,
            cfg.moe_first_dense) == (512, 64, 6, 1)


def test_wrong_kv_lora_rank_fails_with_its_name(stubs):
    config = dict(CONFIG, published=dict(PUBLISHED, kv_lora_rank=256))
    with pytest.raises(ValueError, match="mla_kv_lora=512, published 256"):
        run.program_config(config)


def test_pool_is_sized_by_the_latent_cache(stubs):
    with open(os.path.join(spec.HERE, "traffic", "decode_long.json")) as f:
        traffic = json.load(f)
    cell = spec.Cell(name="deepseek-v2-lite.decode_long", chips=1,
                     config=CONFIG, traffic=traffic, limits={},
                     end_to_end=[], per_layer=[])
    sc = run.sched_config(run.program_config(CONFIG), cell)
    assert cache_bytes_per_token(CONFIG) == 27 * 576 * 2 == 31_104
    assert sc.page_size == 16
    # GQA's K and V of 16 heads of 128 would give 221,184 B a token: 847
    assert sc.num_pages == int(3.0e9 // (16 * 31_104)) == 6028


def test_model_step_metrics_read_its_own_work(stubs):
    recs = [types.SimpleNamespace(prompt=[0] * 3, token_t=[0.5, 0.75])]
    run_data = types.SimpleNamespace(
        cell=types.SimpleNamespace(config=CONFIG, chips=1),
        window=Window(0.0, 1.0, recs, [], first_traced_step=0),
        peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}, profile=None)
    mfu = spec.metric_reader("mfu.decode_long").read(run_data)
    # prefill of 3 prompt tokens (the last emits), then one decoded token
    flops = 1e9 * (1 + 2 + 3) + 5e8 + 1e9 * 4 + 5e8
    assert mfu == pytest.approx(100 * flops / 1e12)
    prof = types.SimpleNamespace(steps=lambda: [(0, 100)],
                                 busy_ns=lambda a, b: 5e6)
    run_data.profile = prof
    run_data.window = Window(0.0, 1.0, [], [StepRec(0, 1, "decode", 4, 900)],
                             first_traced_step=0)
    bw = spec.metric_reader("step_bw_share.decode_long").read(run_data)
    assert bw == pytest.approx(100 * (4e6 + 9e5) / 1e9 / 5e-3)
