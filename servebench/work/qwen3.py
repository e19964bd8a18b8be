"""Work of a whole Qwen3 model step, counted from the published sizes in
a configuration file (never from the program): the work module of the
``qwen3`` reference (``spec.work_module``).

Model FLOPs count what the mathematics needs: every weight matrix once
per token processed, attention over the keys a token actually sees, the
output head once per token emitted. The least bytes of a decode step are
the weights read once, plus the KV cache of the tokens in context read
once and the new rows written (full attention), or the SRF state read and
written once per row (SRF attention).
"""
from __future__ import annotations

from typing import Dict

BF16 = 2


def _p(config: Dict) -> Dict:
    return config["published"]


def matmul_params_per_layer(config: Dict) -> int:
    p = _p(config)
    d, h, kv, hd, ff = (p["hidden_size"], p["num_attention_heads"],
                        p["num_key_value_heads"], p["head_dim"],
                        p["intermediate_size"])
    return d * h * hd * 2 + d * kv * hd * 2 + 3 * d * ff


def weight_bytes(config: Dict, itemsize: int = BF16) -> int:
    """Every weight once: layers, norms, embedding (the tied output head
    is the embedding, read in full for the logits)."""
    p = _p(config)
    d, v, hd = p["hidden_size"], p["vocab_size"], p["head_dim"]
    layer = matmul_params_per_layer(config) + 2 * d + 2 * hd
    srf = config["attention"].get("srf")
    if srf:
        n, m = hd, srf["n_features"]
        layer += p["num_key_value_heads"] * (-(-m // n) * n + 2 * n)
    n = p["num_hidden_layers"] * layer + v * d + d
    if not p["tie_word_embeddings"]:
        n += v * d
    return n * itemsize


def attention_flops(config: Dict, keys: int) -> float:
    """FLOPs of one token's attention in all layers, over ``keys`` keys."""
    p = _p(config)
    h, kv, hd, layers = (p["num_attention_heads"], p["num_key_value_heads"],
                         p["head_dim"], p["num_hidden_layers"])
    srf = config["attention"].get("srf")
    if not srf:
        return layers * 4.0 * h * hd * keys
    m = srf["n_features"]
    feats = (h + kv) * 2.0 * m * hd          # feature maps of q and k
    state = 2.0 * h * m * hd + h * m         # s += phi_k v^T, z += phi_k
    read = 2.0 * h * m * (hd + 1)            # phi_q s, phi_q z
    return layers * (feats + state + read)


def token_flops(config: Dict, keys: int, emits: bool) -> float:
    """One token through the model: weights, attention over ``keys`` keys,
    and the output head when the token's logits are used."""
    p = _p(config)
    f = 2.0 * matmul_params_per_layer(config) * p["num_hidden_layers"]
    f += attention_flops(config, keys)
    if emits:
        f += 2.0 * p["hidden_size"] * p["vocab_size"]
    return f


def cache_bytes_per_token(config: Dict, itemsize: int = BF16) -> int:
    """What one token holds in the paged pool: K and V of every layer."""
    p = _p(config)
    return (p["num_hidden_layers"] * p["num_key_value_heads"]
            * p["head_dim"] * 2 * itemsize)


def srf_state_bytes(config: Dict, itemsize: int = BF16) -> int:
    """One request's SRF state: s (H, m, dv) and z (H, m) in every layer."""
    p = _p(config)
    m = config["attention"]["srf"]["n_features"]
    return (p["num_hidden_layers"] * p["num_attention_heads"] * m
            * (p["head_dim"] + 1) * itemsize)


def decode_least_bytes(config: Dict, rows: int, context: int) -> float:
    """Least bytes one decode step moves: ``rows`` requests attending over
    ``context`` tokens in all."""
    b = float(weight_bytes(config))
    if config["attention"].get("srf"):
        return b + 2.0 * rows * srf_state_bytes(config)
    return b + (context + rows) * float(cache_bytes_per_token(config))
