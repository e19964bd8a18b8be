"""Work counts against qwen3-4b figures worked out by hand from the
published config (hidden 2560, 32 heads, 8 kv heads, head 128, MLP 9728,
36 layers, vocab 151936, tied head) and the SRF sizes (m = 256)."""
import json
import os

import pytest

from servebench import spec
from servebench.work import kernels


def _config(name):
    with open(os.path.join(spec.HERE, "configs", name + ".json")) as f:
        return json.load(f)


KV, SRF = _config("qwen3-4b"), _config("qwen3-4b-srf")
model = spec.work_module(KV)
PEAKS = spec.load_peaks("TPU v5 lite")


def test_matmul_params_per_layer():
    # q and o: 2560 x 4096 twice; k and v: 2560 x 1024 twice; MLP 3 x 2560 x 9728
    assert model.matmul_params_per_layer(KV) == 20_971_520 + 5_242_880 \
        + 74_711_040 == 100_925_440


def test_weight_bytes():
    # 36 x (100,925,440 + two norms of 2560 + q/k norms of 128)
    # + embedding 151,936 x 2560 + final norm 2560, in bf16
    n = 36 * 100_930_816 + 388_956_160 + 2560
    assert model.weight_bytes(KV) == 2 * n == 8_044_936_192
    # plus the circulant generators (2 x 128) and the two sign vectors per
    # kv head and layer
    assert model.weight_bytes(SRF) - model.weight_bytes(KV) \
        == 2 * 36 * 8 * (256 + 256)


def test_cache_bytes():
    assert model.cache_bytes_per_token(KV) == 36 * 8 * 128 * 2 * 2 == 147_456
    assert model.srf_state_bytes(SRF) == 36 * 32 * 256 * 129 * 2 \
        == 76_087_296


def test_token_flops():
    lin = 2 * 100_925_440 * 36
    head = 2 * 2560 * 151_936
    assert model.token_flops(KV, 1000, True) == lin + 36 * 4 * 32 * 128 * 1000 \
        + head
    assert model.token_flops(KV, 1, False) == lin + 36 * 4 * 32 * 128
    srf_attn = 36 * ((32 + 8) * 2 * 256 * 128 + 2 * 32 * 256 * 128
                     + 32 * 256 + 2 * 32 * 256 * 129)
    assert model.token_flops(SRF, 5000, False) == lin + srf_attn
    assert model.token_flops(SRF, 1, False) == model.token_flops(SRF, 9, False)


def test_decode_least_bytes():
    w = model.weight_bytes(KV)
    assert model.decode_least_bytes(KV, 24, 24_000) == w + 24_024 * 147_456
    ws = model.weight_bytes(SRF)
    assert model.decode_least_bytes(SRF, 32, 0) == ws + 64 * 76_087_296


def test_paged_gather_counts():
    f, b = kernels.paged_gather(24, 320, 16, 1024, 2)
    assert f == 0 and b == 2 * 24 * 320 * 16 * 1024 * 2 == 503_316_480
    assert kernels.least_seconds(f, b, PEAKS) == pytest.approx(b / 819e9)


def test_srf_decode_counts():
    f, b = kernels.srf_decode(32, 32, 256, 128)
    bh = 32 * 32
    assert f == bh * (4 * 256 * 128 + 3 * 256)
    # read s, z, phi_q, phi_k, v; write s, z, out: all float32
    assert b == bh * 4 * (2 * 256 * 128 + 4 * 256 + 2 * 128)


def test_spinner_counts():
    f, b = kernels.spinner(8, 32 * 4, 128, 256, 2)
    assert f == 8 * 128 * 2 * 128 * (128 + 256)
    assert b == 8 * (128 * 384 + 256 + 256) * 2
    # bound by bandwidth: 128 flops per byte is under v5e's ridge of ~240
    assert kernels.least_seconds(f, b, PEAKS) == pytest.approx(b / 819e9)


def test_peaks_table_refuses_unknown_devices():
    with pytest.raises(spec.SpecError):
        spec.load_peaks("TPU v9 imaginary")
