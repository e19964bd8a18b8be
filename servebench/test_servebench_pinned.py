"""The two benchmarked configurations read as they did before the sizes,
the pool and the work counts moved into per-architecture files. Every
literal here was printed by the harness's own functions before that move
(``run.program_config``'s checked sizes, ``run.sched_config``, and
``work/model.py``'s ``kv_bytes_per_token``, ``weight_bytes``,
``token_flops`` and ``decode_least_bytes``), on these configuration
files."""
import pytest

from servebench import run, spec

QWEN3 = {"n_layers": 36, "d_model": 2560, "n_heads": 32, "n_kv_heads": 8,
         "head_dim": 128, "d_ff": 9728, "vocab": 151936,
         "rope_theta": 1000000.0, "norm_eps": 1e-06, "tie_embeddings": True,
         "qk_norm": True, "qkv_bias": False, "dtype": "bfloat16"}
SIZES = {
    "qwen3-4b": dict(QWEN3, attn_impl="full"),
    "qwen3-4b-srf": dict(QWEN3, attn_impl="srf", **{
        "srf.kind": "circulant", "srf.n_features": 256,
        "srf.feature": "softmax_pos"}),
}
# (max_batch, prefill_batch, prefill_chunk, page_size, num_pages,
#  table_width, num_slots)
SCHED = {"qwen3-4b": (14, 14, 32, 16, 1271, 320, 15),
         "qwen3-4b-srf": (24, 24, 16, 16, 2, 1, 25)}
WEIGHT_BYTES = {"qwen3-4b": 8_044_936_192, "qwen3-4b-srf": 8_045_231_104}
# token_flops(config, keys, emits) for keys 1, 1025, 5120
TOKEN_FLOPS = {
    "qwen3-4b": {False: (7_267_221_504.0, 7_871_201_280.0, 10_286_530_560.0),
                 True: (8_045_133_824.0, 8_649_113_600.0, 11_064_442_880.0)},
    "qwen3-4b-srf": {False: (7_512_883_200.0,) * 3,
                     True: (8_290_795_520.0,) * 3},
}
# decode_least_bytes(config, rows, context=14,350): 14 rows kv, 24 srf
LEAST = {"qwen3-4b": (14, 10_162_994_176.0),
         "qwen3-4b-srf": (24, 11_697_421_312.0)}
CONFIGS = sorted(SIZES)


def _cell(name):
    return spec.load_cell(name + ".decode_long")


@pytest.mark.parametrize("name", CONFIGS)
def test_program_sizes_pinned(name):
    config = _cell(name).config
    assert spec.reference_module(config).program_sizes(config) == SIZES[name]
    run.program_config(config)          # raises where the program differs


@pytest.mark.parametrize("name", CONFIGS)
def test_scheduler_and_pool_pinned(name):
    cell = _cell(name)
    sc = run.sched_config(run.program_config(cell.config), cell)
    assert (sc.max_batch, sc.prefill_batch, sc.prefill_chunk, sc.page_size,
            sc.num_pages, sc.table_width, sc.num_slots) == SCHED[name]


@pytest.mark.parametrize("name", CONFIGS)
def test_cache_and_weight_bytes_pinned(name):
    config = _cell(name).config
    work = spec.work_module(config)
    assert work.cache_bytes_per_token(config) == 147_456
    assert work.weight_bytes(config) == WEIGHT_BYTES[name]


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("emits", [False, True])
def test_token_flops_pinned(name, emits):
    config = _cell(name).config
    work = spec.work_module(config)
    got = tuple(work.token_flops(config, k, emits) for k in (1, 1025, 5120))
    assert got == TOKEN_FLOPS[name][emits]


@pytest.mark.parametrize("name", CONFIGS)
def test_decode_least_bytes_pinned(name):
    config = _cell(name).config
    rows, want = LEAST[name]
    got = spec.work_module(config).decode_least_bytes(config, rows, 14_350)
    assert got == want
