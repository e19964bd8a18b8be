"""Compile-only checks for a TPU v5e that is described, not attached.

Each Pallas kernel of the serving path, and the standalone circulant
kernel, is compiled by the TPU compiler at qwen3-4b's serving widths, and
the paged serving step is lowered with its kernels on the native route. Nothing runs: this catches what interpret
mode cannot (block shapes the TPU lowering refuses, reshapes and gathers
Mosaic does not lower) at no chip time. The topology is described inside
a fixture, so only the worker that runs these tests loads the TPU library.
"""
import importlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.kernels import ops
from repro.models.attention import srf_cfg

spinner = importlib.import_module("repro.kernels.spinner")
srf_decode = importlib.import_module("repro.kernels.srf_decode")
circulant = importlib.import_module("repro.kernels.circulant")
paged_gather = importlib.import_module("repro.kernels.paged_gather")

QWEN = registry.get("qwen3-4b")
SRF = srf_cfg(registry.get("qwen3-4b", attn_impl="srf"))
G, N, M = QWEN.n_kv_heads, SRF.head_dim, SRF.n_features   # 8, 128, 256
ROWS = 512                                   # batch*tokens rows per group
PAGE, WIDTH, PAGES = 16, 17, 273             # the smoke engine's geometry
KV_D = QWEN.n_kv_heads * QWEN.head_dim
BF = jnp.bfloat16


@pytest.fixture(scope="module")
def topo(tmp_path_factory):
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        # the TPU library otherwise writes its logs under /tmp
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", str(tmp_path_factory.mktemp("tpu_logs")))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without that chip: keep it out of the cache,
        # including one an earlier test of this worker already opened
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _gen_shape(kind):
    return {"circulant": (G, -(-M // N), N),
            "skew_circulant": (G, -(-M // N), N),
            "toeplitz": (G, N + M - 1), "hankel": (G, N + M - 1),
            "unstructured": (G, M, N)}[kind]


@pytest.mark.parametrize("kind", spinner.PALLAS_KINDS)
def test_spinner_compiles(one_chip, kind):
    tb, tm = ops.spinner_plan(kind, N, M, epilogue="exp", dtype=BF)

    def f(g, x, d0, d1):
        return spinner.spinner_project_pallas(
            kind, g, x, M, d0=d0, d1=d1, epilogue="exp", block_b=tb,
            block_m=tm, interpret=False)
    txt = _compiled_text(f, _sds(one_chip, _gen_shape(kind), BF),
                         _sds(one_chip, (G, ROWS, N), BF),
                         _sds(one_chip, (G, N), BF),
                         _sds(one_chip, (G, N), BF))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("kind", ["circulant", "toeplitz", "hankel"])
def test_spinner_seeded_compiles(one_chip, kind):
    tb, tm = ops.spinner_plan(kind, N, M, epilogue="exp", dtype=BF,
                              seeded=True)

    def f(seeds, x):
        return spinner.spinner_project_seeded_pallas(
            kind, seeds, x, M, epilogue="exp", block_b=tb, block_m=tm,
            interpret=False)
    txt = _compiled_text(f, _sds(one_chip, (G,), jnp.uint32),
                         _sds(one_chip, (G, ROWS, N), BF))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("epilogue", ["identity", "exp"])
def test_circulant_compiles(one_chip, epilogue):
    """The standalone circulant kernel ``ops.circulant_project`` routes to
    on TPU, at one head's widths."""
    def f(g, x, sq):
        return circulant.circulant_project_pallas(
            g, x, M, epilogue, sq, interpret=False)
    txt = _compiled_text(f, _sds(one_chip, (-(-M // N), N), BF),
                         _sds(one_chip, (ROWS, N), BF),
                         _sds(one_chip, (ROWS, 1), BF))
    assert "tpu_custom_call" in txt


def test_srf_decode_compiles(one_chip):
    b, h = 8, QWEN.n_heads
    f32 = jnp.float32

    def f(s, z, pq, pk, v):
        return srf_decode.srf_decode_pallas(s, z, pq, pk, v, interpret=False)
    txt = _compiled_text(f, _sds(one_chip, (b, h, M, N), f32),
                         _sds(one_chip, (b, h, M), f32),
                         _sds(one_chip, (b, h, M), f32),
                         _sds(one_chip, (b, h, M), f32),
                         _sds(one_chip, (b, h, N), f32))
    assert "tpu_custom_call" in txt


def test_paged_gather_compiles(one_chip):
    def f(pool, tables):
        return paged_gather.paged_gather_pallas(pool, tables, interpret=False)
    txt = _compiled_text(f, _sds(one_chip, (PAGES, PAGE, KV_D), BF),
                         _sds(one_chip, (8, WIDTH), jnp.int32))
    assert "tpu_custom_call" in txt


def test_paged_gather_dequant_compiles(one_chip):
    def f(pool, scales, tables):
        return paged_gather.paged_gather_dequant_pallas(
            pool, scales, tables, out_dtype=BF, interpret=False)
    txt = _compiled_text(f, _sds(one_chip, (PAGES, PAGE, KV_D), jnp.int8),
                         _sds(one_chip, (PAGES, PAGE, 1), jnp.float32),
                         _sds(one_chip, (8, WIDTH), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("attn,kernels", [
    ("full", {"_gather_kernel"}),
    ("srf", {"_spinner_kernel", "_srf_decode_kernel"}),
])
def test_serving_step_lowers_with_kernels(one_chip, monkeypatch, attn,
                                          kernels):
    """The paged decode step at qwen3-4b widths (two layers) holds a
    ``tpu_custom_call`` for each kernel of its attention family."""
    from repro.launch import steps
    from repro.models import transformer as model_lib
    from repro.serving import paged_cache
    # the process's backend is the CPU; steer the kernels onto the route
    # the chip takes
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "native")
    cfg = registry.get("qwen3-4b", n_layers=2, attn_impl=attn)
    b = 8

    def place(tree):
        return jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype), tree)
    params = place(jax.eval_shape(
        lambda: model_lib.init(jax.random.PRNGKey(0), cfg)))
    pools = place(jax.eval_shape(lambda: paged_cache.init_pools(
        cfg, 2 * b * WIDTH + 1, PAGE, num_slots=b + 1)))
    args = [params, pools, _sds(one_chip, (b, 1), jnp.int32),
            _sds(one_chip, (b, 1), jnp.int32),
            _sds(one_chip, (b, 1), jnp.bool_),
            _sds(one_chip, (b, WIDTH), jnp.int32),
            _sds(one_chip, (b,), jnp.int32)]
    txt = jax.jit(steps.make_paged_step(cfg)).lower(*args).as_text()
    found = set(re.findall(r'tpu_custom_call.*?kernel_name = "(\w+)"', txt))
    assert kernels <= found, found
