"""Composable Spinner API: multi-block pipelines vs dense oracles, grads,
bf16 bounds, back-compat shims, (de)serialization, registry extension."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.core import coherence, estimators, features, pmodel, spinner
from repro.core.pmodel import PModelSpec
from repro.core.spinner import KindDef, Nonlinearity, SpinnerBlock, SpinnerPipeline
from repro.kernels import ops as kops

KINDS = list(spinner.structured.KINDS)
NLS = ["identity", "relu", "heaviside", "sign", "exp", "cos_sin"]


def _oracle(pipe, params, x, y_scale=1.0, out_scale=1.0):
    """f(y_scale . A_k...A_1 x) . out_scale via the dense materialized
    product — the semantic ground truth for any pipeline."""
    a = pipe.materialize(params).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    y = (xf @ a.T) * y_scale
    nl = spinner.nonlinearity(pipe.f)
    sq = 0.5 * jnp.sum(xf * xf, -1, keepdims=True) if nl.needs_input else None
    return nl.fn(y, sq) * out_scale


# ---------------------------------------------------------------------------
# multi-block correctness: materialized-product oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("f", NLS)
def test_three_block_matches_dense_oracle(kind, f):
    """HD3.HD2.HD1 stack == its dense product, every kind x nonlinearity."""
    pipe = spinner.hd_chain(kind, n=16, m=24, depth=3, r=2, f=f)
    params = pipe.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 16)) * 0.05
    y = pipe.apply(params, x, y_scale=0.7, out_scale=1.3)
    yo = _oracle(pipe, params, x, y_scale=0.7, out_scale=1.3)
    assert y.shape == (5, pipe.out_dim)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yo),
                               rtol=2e-3, atol=2e-3)


def test_mixed_kind_chain_matches_oracle():
    pipe = spinner.chain([SpinnerBlock("circulant", 32, 32),
                          SpinnerBlock("toeplitz", 16, 32),
                          SpinnerBlock("hankel", 48, 16, use_hd=True)],
                         f="relu")
    params = pipe.init(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 32)) * 0.1
    np.testing.assert_allclose(np.asarray(pipe.apply(params, x)),
                               np.asarray(_oracle(pipe, params, x)),
                               rtol=2e-3, atol=2e-3)


def test_one_block_identical_to_kernel_op():
    """A 1-block pipeline IS the fused spinner_project dispatch (bitwise)."""
    pipe = spinner.single("skew_circulant", m=96, n=64, f="relu")
    (p,) = pipe.init(jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (7, 64)) * 0.3
    y = pipe.apply((p,), x, out_scale=0.25)
    yk = kops.spinner_project("skew_circulant", p, x, 96, epilogue="relu",
                              out_scale=0.25)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yk))


def test_grouped_multiblock_matches_pergroup():
    pipe = spinner.hd_chain("toeplitz", n=16, m=24, depth=2, f="cos_sin")
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    gp = jax.vmap(lambda k: pipe.init(k))(keys)
    xg = jax.random.normal(jax.random.PRNGKey(7), (3, 6, 16)) * 0.2
    yg = pipe.apply(gp, xg, grouped=True)
    assert yg.shape == (3, 6, pipe.out_dim)
    for g in range(3):
        one = jax.tree_util.tree_map(lambda t: t[g], gp)
        np.testing.assert_allclose(np.asarray(yg[g]),
                                   np.asarray(pipe.apply(one, xg[g])),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# gradients through 2- and 3-block stacks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("depth", [2, 3])
@pytest.mark.parametrize("kind", ["circulant", "toeplitz"])
def test_gradients_match_dense_oracle(kind, depth):
    pipe = spinner.hd_chain(kind, n=8, m=8, depth=depth, f="cos_sin")
    params = pipe.init(jax.random.PRNGKey(8))
    x = jax.random.normal(jax.random.PRNGKey(9), (3, 8)) * 0.2
    w = jax.random.normal(jax.random.PRNGKey(10), (3, pipe.out_dim))

    def loss_fast(p, xx):
        return jnp.sum(w * pipe.apply(p, xx))

    def loss_oracle(p, xx):
        return jnp.sum(w * _oracle(pipe, p, xx))

    gf = jax.grad(loss_fast, argnums=(0, 1))(params, x)
    go = jax.grad(loss_oracle, argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(go)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# bf16 tolerance bounds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("f", ["identity", "relu", "cos_sin"])
def test_bf16_three_block_within_bounds(f):
    pipe = spinner.hd_chain("circulant", n=32, m=32, depth=3, f=f)
    p16 = jax.tree_util.tree_map(lambda t: t.astype(jnp.bfloat16),
                                 pipe.init(jax.random.PRNGKey(11)))
    x32 = jax.random.normal(jax.random.PRNGKey(12), (6, 32)) * 0.02
    y16 = pipe.apply(p16, x32.astype(jnp.bfloat16))
    assert y16.dtype == jnp.bfloat16
    # oracle from the SAME (bf16-rounded) params, so the bound measures
    # the chained compute path: 3 blocks compound ~3x the 1-block bound
    p32 = jax.tree_util.tree_map(lambda t: t.astype(jnp.float32), p16)
    yo = _oracle(pipe, p32, x32)
    tol = dict(rtol=1.5e-1, atol=1.5e-1) if f == "cos_sin" \
        else dict(rtol=6e-2, atol=1e-1)
    np.testing.assert_allclose(np.asarray(y16, np.float32),
                               np.asarray(yo, np.float32), **tol)


# ---------------------------------------------------------------------------
# back-compat shims: identical outputs + DeprecationWarning
# ---------------------------------------------------------------------------

def test_pmodel_shim_identical_outputs_and_warns():
    spec = PModelSpec(kind="toeplitz", m=48, n=32)
    pipe = spec.pipeline
    with pytest.warns(DeprecationWarning):
        params = pmodel.init(jax.random.PRNGKey(0), spec)
    params_new = pipe.init(jax.random.PRNGKey(0))
    for k in params:
        np.testing.assert_array_equal(np.asarray(params[k]),
                                      np.asarray(params_new[0][k]))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32)) * 0.3
    with pytest.warns(DeprecationWarning):
        y_old = pmodel.project(spec, params, x)
    np.testing.assert_array_equal(np.asarray(y_old),
                                  np.asarray(pipe.apply(params_new, x)))
    with pytest.warns(DeprecationWarning):
        z_old = pmodel.project_fused(spec, params, x, epilogue="relu",
                                     y_scale=0.5, out_scale=2.0)
    z_new = pipe.with_f("relu").apply(params_new, x, y_scale=0.5,
                                      out_scale=2.0)
    np.testing.assert_array_equal(np.asarray(z_old), np.asarray(z_new))
    np.testing.assert_array_equal(
        np.asarray(pmodel.materialize(spec, params)),
        np.asarray(pipe.materialize(params_new)))


def test_phi_shims_identical_outputs_and_warn():
    spec = PModelSpec(kind="circulant", m=64, n=32)
    pipe = spec.pipeline
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        params = pmodel.init(jax.random.PRNGKey(0), spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 32)) * 0.4
    cases = [
        (lambda p: features.phi_scalar(p, params, x, "heaviside"),),
        (lambda p: features.phi_trig(p, params, x, sigma=1.5),),
        (lambda p: features.phi_softmax_pos(p, params, x, stabilize=False),),
        (lambda p: features.phi_softmax_pos(p, params, x, stabilize=True),),
        (lambda p: features.phi_softmax_trig(p, params, x),),
    ]
    for (fn,) in cases:
        with pytest.warns(DeprecationWarning):
            z_old = fn(spec)
        np.testing.assert_array_equal(np.asarray(z_old), np.asarray(fn(pipe)))


def test_estimator_accepts_pipeline_and_legacy_spec():
    v1 = jax.random.normal(jax.random.PRNGKey(2), (32,))
    v1 = v1 / jnp.linalg.norm(v1)
    v2 = jax.random.normal(jax.random.PRNGKey(3), (32,))
    v2 = v2 / jnp.linalg.norm(v2)
    pipe = spinner.single("circulant", m=128, n=32)
    params = pipe.init(jax.random.PRNGKey(4))
    e_new = float(estimators.estimate(pipe, params, "heaviside", v1, v2))
    with pytest.warns(DeprecationWarning):
        e_old = float(estimators.estimate(
            PModelSpec(kind="circulant", m=128, n=32), params[0],
            "heaviside", v1, v2))
    assert e_new == e_old


# ---------------------------------------------------------------------------
# (de)serialization + checkpointing
# ---------------------------------------------------------------------------

def test_config_roundtrip_and_apply_identical():
    pipe = spinner.chain([SpinnerBlock("circulant", 32, 32),
                          SpinnerBlock("ldr", 48, 32, r=2, ldr_nnz=3)],
                         f="exp")
    pipe2 = spinner.loads(spinner.dumps(pipe))
    assert pipe2 == pipe
    params = pipe.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 32)) * 0.1
    np.testing.assert_array_equal(np.asarray(pipe.apply(params, x)),
                                  np.asarray(pipe2.apply(params, x)))


def test_config_version_guard():
    cfg = spinner.to_config(spinner.single("circulant", m=8, n=8))
    cfg["version"] = 99
    with pytest.raises(ValueError, match="version"):
        spinner.from_config(cfg)


def test_params_checkpoint_roundtrip(tmp_path):
    """Pipeline params are a plain pytree: the checkpoint manager
    round-trips them against a freshly-initialized target."""
    pipe = spinner.hd_chain("circulant", n=16, m=32, depth=2)
    params = pipe.init(jax.random.PRNGKey(0))
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(7, {"spinner": params, "pipeline_json": np.frombuffer(
        spinner.dumps(pipe).encode(), dtype=np.uint8)}, blocking=True)
    blank = {"spinner": pipe.init(jax.random.PRNGKey(99)),
             "pipeline_json": np.zeros(
                 len(spinner.dumps(pipe).encode()), np.uint8)}
    restored, step, _ = mgr.restore(blank)
    assert step == 7
    assert spinner.loads(bytes(restored["pipeline_json"]).decode()) == pipe
    for a, b in zip(jax.tree_util.tree_leaves(restored["spinner"]),
                    jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_legacy_srf_checkpoint_layout(tmp_path):
    """Pre-pipeline checkpoints stored SRF params as ONE dict
    ('.../srf/g'); restore maps them onto the 1-block tuple layout."""
    pipe = spinner.single("circulant", m=32, n=16)
    (old,) = pipe.init(jax.random.PRNGKey(0))
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, {"layers": {"attn": {"srf": old}}}, blocking=True)
    target = {"layers": {"attn": {"srf": pipe.init(jax.random.PRNGKey(5))}}}
    restored, step, _ = mgr.restore(target)
    assert step == 1
    for k in old:
        np.testing.assert_array_equal(
            np.asarray(restored["layers"]["attn"]["srf"][0][k]),
            np.asarray(old[k]))
    # root-level srf params (no path prefix) alias too
    mgr.save(2, {"srf": old}, blocking=True)
    restored2, _, _ = mgr.restore({"srf": pipe.init(jax.random.PRNGKey(6))},
                                  step=2)
    for k in old:
        np.testing.assert_array_equal(np.asarray(restored2["srf"][0][k]),
                                      np.asarray(old[k]))


def test_phi_scalar_accepts_registered_custom_nonlinearity():
    _ensure_test_registrations()
    pipe = spinner.single("circulant", m=32, n=16)
    params = pipe.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 16)) * 0.2
    z = features.phi_scalar(pipe, params, x, "tanh_test")
    a = pipe.materialize(params).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(z),
                               np.asarray(jnp.tanh(x @ a.T) * 32 ** -0.5),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(KeyError, match="scalar pointwise"):
        features.phi_scalar(pipe, params, x, "cos_sin")


def test_specs_are_zero_leaf_pytrees_and_static():
    pipe = spinner.hd_chain("circulant", n=8, m=8, depth=2, f="relu")
    assert jax.tree_util.tree_leaves(pipe) == []
    assert jax.tree_util.tree_leaves(SpinnerBlock()) == []

    calls = []

    @jax.jit
    def emb(p, params, x):          # pipeline as a (static) jit argument
        calls.append(1)
        return p.apply(params, x)

    params = pipe.init(jax.random.PRNGKey(0))
    x = jnp.ones((2, 8)) * 0.1
    emb(pipe, params, x)
    emb(pipe, params, x)
    assert len(calls) == 1          # retrace only on new spec


# ---------------------------------------------------------------------------
# registries: extension points
# ---------------------------------------------------------------------------

def _ensure_test_registrations():
    if "diag_test" not in spinner.registered_kinds():
        spinner.register_kind(KindDef(
            name="diag_test",
            init=lambda rng, m, n, r=1, ldr_nnz=4, dtype=jnp.float32:
                {"g": jax.random.normal(rng, (n,), dtype)},
            matvec=lambda params, x, m: x * params["g"],
            materialize=lambda params, m, n: jnp.diag(params["g"]),
            budget=lambda m, n, r: n,
            storage=lambda m, n, r: n,
            flops=lambda m, n, r: float(n)))
    if "tanh_test" not in spinner.registered_nonlinearities():
        spinner.register_nonlinearity(Nonlinearity(
            "tanh_test", lambda y, sq: jnp.tanh(y)))


def test_custom_kind_and_nonlinearity_in_pipeline():
    _ensure_test_registrations()
    pipe = spinner.chain([SpinnerBlock("circulant", 16, 16),
                          SpinnerBlock("diag_test", 16, 16, use_hd=False)],
                         f="tanh_test")
    params = pipe.init(jax.random.PRNGKey(0))
    assert pipe.budget == 16 + 16 and pipe.out_dim == 16
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16)) * 0.2
    y = pipe.apply(params, x, out_scale=0.5)
    a = pipe.materialize(params).astype(jnp.float32)
    yo = jnp.tanh(x @ a.T) * 0.5
    np.testing.assert_allclose(np.asarray(y), np.asarray(yo),
                               rtol=1e-4, atol=1e-4)


def test_custom_kind_gets_coherence_diagnostics():
    _ensure_test_registrations()
    blk = SpinnerBlock("diag_test", 8, 8, use_hd=False)
    st = coherence.block_stats(blk, blk.init(jax.random.PRNGKey(0)))
    # diag rows touch a single Gaussian: trivial coherence graphs, and NOT
    # row-normalized in the Def-1 sense (zero off-diagonal P_i columns)
    assert st["budget_t"] == 8.0 and st["chi"] <= 1.0
    assert st["mu_tilde"] == 0.0 and st["normalized"] == 0.0


def test_duplicate_registration_rejected():
    with pytest.raises(ValueError, match="already registered"):
        spinner.register_kind(spinner.kind_def("circulant"))
    with pytest.raises(ValueError, match="already registered"):
        spinner.register_nonlinearity(spinner.nonlinearity("relu"))


# ---------------------------------------------------------------------------
# validation, accounting, diagnostics
# ---------------------------------------------------------------------------

def test_chain_dim_mismatch_rejected():
    with pytest.raises(ValueError, match="chain mismatch"):
        SpinnerPipeline((SpinnerBlock("circulant", 32, 16),
                         SpinnerBlock("circulant", 16, 64)))


def test_unknown_kind_and_f_rejected():
    with pytest.raises(ValueError, match="unknown spinner kind"):
        SpinnerBlock("nope", 8, 8)
    with pytest.raises(ValueError, match="unknown nonlinearity"):
        spinner.single("circulant", m=8, n=8, f="nope")


def test_multiblock_rejects_bare_dict_params():
    pipe = spinner.hd_chain("circulant", n=8, m=8, depth=2)
    with pytest.raises(ValueError, match="param"):
        pipe.apply(pipe.init(jax.random.PRNGKey(0))[0], jnp.ones((1, 8)))


def test_accounting_sums_blocks():
    pipe = spinner.hd_chain("circulant", n=16, m=32, depth=3)
    blocks = pipe.blocks
    assert pipe.budget == sum(b.budget for b in blocks)
    assert pipe.storage == sum(b.storage for b in blocks)
    assert pipe.flops == sum(b.flops for b in blocks)
    assert pipe.with_f("cos_sin").out_dim == 2 * pipe.m_out
    # per-block HD storage: 2n signs each
    assert all(b.storage == b.budget + 2 * b.n for b in blocks)


def test_per_block_row_moments_and_coherence():
    pipe = spinner.hd_chain("circulant", n=8, m=8, depth=2)
    params = pipe.init(jax.random.PRNGKey(0))
    moments = pipe.row_gaussianity_moments(params)
    assert len(moments) == 2
    for mean, var in moments:
        assert mean.shape == (8,) and var.shape == (8,)
    stats = coherence.pipeline_stats(pipe, params)
    assert len(stats) == 2
    assert all(s["chi"] <= 3 for s in stats)        # circulant: Sec 2.2
    assert all(s["mu_tilde"] < 1e-6 for s in stats)
    with pytest.raises(ValueError, match="per-block"):
        coherence.pipeline_stats(pipe, params[:1])


# ---------------------------------------------------------------------------
# spinner_plan dtype cache key (VMEM satellite)
# ---------------------------------------------------------------------------

def test_spinner_plan_dtype_separates_cache_entries():
    n, m = 256, 8192
    kw = dict(use_hd=True, epilogue="identity")
    f32 = kops.spinner_plan("circulant", n, m, dtype=jnp.float32, **kw)
    b16 = kops.spinner_plan("circulant", n, m, dtype=jnp.bfloat16, **kw)
    # bf16 x/out tiles are half the bytes (compute scratch stays f32):
    # its plan must be at least as large, and at this (small n, big m)
    # shape strictly larger.
    assert b16[0] * b16[1] > f32[0] * f32[1]
    f32_bytes = kops._spinner_vmem_bytes("circulant", n, m, f32[0],
                                         min(f32[1], m), True,
                                         "identity", 4)
    assert f32_bytes <= kops._VMEM_BUDGET
    b16_as_f32 = kops._spinner_vmem_bytes("circulant", n, m, b16[0],
                                          min(b16[1], m), True,
                                          "identity", 4)
    assert b16_as_f32 > kops._VMEM_BUDGET    # the shared-plan bug this fixes


# ---------------------------------------------------------------------------
# seeded (zero-storage) pipelines
# ---------------------------------------------------------------------------

def test_seeded_pipeline_matches_dense_oracle():
    """seeded=True: params are one uint32 per block, yet the pipeline's
    output matches the dense product of the regenerated matrices (the
    oracle materializes through the same generator)."""
    pipe = spinner.hd_chain("circulant", n=16, m=24, depth=2, seeded=True)
    params = pipe.init(jax.random.PRNGKey(0))
    assert all(set(p) == {"seed"} for p in params)
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 16)) * 0.05
    y = pipe.apply(params, x, y_scale=0.7, out_scale=1.3)
    yo = _oracle(pipe, params, x, y_scale=0.7, out_scale=1.3)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yo),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("kind", KINDS)
def test_seeded_single_bitmatches_materialized_twin(kind):
    """A seeded block applied == the SAME pipeline with the generator-
    oracle params materialized up front, bit for bit, for every kind."""
    from repro.kernels import seedgen
    pipe_s = spinner.single(kind, m=96, n=64, seeded=True)
    pipe_m = spinner.single(kind, m=96, n=64)
    params_s = pipe_s.init(jax.random.PRNGKey(0))
    oracle = (seedgen.seeded_params(kind, 64, 96, params_s[0]["seed"]),)
    x = jax.random.normal(jax.random.PRNGKey(1), (7, 64)) * 0.1
    np.testing.assert_array_equal(np.asarray(pipe_s.apply(params_s, x)),
                                  np.asarray(pipe_m.apply(oracle, x)))


def test_seeded_storage_is_o1():
    """Acceptance: seeded storage is O(1) in (n, m) — one scalar per
    block — while the dense twin grows with the matrix."""
    big = spinner.hd_chain("circulant", n=512, m=2048, depth=2, seeded=True)
    small = spinner.hd_chain("circulant", n=16, m=32, depth=2, seeded=True)
    assert big.storage == small.storage == 2
    assert spinner.hd_chain("circulant", n=512, m=2048, depth=2).storage \
        > 1000
    params = big.init(jax.random.PRNGKey(0))
    for p in params:
        assert p["seed"].shape == () and p["seed"].dtype == jnp.uint32


def test_seeded_config_roundtrip_and_apply_identical():
    pipe = spinner.hd_chain("toeplitz", n=16, m=24, depth=2, seeded=True)
    pipe2 = spinner.loads(spinner.dumps(pipe))
    assert pipe2 == pipe and all(b.seeded for b in pipe2.blocks)
    params = pipe.init(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 16)) * 0.1
    np.testing.assert_array_equal(np.asarray(pipe.apply(params, x)),
                                  np.asarray(pipe2.apply(params, x)))


def test_seeded_rejects_unregenerable_kind():
    """Custom registered kinds have no positional generator; seeded mode
    must refuse them at construction, not fail at dispatch."""
    _ensure_test_registrations()
    with pytest.raises(ValueError, match="seeded"):
        SpinnerBlock("diag_test", 8, 8, seeded=True)


def test_seeded_row_moments_regenerate():
    """Gaussianity diagnostics work on seeded blocks by regenerating the
    oracle params — moments match the materialized twin exactly."""
    from repro.kernels import seedgen
    blk = SpinnerBlock("circulant", 48, 32, seeded=True)
    params = blk.init(jax.random.PRNGKey(4))
    mean_s, var_s = blk.row_gaussianity_moments(params)
    twin = SpinnerBlock("circulant", 48, 32)
    oracle = seedgen.seeded_params("circulant", 32, 48, params["seed"])
    mean_m, var_m = twin.row_gaussianity_moments(oracle)
    np.testing.assert_array_equal(np.asarray(mean_s), np.asarray(mean_m))
    np.testing.assert_array_equal(np.asarray(var_s), np.asarray(var_m))
