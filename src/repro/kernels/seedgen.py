"""Counter-based Gaussian regeneration for the seeded spinner.

The paper's space-complexity story taken to its limit: instead of storing
the O(n) generator ``g`` (let alone the (m, n) matrix), store ONE 32-bit
seed and regenerate every matrix entry *at its position* when the kernel
needs it. The PRNG is a counter-based threefry2x32 (the same 20-round
permutation JAX's PRNG is built on) + Box-Muller, evaluated elementwise
at the entry's FLAT POSITION in the canonical parameter array:

    value(seed, domain, p) = BoxMuller(threefry2x32((seed, domain), (p, 0)))

Because generation is a pure elementwise function of (seed, domain,
position), any tiling of the computation — the Pallas kernel's (tm, n)
row tiles, the jnp reference's full-array materialization, the dense
test oracle — produces bit-identical values: there is no sequential
stream to keep in sync, and the autotuner's block-size choices can never
change results. ``seeded_params`` is the generator oracle: it rebuilds
the exact ``structured.init``-shaped param dict from a seed, so
``materialize`` / tests can compare the zero-storage path against the
materialized one bit for bit (on the interpret/ref routes; native TPU
transcendentals may differ in the last ulp).

Domain constants separate the independent streams a spinner block
consumes (generator core, the two HD Rademacher diagonals, the ldr
h-vector index/sign draws, and seed folding for per-head / per-request
derivation). All generation is f32 regardless of the activation dtype —
there is no stored tensor whose dtype could disagree.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

# Domain separation constants (the second threefry key word).
DOM_G = 0       # generator core g
DOM_D0 = 1      # HD input Rademacher diagonal
DOM_D1 = 2      # HD output Rademacher diagonal
DOM_H_IDX = 3   # ldr h-vector support draw (uniform keys, top-nnz)
DOM_H_SGN = 4   # ldr h-vector signs
DOM_FOLD = 7    # fold_seed sub-stream derivation

_ROT_A = (13, 15, 26, 6)
_ROT_B = (17, 29, 16, 24)
_PARITY = 0x1BD11BDA


def _rotl(x: jax.Array, d: int) -> jax.Array:
    return (x << jnp.uint32(d)) | (x >> jnp.uint32(32 - d))


def threefry2x32(k0, k1, c0, c1):
    """The standard 20-round threefry-2x32 block cipher, elementwise over
    broadcastable uint32 inputs: key (k0, k1), counter (c0, c1) -> two
    independent uint32 streams."""
    k0 = jnp.asarray(k0, jnp.uint32)
    k1 = jnp.asarray(k1, jnp.uint32)
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(_PARITY))
    x0 = jnp.asarray(c0, jnp.uint32) + k0
    x1 = jnp.asarray(c1, jnp.uint32) + k1
    for i in range(5):
        for r in (_ROT_A if i % 2 == 0 else _ROT_B):
            x0 = x0 + x1
            x1 = _rotl(x1, r)
            x1 = x1 ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + jnp.uint32(i + 1)
    return x0, x1


def _bits2(seed, domain: int, pos: jax.Array):
    """Two uint32 streams at flat positions ``pos`` of (seed, domain)."""
    c0 = pos.astype(jnp.uint32)
    return threefry2x32(jnp.asarray(seed, jnp.uint32), jnp.uint32(domain),
                        c0, jnp.zeros_like(c0))


def _u01(bits: jax.Array) -> jax.Array:
    """uint32 -> f32 uniform in [0, 1): mantissa-fill then subtract 1."""
    f = jax.lax.bitcast_convert_type(
        (bits >> jnp.uint32(9)) | jnp.uint32(0x3F800000), jnp.float32)
    return f - jnp.float32(1.0)


def normal_at(seed, domain: int, pos: jax.Array) -> jax.Array:
    """f32 standard normals at flat positions ``pos`` (any shape), via
    Box-Muller over the position's two counter streams."""
    b0, b1 = _bits2(seed, domain, pos)
    u1 = jnp.float32(1.0) - _u01(b0)                 # (0, 1] — log-safe
    u2 = _u01(b1)
    rad = jnp.sqrt(jnp.float32(-2.0) * jnp.log(u1))
    return rad * jnp.cos(jnp.float32(2.0 * math.pi) * u2)


def sign_at(seed, domain: int, pos: jax.Array) -> jax.Array:
    """f32 Rademacher (+/-1) draws at flat positions ``pos``."""
    b0, _ = _bits2(seed, domain, pos)
    return jnp.where(b0 >> jnp.uint32(31) > 0,
                     jnp.float32(1.0), jnp.float32(-1.0))


def uniform_bits_at(seed, domain: int, pos: jax.Array) -> jax.Array:
    """Raw uint32 stream at flat positions ``pos`` (ldr support draw)."""
    b0, _ = _bits2(seed, domain, pos)
    return b0


def fold_seed(seed, data) -> jax.Array:
    """Derive a sub-seed: an independent uint32 stream keyed by ``data``
    (per-head index, per-request embed seed, ...). Broadcasting applies:
    fold_seed((H, 1), (1, B)) -> (H, B)."""
    d = jnp.asarray(data, jnp.uint32)
    x0, _ = threefry2x32(jnp.asarray(seed, jnp.uint32), jnp.uint32(DOM_FOLD),
                         d, jnp.zeros_like(d))
    return x0


# ---------------------------------------------------------------------------
# in-kernel tile regeneration (shared by the Pallas kernel and the tests)
# ---------------------------------------------------------------------------

def gen_tile(kind: str, seed, rows: jax.Array, cols: jax.Array, *,
             n: int, m: int, nb: int) -> jax.Array:
    """Regenerate the (tm, n) row tile A[rows, cols] straight from the
    seed — the zero-storage analogue of ``spinner.regen_tile``.

    ``rows``/``cols`` are int32 index grids (rows may exceed m on padded
    tiles; positions stay in-range by construction, the garbage rows'
    write-back is dropped by the out BlockSpec). Every entry is generated
    at its flat position in the canonical ``structured.init`` param
    array, so values match ``seeded_params`` bit for bit.
    """
    if kind in ("circulant", "skew_circulant"):
        blk = jnp.minimum(rows // n, nb - 1)
        off = rows % n
        pos = blk * n + (cols - off) % n             # flat into (nb, n) g
        val = normal_at(seed, DOM_G, pos)
        if kind == "skew_circulant":
            val = jnp.where(cols < off, -val, val)   # wrapped entries negated
        return val
    if kind == "toeplitz":
        d = jnp.clip(cols - rows, -(m - 1), n - 1)
        pos = jnp.where(d >= 0, d, n - 1 - d)        # structured._toeplitz_dense
        return normal_at(seed, DOM_G, pos)
    if kind == "hankel":
        pos = jnp.clip(rows + cols, 0, n + m - 2)
        return normal_at(seed, DOM_G, pos)
    if kind == "unstructured":
        pos = jnp.minimum(rows, m - 1) * n + cols    # flat into (m, n) g
        return normal_at(seed, DOM_G, pos)
    raise ValueError(kind)


def hd_signs(seed, n: int) -> tuple:
    """(d0, d1) f32 Rademacher diagonals of the HD preconditioner."""
    pos = jnp.arange(n, dtype=jnp.int32)
    return sign_at(seed, DOM_D0, pos), sign_at(seed, DOM_D1, pos)


# ---------------------------------------------------------------------------
# generator oracle: rebuild the structured.init param dict from a seed
# ---------------------------------------------------------------------------

def seeded_params(kind: str, n: int, m: int, seed, *, r: int = 1,
                  ldr_nnz: int = 4, use_hd: bool = True
                  ) -> Dict[str, jax.Array]:
    """The materialized twin of the zero-storage path: the exact f32
    param dict (``structured.init`` shapes) the seed encodes. Used by
    ``materialize`` / diagnostics / the ref+backward routes, and as the
    bit-exactness oracle in kernel tests."""
    from repro.core import structured
    b = structured.n_blocks(kind, m, n)
    if kind == "unstructured":
        g = normal_at(seed, DOM_G, jnp.arange(m * n)).reshape(m, n)
        params = {"g": g}
    elif kind in ("circulant", "skew_circulant"):
        params = {"g": normal_at(seed, DOM_G, jnp.arange(b * n)).reshape(b, n)}
    elif kind in ("toeplitz", "hankel"):
        params = {"g": normal_at(seed, DOM_G, jnp.arange(n + m - 1))}
    elif kind == "ldr":
        flat = jnp.arange(b * r * n)
        g = normal_at(seed, DOM_G, flat).reshape(b, r, n)
        # h support: the ldr_nnz smallest uniform keys per (block, rank)
        # row — a deterministic without-replacement draw; signs from an
        # independent stream, magnitude 1/sqrt(nnz * r) as in the paper.
        keys = uniform_bits_at(seed, DOM_H_IDX, flat).reshape(b, r, n)
        rank = jnp.argsort(jnp.argsort(keys, axis=-1), axis=-1)
        sgn = sign_at(seed, DOM_H_SGN, flat).reshape(b, r, n)
        val = sgn * jnp.float32(1.0 / math.sqrt(ldr_nnz * r))
        params = {"g": g, "h": jnp.where(rank < ldr_nnz, val, 0.0)}
    else:
        raise ValueError(f"unknown structured kind: {kind}")
    if use_hd:
        params["d0"], params["d1"] = hd_signs(seed, n)
    return params


def grouped_params(kind: str, n: int, m: int, seeds: jax.Array, *,
                   r: int = 1, ldr_nnz: int = 4, use_hd: bool = True
                   ) -> Dict[str, jax.Array]:
    """``seeded_params`` vmapped over a (G,) seed vector: every leaf gains
    the leading group axis the grouped spinner dispatch expects."""
    return jax.vmap(lambda s: seeded_params(kind, n, m, s, r=r,
                                            ldr_nnz=ldr_nnz,
                                            use_hd=use_hd))(seeds)
