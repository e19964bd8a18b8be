"""Pallas TPU kernel: block-circulant projection with fused feature epilogue.

The paper computes f(A x) with A an (m, n) structured matrix. On GPU/CPU the
fast path is FFT (O(n log n)); on TPU we instead *regenerate* each circulant
tile from the O(n) generator directly in VMEM and feed the MXU:

    HBM traffic:  g (nb*n floats)  +  x tile  +  y tile      [O(n + B n)]
    dense equiv:  W (m*n floats)   +  x tile  +  y tile      [O(m n + B n)]

For m = 2n..8n (SRF attention feature expansion) this cuts projection
weight traffic by m/nb·n = n, turning a memory-bound matvec into a
compute-bound MXU op — the paper's space claim converted into arithmetic
intensity (DESIGN.md Sec 2).

Tile generation: A[i, j] = g[b(i), (j - i mod n) mod n]. Every row is
the generator rotated by i mod n, so the tile is the broadcast generator
with row-dependent lane rotations (the spinner kernel's ``regen_tile``;
Mosaic lowers no gather).

The pointwise nonlinearity f runs as an epilogue while the tile is still
in VMEM (identity | relu | heaviside | exp(y - sq) | cos_sin).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .spinner import regen_tile

EPILOGUES = ("identity", "relu", "heaviside", "exp", "cos_sin")


def _epilogue(y, epilogue, sq):
    if epilogue == "identity":
        return y
    if epilogue == "relu":
        return jnp.maximum(y, 0.0)
    if epilogue == "heaviside":
        return (y >= 0).astype(y.dtype)
    if epilogue == "exp":
        return jnp.exp(y - sq)
    raise ValueError(epilogue)


def _circ_kernel(x_ref, g_ref, sq_ref, o_ref, *, n: int, m: int, tm: int,
                 nb: int, epilogue: str):
    """Grid (batch_tiles, row_tiles). Regenerate (TM, n) tile rows from g."""
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)               # (TB, n)
    tile = regen_tile("circulant", g_ref, j, n=n, m=m, tm=tm, nb=nb)
    y = jax.lax.dot_general(
        x, tile, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (TB, TM)
    if epilogue == "cos_sin":
        o_ref[0] = jnp.cos(y).astype(o_ref.dtype)
        o_ref[1] = jnp.sin(y).astype(o_ref.dtype)
    else:
        sq = sq_ref[...].astype(jnp.float32) if epilogue == "exp" else None
        o_ref[...] = _epilogue(y, epilogue, sq).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("m", "epilogue", "block_b",
                                             "block_m", "interpret"))
def circulant_project_pallas(g: jax.Array, x: jax.Array, m: int,
                             epilogue: str = "identity",
                             sq: Optional[jax.Array] = None,
                             block_b: int = 256, block_m: int = 256, *,
                             interpret: bool) -> jax.Array:
    """g: (nb, n) generators; x: (B, n) -> (B, m) (or (B, 2m) for cos_sin).
    ``interpret`` picks the Pallas interpreter (CPU) over the compiled
    kernel (TPU).

    Requires m % block_m == 0 or block_m >= m; n enters VMEM whole
    (n <= ~4096 for f32 — callers with bigger n use the jnp path).
    """
    assert epilogue in EPILOGUES, epilogue
    nb, n = g.shape
    bsz = x.shape[0]
    assert nb * n >= m, f"generators cover {nb*n} rows < m={m}"
    tb = min(block_b, bsz)
    tm = min(block_m, m)
    assert m % tm == 0, f"m={m} must tile by block_m={tm}"
    gt = g.astype(jnp.float32)[None]                 # (1, nb, n) f32 rows
    if sq is None:
        sq = jnp.zeros((bsz, 1), x.dtype)
    sq = sq.reshape(bsz, 1)
    grid = (pl.cdiv(bsz, tb), m // tm)
    kernel = functools.partial(_circ_kernel, n=n, m=m, tm=tm, nb=nb,
                               epilogue=epilogue)
    if epilogue == "cos_sin":
        out_shape = jax.ShapeDtypeStruct((2, bsz, m), x.dtype)
        out_specs = pl.BlockSpec((2, tb, tm), lambda i, j: (0, i, j))
    else:
        out_shape = jax.ShapeDtypeStruct((bsz, m), x.dtype)
        out_specs = pl.BlockSpec((tb, tm), lambda i, j: (i, j))
    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, n), lambda i, j: (i, 0)),
            pl.BlockSpec((1, nb, n), lambda i, j: (0, 0, 0)),
            pl.BlockSpec((tb, 1), lambda i, j: (i, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(x, gt, sq)
    if epilogue == "cos_sin":
        y = jnp.concatenate([y[0], y[1]], axis=-1)
    return y
