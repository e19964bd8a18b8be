"""Pallas TPU kernel: Fast Walsh-Hadamard transform in lane-chunk (MXU) form.

H_n = H_a (x) H_c  with c = min(n, 128) and a = n / c  =>  the row-major
(a, c) view of x is transformed as  H_a . mat(x) . H_c.

The log-radix butterfly FWHT is VPU-hostile on TPU (strided element
shuffles), and reshaping a lane row into sub-lane factors is a relayout
Mosaic refuses. Here the H_c factor is one dense matmul per 128-lane chunk
(the MXU's native width; static lane slices cost nothing), and the H_a
factor is a butterfly over whole chunks: log2(a) rounds of (tb, c) adds
and subtracts. HBM traffic: x in, y out, one (c, c) factor.

Grid: 1-D over batch tiles. Each program holds an (TB, n) slice of x plus
the factor in VMEM and writes the transformed (TB, n) tile.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import transforms

LANES = 128


def chunk_width(n: int) -> int:
    """Width c of the dense Hadamard factor for a length-n transform."""
    return min(n, LANES)


def fwht_tile(x: jax.Array, hc: jax.Array) -> jax.Array:
    """Unnormalized Sylvester-order FWHT of the rows of an f32 (tb, n) tile.

    ``hc`` is the unnormalized (c, c) Hadamard factor, c = chunk_width(n).
    Chunk q holds lanes [q*c, (q+1)*c): y_p = sum_q H_a[p, q] (x_q . H_c).
    """
    c = hc.shape[0]
    a = x.shape[-1] // c
    z = [jnp.dot(x[:, q * c:(q + 1) * c], hc,
                 preferred_element_type=jnp.float32) for q in range(a)]
    h = 1
    while h < a:                                 # butterfly over the chunks
        for i in range(0, a, 2 * h):
            for k in range(i, i + h):
                z[k], z[k + h] = z[k] + z[k + h], z[k] - z[k + h]
        h *= 2
    return z[0] if a == 1 else jnp.concatenate(z, axis=-1)


def _fwht_kernel(x_ref, hc_ref, o_ref, *, scale: float):
    y = fwht_tile(x_ref[...].astype(jnp.float32), hc_ref[...])
    o_ref[...] = (y * scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("normalized", "block_b", "interpret"))
def fwht_pallas(x: jax.Array, normalized: bool = True, block_b: int = 256, *,
                interpret: bool) -> jax.Array:
    """(B, n) -> (B, n); n = 2^k. ``interpret`` picks the Pallas
    interpreter (CPU) over the compiled kernel (TPU)."""
    bsz, n = x.shape
    assert transforms.is_pow2(n), f"n must be a power of two, got {n}"
    c = chunk_width(n)
    hc = transforms.hadamard(c, jnp.float32, normalized=False)
    tb = min(block_b, bsz)
    grid = (pl.cdiv(bsz, tb),)
    scale = (1.0 / math.sqrt(n)) if normalized else 1.0
    kernel = functools.partial(_fwht_kernel, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, n), lambda i: (i, 0)),
            pl.BlockSpec((c, c), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tb, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, n), x.dtype),
        interpret=interpret,
    )(x, hc)
