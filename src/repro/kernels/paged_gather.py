"""Pallas TPU kernel: gather non-contiguous cache pages for batched decode.

Paged serving stores each request's KV (or MLA-latent) history as a set
of fixed-size pages scattered through one pooled buffer; batched decode
attention needs each request's history contiguous. This kernel performs

    out[r, j*P:(j+1)*P, :] = pool[table[r, j], :, :]

with the block table prefetched as a scalar operand
(``PrefetchScalarGridSpec``), so the page id is known *before* the body
runs and the pool page is DMA'd straight into the output block — the
kernel body is a pure VMEM copy, and the gather is one grid step per
(request, page) with no gather/scatter HLO in between.

Unallocated table slots point at the reserved null page 0; the garbage
they fetch is masked by the attention length mask downstream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(tables_ref, pool_ref, out_ref):
    # index maps already routed the right page into pool_ref
    out_ref[0, 0] = pool_ref[0]


def _gather_dequant_kernel(tables_ref, pool_ref, scale_ref, out_ref):
    # int8 page * f32 per-row scale, fused into the same DMA'd copy: the
    # quantized page never round-trips through HBM at full width.
    out_ref[0, 0] = (pool_ref[0].astype(jnp.float32)
                     * scale_ref[0]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_gather_pallas(pool: jax.Array, tables: jax.Array, *,
                        interpret: bool) -> jax.Array:
    """pool: (N, P, D); tables: (R, M) int32 page ids -> (R, M*P, D).

    Grid (R, M): one program per (request, page slot). The scalar-prefetch
    block table drives the input index map.
    """
    n, p, d = pool.shape
    r, m = tables.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r, m),
        in_specs=[
            pl.BlockSpec((1, p, d), lambda i, j, tbl: (tbl[i, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, p, d), lambda i, j, tbl: (i, j, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, m, p, d), pool.dtype),
        interpret=interpret,
    )(tables, pool)
    return out.reshape(r, m * p, d)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def paged_gather_dequant_pallas(pool: jax.Array, scales: jax.Array,
                                tables: jax.Array,
                                out_dtype=jnp.float32, *,
                                interpret: bool) -> jax.Array:
    """Fused int8 page gather + dequant.

    pool: (N, P, D) int8; scales: (N, P, 1) f32 per-row (per token) scales;
    tables: (R, M) int32 page ids -> (R, M*P, D) ``out_dtype``.

    Same (R, M) grid and scalar-prefetched table as ``paged_gather_pallas``;
    the dequant multiply rides the VMEM copy so the int8 pool is the only
    HBM-resident form of the quantized cache.
    """
    n, p, d = pool.shape
    r, m = tables.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r, m),
        in_specs=[
            pl.BlockSpec((1, p, d), lambda i, j, tbl: (tbl[i, j], 0, 0)),
            pl.BlockSpec((1, p, 1), lambda i, j, tbl: (tbl[i, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, p, d), lambda i, j, tbl: (i, j, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_dequant_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, m, p, d), jnp.dtype(out_dtype)),
        interpret=interpret,
    )(tables, pool, scales)
    return out.reshape(r, m * p, d)
