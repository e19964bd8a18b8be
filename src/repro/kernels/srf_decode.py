"""Pallas TPU kernel: fused SRF decode step (state update + readout).

Decode with SRF attention touches the O(m x dv) state three times if
written naively (update S, read S for the numerator, reduce z). This
kernel performs

    S' = S + phi_k^T v ;  z' = z + phi_k ;
    out = (phi_q S') / (phi_q . z' + eps)

in a single VMEM residency of the state tile. Decode is memory-bound
(roofline: bytes of S dominate), so 3x -> 1x state traffic is a direct
3x on the achievable decode rate.

Grid: (B*H,) — one program per (batch, head) state. Every operand is a
3-D (B*H, rows, cols) array whose block spans its last two dims whole,
which is what the TPU lowering requires of a one-row block. phi_k and z
are columns (m, 1), so the outer product phi_k^T v is a broadcast, and
phi_q is a row (1, m), so both readouts are MXU dots, asked for at full
f32 precision: the state and normalizer must not round to bf16. State tiles are
donated/aliased so the update is in-place in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _srf_decode_kernel(s_ref, z_ref, pq_ref, pk_ref, v_ref, s_out, z_out,
                       o_ref, *, eps: float):
    s = s_ref[0].astype(jnp.float32)       # (m, dv)
    z = z_ref[0].astype(jnp.float32)       # (m, 1)
    pq = pq_ref[0].astype(jnp.float32)     # (1, m)
    pk = pk_ref[0].astype(jnp.float32)     # (m, 1)
    v = v_ref[0].astype(jnp.float32)       # (1, dv)
    s2 = s + pk * v
    z2 = z + pk
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    num = dot(pq, s2)                      # (1, dv)
    den = dot(pq, z2)                      # (1, 1)
    s_out[0] = s2.astype(s_out.dtype)
    z_out[0] = z2.astype(z_out.dtype)
    o_ref[0] = (num / (den + eps)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def srf_decode_pallas(s: jax.Array, z: jax.Array, phi_q: jax.Array,
                      phi_k: jax.Array, v: jax.Array, eps: float = 1e-6, *,
                      interpret: bool):
    """s: (B,H,m,dv) z: (B,H,m) phi_*: (B,H,m) v: (B,H,dv)
    -> (s', z', out) with out (B,H,dv). One grid step per (b,h)."""
    b, h, m, dv = s.shape
    bh = b * h
    operands = (s.reshape(bh, m, dv), z.reshape(bh, m, 1),
                phi_q.reshape(bh, 1, m), phi_k.reshape(bh, m, 1),
                v.reshape(bh, 1, dv))

    def whole(rows, cols):
        return pl.BlockSpec((1, rows, cols), lambda i: (i, 0, 0))

    kernel = functools.partial(_srf_decode_kernel, eps=eps)
    s2, z2, out = pl.pallas_call(
        kernel,
        grid=(bh,),
        in_specs=[whole(m, dv), whole(m, 1), whole(1, m), whole(m, 1),
                  whole(1, dv)],
        out_specs=[whole(m, dv), whole(m, 1), whole(1, dv)],
        out_shape=[jax.ShapeDtypeStruct((bh, m, dv), s.dtype),
                   jax.ShapeDtypeStruct((bh, m, 1), z.dtype),
                   jax.ShapeDtypeStruct((bh, 1, dv), v.dtype)],
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
    )(*operands)
    return (s2.reshape(b, h, m, dv), z2.reshape(b, h, m),
            out.reshape(b, h, dv))
