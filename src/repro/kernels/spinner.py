"""Pallas TPU kernel: the fused structured spinner  f(A . D1 H D0 . x).

The paper's whole pipeline (Step-1 HD preconditioner -> structured
projection -> pointwise f) is one cheap operator, but executed naively it
is 3+ dispatches with an HBM round trip between each:

    u = D0 x ; w = H u ; v = D1 w      (transforms.hd_preprocess)
    y = A v                            (structured.matvec, FFT)
    out = f(y)                         (pointwise epilogue)

This kernel runs the whole chain in a single ``pallas_call``: per batch
tile the HD sandwich is computed ONCE into VMEM scratch (Kronecker-form
FWHT — the MXU sandwich of kernels/fwht.py), then every row tile of the
structured matrix A is REGENERATED in VMEM from its O(n) generator and
fed straight to the MXU, with f fused as an epilogue before the single
write-back.  HBM traffic: x in, f(y) out, generators (O(n)); no
intermediate ever leaves the chip.

Implicit tile regeneration (A is never materialized in HBM). Every row
of a circulant, toeplitz or hankel tile is a cyclically shifted copy of
its generator, so the (tm, n) tile is a broadcast generator whose row r
is rotated along the lanes by ``shift + r * stride`` (``_row_roll``: one
uniform ``pltpu.roll`` plus one per bit of r) — no gather, which Mosaic
does not lower. With ``rows = j*tm + iota`` the global row ids:

  circulant       A[i,j] = g[i//n, (j - i) mod n]
                  -> src row = g[i//n] (block picked by row compares),
                     rotated by i mod n
  skew_circulant  same, wrapped entries (j < i mod n) negated
  toeplitz        A[i,j] = gen(j - i), gen(d>=0) = g[d], gen(d<0) = g[n-1-d]
                  -> glin = [flip(g[n:]), g[:n]] (length n+m-1, padded to
                     a lane multiple W), rotated by i - (m-1) mod W
  hankel          A[i,j] = g[i + j] -> g padded to W, rotated by -i mod W
                  (stride W-1)
  unstructured    dense g, streamed per row tile by BlockSpec (no
                  regeneration — still fuses HD + matmul + epilogue)

``ldr`` tiles cost O(r n) per entry to regenerate and stay on the jnp
reference path (kernels/ref.py).

The HD sandwich runs in lane-chunk form (kernels/fwht.py ``fwht_tile``):
one dense 128-wide Hadamard matmul per lane chunk plus a butterfly over
the chunks — never a reshape that splits a lane row.

Grid: (groups, batch_tiles, row_tiles); the group axis carries
independent P-models (one per kv head in SRF attention) so per-head
feature maps run as ONE kernel instead of a vmap of dispatches.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import transforms

from . import fwht, seedgen

EPILOGUES = ("identity", "relu", "heaviside", "sign", "exp", "cos_sin")
PALLAS_KINDS = ("circulant", "skew_circulant", "toeplitz", "hankel",
                "unstructured")


def _apply_epilogue(y, epilogue, sq, out_scale):
    if epilogue == "identity":
        r = y
    elif epilogue == "relu":
        r = jnp.maximum(y, 0.0)
    elif epilogue == "heaviside":
        r = (y >= 0).astype(y.dtype)
    elif epilogue == "sign":
        r = jnp.sign(y)
    elif epilogue == "exp":
        r = jnp.exp(y - sq)
    else:
        raise ValueError(epilogue)
    return r if out_scale == 1.0 else r * out_scale


def table_width(n: int, m: int) -> int:
    """Lane width of the generator table a toeplitz / hankel tile rotates
    (n + m - 1 rounded up to whole 128-lane vregs)."""
    return -(-(n + m - 1) // fwht.LANES) * fwht.LANES


def _blocks_per_tile(n: int, tm: int, nb: int) -> int:
    """Most circulant blocks one (tm, n) row tile can touch."""
    if tm % n == 0:
        k = tm // n                  # tiles start on block boundaries
    elif n % tm == 0:
        k = 1                        # tiles never cross a boundary
    else:
        k = 1 + (n + tm - 2) // n
    return min(k, nb)


def _row_roll(x: jax.Array, shift, stride: int) -> jax.Array:
    """Rotate row r of the (rows, w) tile x along its lanes by
    ``shift + r * stride`` (mod w): one uniform rotation, then one
    rotation per bit of the row index, selected by that bit. Plain lane
    rotations lower compactly on every route (a strided ``pltpu.roll``
    interprets as one rotation per row)."""
    rows, w = x.shape
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    x = pltpu.roll(x, shift, 1)
    bit = 1
    while bit < rows:
        step = (stride * bit) % w
        if step:
            x = jnp.where((r & bit) != 0, pltpu.roll(x, step, 1), x)
        bit *= 2
    return x


def regen_tile(kind, gt_ref, j, *, n, m, tm, nb):
    """Rebuild the (tm, n) row tile of A in VMEM from the O(n) generator
    by row-dependent lane rotations (see the module docstring).

    Rows of padded tiles (rows >= m) hold garbage; their write-back is
    dropped by the out BlockSpec.
    """
    row0 = j * tm
    if kind in ("circulant", "skew_circulant"):
        b0, base = row0 // n, row0 % n
        rel = base + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        src = jnp.broadcast_to(gt_ref[0, pl.ds(jnp.minimum(b0, nb - 1), 1), :],
                               (tm, n))
        crossed = jnp.zeros((tm, 1), jnp.int32)  # block boundaries passed
        for k in range(1, _blocks_per_tile(n, tm, nb)):
            blk = gt_ref[0, pl.ds(jnp.minimum(b0 + k, nb - 1), 1), :]
            past = rel >= k * n
            src = jnp.where(past, blk, src)
            crossed = crossed + past.astype(jnp.int32)
        tile = _row_roll(src, base, 1)
        if kind == "skew_circulant":
            off = rel - n * crossed                          # i mod n
            cols = jax.lax.broadcasted_iota(jnp.int32, (tm, n), 1)
            tile = jnp.where(cols < off, -tile, tile)
        return tile
    w = gt_ref.shape[-1]
    src = jnp.broadcast_to(gt_ref[0], (tm, w))
    if kind == "toeplitz":
        shift = (row0 + (w - (m - 1) % w)) % w               # i - (m-1) mod W
        return _row_roll(src, shift, 1)[:, :n]
    if kind == "hankel":
        shift = (w - row0 % w) % w                           # -i mod W
        return _row_roll(src, shift, w - 1)[:, :n]
    raise ValueError(kind)


def _hd_tile(x, d0, d1, hc):
    """D1 H D0 x for an f32 (tb, n) tile (normalized Sylvester H)."""
    n = x.shape[-1]
    return fwht.fwht_tile(x * d0, hc) * (1.0 / math.sqrt(n)) * d1


def _write_tile(o_ref, y, epilogue: str, sq_ref, out_scale: float):
    """Fused epilogue + the single write-back (shared by the materialized
    and the seeded kernels — identical tail, bit for bit)."""
    if epilogue == "cos_sin":
        s = out_scale
        o_ref[0, 0] = (jnp.cos(y) * s).astype(o_ref.dtype)
        o_ref[0, 1] = (jnp.sin(y) * s).astype(o_ref.dtype)
    else:
        sq = sq_ref[...] if epilogue == "exp" else None
        o_ref[0] = _apply_epilogue(y, epilogue, sq, out_scale).astype(o_ref.dtype)


def _spinner_kernel(*refs, kind: str, n: int, m: int, tm: int, nb: int,
                    use_hd: bool, epilogue: str, y_scale: float,
                    out_scale: float):
    it = iter(refs)
    x_ref = next(it)
    if use_hd:
        d0_ref, d1_ref, hc_ref = next(it), next(it), next(it)
    gt_ref = next(it)
    o_ref = next(it)
    hd_ref = next(it)                            # VMEM scratch (tb, n) f32
    sq_ref = next(it)                            # VMEM scratch (tb, 1) f32
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _hd():                                   # once per (group, batch tile)
        x = x_ref[0].astype(jnp.float32)         # (tb, n)
        if epilogue == "exp":                    # ||v|| = ||x|| (HD isometry)
            sq_ref[...] = 0.5 * jnp.sum(x * x, axis=-1, keepdims=True)
        if use_hd:
            x = _hd_tile(x, d0_ref[0].astype(jnp.float32),
                         d1_ref[0].astype(jnp.float32), hc_ref[...])
        hd_ref[...] = x

    v = hd_ref[...]                              # (tb, n) f32
    if kind == "unstructured":
        tile = gt_ref[0]                         # (tm, n) streamed by BlockSpec
    else:
        tile = regen_tile(kind, gt_ref, j, n=n, m=m, tm=tm, nb=nb)
    y = jax.lax.dot_general(v, tile.astype(jnp.float32),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (tb, tm)
    if y_scale != 1.0:
        y = y * y_scale
    _write_tile(o_ref, y, epilogue, sq_ref, out_scale)


def _gen_table(kind: str, g: jax.Array, n: int, m: int) -> jax.Array:
    """Per-kind generator layout consumed by ``regen_tile`` (leading G).
    Regenerated tables are f32: Mosaic rotates 32-bit lanes only, and a
    dynamic row of a packed bf16 table is not addressable."""
    if kind == "unstructured":
        return g                                 # (G, m, n) dense, streamed
    g = g.astype(jnp.float32)
    if kind in ("circulant", "skew_circulant"):
        return g                                 # (G, nb, n)
    if kind == "toeplitz":                       # glin[d + m - 1]
        g = jnp.concatenate([jnp.flip(g[..., n:], -1), g[..., :n]], axis=-1)
    elif kind != "hankel":
        raise ValueError(kind)
    pad = table_width(n, m) - g.shape[-1]
    return jnp.pad(g, ((0, 0), (0, pad)))[:, None, :]        # (G, 1, W)


def _out_layout(epilogue: str, gsz: int, bsz: int, m: int, tb: int, tm: int,
                dtype):
    """(out_shape, out_spec) of both spinner kernels. cos_sin writes a
    (G, 2, B, m) [cos, sin] pair that ``_finish`` folds to (G, B, 2m)."""
    if epilogue == "cos_sin":
        return (jax.ShapeDtypeStruct((gsz, 2, bsz, m), dtype),
                pl.BlockSpec((1, 2, tb, tm), lambda gi, i, j: (gi, 0, i, j)))
    return (jax.ShapeDtypeStruct((gsz, bsz, m), dtype),
            pl.BlockSpec((1, tb, tm), lambda gi, i, j: (gi, i, j)))


def _finish(y: jax.Array, epilogue: str) -> jax.Array:
    if epilogue == "cos_sin":                    # -> row-major [cos | sin]
        gsz, _, bsz, m = y.shape
        y = y.transpose(0, 2, 1, 3).reshape(gsz, bsz, 2 * m)
    return y


def _hadamard_factor(n: int):
    c = fwht.chunk_width(n)
    return (transforms.hadamard(c, jnp.float32, normalized=False),
            pl.BlockSpec((c, c), lambda gi, i, j: (0, 0)))


@functools.partial(jax.jit, static_argnames=(
    "kind", "m", "use_hd", "epilogue", "y_scale", "out_scale",
    "block_b", "block_m", "interpret"))
def spinner_project_pallas(kind: str, g: jax.Array, x: jax.Array, m: int,
                           d0: Optional[jax.Array] = None,
                           d1: Optional[jax.Array] = None,
                           use_hd: bool = True,
                           epilogue: str = "identity",
                           y_scale: float = 1.0, out_scale: float = 1.0,
                           block_b: int = 256, block_m: int = 512, *,
                           interpret: bool) -> jax.Array:
    """x: (G, B, n) -> (G, B, m)  ((G, B, 2m) for cos_sin: [cos | sin]).

    g: generators with leading group axis — (G, nb, n) for circulant /
    skew_circulant, (G, n+m-1) for toeplitz / hankel, (G, m, n) dense.
    d0/d1: (G, n) Rademacher diagonals when ``use_hd``. ``interpret``
    picks the Pallas interpreter (CPU) over the compiled kernel (TPU).

    All arithmetic is f32 in VMEM (bf16 inputs upcast on load, cast back
    on the single write). Awkward B / m (not multiples of the block
    sizes) are handled by grid padding: padded rows regenerate garbage
    that the out BlockSpec drops.
    """
    assert epilogue in EPILOGUES, epilogue
    assert kind in PALLAS_KINDS, kind
    gsz, bsz, n = x.shape
    if use_hd:
        assert transforms.is_pow2(n), f"HD needs power-of-two n, got {n}"
    tb = min(block_b, bsz)
    tm = min(block_m, m)
    gt = _gen_table(kind, g, n, m)
    grid = (gsz, pl.cdiv(bsz, tb), pl.cdiv(m, tm))

    in_specs = [pl.BlockSpec((1, tb, n), lambda gi, i, j: (gi, i, 0))]
    inputs = [x]
    if use_hd:
        hc, hc_spec = _hadamard_factor(n)
        in_specs += [pl.BlockSpec((1, 1, n), lambda gi, i, j: (gi, 0, 0)),
                     pl.BlockSpec((1, 1, n), lambda gi, i, j: (gi, 0, 0)),
                     hc_spec]
        inputs += [d0[:, None, :], d1[:, None, :], hc]
    if kind == "unstructured":                   # stream dense row tiles
        in_specs += [pl.BlockSpec((1, tm, n), lambda gi, i, j: (gi, j, 0))]
    else:                                        # O(n) generator resident
        in_specs += [pl.BlockSpec((1,) + gt.shape[1:],
                                  lambda gi, i, j: (gi, 0, 0))]
    inputs += [gt]
    out_shape, out_specs = _out_layout(epilogue, gsz, bsz, m, tb, tm, x.dtype)

    kernel = functools.partial(
        _spinner_kernel, kind=kind, n=n, m=m, tm=tm, nb=gt.shape[1],
        use_hd=use_hd, epilogue=epilogue, y_scale=y_scale,
        out_scale=out_scale)
    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((tb, n), jnp.float32),
                        pltpu.VMEM((tb, 1), jnp.float32)],
        interpret=interpret,
    )(*inputs)
    return _finish(y, epilogue)


# ---------------------------------------------------------------------------
# seed mode: regenerate g / D0 / D1 from a 32-bit seed INSIDE the kernel
# ---------------------------------------------------------------------------

def _seeded_spinner_kernel(*refs, kind: str, n: int, m: int, tm: int,
                           nb: int, use_hd: bool, epilogue: str,
                           y_scale: float, out_scale: float):
    """The fused spinner with ZERO generator inputs: every A-tile entry
    and both HD diagonals are regenerated in VMEM from the group's seed
    via the counter-based PRNG (kernels/seedgen.py). HBM traffic is x in,
    f(y) out, and one uint32 per group — the O(1)-storage limit of the
    paper's randomness recycling.

    Values are generated at FLAT PARAM POSITIONS, so they match the
    materialized ``seedgen.seeded_params`` oracle bit for bit and are
    independent of the (tb, tm) tiling the autotuner picks.
    """
    it = iter(refs)
    x_ref = next(it)
    seed_ref = next(it)                          # (G,) uint32 in SMEM
    if use_hd:
        hc_ref = next(it)
    o_ref = next(it)
    hd_ref = next(it)                            # VMEM scratch (tb, n) f32
    sq_ref = next(it)                            # VMEM scratch (tb, 1) f32
    j = pl.program_id(2)
    seed = seed_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _hd():                                   # once per (group, batch tile)
        x = x_ref[0].astype(jnp.float32)         # (tb, n)
        if epilogue == "exp":                    # ||v|| = ||x|| (HD isometry)
            sq_ref[...] = 0.5 * jnp.sum(x * x, axis=-1, keepdims=True)
        if use_hd:
            pos = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
            x = _hd_tile(x, seedgen.sign_at(seed, seedgen.DOM_D0, pos),
                         seedgen.sign_at(seed, seedgen.DOM_D1, pos),
                         hc_ref[...])
        hd_ref[...] = x

    v = hd_ref[...]                              # (tb, n) f32
    rows = j * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (tm, n), 1)
    tile = seedgen.gen_tile(kind, seed, rows, cols, n=n, m=m, nb=nb)
    y = jax.lax.dot_general(v, tile, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (tb, tm)
    if y_scale != 1.0:
        y = y * y_scale
    _write_tile(o_ref, y, epilogue, sq_ref, out_scale)


@functools.partial(jax.jit, static_argnames=(
    "kind", "m", "use_hd", "epilogue", "y_scale", "out_scale",
    "block_b", "block_m", "interpret"))
def spinner_project_seeded_pallas(kind: str, seeds: jax.Array, x: jax.Array,
                                  m: int, use_hd: bool = True,
                                  epilogue: str = "identity",
                                  y_scale: float = 1.0,
                                  out_scale: float = 1.0,
                                  block_b: int = 256, block_m: int = 512, *,
                                  interpret: bool) -> jax.Array:
    """Seed-mode twin of :func:`spinner_project_pallas`.

    x: (G, B, n) -> (G, B, m) ((G, B, 2m) for cos_sin); ``seeds``: (G,)
    uint32, one independent projection per group. No generator, d0 or d1
    tensors exist anywhere — each grid step regenerates what it consumes.
    """
    assert epilogue in EPILOGUES, epilogue
    assert kind in PALLAS_KINDS, kind
    gsz, bsz, n = x.shape
    if use_hd:
        assert transforms.is_pow2(n), f"HD needs power-of-two n, got {n}"
    tb = min(block_b, bsz)
    tm = min(block_m, m)
    nb = -(-m // n) if kind in ("circulant", "skew_circulant") else 1
    grid = (gsz, pl.cdiv(bsz, tb), pl.cdiv(m, tm))

    in_specs = [pl.BlockSpec((1, tb, n), lambda gi, i, j: (gi, i, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM)]
    inputs = [x, seeds.astype(jnp.uint32).reshape(gsz)]
    if use_hd:
        hc, hc_spec = _hadamard_factor(n)
        in_specs += [hc_spec]
        inputs += [hc]
    out_shape, out_specs = _out_layout(epilogue, gsz, bsz, m, tb, tm, x.dtype)

    kernel = functools.partial(
        _seeded_spinner_kernel, kind=kind, n=n, m=m, tm=tm, nb=nb,
        use_hd=use_hd, epilogue=epilogue, y_scale=y_scale,
        out_scale=out_scale)
    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((tb, n), jnp.float32),
                        pltpu.VMEM((tb, 1), jnp.float32)],
        interpret=interpret,
    )(*inputs)
    return _finish(y, epilogue)
