"""Public jit'd wrappers for the Pallas kernels, one route per call.

On TPU the Pallas path compiles natively, and a call the kernel cannot
take raises instead of leaving the device path; on CPU kernels run in
``interpret=True`` mode for correctness, and large shapes route to the
pure-jnp reference (same semantics, faster than interpreting).

``use_pallas``: None = auto (pallas-interpret for small, jnp for big on
CPU; pallas-native on TPU), True = Pallas, False = the jnp reference on
any backend.

Routing cost model: every kernel routes on its TRUE work estimate (the
number of MACs / elements moved, B*n*m-style), not on input sizes — see
kernels/README.md for the table. ``REPRO_FORCE_PALLAS`` overrides the
auto route for debugging: ``1``/``true`` force the Pallas path (native on
TPU, interpret elsewhere), ``native``/``interpret`` force that exact
mode, ``0``/``false``/``ref`` force the jnp reference.

Every dispatch runs through ``repro.obs.profiling.dispatch``: the call is
wrapped in a ``jax.named_scope`` (profiler/HLO-visible, free at runtime).
"""
from __future__ import annotations

import functools
import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import transforms
from repro.obs import profiling as _prof

from . import circulant as _circ
from . import fwht as _fwht
from . import paged_gather as _pgather
from . import ref as _ref
from . import seedgen as _seedgen
from . import spinner as _spin
from . import srf_decode as _dec


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _route(use_pallas: Optional[bool], work_elems: int,
           interp_budget: int = 1 << 24,
           auto_interpret: bool = True) -> str:
    """-> 'native' | 'interpret' | 'ref'.

    ``work_elems`` is the kernel's true work estimate (MACs or elements
    moved); the interpreter budget is compared against it, so all kernels
    flip to the jnp reference at the same *work* level, not at
    incomparable input-size levels.

    ``auto_interpret=False`` disables the small-shape interpreter pick in
    auto mode: off-TPU the jnp ref is chosen unless Pallas is explicitly
    forced. Hot-path ops (the fused spinner) use this — the interpreter
    is a correctness vehicle, measurably slower than the ref on CPU.
    """
    env = os.environ.get("REPRO_FORCE_PALLAS")
    if env:
        e = env.strip().lower()
        if e in ("0", "false", "ref"):
            return "ref"
        if e in ("native", "interpret"):
            return e
        if e in ("1", "true"):
            return "native" if _on_tpu() else "interpret"
        raise ValueError(     # a typo'd debug override must not misroute
            f"REPRO_FORCE_PALLAS={env!r}: expected 1/true/0/false/"
            "ref/native/interpret")
    if use_pallas is False:
        return "ref"
    if _on_tpu():
        return "native"
    if use_pallas is True:
        return "interpret"
    if not auto_interpret:
        return "ref"
    return "interpret" if work_elems <= interp_budget else "ref"


def fwht(x: jax.Array, normalized: bool = True,
         use_pallas: Optional[bool] = None) -> jax.Array:
    route = _route(use_pallas, x.size * _fwht.chunk_width(x.shape[-1]))
    if route == "ref":
        return _prof.dispatch("fwht", lambda: _ref.fwht_ref(x, normalized))
    return _prof.dispatch("fwht", lambda: _fwht.fwht_pallas(
        x, normalized, interpret=(route == "interpret")))


def circulant_project(g: jax.Array, x: jax.Array, m: int,
                      epilogue: str = "identity",
                      sq: Optional[jax.Array] = None,
                      use_pallas: Optional[bool] = None) -> jax.Array:
    route = _route(use_pallas, x.shape[0] * x.shape[-1] * m)   # B*n*m MACs
    if route == "ref":
        return _prof.dispatch("circulant_project",
                              lambda: _ref.circulant_project_ref(
                                  g, x, m, epilogue, sq))
    return _prof.dispatch("circulant_project",
                          lambda: _circ.circulant_project_pallas(
                              g, x, m, epilogue, sq,
                              interpret=(route == "interpret")))


def paged_gather(pool: jax.Array, tables: jax.Array,
                 use_pallas: Optional[bool] = None) -> jax.Array:
    """pool (N, P, D), tables (R, M) -> (R, M*P, D) contiguous history."""
    r, m = tables.shape
    route = _route(use_pallas, r * m * pool.shape[1] * pool.shape[2])
    if route == "ref":
        return _prof.dispatch("paged_gather",
                              lambda: _ref.paged_gather_ref(pool, tables))
    return _prof.dispatch("paged_gather",
                          lambda: _pgather.paged_gather_pallas(
                              pool, tables, interpret=(route == "interpret")))


def paged_gather_dequant(pool: jax.Array, scales: jax.Array,
                         tables: jax.Array, out_dtype=jnp.float32,
                         use_pallas: Optional[bool] = None) -> jax.Array:
    """int8 pool (N, P, D) + scales (N, P, 1), tables (R, M) ->
    (R, M*P, D) dequantized history in ``out_dtype`` (fused: the int8
    page never materializes at full width in HBM)."""
    r, m = tables.shape
    route = _route(use_pallas, r * m * pool.shape[1] * pool.shape[2])
    if route == "ref":
        return _prof.dispatch("paged_gather_dequant",
                              lambda: _ref.paged_gather_dequant_ref(
                                  pool, scales, tables, out_dtype))
    return _prof.dispatch("paged_gather_dequant",
                          lambda: _pgather.paged_gather_dequant_pallas(
                              pool, scales, tables, out_dtype,
                              interpret=(route == "interpret")))


def srf_decode(s, z, phi_q, phi_k, v, eps: float = 1e-6,
               use_pallas: Optional[bool] = None):
    route = _route(use_pallas, s.size)               # state bytes dominate
    if route == "ref":
        return _prof.dispatch("srf_decode",
                              lambda: _ref.srf_decode_ref(
                                  s, z, phi_q, phi_k, v, eps))
    return _prof.dispatch("srf_decode",
                          lambda: _dec.srf_decode_pallas(
                              s, z, phi_q, phi_k, v, eps,
                              interpret=(route == "interpret")))


# ---------------------------------------------------------------------------
# fused structured spinner  f(A . D1 H D0 . x)
# ---------------------------------------------------------------------------

_VMEM_BUDGET = 8 * 1024 * 1024     # bytes; ~half of a 16 MB VMEM core
_BLOCK_B_CANDIDATES = (512, 256, 128, 64, 32, 16, 8)
_BLOCK_M_CANDIDATES = (2048, 1024, 512, 256, 128)
_plan_cache: Dict[tuple, Tuple[int, int]] = {}


def _spinner_vmem_bytes(kind: str, n: int, m: int, tb: int, tm: int,
                        use_hd: bool, epilogue: str,
                        itemsize: int = 4, seeded: bool = False) -> int:
    """Resident bytes of one spinner program (VMEM feasibility model).

    Input/output tiles and d0/d1 are VMEM-resident at the INPUT dtype
    (``itemsize``). Everything the kernel COMPUTES with is f32 regardless
    of input dtype: the HD/sq scratch, the Hadamard factor, the HD chunk
    intermediates, the generator tables, the rotated source and the
    regenerated A tile, and the pre-epilogue y — so those terms never
    shrink with a narrower input dtype.
    """
    f32 = 4
    by = tb * n * itemsize    # x tile
    by += (tb * n + tb) * f32                        # HD scratch + sq scratch
    by += tm * n * f32        # regenerated / streamed A tile (f32 for the dot)
    by += tb * tm * f32       # pre-epilogue y (f32)
    by += tb * tm * (2 if epilogue == "cos_sin" else 1) * itemsize  # out tile
    if use_hd:
        c = _fwht.chunk_width(n)
        by += c * c * f32                            # hadamard factor
        by += 2 * n * itemsize                       # d0 / d1
        by += tb * n * f32                           # chunk intermediates
    if seeded:
        # no resident generators; the counter-PRNG's uint32 grids and
        # Box-Muller temporaries live alongside the regenerated tile
        by += 2 * tm * n * 4
        return by
    if kind in ("circulant", "skew_circulant"):
        by += -(-m // n) * n * f32                   # generator blocks
        by += tm * n * f32                           # broadcast source
    elif kind in ("toeplitz", "hankel"):
        w = _spin.table_width(n, m)
        by += w * f32 + tm * w * f32                 # table + rotated source
    # unstructured streams its (tm, n) tile — already counted above
    return by


def spinner_plan(kind: str, n: int, m: int, *, use_hd: bool = True,
                 epilogue: str = "identity", dtype=jnp.float32,
                 budget: int = _VMEM_BUDGET,
                 seeded: bool = False) -> Tuple[int, int]:
    """Pick (block_b, block_m) for the spinner kernel: sweep the candidate
    grid against the VMEM budget, preferring large row tiles (they
    amortize grid overhead) then large batch tiles. Cached per shape AND
    per dtype — bf16 tiles are half the resident bytes of f32 tiles, so
    the two must not share a plan (a bf16 warm-up would hand f32 an
    over-budget block). Serving factories pre-warm it (launch/steps.py)."""
    dt = jnp.dtype(dtype)
    key = (kind, n, m, use_hd, epilogue, dt.name, budget, seeded)
    if key in _plan_cache:
        return _plan_cache[key]
    best = (_BLOCK_B_CANDIDATES[-1], _BLOCK_M_CANDIDATES[-1])
    found = False
    for tm in _BLOCK_M_CANDIDATES:
        if found:
            break
        for tb in _BLOCK_B_CANDIDATES:
            if _spinner_vmem_bytes(kind, n, m, tb, min(tm, m), use_hd,
                                   epilogue, dt.itemsize, seeded) <= budget:
                best = (tb, tm)
                found = True
                break
    _plan_cache[key] = best
    return best


def _spinner_route(kind: str, n: int, m: int, use_hd: bool,
                   use_pallas: Optional[bool], work: int) -> str:
    """Route of both spinner entry points. Shapes the kernel does not
    cover (``ldr``, HD over a non-power-of-two n, oversized n or m) take
    the jnp reference off-TPU; on TPU they raise rather than leave the
    device path, unless the caller asked for the reference itself."""
    route = _route(use_pallas, work, auto_interpret=False)
    pallas_ok = (kind in _spin.PALLAS_KINDS
                 and (not use_hd or transforms.is_pow2(n))
                 and n <= 8192 and n + m - 1 <= (1 << 22))
    if pallas_ok or route == "ref":
        return route
    if route == "native":
        raise ValueError(
            f"spinner kind={kind!r} n={n} m={m} use_hd={use_hd} has no "
            "compiled TPU kernel; pass use_pallas=False to run the jnp "
            "reference on purpose")
    return "ref"


def _spinner_pallas_vjp(kind: str, m: int, use_hd: bool, epilogue: str,
                        y_scale: float, out_scale: float, tb: int, tm: int,
                        interpret: bool):
    """Pallas forward + jnp-reference backward (Pallas kernels have no
    native autodiff; the ref graph IS the semantics, so its VJP is exact
    up to float re-association)."""
    fwd_fn = functools.partial(
        _spin.spinner_project_pallas, kind, m=m, use_hd=use_hd,
        epilogue=epilogue, y_scale=y_scale, out_scale=out_scale,
        block_b=tb, block_m=tm, interpret=interpret)
    ref_fn = functools.partial(
        _ref.spinner_project_ref, kind, m=m, epilogue=epilogue,
        y_scale=y_scale, out_scale=out_scale)

    @jax.custom_vjp
    def f(g, x, d0, d1):
        return fwd_fn(g, x, d0=d0, d1=d1)

    def fwd(g, x, d0, d1):
        return f(g, x, d0, d1), (g, x, d0, d1)

    def bwd(res, dy):
        g, x, d0, d1 = res
        _, vjp = jax.vjp(lambda gg, xx, dd0, dd1:
                         ref_fn(gg, xx, d0=dd0, d1=dd1), g, x, d0, d1)
        return vjp(dy)

    f.defvjp(fwd, bwd)
    return f


@functools.partial(jax.jit, static_argnames=(
    "kind", "m", "epilogue", "y_scale", "out_scale", "grouped", "route",
    "block_b", "block_m"))
def _spinner_call(kind, g, x, m, d0, d1, h, *, epilogue, y_scale, out_scale,
                  grouped, route, block_b, block_m):
    """Single jit entry for both spinner routes: the group lift / leading-
    dim flatten / output reshape all trace away, so an eager caller pays
    exactly one dispatch (consumers under their own jit inline this)."""
    n = x.shape[-1]
    if grouped:
        gsz, lead = x.shape[0], x.shape[1:-1]
        xf = x.reshape(gsz, -1, n)
    else:
        gsz, lead = 1, x.shape[:-1]
        xf = x.reshape(1, -1, n)
        g = g[None]
        h = None if h is None else h[None]
        d0 = None if d0 is None else d0[None]
        d1 = None if d1 is None else d1[None]
    if route == "ref":
        y = _ref.spinner_project_ref(kind, g, xf, m, d0=d0, d1=d1, h=h,
                                     epilogue=epilogue, y_scale=y_scale,
                                     out_scale=out_scale)
    else:
        fn = _spinner_pallas_vjp(kind, m, d0 is not None, epilogue, y_scale,
                                 out_scale, block_b, block_m,
                                 interpret=(route == "interpret"))
        y = fn(g, xf, d0, d1)
    out_dim = 2 * m if epilogue == "cos_sin" else m
    shape = ((gsz,) + lead + (out_dim,)) if grouped else (lead + (out_dim,))
    return y.reshape(shape)


def spinner_project(kind: str, params: Dict[str, jax.Array], x: jax.Array,
                    m: int, epilogue: str = "identity",
                    y_scale: float = 1.0, out_scale: float = 1.0,
                    grouped: bool = False,
                    use_pallas: Optional[bool] = None,
                    block_b: Optional[int] = None,
                    block_m: Optional[int] = None) -> jax.Array:
    """One-pass  f(y_scale * A . D1 H D0 . x) * out_scale  for any P-model.

    params: the pmodel.init dict ({"g", optional "h", "d0", "d1"}); HD is
    applied iff "d0" is present. x: (..., n) — or (G, ..., n) with
    ``grouped=True`` and a leading group axis G on every param leaf
    (per-kv-head P-models in SRF attention run as one fused dispatch).

    Output (..., m), or (..., 2m) = [cos | sin] for the cos_sin epilogue.
    epilogues: identity | relu | heaviside | sign | exp | cos_sin; ``exp``
    computes exp(y - 0.5||x||^2) with the subtrahend taken in-kernel
    (valid because the normalized HD block is an isometry).

    Kinds circulant / skew_circulant / toeplitz / hankel run as implicit-
    tile Pallas kernels; unstructured streams dense row tiles through the
    same fused kernel; ldr always takes the fused jnp reference. The
    Pallas path carries a jnp-reference VJP, so it is safe under grad.
    """
    g = params["g"]
    h = params.get("h")
    d0 = params.get("d0")
    d1 = params.get("d1")
    use_hd = d0 is not None
    n = x.shape[-1]
    work = (x.size // n) * n * m

    route = _spinner_route(kind, n, m, use_hd, use_pallas, work)
    if route != "ref" and (block_b is None or block_m is None):
        auto_b, auto_m = spinner_plan(kind, n, m, use_hd=use_hd,
                                      epilogue=epilogue, dtype=x.dtype)
        block_b = block_b or auto_b
        block_m = block_m or auto_m
    return _prof.dispatch(
        "spinner_project",
        lambda: _spinner_call(kind, g, x, m, d0, d1, h, epilogue=epilogue,
                              y_scale=y_scale, out_scale=out_scale,
                              grouped=grouped, route=route,
                              block_b=block_b, block_m=block_m))


# ---------------------------------------------------------------------------
# seed mode: zero-storage spinner (one uint32 per projection)
# ---------------------------------------------------------------------------

def _spinner_seeded_vjp(kind: str, m: int, r: int, ldr_nnz: int,
                        use_hd: bool, epilogue: str, y_scale: float,
                        out_scale: float, tb: int, tm: int, interpret: bool):
    """Seeded Pallas forward + jnp-reference backward. The backward
    regenerates the oracle params from the seeds and differentiates the
    materialized reference w.r.t. x only — the seeds are integers, their
    cotangent is the symbolic float0 zero."""
    fwd_fn = functools.partial(
        _spin.spinner_project_seeded_pallas, kind, m=m, use_hd=use_hd,
        epilogue=epilogue, y_scale=y_scale, out_scale=out_scale,
        block_b=tb, block_m=tm, interpret=interpret)

    @jax.custom_vjp
    def f(seeds, x):
        return fwd_fn(seeds, x)

    def fwd(seeds, x):
        return f(seeds, x), (seeds, x)

    def bwd(res, dy):
        seeds, x = res
        n = x.shape[-1]
        params = _seedgen.grouped_params(kind, n, m, seeds, r=r,
                                         ldr_nnz=ldr_nnz, use_hd=use_hd)
        _, vjp = jax.vjp(
            lambda xx: _ref.spinner_project_ref(
                kind, params["g"], xx, m, d0=params.get("d0"),
                d1=params.get("d1"), h=params.get("h"), epilogue=epilogue,
                y_scale=y_scale, out_scale=out_scale), x)
        dx, = vjp(dy)
        return np.zeros(seeds.shape, jax.dtypes.float0), dx

    f.defvjp(fwd, bwd)
    return f


@functools.partial(jax.jit, static_argnames=(
    "kind", "m", "r", "ldr_nnz", "use_hd", "epilogue", "y_scale",
    "out_scale", "grouped", "route", "block_b", "block_m"))
def _spinner_seeded_call(kind, seeds, x, m, *, r, ldr_nnz, use_hd, epilogue,
                         y_scale, out_scale, grouped, route, block_b,
                         block_m):
    """Single jit entry for the seeded routes (mirror of _spinner_call)."""
    n = x.shape[-1]
    if grouped:
        gsz, lead = x.shape[0], x.shape[1:-1]
        xf = x.reshape(gsz, -1, n)
        sd = seeds.astype(jnp.uint32).reshape(gsz)
    else:
        gsz, lead = 1, x.shape[:-1]
        xf = x.reshape(1, -1, n)
        sd = jnp.asarray(seeds, jnp.uint32).reshape(1)
    if route == "ref":
        y = _ref.spinner_project_seeded_ref(kind, sd, xf, m, r=r,
                                            ldr_nnz=ldr_nnz, use_hd=use_hd,
                                            epilogue=epilogue,
                                            y_scale=y_scale,
                                            out_scale=out_scale)
    else:
        fn = _spinner_seeded_vjp(kind, m, r, ldr_nnz, use_hd, epilogue,
                                 y_scale, out_scale, block_b, block_m,
                                 interpret=(route == "interpret"))
        y = fn(sd, xf)
    out_dim = 2 * m if epilogue == "cos_sin" else m
    shape = ((gsz,) + lead + (out_dim,)) if grouped else (lead + (out_dim,))
    return y.reshape(shape)


def spinner_project_seeded(kind: str, seeds: jax.Array, x: jax.Array,
                           m: int, *, r: int = 1, ldr_nnz: int = 4,
                           use_hd: bool = True, epilogue: str = "identity",
                           y_scale: float = 1.0, out_scale: float = 1.0,
                           grouped: bool = False,
                           use_pallas: Optional[bool] = None,
                           block_b: Optional[int] = None,
                           block_m: Optional[int] = None) -> jax.Array:
    """Zero-storage  f(y_scale * A . D1 H D0 . x) * out_scale  where the
    whole projection — generator core AND the HD Rademacher diagonals —
    is regenerated on the fly from ``seeds`` (uint32; scalar, or (G,)
    with ``grouped=True``). No (m,)- or (m,n)-sized parameter tensor ever
    exists: the Pallas routes generate entries in VMEM per tile; the ref
    route materializes the oracle params transiently inside its trace.

    Same routing contract as :func:`spinner_project` (``ldr`` and custom
    shapes take the ref path); bit-identical to running the materialized
    spinner on ``kernels.seedgen.seeded_params(...)`` on the interpret /
    ref routes. Differentiable w.r.t. ``x``.
    """
    n = x.shape[-1]
    work = (x.size // n) * n * m

    route = _spinner_route(kind, n, m, use_hd, use_pallas, work)
    if route != "ref" and (block_b is None or block_m is None):
        auto_b, auto_m = spinner_plan(kind, n, m, use_hd=use_hd,
                                      epilogue=epilogue, dtype=x.dtype,
                                      seeded=True)
        block_b = block_b or auto_b
        block_m = block_m or auto_m
    return _prof.dispatch(
        "spinner_project_seeded",
        lambda: _spinner_seeded_call(kind, seeds, x, m, r=r,
                                     ldr_nnz=ldr_nnz, use_hd=use_hd,
                                     epilogue=epilogue, y_scale=y_scale,
                                     out_scale=out_scale, grouped=grouped,
                                     route=route, block_b=block_b,
                                     block_m=block_m))
