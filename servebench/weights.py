"""Weights made on the device from ``--seed``.

The plain reference names each weight and its shape (``weight_specs``).
Every weight is a pure function of (seed, name, layer), so the reference
can make one layer at a time after the timed window, and the program gets
the very same values laid out in its own parameter tree, made in one
jitted call. Nothing made by the program is read back.
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class WeightSpec:
    name: str
    shape: Tuple[int, ...]     # of one layer, or of the whole weight
    per_layer: bool
    init: str                  # normal | norm | sign
    scale: float = 1.0         # std of ``normal``; jitter of ``norm``


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key from any whole number (more than 32 bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(words, dtype=jnp.uint32)


def _name_id(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def _one(spec: WeightSpec, key: jax.Array, dtype) -> jax.Array:
    if spec.init == "normal":
        x = jax.random.normal(key, spec.shape, jnp.float32) * spec.scale
    elif spec.init == "norm":
        x = 1.0 + spec.scale * jax.random.normal(key, spec.shape, jnp.float32)
    elif spec.init == "sign":
        x = jnp.where(jax.random.bernoulli(key, 0.5, spec.shape), 1.0, -1.0)
    else:
        raise ValueError(spec.init)
    return x.astype(dtype)


def make(spec: WeightSpec, key: jax.Array, layers: jax.Array, dtype
         ) -> jax.Array:
    """The weight ``spec`` for each layer in ``layers`` (stacked on a
    leading axis), or the whole weight when it is not per layer. Traceable;
    the values do not depend on which other layers are made with it."""
    k = jax.random.fold_in(key, _name_id(spec.name))
    if not spec.per_layer:
        return _one(spec, k, dtype)
    return jax.vmap(lambda l: _one(spec, jax.random.fold_in(k, l), dtype))(
        layers)


def layer_maker(specs: List[WeightSpec], dtype) -> Callable:
    """``f(key, layer) -> {name: weight of that layer}`` for the per-layer
    specs, compiled once and called per layer."""
    per = [s for s in specs if s.per_layer]

    @jax.jit
    def f(key, layer):
        ls = jnp.reshape(layer, (1,)).astype(jnp.int32)
        return {s.name: make(s, key, ls, dtype)[0] for s in per}
    return f


def global_maker(specs: List[WeightSpec], dtype) -> Callable:
    glob = [s for s in specs if not s.per_layer]

    @jax.jit
    def f(key):
        return {s.name: make(s, key, jnp.zeros((0,), jnp.int32), dtype)
                for s in glob}
    return f


def _path_str(path) -> str:
    parts = []
    for p in path:
        for attr in ("key", "idx", "name"):
            if hasattr(p, attr):
                parts.append(str(getattr(p, attr)))
                break
    return "/".join(parts)


def _placements(layout: Dict) -> Dict[str, Tuple[str, int]]:
    """``{program path: (reference name, first layer)}``. A name maps to
    one path, whose leading axis holds layers 0, 1, ..., or to
    ``{path: first layer}``, one path per segment that holds it."""
    out: Dict[str, Tuple[str, int]] = {}
    for name, where in layout.items():
        for path, first in ({where: 0} if isinstance(where, str)
                            else where).items():
            if path in out:
                raise ValueError(f"{path} is named by both {out[path][0]} "
                                 f"and {name}")
            out[path] = (name, int(first))
    return out


def program_params(specs: List[WeightSpec], n_layers: int, shapes_tree,
                   layout: Dict, key: jax.Array, dtype):
    """The program's parameter tree (structure and leaf shapes of
    ``shapes_tree``), every leaf the weight that ``layout`` places at its
    path, zero-padded where the program pads (e.g. a padded vocabulary).
    A per-layer leaf holds consecutive layers from the first one its
    placement states, as many as its leading axis; layer ``l`` is what
    ``layer_maker`` makes for ``l``, in whichever segment holds it, and the
    segments tile the program's ``n_layers`` layers. One jitted call on
    the device."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes_tree)
    by_path = {_path_str(p): leaf for p, leaf in leaves}
    byname = {s.name: s for s in specs}
    where = _placements(layout)
    if set(where) != set(by_path):
        raise ValueError(
            "weight layout does not cover the program's parameters: "
            f"unmapped {sorted(set(by_path) - set(where))}, "
            f"unknown {sorted(set(where) - set(by_path))}")
    segs = set()
    for path, leaf in by_path.items():
        name, first = where[path]
        s = byname[name]
        want = ((leaf.shape[0],) if s.per_layer else ()) + s.shape
        if len(want) != len(leaf.shape) or any(
                w > h for w, h in zip(want, leaf.shape)):
            raise ValueError(f"{name} {want} does not fit the "
                             f"program's {path} {leaf.shape}")
        if s.per_layer:
            segs.add((first, first + leaf.shape[0]))
    segs = sorted(segs)
    ends = [0] + [b for _, b in segs]
    if [a for a, _ in segs] != ends[:-1] or ends[-1] != n_layers:
        raise ValueError(f"the layout's segments {segs} do not tile the "
                         f"program's {n_layers} layers")

    @jax.jit
    def build(key):
        out = []
        for p, leaf in leaves:
            name, first = where[_path_str(p)]
            s = byname[name]
            x = make(s, key, jnp.arange(first, first + leaf.shape[0]), dtype)
            pad = [(0, h - w) for w, h in zip(x.shape, leaf.shape)]
            if any(hi for _, hi in pad):
                x = jnp.pad(x, pad)
            out.append(x.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return build(key)
