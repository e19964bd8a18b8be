"""Production meshes. Importing this module never touches jax device state;
``make_production_mesh`` is a function (per spec)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding constraints
    and ``in_shardings`` of this repo are written for the partitioner to
    complete, which explicit axes (the ``make_mesh`` default) refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) ('data', 'model') = 256 chips (v5e pod).
    Multi-pod:  (2, 16, 16) ('pod', 'data', 'model') = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests, elastic reshapes)."""
    return _auto_mesh(shape, axes)


def make_serving_meshes(replicas: int, model_parallel: int = 1,
                        devices=None):
    """Partition the device set into per-replica ('data', 'model') meshes
    for the mesh-serving router: ``replicas`` engine replicas, each a
    ``model_parallel``-wide tensor-parallel slice (data axis is 1 — the
    router, not a batch axis, spreads requests over replicas).

    On a real deployment each slice is one host's chips; in tests the
    forced host platform supplies the devices. Raises when the device
    set cannot cover ``replicas * model_parallel``.
    """
    import numpy as np
    from jax.sharding import Mesh
    devs = list(devices if devices is not None else jax.devices())
    need = replicas * model_parallel
    if len(devs) < need:
        raise ValueError(f"need {need} devices for {replicas} replicas x "
                         f"model={model_parallel}, have {len(devs)}")
    return [Mesh(np.array(devs[i * model_parallel:(i + 1) * model_parallel]
                          ).reshape(1, model_parallel), ("data", "model"))
            for i in range(replicas)]


def describe(mesh) -> str:
    return " x ".join(f"{n}={s}" for n, s in
                      zip(mesh.axis_names, mesh.devices.shape))
