"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state). Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod: 2×16×16 = 512 chips, axes ("pod", "data", "model") — the "pod"
axis is pure data parallelism (DCN-ish), "data"/"model" stay within a pod.

``make_mesh_from`` supports elastic scaling: given whatever devices survive,
it builds the largest valid (data, model) mesh — used by the serving engine
when the pool shrinks.
"""

from __future__ import annotations


import jax
from jax.sharding import AxisType, Mesh


def _make(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh_from(devices=None, *, max_model: int = 16) -> Mesh:
    """Largest (data, model) mesh over the given (surviving) devices.

    model axis = largest power of two ≤ max_model dividing the device count;
    any leftover devices are dropped (elastic downsize never deadlocks).
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    model = 1
    while model * 2 <= max_model and n % (model * 2) == 0:
        model *= 2
    data = n // model
    import numpy as np
    dev_array = np.asarray(devices[: data * model]).reshape(data, model)
    return Mesh(dev_array, ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


def make_test_mesh(n_devices: int | None = None) -> Mesh:
    """Small mesh over however many (possibly fake) devices tests have."""
    return make_mesh_from(jax.devices()[:n_devices])
