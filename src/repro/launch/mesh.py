"""Production mesh construction (assignment-specified geometry).

Defined as functions (never module-level constants) so importing this
module never touches jax device state. Every mesh has Auto axes: the
step functions place activations with ``with_sharding_constraint``,
which Explicit axes (``jax.make_mesh``'s default) refuse.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi-pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh_for_devices(n_devices: int, model_parallel: int = None):
    """Elastic variant: best (data, model) mesh for a surviving device set
    (used by launch/elastic.py after a pod/host failure)."""
    if model_parallel is None:
        model_parallel = 16 if n_devices % 16 == 0 else 1
    while n_devices % model_parallel:
        model_parallel //= 2
    return _auto_mesh((n_devices // model_parallel, model_parallel),
                      ("data", "model"))


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many (CPU) devices exist — for tests."""
    return _auto_mesh((data, model), ("data", "model"))


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
