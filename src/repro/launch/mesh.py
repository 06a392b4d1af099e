"""Production mesh definitions.

Functions (not module-level constants) so importing this module never touches
jax device state. Single pod = 16×16 = 256 chips ("data", "model"); multi-pod
= 2×16×16 = 512 chips with the leading "pod" axis spanning the (slower)
inter-pod links — batch shards over ("pod", "data") so cross-pod traffic is
gradient reduction only.
"""
from __future__ import annotations

import jax


def _auto(axes):
    # Auto axes: shardings propagate through jit, and `with mesh:` places
    # the computation (make_mesh's default axis type is Explicit)
    return (jax.sharding.AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def make_host_mesh(model: int = 1):
    """Whatever this host has (tests / examples): (n_devices/model, model)."""
    n = jax.device_count()
    assert n % model == 0
    axes = ("data", "model")
    return jax.make_mesh((n // model, model), axes, axis_types=_auto(axes))
