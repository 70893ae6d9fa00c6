"""Production mesh construction.

A FUNCTION (not module-level state) so importing never touches jax device
state. Single-pod: (data=16, model=16) = 256 chips; multi-pod adds a leading
pure-DP 'pod' axis (2 x 16 x 16 = 512 chips, DCN-crossing gradient psum).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def make_test_mesh(n_devices: int = None):
    """Tiny mesh over however many devices exist (unit tests)."""
    n = n_devices or len(jax.devices())
    model = 2 if n % 2 == 0 and n >= 2 else 1
    axes = ("data", "model")
    return jax.make_mesh((n // model, model), axes, axis_types=_auto(axes))


def _auto(axes) -> tuple:
    """``jax.make_mesh`` defaults to Explicit axes; the sharding constraints
    and shard_map specs here are written for Auto axes."""
    return (jax.sharding.AxisType.Auto,) * len(axes)


def dp_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
