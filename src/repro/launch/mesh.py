"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (dry-run contract).
"""

from __future__ import annotations

import jax


def make_auto_mesh(shape, axes):
    """`jax.make_mesh` with Auto (compiler-sharded) axes; `jax.make_mesh`
    alone defaults to Explicit axes, which refuse sharding constraints."""
    axes = tuple(axes)
    return jax.make_mesh(
        tuple(shape), axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod; two pods for the multi-pod dry run."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_host_mesh(n_devices: int | None = None, model: int = 1):
    """Small mesh over this host's devices (tests / CPU demos)."""
    n = n_devices or len(jax.devices())
    assert n % model == 0
    return make_auto_mesh((n // model, model), ("data", "model"))
