"""Production mesh construction (function, not module constant — importing
this module never touches jax device state)."""

from __future__ import annotations

import jax

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh"]


def make_mesh(shape: tuple, axes: tuple, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``.

    The logical-axis layer (parallel/sharding.py ``constrain``) places
    arrays with ``with_sharding_constraint``, which only accepts Auto axes;
    ``jax.make_mesh`` defaults to Explicit axes on current JAX."""
    return jax.make_mesh(
        shape, axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(shape),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is
    pure data parallelism across the slower inter-pod (DCN-class) links."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over the first ``data * model`` devices (tests / CPU
    examples / one-chip serving)."""
    devs = jax.devices()
    assert data * model <= len(devs), (data, model, len(devs))
    return make_mesh((data, model), ("data", "model"), devices=devs[: data * model])
