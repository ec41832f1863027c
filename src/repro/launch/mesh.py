"""Production mesh definitions (TPU v5e pod slices).

A FUNCTION, not a module constant, so importing never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def make_host_mesh(model: int | None = None, data: int | None = None):
    """A small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if model is None:
        model = 1
        for m in (8, 4, 2):
            if n % m == 0 and n >= m:
                model = m
                break
    data = data or (n // model)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def batch_axes(mesh) -> tuple:
    """Mesh axes that carry pure data parallelism."""
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)
