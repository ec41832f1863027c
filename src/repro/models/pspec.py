"""Mesh-aware activation sharding constraints.

Constraints apply only when the ambient (set_mesh) mesh defines the axes and
the dimension divides — so the same model code runs unsharded smoke tests,
host meshes, and the 512-chip production mesh unchanged.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import PartitionSpec as P


def _mesh():
    """The ambient (``jax.set_mesh``) mesh, or None when there is none."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh.axis_names else None


def dp_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_divides(n: int) -> bool:
    """True iff the ambient mesh has a `model` axis that divides n."""
    mesh = _mesh()
    return (mesh is not None and "model" in mesh.axis_names
            and n % mesh.shape["model"] == 0)


def constrain(x: jax.Array, *dim_roles: Optional[str]) -> jax.Array:
    """dim_roles per axis: 'batch' | 'model' | None.

    'batch' → the DP axes (if the dim divides their product);
    'model' → TP axis (if divisible); None → replicated.
    """
    mesh = _mesh()
    if mesh is None:
        return x
    spec = []
    for dim, role in zip(x.shape, dim_roles):
        if role == "batch":
            axes = dp_axes(mesh)
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            spec.append(axes if (axes and dim % size == 0) else None)
        elif role == "model":
            ok = "model" in mesh.axis_names and dim % mesh.shape["model"] == 0
            spec.append("model" if ok else None)
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))
