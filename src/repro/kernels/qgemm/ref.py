"""Test oracle for the qgemm kernel: direct i64 accumulation (paper §5.1)."""
from __future__ import annotations

import jax.numpy as jnp


def qgemm_ref(queries: jnp.ndarray, database: jnp.ndarray) -> jnp.ndarray:
    """Exact wide dot scores [nq, nn] int64 — the paper's i64-accumulator rule."""
    return jnp.einsum(
        "qd,nd->qn", queries.astype(jnp.int64), database.astype(jnp.int64)
    )
