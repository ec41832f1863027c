"""Pure-jnp oracle for qtopk: full (score, key) lexicographic sort."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def qtopk_ref(scores: jax.Array, keys: jax.Array, k: int
              ) -> Tuple[jax.Array, jax.Array]:
    """k smallest (score int64, key int64) pairs per row, sorted lexicographically.

    scores [nq, n]; keys [n] (tie-break). Returns (scores [nq,k], keys [nq,k]).
    """
    nq, n = scores.shape
    keys_b = jnp.broadcast_to(keys[None, :].astype(jnp.int64), (nq, n))
    s, i = jax.lax.sort((scores, keys_b), num_keys=2, dimension=1)
    return s[:, :k], i[:, :k]
