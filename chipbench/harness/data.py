"""Seeded inputs: stored rows, query vectors and arrival gaps.

Rows are clustered float32 vectors (ANN-Benchmarks style: seeded centres,
per-row noise). Every value is drawn as an integer and scaled by a power
of two, so each float is exact: a centre is j/16 with |j| <= 16 and the
noise is m/2^22 with |m| <= 2^21, and their sum has fewer than 24
significant bits. No rounding happens anywhere in the generator, so any
compiled program that draws the same integers (threefry is integer
arithmetic) gives the same floats bit for bit. The plain references rely
on that: they regenerate rows block by block after the window instead of
keeping the program's copy. Quantizing x * 2^16 leaves a fraction in
steps of 1/64, so the boundary's round-half-away rule is exercised,
halves included.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

CENTRES = 64
_ROWS, _QUERIES, _CENTRES = 0, 1, 2


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, 64-bit seeds included."""
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _centres(key, dim: int) -> jax.Array:
    k = jax.random.fold_in(key, _CENTRES)
    return jax.random.randint(k, (CENTRES, dim), -16, 17, jnp.int32).astype(
        jnp.float32) * jnp.float32(1 / 16)


def _clustered(key, centres, n: int, dim: int) -> jax.Array:
    ka, kn = jax.random.split(key)
    assign = jax.random.randint(ka, (n,), 0, CENTRES, jnp.int32)
    noise = jax.random.randint(kn, (n, dim), -(1 << 21), 1 << 21,
                               jnp.int32).astype(
        jnp.float32) * jnp.float32(2.0 ** -22)
    return centres[assign] + noise


def rows_block(key, block: jax.Array, n: int, dim: int) -> jax.Array:
    """Stored rows [block * n, (block + 1) * n) as float32 [n, dim]."""
    k = jax.random.fold_in(jax.random.fold_in(key, _ROWS), block)
    return _clustered(k, _centres(key, dim), n, dim)


rows_block_jit = jax.jit(rows_block, static_argnames=("n", "dim"))


@partial(jax.jit, static_argnames=("n", "nq", "dim"))
def queries(key, n: int, nq: int, dim: int) -> jax.Array:
    """n requests of nq query vectors each, float32 [n, nq, dim], near the
    same centres as the stored rows."""
    k = jax.random.fold_in(key, _QUERIES)
    return _clustered(k, _centres(key, dim), n * nq, dim).reshape(n, nq, dim)


def poisson_gaps(rate_per_s: float, n: int, schedule_seed: int
                 ) -> np.ndarray:
    """n inter-arrival gaps of a Poisson stream at ``rate_per_s``: the
    exponential distribution's n quantile midpoints, shuffled by
    ``schedule_seed``.

    A traffic mix fixes ``schedule_seed``, so every run seed sees the same
    arrivals in the same order and draws only its queries and rows. At
    4/5 of capacity the order of the gaps alone moves the 95th percentile
    by a third between seeds, which measures arrival luck, not the
    program."""
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate_per_s
    return np.random.default_rng(schedule_seed).permutation(gaps)
