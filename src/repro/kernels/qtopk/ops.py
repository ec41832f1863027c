"""jit'd public wrapper for qtopk: plane split, padding, final candidate merge."""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import on_platform
from repro.kernels.qtopk import kernel as _kernel

# plain int, not a jnp scalar: a module-level jnp constant would become a
# leaked tracer when this module is first imported inside a jit trace
# (core.search lazily imports us from within jitted exact_search)
_BIAS = 0x80000000


def split_planes(scores: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """int64 scores → (hi int32, sign-biased lo int32); lex order preserved."""
    s = scores.astype(jnp.int64)
    hi = (s >> 32).astype(jnp.int32)
    lo_u = (s & 0xFFFFFFFF).astype(jnp.uint32) ^ jnp.uint32(_BIAS)
    return hi, lo_u.astype(jnp.int32)


def combine_planes(hi: jax.Array, lo: jax.Array) -> jax.Array:
    lo_u = (jax.lax.bitcast_convert_type(lo.astype(jnp.int32), jnp.uint32)
            ^ jnp.uint32(_BIAS)).astype(jnp.int64)
    return (hi.astype(jnp.int64) << 32) | lo_u


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.partial(jax.jit, static_argnames=("k",))
def qtopk(scores: jax.Array, keys: jax.Array, k: int
          ) -> Tuple[jax.Array, jax.Array]:
    """Deterministic k smallest (score, key) per row.

    scores [nq, n] int64 wide scores; keys [n] int32 tie keys (unique).
    Returns (scores [nq, k] int64, keys [nq, k] int32), sorted.
    Bit-identical to ref.qtopk_ref.
    """
    nq, n = scores.shape
    bq = min(128, _round_up(nq, 8))
    bn = min(1024, _round_up(n, _kernel.LANES))
    hi, lo = split_planes(scores)

    pq = _round_up(nq, bq) - nq
    pn = _round_up(n, bn) - n
    fill = _kernel.I32_MAX
    hi = jnp.pad(hi, ((0, pq), (0, pn)), constant_values=fill)
    lo = jnp.pad(lo, ((0, pq), (0, pn)), constant_values=fill)
    keys_p = jnp.pad(keys.astype(jnp.int32), (0, pn),
                     constant_values=fill)[None, :]

    kk = min(k, bn)
    cands = on_platform(
        functools.partial(_kernel.qtopk_pallas, k=kk, block_q=bq,
                          block_n=bn), hi, lo, keys_p)
    # drop each block's lane padding past its kk candidates
    n_blocks = hi.shape[1] // bn
    c_hi, c_lo, c_key = (
        c[:nq].reshape(nq, n_blocks, -1)[:, :, :kk].reshape(nq, -1)
        for c in cands)
    # final merge over the per-block candidates (small): exact int64 sort
    s, i = jax.lax.sort((combine_planes(c_hi, c_lo), c_key), num_keys=2,
                        dimension=1)
    return s[:, :k], i[:, :k]
