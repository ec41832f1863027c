"""jit'd public wrapper for qtopk: plane split, padding, the survivors' sort.

This is the blockwise half of the one exact select,
``core.search.topk_by_score``: on a TPU, for k <= 128 over rows of at
least a few blocks, it replaces the full two-key sort of each row with one
streaming pass of the kernel over the row's blocks and a sort of the k
survivors.
"""
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
    """int64 values → (hi int32, sign-biased lo int32); lex order preserved."""
    s = scores.astype(jnp.int64)
    hi = (s >> 32).astype(jnp.int32)
    lo_u = (s & 0xFFFFFFFF).astype(jnp.uint32) ^ jnp.uint32(_BIAS)
    return hi, lo_u.astype(jnp.int32)


def combine_planes(hi: jax.Array, lo: jax.Array) -> jax.Array:
    lo_u = (jax.lax.bitcast_convert_type(lo.astype(jnp.int32), jnp.uint32)
            ^ jnp.uint32(_BIAS)).astype(jnp.int64)
    return (hi.astype(jnp.int64) << 32) | lo_u


BLOCK_N = 1024  # scores per block along a row (fewer when the row is short)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.partial(jax.jit, static_argnames=("k",))
def qtopk(scores: jax.Array, keys: jax.Array, k: int
          ) -> Tuple[jax.Array, jax.Array]:
    """Deterministic k smallest (score, key) per row.

    scores [nq, n] int64 wide scores; keys [n] integer tie keys, unique;
    k <= n. Returns (scores [nq, k] int64, keys [nq, k] int64), sorted.
    Bit-identical to ref.qtopk_ref.
    """
    nq, n = scores.shape
    if k > n:
        raise ValueError(f"qtopk needs k <= n (got k={k}, n={n})")
    bq = min(128, _round_up(nq, 8))
    bn = min(BLOCK_N, _round_up(n, _kernel.LANES))
    hi, lo = split_planes(scores)
    key_hi, key_lo = split_planes(keys)

    # padding is all-I32_MAX: it never beats a member of the running set
    pq = _round_up(nq, bq) - nq
    pn = _round_up(n, bn) - n
    fill = _kernel.I32_MAX
    hi, lo = (jnp.pad(p, ((0, pq), (0, pn)), constant_values=fill)
              for p in (hi, lo))
    key_hi, key_lo = (jnp.pad(p, (0, pn), constant_values=fill)[None, :]
                      for p in (key_hi, key_lo))

    best = on_platform(
        functools.partial(_kernel.qtopk_pallas, k=k, block_q=bq,
                          block_n=bn), hi, lo, key_hi, key_lo)
    c_hi, c_lo, c_key_hi, c_key_lo = (c[:nq, :k] for c in best)
    # the k survivors of each row, in (score, key) order
    return jax.lax.sort((combine_planes(c_hi, c_lo),
                         combine_planes(c_key_hi, c_key_lo)),
                        num_keys=2, dimension=1)
