"""Pallas TPU kernel: deterministic k-smallest selection over wide scores.

Input scores are int64 conceptually, carried as two int32 planes:
    hi = s >> 32,  lo = (s & 0xFFFFFFFF) XOR 0x80000000  (sign-bias)
so that signed lexicographic (hi, lo) comparison equals int64 comparison —
again because the target TPU has no native int64 (DESIGN.md §2).

Selection is deterministic by construction: ties on (hi, lo) are broken by
the smallest int32 tie key (caller supplies arena positions or external ids).

Tiling: grid (nq/BQ, n/BN). Each grid step extracts its block's k best
candidates with k passes (a ``fori_loop``) of a three-stage vectorized min
reduction (hi → lo → key), and writes them into a [BQ, KP] output tile,
KP = k rounded up to the 128-lane width; the columns past k hold the
(I32_MAX, I32_MAX, I32_MAX) sentinel, which sorts after every real score.
ops.py merges the per-block candidates with one small sort. Everything stays
in registers/VMEM — no cross-lane sort network needed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

I32_MAX = 2**31 - 1  # Python int: folded into the kernel as an immediate
LANES = 128


def _qtopk_kernel(hi_ref, lo_ref, key_ref, out_hi_ref, out_lo_ref,
                  out_key_ref, *, k: int):
    hi = hi_ref[...]           # [BQ, BN] int32
    lo = lo_ref[...]           # [BQ, BN] int32 (sign-biased)
    bq, bn = hi.shape
    key = jnp.broadcast_to(key_ref[...], (bq, bn))  # [1, BN] tie keys
    kp = out_hi_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (bq, kp), 1)
    fill = jnp.full((bq, kp), I32_MAX, jnp.int32)

    def extract(t, carry):
        hi, lo, o_hi, o_lo, o_key = carry
        min_hi = jnp.min(hi, axis=1, keepdims=True)
        on_hi = hi == min_hi
        lo_m = jnp.where(on_hi, lo, I32_MAX)
        min_lo = jnp.min(lo_m, axis=1, keepdims=True)
        on_lo = on_hi & (lo_m == min_lo)
        key_m = jnp.where(on_lo, key, I32_MAX)
        min_key = jnp.min(key_m, axis=1, keepdims=True)
        chosen = key_m == min_key  # exactly one lane per row
        at = lane == t
        o_hi = jnp.where(at, min_hi, o_hi)
        o_lo = jnp.where(at, min_lo, o_lo)
        o_key = jnp.where(at, min_key, o_key)
        # retire the chosen lane
        hi = jnp.where(chosen, I32_MAX, hi)
        lo = jnp.where(chosen, I32_MAX, lo)
        return hi, lo, o_hi, o_lo, o_key

    _, _, o_hi, o_lo, o_key = jax.lax.fori_loop(
        0, k, extract, (hi, lo, fill, fill, fill))
    out_hi_ref[...] = o_hi
    out_lo_ref[...] = o_lo
    out_key_ref[...] = o_key


def qtopk_pallas(
    hi: jax.Array,   # [nq, n] int32
    lo: jax.Array,   # [nq, n] int32 sign-biased
    key: jax.Array,  # [1, n] int32 tie keys
    k: int,
    *,
    block_q: int,
    block_n: int,
    interpret: bool,
):
    """Per-block candidates: three int32 arrays [nq, n_blocks * KP].

    Traced with x64 off, like every kernel here: Mosaic takes 32-bit grid
    indices and loop counters only.
    """
    nq, n = hi.shape
    assert nq % block_q == 0 and n % block_n == 0 and k <= block_n
    kp = -(-k // LANES) * LANES
    n_blocks = n // block_n
    out_spec = pl.BlockSpec((block_q, kp), lambda i, j: (i, j))
    with jax.enable_x64(False):
        return pl.pallas_call(
            lambda *refs: _qtopk_kernel(*refs, k=k),
            grid=(nq // block_q, n_blocks),
            in_specs=[
                pl.BlockSpec((block_q, block_n), lambda i, j: (i, j)),
                pl.BlockSpec((block_q, block_n), lambda i, j: (i, j)),
                pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
            ],
            out_specs=[out_spec, out_spec, out_spec],
            out_shape=[jax.ShapeDtypeStruct((nq, n_blocks * kp),
                                            jnp.int32)] * 3,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
            name="qtopk",
        )(hi, lo, key)
