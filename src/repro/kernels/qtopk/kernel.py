"""Pallas TPU kernel: deterministic k-smallest selection over wide scores.

Input scores are int64 conceptually, carried as two int32 planes:
    hi = s >> 32,  lo = (s & 0xFFFFFFFF) XOR 0x80000000  (sign-bias)
so that signed lexicographic (hi, lo) comparison equals int64 comparison —
again because the target TPU has no native int64 (DESIGN.md §2). Ties on
(hi, lo) are broken by the tie key (the caller's ids or arena slots,
unique along a row), an int64 carried as two int32 planes in the same way.
So every comparison here is lexicographic over four int32 planes, and no
two entries of a row compare equal: the selection is deterministic.

Tiling: grid (nq/BQ, n/BN), the second axis walked in order. Each row
block keeps its k best so far in the output tile, a [BQ, KP] running set
per plane (KP = k rounded up to the 128-lane width), which stays in VMEM
across the walk. It starts as distinct sentinels (I32_MAX scores, keys
I32_MAX - lane), which sort after every real entry. A grid step reads one [BQ, BN] block and:

1. finds each row's worst member of the running set (a max chain);
2. masks out every element that does not beat it; what is left per row is
   all the block can contribute, and its largest count over the rows, at
   most k, is how many passes the step makes;
3. each pass takes every row's best remaining element (a min chain over
   the four planes), retires it, and puts it in place of the row's worst
   member if it beats it.

With k <= n every row fills its set with real entries before it runs out
of elements that beat the worst: until then all real elements beat the
sentinels, in every row alike. Passes past a row's own count then only
propose masked elements (I32_MAX scores), which never beat a real member.
After the last block the running set holds each row's k best by
(score, key). Past the first blocks few elements beat the running worst,
so most steps make far fewer than k passes.
ops.py sorts the k survivors (a [nq, k] sort).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

I32_MAX = 2**31 - 1  # Python ints: folded into the kernel as immediates
I32_MIN = -2**31
LANES = 128


def _lex_less(a, b):
    """a < b, lexicographic over matching lists of int32 planes."""
    less = a[-1] < b[-1]
    for x, y in zip(a[-2::-1], b[-2::-1]):
        less = (x < y) | ((x == y) & less)
    return less


def _lex_extreme(planes, reduce, fill, on=None):
    """Per row, the lexicographic min or max (``reduce``) over the lanes in
    ``on`` (all lanes when None): its planes [BQ, 1] each, and the lanes
    that hold it (one, the entries of a row being distinct)."""
    best = []
    for p in planes:
        x = p if on is None else jnp.where(on, p, fill)
        m = reduce(x, axis=1, keepdims=True)
        on = x == m if on is None else on & (x == m)
        best.append(m)
    return best, on


def _qtopk_kernel(hi_ref, lo_ref, key_hi_ref, key_lo_ref, *set_refs, k: int):
    bq, kp = set_refs[0].shape
    bn = hi_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (bq, kp), 1)
    in_set = lane < k

    @pl.when(pl.program_id(1) == 0)
    def _():
        for ref in set_refs[:3]:
            ref[...] = jnp.full((bq, kp), I32_MAX, jnp.int32)
        set_refs[3][...] = I32_MAX - lane  # distinct sentinels

    key_hi = jnp.broadcast_to(key_hi_ref[...], (bq, bn))  # [1, BN] tie keys
    key_lo = jnp.broadcast_to(key_lo_ref[...], (bq, bn))
    worst, _ = _lex_extreme([r[...] for r in set_refs], jnp.max, I32_MIN,
                            in_set)
    beats = _lex_less([hi_ref[...], lo_ref[...], key_hi, key_lo], worst)
    hi = jnp.where(beats, hi_ref[...], I32_MAX)
    lo = jnp.where(beats, lo_ref[...], I32_MAX)
    passes = jnp.minimum(
        jnp.max(jnp.sum(beats.astype(jnp.int32), axis=1, keepdims=True)), k)

    def extract(_, carry):
        hi, lo, *members = carry
        worst, at_worst = _lex_extreme(members, jnp.max, I32_MIN, in_set)
        best, chosen = _lex_extreme([hi, lo, key_hi, key_lo], jnp.min,
                                    I32_MAX)
        hi = jnp.where(chosen, I32_MAX, hi)
        lo = jnp.where(chosen, I32_MAX, lo)
        put = at_worst & _lex_less(best, worst)
        return hi, lo, *(jnp.where(put, b, m) for b, m in zip(best, members))

    # loop carries stay int32: Mosaic does not carry i1 vectors in scf.for
    _, _, *members = jax.lax.fori_loop(
        0, passes, extract, (hi, lo, *(r[...] for r in set_refs)))
    for ref, m in zip(set_refs, members):
        ref[...] = m


def qtopk_pallas(
    hi: jax.Array,      # [nq, n] int32
    lo: jax.Array,      # [nq, n] int32 sign-biased
    key_hi: jax.Array,  # [1, n] int32 tie keys, high plane
    key_lo: jax.Array,  # [1, n] int32 tie keys, sign-biased low plane
    k: int,
    *,
    block_q: int,
    block_n: int,
    interpret: bool,
):
    """Each row's k best, unsorted: four int32 arrays [nq, KP], the first k
    columns the (hi, lo, key hi, key lo) planes.

    Traced with x64 off, like every kernel here: Mosaic takes 32-bit grid
    indices and loop counters only.
    """
    nq, n = hi.shape
    assert nq % block_q == 0 and n % block_n == 0 and k <= n
    kp = -(-k // LANES) * LANES
    row_spec = pl.BlockSpec((block_q, block_n), lambda i, j: (i, j))
    key_spec = pl.BlockSpec((1, block_n), lambda i, j: (0, j))
    set_spec = pl.BlockSpec((block_q, kp), lambda i, j: (i, 0))
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_qtopk_kernel, k=k),
            grid=(nq // block_q, n // block_n),
            in_specs=[row_spec, row_spec, key_spec, key_spec],
            out_specs=[set_spec] * 4,
            out_shape=[jax.ShapeDtypeStruct((nq, kp), jnp.int32)] * 4,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name="qtopk",
        )(hi, lo, key_hi, key_lo)
