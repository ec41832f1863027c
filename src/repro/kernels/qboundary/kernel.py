"""Pallas TPU kernel: the fused determinism boundary (paper §5.3).

Every embedding that enters the memory substrate crosses
float → Q-encode (round-half-away, saturate) → exact integer L2-normalize.
In serving this runs per request batch, so it is the substrate's hottest
entry point. The fusion keeps the whole pipeline in VMEM: one row tile is
read once from HBM and the raw fixed-point unit vector is written once.

Integer sqrt inside the kernel is the same 32-step digit recurrence as
fixedpoint.isqrt, but expressed with a fori_loop over VMEM-resident rows.

Tiling: grid over row blocks [BR, D]; D ≤ MAX_D so a row's wide accumulator
(int64 semantics emulated exactly: the squared-norm of a Q16.16-bounded row
fits 62 bits, and we carry it as two f32-free int32 limbs? No — inside the
kernel we use jnp int64 ops, which interpret mode executes exactly and which
Mosaic lowers to 32-bit pairs on TPU; the kernel only relies on exactness,
verified bit-for-bit against ref.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _qboundary_kernel(x_ref, out_ref, *, one: int, min_raw: int, max_raw: int,
                      unit_norm: bool):
    x = x_ref[...].astype(jnp.float32)            # [BR, D]
    # encode: round half away from zero, saturate
    scaled = x * one
    rounded = jnp.sign(scaled) * jnp.floor(jnp.abs(scaled) + 0.5)
    raw = jnp.clip(rounded, min_raw, max_raw).astype(jnp.int32)

    if unit_norm:
        wide = raw.astype(jnp.int64)
        sq = jnp.sum(wide * wide, axis=-1, keepdims=True)  # [BR, 1] ≤ 2^62

        def isqrt_body(i, carry):
            rem, res = carry
            bit = jnp.int64(1) << (62 - 2 * i)
            take = rem >= res + bit
            rem = jnp.where(take, rem - (res + bit), rem)
            res = jnp.where(take, (res >> 1) + bit, res >> 1)
            return rem, res

        _, norm = jax.lax.fori_loop(
            0, 32, isqrt_body, (sq, jnp.zeros_like(sq)))
        safe = jnp.where(norm == 0, jnp.ones_like(norm), norm)
        num = wide << 16
        # round-to-nearest integer division (half away from zero)
        q = jnp.abs(num) // safe
        rem = jnp.abs(num) - q * safe
        adjust = (2 * rem >= safe).astype(jnp.int64)
        signed = jnp.sign(num) * (q + adjust)
        raw = jnp.where(norm == 0, wide, signed).astype(jnp.int32)
        raw = jnp.clip(raw, min_raw, max_raw)

    out_ref[...] = raw


def qboundary_pallas(x: jax.Array, *, one: int, min_raw: int, max_raw: int,
                     unit_norm: bool = True, block_rows: int = 128,
                     interpret: bool = True) -> jax.Array:
    n, d = x.shape
    assert n % block_rows == 0, (n, block_rows)
    kern = lambda xr, orr: _qboundary_kernel(
        xr, orr, one=one, min_raw=min_raw, max_raw=max_raw,
        unit_norm=unit_norm)
    return pl.pallas_call(
        kern,
        grid=(n // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x)
