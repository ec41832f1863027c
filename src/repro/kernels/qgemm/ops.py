"""jit'd public wrapper for the qgemm kernel: padding, range checks, combine."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import limbs
from repro.kernels import on_platform
from repro.kernels.qgemm import kernel as _kernel

# the largest dim any caller scores (the planner's coarse-route cap agrees)
MAX_DIM = 1 << 13


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pick_blocks(nq: int, nn: int, d: int):
    """(BQ, BN, BK, padded D). BQ and BN are multiples of 32 (the int8
    sublane tile), D pads to the 128-lane width, and BK is the largest
    multiple of 128 up to 1024 that divides the padded D (768 stays one
    step, 8192 takes eight)."""
    bq = min(128, _round_up(nq, 32))
    bn = min(256, _round_up(nn, 32))
    dp = _round_up(d, 128)
    bk = max(b for b in range(128, min(dp, 1024) + 1, 128) if dp % b == 0)
    return bq, bn, bk, dp


@jax.jit
def qgemm(a: jax.Array, b: jax.Array) -> jax.Array:
    """Exact ``a [nq, D] . b [nn, D]^T`` as int64 [nq, nn] from the kernel's
    digit planes — bit-identical to ``limbs.exact_dot`` and to the int64
    einsum oracle (``ref.qgemm_ref``) on every input of at most 4 bytes."""
    nq, d = a.shape
    nn = b.shape[0]
    if d > MAX_DIM:
        raise ValueError(f"qgemm supports dim <= {MAX_DIM}, got {d}")
    if max(a.dtype.itemsize, b.dtype.itemsize) > 4:
        raise ValueError(f"qgemm takes operands of at most 4 bytes, got "
                         f"{a.dtype} and {b.dtype}")
    bq, bn, bk, dp = _pick_blocks(nq, nn, d)
    limbs.check_digit_bound(a.dtype, b.dtype, dp)
    ap = jnp.pad(a, ((0, _round_up(nq, bq) - nq), (0, dp - d)))
    bp = jnp.pad(b, ((0, _round_up(nn, bn) - nn), (0, dp - d)))
    planes = on_platform(
        functools.partial(_kernel.digit_planes_pallas, block_q=bq,
                          block_n=bn, block_k=bk), ap, bp)
    # zero-padded columns carry digits too: combine over the padded width
    return limbs.combine_planes(
        [planes[s, :nq, :nn] for s in range(planes.shape[0])],
        ap[:nq], bp[:nn])
