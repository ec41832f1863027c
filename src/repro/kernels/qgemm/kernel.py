"""Pallas TPU kernel: exact integer scoring matmul (paper §5.1, hot spot).

The paper's dot products accumulate in i64. A TPU has no native int64, and
its matrix unit multiplies int8 (or bf16), not int32. So each operand tile is
split in VMEM into signed int8 digits (``core/limbs.py``: ``x = sum_i t_i
256^i + bias``), every digit pair goes through the MXU as an int8 x int8 ->
int32 matmul, and the products of equal weight 256^s are summed into one
int32 plane P_s. The kernel writes the planes; ``ops.py`` combines them in
int64 outside the kernel, together with the bias terms, exactly as
``limbs.exact_dot`` does in XLA — one decomposition, two backends.

Exactness: every plane is a sum of int8 products, each at most 2^14 in
magnitude, with at most min(digits) products per contracted element, so it
fits int32 while ``min(digits) * D < 2^17`` (``limbs.check_digit_bound``;
D <= 8192 for two int32 operands). The same bound holds for every partial
sum across the K grid axis.

One kernel serves both scans: int32 x int32 Q16.16 rows (the exact scan,
4x4 digits, 7 planes) and int32 query weights x int8 codes (the coarse
scan, 4x1 digits, 4 planes — the database operand streams as int8).

Tiling: grid (nq/BQ, nn/BN, D/BK). Planes are plane-major, [P, nq, nn], so
an output tile [P, BQ, BN] keeps BN on the lanes (a trailing plane axis of
3 or 4 would pad to 128 lanes in VMEM). The tile accumulates across the BK
grid axis ('arbitrary' semantics).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import limbs

_mxu_dot = functools.partial(
    jax.lax.dot_general,
    dimension_numbers=(((1,), (1,)), ((), ())),  # contract BK, no batch
    preferred_element_type=jnp.int32,
)


def n_planes(a_dtype, b_dtype) -> int:
    return jnp.dtype(a_dtype).itemsize + jnp.dtype(b_dtype).itemsize - 1


def _digit_matmul_kernel(a_ref, b_ref, out_ref):
    """One (BQ, BN) tile of every plane, accumulated across the K axis."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    planes = limbs.digit_planes(a_ref[...], b_ref[...], _mxu_dot)
    for s, plane in enumerate(planes):
        out_ref[s] += plane


def digit_planes_pallas(
    a: jax.Array,  # [nq, D] integer, at most 4 bytes
    b: jax.Array,  # [nn, D] integer, at most 4 bytes
    *,
    block_q: int,
    block_n: int,
    block_k: int,
    interpret: bool,
) -> jax.Array:
    """The int32 digit planes [P, nq, nn] of ``a . b^T``.

    Shapes must be multiples of the block sizes (ops.py pads). The kernel is
    a 32-bit program, so it is traced with x64 off: under the package's x64
    mode its grid indices would otherwise be int64, which Mosaic refuses.
    """
    nq, d = a.shape
    nn, d2 = b.shape
    assert d == d2, (d, d2)
    assert nq % block_q == 0 and nn % block_n == 0 and d % block_k == 0
    p = n_planes(a.dtype, b.dtype)
    with jax.enable_x64(False):
        return pl.pallas_call(
            _digit_matmul_kernel,
            grid=(nq // block_q, nn // block_n, d // block_k),
            in_specs=[
                pl.BlockSpec((block_q, block_k), lambda i, j, k: (i, k)),
                pl.BlockSpec((block_n, block_k), lambda i, j, k: (j, k)),
            ],
            out_specs=pl.BlockSpec((p, block_q, block_n),
                                   lambda i, j, k: (0, i, j)),
            out_shape=jax.ShapeDtypeStruct((p, nq, nn), jnp.int32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(a, b)
