"""Exact wide-integer (128-bit) arithmetic from 32-bit limbs.

Realizes the paper's Table 2 "future" contracts: Q32.32 products need 128-bit
accumulation, which neither JAX nor TPU offer natively. We represent signed
128-bit values as four uint32 limbs (little-endian) and build
add/mul/accumulate from single-width ops with explicit carries — every step
is a native integer instruction, so the § 5.1 determinism argument extends
unchanged to the wide domain.

Used by fixedpoint.qdot_q32 (exact Q32.32 dot products) and validated against
Python bigints in tests/test_limbs.py. Throughput is ~10 int ops per MAC —
the paper's anticipated cost of the "enterprise" contract.

The second half builds the substrate's scoring dot product the same way:
exact int64 sums of products from int8 digit planes, the one integer matmul
a TPU runs natively (its compiler refuses an s64 ``dot``). ``exact_dot`` is
the XLA form and ``kernels/qgemm`` the Pallas form of one decomposition.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, List, Tuple

import jax
import jax.numpy as jnp

# a Python int, not a jnp scalar: importing the package must not create a
# device array (that would initialize a backend and claim the chip)
_MASK32 = 0xFFFFFFFF

# A wide value is a tuple of 4 uint32 arrays (lo → hi limbs), two's complement.
Wide = Tuple[jax.Array, jax.Array, jax.Array, jax.Array]


def from_int64(x: jax.Array) -> Wide:
    """Sign-extend int64 → 4-limb two's complement."""
    u = x.astype(jnp.uint64)
    lo = (u & _MASK32).astype(jnp.uint32)
    hi = ((u >> jnp.uint64(32)) & _MASK32).astype(jnp.uint32)
    sign = jnp.where(x < 0, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
    return (lo, hi, sign, sign)


def zeros_like_wide(x: jax.Array) -> Wide:
    z = jnp.zeros(x.shape, jnp.uint32)
    return (z, z, z, z)


def wide_add(a: Wide, b: Wide) -> Wide:
    """Limbwise add with carry propagation (mod 2^128, two's complement)."""
    out = []
    carry = jnp.zeros(a[0].shape, jnp.uint64)
    for i in range(4):
        s = a[i].astype(jnp.uint64) + b[i].astype(jnp.uint64) + carry
        out.append((s & _MASK32).astype(jnp.uint32))
        carry = s >> jnp.uint64(32)
    return tuple(out)


def wide_neg(a: Wide) -> Wide:
    inv = tuple((~x) for x in a)
    one = (jnp.ones(a[0].shape, jnp.uint32), jnp.zeros(a[0].shape, jnp.uint32),
           jnp.zeros(a[0].shape, jnp.uint32), jnp.zeros(a[0].shape, jnp.uint32))
    return wide_add(inv, one)


def mul_i64_i64(a: jax.Array, b: jax.Array) -> Wide:
    """Exact signed 64×64 → 128-bit product via 32-bit limb partials.

    |a|,|b| split into (lo, hi) uint32 limbs; four 32×32→64 partial products
    are accumulated with carries; the sign is applied by two's complement.
    """
    sign = (a < 0) ^ (b < 0)
    ua = jnp.abs(a).astype(jnp.uint64)
    ub = jnp.abs(b).astype(jnp.uint64)
    a0 = ua & _MASK32
    a1 = ua >> jnp.uint64(32)
    b0 = ub & _MASK32
    b1 = ub >> jnp.uint64(32)

    p00 = a0 * b0                     # ≤ 2^64-ish, exact in uint64
    p01 = a0 * b1
    p10 = a1 * b0
    p11 = a1 * b1

    # accumulate into limbs l0..l3 with carries
    l0 = p00 & _MASK32
    t1 = (p00 >> jnp.uint64(32)) + (p01 & _MASK32) + (p10 & _MASK32)
    l1 = t1 & _MASK32
    t2 = (t1 >> jnp.uint64(32)) + (p01 >> jnp.uint64(32)) \
        + (p10 >> jnp.uint64(32)) + (p11 & _MASK32)
    l2 = t2 & _MASK32
    l3 = (t2 >> jnp.uint64(32)) + (p11 >> jnp.uint64(32))
    mag = (l0.astype(jnp.uint32), l1.astype(jnp.uint32),
           l2.astype(jnp.uint32), (l3 & _MASK32).astype(jnp.uint32))
    neg = wide_neg(mag)
    return tuple(jnp.where(sign, n, m) for n, m in zip(neg, mag))


def wide_sum(w: Wide, axis: int = -1) -> Wide:
    """Order-invariant exact sum along an axis: per-limb uint64 partial sums
    with deferred carry propagation (each limb sum ≤ 2^32 · n < 2^64 for
    n < 2^32 elements)."""
    sums = [jnp.sum(x.astype(jnp.uint64), axis=axis) for x in w]
    out = []
    carry = jnp.zeros(sums[0].shape, jnp.uint64)
    for s in sums:
        t = s + carry
        out.append((t & _MASK32).astype(jnp.uint32))
        carry = t >> jnp.uint64(32)
    return tuple(out)


def to_float(w: Wide) -> jax.Array:
    """Approximate float64 view (for diagnostics; exactness lives in limbs)."""
    sign_bit = (w[3] >> jnp.uint32(31)) & jnp.uint32(1)
    # two's complement magnitude
    neg = wide_neg(w)
    limbs = [jnp.where(sign_bit == 1, n, p) for n, p in zip(neg, w)]
    val = jnp.zeros(w[0].shape, jnp.float64)
    for i, x in enumerate(limbs):
        val = val + x.astype(jnp.float64) * (2.0 ** (32 * i))
    return jnp.where(sign_bit == 1, -val, val)


def to_python_int(w) -> int:
    """Host-side exact conversion (scalar) for tests."""
    import numpy as np
    limbs = [int(np.asarray(x)) for x in w]
    u = sum(l << (32 * i) for i, l in enumerate(limbs))
    if u >= 1 << 127:
        u -= 1 << 128
    return u


# --------------------------------------------------------------------------- #
# Q32.32 operations built on limbs
# --------------------------------------------------------------------------- #


def qdot_q32_wide(a: jax.Array, b: jax.Array, axis: int = -1) -> Wide:
    """Exact Q32.32 dot product accumulated in 128 bits (Q(64) scale).

    a, b: int64 raw Q32.32 arrays. The result is the exact Σ aᵢ·bᵢ — wide,
    unshifted — monotone for ranking, order-invariant by construction.
    """
    prods = mul_i64_i64(a, b)
    return wide_sum(prods, axis=axis)


def q32_dot_to_q32(a: jax.Array, b: jax.Array, axis: int = -1) -> jax.Array:
    """Q32.32 dot renormalized back to Q32.32 (int64), saturating.

    Shift right by 32 = drop limb 0; saturate to int64 if the true value
    exceeds 64 bits (|limb3| must be pure sign extension of limb2's msb).
    """
    w = qdot_q32_wide(a, b, axis)
    l0, l1, l2, l3 = w
    val = (l1.astype(jnp.uint64)
           | (l2.astype(jnp.uint64) << jnp.uint64(32))).astype(jnp.int64)
    # overflow detection: l3 (and l2's sign) must match val's sign extension
    sign = (l2 >> jnp.uint32(31)) & jnp.uint32(1)
    expect_l3 = jnp.where(sign == 1, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
    ok = l3 == expect_l3
    maxv = jnp.int64(2**63 - 1)
    minv = jnp.int64(-(2**63))
    pos_overflow = (l3 >> jnp.uint32(31)) == 0
    return jnp.where(ok, val, jnp.where(pos_overflow, maxv, minv))


# --------------------------------------------------------------------------- #
# exact integer dot products from int8 digit planes
# --------------------------------------------------------------------------- #
#
# A w-byte integer x is written as w signed base-256 digits plus a constant:
#
#     x = sum_i t_i * 256^i + bias(w),   t_i in [-128, 127] (int8),
#
# with bias(w) = 0x80 in every byte but the top one (0 for int8). The digits
# are the bytes of x ^ bias, each read as a signed byte, so the split never
# overflows and covers the whole dtype range. For x = X + Cx, y = Y + Cy:
#
#     sum_k x_k y_k = sum_s 256^s P_s + Cy sum_k x_k + Cx sum_k y_k - D Cx Cy,
#     P_s = sum_{i+j=s} sum_k t_i,k u_j,k
#
# Each P_s is a sum of int8 x int8 products accumulated in int32: at most
# m = min(digits of x, digits of y) products of magnitude <= 2^14 per k, so
# |P_s| <= m * 2^14 * D, which fits int32 while m * D < 2^17 (D < 2^15 for
# two int32 operands). The combination runs in int64, whose wrap-around is
# arithmetic mod 2^64 like the int64 einsum it replaces: the two agree
# bit for bit on every input.

DIGIT_PRODUCT_BOUND = 1 << 17  # m * D must stay below this (see above)


def digit_bias(dtype) -> int:
    """The constant C with ``x == sum_i digits(x)[i] * 256**i + C``."""
    return sum(0x80 << (8 * i) for i in range(jnp.dtype(dtype).itemsize - 1))


def digits(x: jax.Array) -> List[jax.Array]:
    """Signed int8 digits of an integer array, least significant first."""
    n = jnp.dtype(x.dtype).itemsize
    if n == 1:
        return [x.astype(jnp.int8)]
    bits = 8 * n
    u = x ^ digit_bias(x.dtype)
    return [((u << (bits - 8 - 8 * i)) >> (bits - 8)).astype(jnp.int8)
            for i in range(n)]


def check_digit_bound(a_dtype, b_dtype, dim: int) -> None:
    """Raise unless the int32 digit planes of an ``[*, dim]`` dot are exact."""
    m = min(jnp.dtype(a_dtype).itemsize, jnp.dtype(b_dtype).itemsize)
    if m * dim >= DIGIT_PRODUCT_BOUND:
        raise ValueError(
            f"exact digit-plane dot needs {m} * dim < {DIGIT_PRODUCT_BOUND}, "
            f"got dim {dim}")


def digit_planes(a: jax.Array, b: jax.Array,
                 dot: Callable[[jax.Array, jax.Array], jax.Array]
                 ) -> List[jax.Array]:
    """The int32 planes P_s of ``a [m, D] . b [n, D]^T``; ``dot`` contracts
    two int8 operands into int32 (XLA's ``dot_general`` or the MXU inside a
    Pallas kernel)."""
    ta, tb = digits(a), digits(b)
    planes = []
    for s in range(len(ta) + len(tb) - 1):
        terms = [dot(ta[i], tb[s - i]) for i in range(len(ta))
                 if 0 <= s - i < len(tb)]
        planes.append(sum(terms[1:], terms[0]))
    return planes


def _wrap64(v: int) -> int:
    return ((v + (1 << 63)) % (1 << 64)) - (1 << 63)


def combine_planes(planes, a: jax.Array, b: jax.Array) -> jax.Array:
    """``sum_k a[q, k] * b[n, k]`` as int64 [m, n] from the planes of
    ``digit_planes(a, b, ...)``; ``a``/``b`` are the operands the planes were
    built from (zero columns included: they carry digits too)."""
    out = planes[0].astype(jnp.int64)
    for s in range(1, len(planes)):
        out = out + (planes[s].astype(jnp.int64) << (8 * s))
    ca, cb = digit_bias(a.dtype), digit_bias(b.dtype)
    if cb:
        out = out + _wrap64(cb) * jnp.sum(a.astype(jnp.int64), axis=-1)[:, None]
    if ca:
        out = out + _wrap64(ca) * jnp.sum(b.astype(jnp.int64), axis=-1)[None, :]
    if ca and cb:
        out = out - _wrap64(a.shape[-1] * ca * cb)
    return out


_int8_dot = partial(jax.lax.dot_general,
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.int32)


def exact_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """Exact ``a [m, D] . b [n, D]^T`` as int64 [m, n] for integer operands
    of any width, with XLA int8 dots only: bit-identical to
    ``einsum("qd,nd->qn", a.astype(int64), b.astype(int64))`` on every
    platform, including a TPU that has no s64 ``dot``."""
    check_digit_bound(a.dtype, b.dtype, a.shape[-1])
    return combine_planes(digit_planes(a, b, _int8_dot), a, b)
