"""Deterministic compressed vector tier: int8 codes over Q16.16 rows.

The exact arena stores one int32 Q16.16 raw value per (row, dim). At scale
that costs twice: bytes held AND bytes streamed per exact-route scan. This
module adds a compressed tier in the MonaVec direction (PAPERS.md) without
giving up the substrate's core property: every byte of it is a *pure integer
function of the live rows*, so the code table is replay-invariant state, not
a cache — the same live content produces the same codes on every platform,
every layout, every replay.

Per-dimension integer scalar quantization (DESIGN.md §10):

    offset_j = ((lo_j + hi_j) >> 1 >> e_j) << e_j      (multiple of scale_j)
    scale_j  = 2^e_j,  e_j = smallest e with 127 * 2^e >= dev_j
    code_ij  = clip(round_nearest((raw_ij - offset_j) / scale_j), -127, 127)

with lo/hi the per-dim min/max over live rows and dev_j the max deviation
from the midpoint. Everything is shifts, integer compares and the
round-to-nearest integer division from ``core/fixedpoint.py`` — bit-exact
everywhere. Dead rows encode as all-zero codes with zero norms, so the
table's bytes are themselves layout-hashable.

Why powers of two: params only change when a per-dim extreme moves far
enough to cross a power-of-two bucket, so ``refresh`` (the incremental
maintenance rule ``bulk_apply`` callers use) almost always re-encodes only
the touched rows; when params do drift it falls back to a full rebuild that
is bit-identical to ``build`` by construction (tests/test_codes.py proves
``refresh == build`` over randomized six-opcode logs).

Coarse scoring (``search.coarse_search``) ranks by an int32-weighted dot against the
codes; re-ranking the survivors with the exact wide Q16.16 scores restores
bit-exactness whenever the candidate set covers the exact top-k — in
particular, ``ef_coarse >= live_count`` makes the served answer equal
``exact_search``'s hash regardless of quantization error (the
coverage-implies-bit-exact contract the conformance suite pins).

Range analysis: boundary-normalized rows satisfy |raw| <= 2^16, so
dev <= 2^17, e <= 11, scale <= 2^11, and a query weight
|w_j| = |(q_j - offset_j) * scale_j| <= 2^28 = ``W_BOUND``. Clipping to it
keeps every weight an int32, the operand width of the coarse scan's exact
int8 digit-plane dot (``limbs.exact_dot``, kernels/qgemm).
"""
from __future__ import annotations

import dataclasses
import io
import struct
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fixedpoint as fp
from repro.core import hashing
from repro.core.state import MemoryState

# smallest e with 127 * 2^e >= dev, searched over e in [0, MAX_EXP)
MAX_EXP = 16
# |query weight| bound for boundary-normalized inputs (keeps weights int32)
W_BOUND = 1 << 28

METRIC_L2 = "l2"
METRIC_DOT = "dot"


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CodeTable:
    """The compressed tier. Invariant: ``table == build(state)`` — a pure
    function of the live rows, maintained incrementally by ``refresh``."""
    codes: jax.Array    # [capacity, dim] int8; dead rows all-zero
    offset: jax.Array   # [dim] int32, a multiple of scale
    scale: jax.Array    # [dim] int32, a power of two >= 1
    norms: jax.Array    # [capacity] int64: sum_j (codes*scale)^2; dead rows 0


# --------------------------------------------------------------------------- #
# params + encoding: integer-only, pure in the live rows
# --------------------------------------------------------------------------- #


def code_params(vectors: jax.Array, valid: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """Per-dim (offset int32, scale int32) from the live rows only.

    Pure in the live *multiset*: any layout/permutation of the same live
    content produces the same params (min/max are order-invariant), which
    is what keeps sharded and flat coarse tiers comparable.
    """
    v = vectors.astype(jnp.int32)
    live = valid[:, None]
    big = jnp.int32(2**31 - 1)
    lo = jnp.min(jnp.where(live, v, big), axis=0)
    hi = jnp.max(jnp.where(live, v, -big), axis=0)
    has = jnp.any(valid)
    lo = jnp.where(has, lo, jnp.int32(0))
    hi = jnp.where(has, hi, jnp.int32(0))
    # midpoint in int64: lo+hi can overflow int32 at the contract extremes
    mid = ((lo.astype(jnp.int64) + hi.astype(jnp.int64)) >> 1).astype(jnp.int32)
    dev = jnp.maximum(hi - mid, mid - lo)                  # >= 0
    need = (dev + 126) // 127                              # ceil(dev / 127)
    powers = jnp.left_shift(jnp.int32(1), jnp.arange(MAX_EXP, dtype=jnp.int32))
    e = jnp.sum((powers[None, :] < need[:, None]).astype(jnp.int32), axis=1)
    scale = jnp.left_shift(jnp.int32(1), e).astype(jnp.int32)
    # bucket the offset to a multiple of scale: extremes must shift the
    # midpoint by >= scale before params change at all — the stability
    # that makes refresh() incremental in practice
    offset = jnp.left_shift(jnp.right_shift(mid, e), e).astype(jnp.int32)
    return offset, scale


def encode_rows(vectors: jax.Array, valid: jax.Array,
                offset: jax.Array, scale: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """(codes int8 [n, dim], norms int64 [n]) for rows under fixed params.

    Element-local: code_ij depends only on (raw_ij, valid_i, offset_j,
    scale_j) — the fact that makes row-sliced refresh bit-equal to a full
    rebuild. Rounding is the round-half-away-from-zero integer division
    every fixed-point op in this repo uses.
    """
    v = vectors.astype(jnp.int64)
    delta = v - offset.astype(jnp.int64)[None, :]
    c = fp._int_div_round_to_nearest(delta, scale.astype(jnp.int64)[None, :])
    c = jnp.clip(c, -127, 127)
    c = jnp.where(valid[:, None], c, 0).astype(jnp.int8)
    deq = c.astype(jnp.int64) * scale.astype(jnp.int64)[None, :]
    norms = jnp.where(valid, jnp.sum(deq * deq, axis=-1), jnp.int64(0))
    return c, norms


@jax.jit
def build(state: MemoryState) -> CodeTable:
    """The reference constructor: the whole table from the live rows."""
    offset, scale = code_params(state.vectors, state.valid)
    c, norms = encode_rows(state.vectors, state.valid, offset, scale)
    return CodeTable(codes=c, offset=offset, scale=scale, norms=norms)


def refresh(table: CodeTable, state: MemoryState,
            touched_slots: np.ndarray) -> CodeTable:
    """Incremental maintenance: bit-identical to ``build(state)`` given
    ``touched_slots`` covers every slot whose (vector, valid) changed.

    Params are recomputed (cheap: one masked min/max) and compared; while
    they hold steady — the common case, thanks to power-of-two bucketing —
    only the touched rows re-encode. A param drift (a new per-dim extreme
    crossed a bucket) re-encodes everything, which is exactly ``build``.
    """
    offset, scale = code_params(state.vectors, state.valid)
    if (np.any(np.asarray(offset) != np.asarray(table.offset))
            or np.any(np.asarray(scale) != np.asarray(table.scale))):
        return build(state)
    t = np.asarray(touched_slots, np.int32)
    if t.size == 0:
        return table
    ti = jnp.asarray(t)
    c_sub, n_sub = encode_rows(state.vectors[ti], state.valid[ti],
                               table.offset, table.scale)
    return CodeTable(codes=table.codes.at[ti].set(c_sub),
                     offset=table.offset, scale=table.scale,
                     norms=table.norms.at[ti].set(n_sub))


def diff_slots(prev: MemoryState, cur: MemoryState) -> np.ndarray:
    """Slots whose (vector, valid) changed between two states — the touched
    set a generic log application must refresh. Host-side; used by
    ``apply_with_codes`` so arbitrary six-opcode logs maintain the table."""
    pv = np.asarray(prev.vectors)
    cv = np.asarray(cur.vectors)
    changed = np.any(pv != cv, axis=-1)
    changed |= np.asarray(prev.valid) != np.asarray(cur.valid)
    return np.nonzero(changed)[0].astype(np.int32)


def apply_with_codes(state: MemoryState, table: CodeTable, log,
                     *, ef_construction: int = 32
                     ) -> Tuple[MemoryState, CodeTable]:
    """``machine.bulk_apply`` plus table maintenance in one step — the
    write-path pairing that keeps ``table == build(state)`` an invariant
    across INSERT/DELETE/upsert (tests/test_codes.py replays randomized
    logs through this and checks the invariant bit-for-bit)."""
    from repro.core import machine  # lazy: machine must not depend on us
    new_state = machine.bulk_apply(state, log, ef_construction=ef_construction)
    return new_state, refresh(table, new_state, diff_slots(state, new_state))


# --------------------------------------------------------------------------- #
# query-side weights for the coarse scan
# --------------------------------------------------------------------------- #


def query_weights(queries_raw: jax.Array, table: CodeTable, metric: str
                  ) -> jax.Array:
    """int32 weights w [nq, dim] such that ranking by the integer dot
    ``S_i = sum_j w_j * codes_ij`` (plus the stored row norms for L2)
    orders rows by their metric against the *dequantized* vectors:

      l2 : ||q - (offset + c*scale)||^2 = const - 2*S_i + norms_i,
           w_j = (q_j - offset_j) * scale_j
      dot: -<q, offset + c*scale>      = const - S_i,
           w_j = q_j * scale_j

    Computed in int64 then clipped to +-W_BOUND so every weight is an int32
    (boundary-normalized inputs never reach the clip).
    """
    q = queries_raw.astype(jnp.int64)
    s = table.scale.astype(jnp.int64)[None, :]
    if metric == METRIC_L2:
        w = (q - table.offset.astype(jnp.int64)[None, :]) * s
    elif metric == METRIC_DOT:
        w = q * s
    else:
        raise ValueError(f"unknown metric {metric!r}")
    return jnp.clip(w, -W_BOUND, W_BOUND).astype(jnp.int32)


def table_hash(table: CodeTable) -> int:
    """Platform-invariant hash of the table — must equal the hash of
    ``build(state)`` on every holder of the same state (audit artifact)."""
    return hashing.hash_pytree(table)


# --------------------------------------------------------------------------- #
# durability: the table rides the chunked v2 snapshot format
# --------------------------------------------------------------------------- #

MAGIC_CODES = b"VLRQ"
_FORMAT_VERSION = 1
_U64 = (1 << 64) - 1
# fixed leaf order + dtypes: the manifest is self-describing but the
# restore refuses anything that isn't exactly a CodeTable
_LEAVES = (("codes", np.int8), ("offset", np.int32),
           ("scale", np.int32), ("norms", np.int64))


def snapshot_table_v2(table: CodeTable, cursor: int, store, *,
                      chunk_size: int = 8192) -> Tuple[bytes, dict]:
    """Write the table's chunks into a ``snapshot.ChunkStore`` and return
    (manifest bytes, stats) — the same content-addressed manifest shape as
    ``snapshot.snapshot_v2``, so repeated checkpoints of a slowly-changing
    table cost only the dirty chunks (param-stable refreshes dirty only
    the touched rows' chunks)."""
    from repro.core import snapshot as snap
    store.reset_stats()
    buf = io.BytesIO()
    buf.write(MAGIC_CODES)
    buf.write(struct.pack("<I", _FORMAT_VERSION))
    buf.write(struct.pack("<Q", int(cursor) & _U64))
    buf.write(struct.pack("<I", chunk_size))
    buf.write(struct.pack("<I", len(_LEAVES)))
    total = 0
    for name, dtype in _LEAVES:
        arr = np.asarray(getattr(table, name), dtype=dtype)
        payload = arr.astype(arr.dtype.newbyteorder("<"), copy=False).tobytes()
        total += len(payload)
        snap._write_str(buf, name)
        buf.write(struct.pack("<I", arr.ndim))
        for d in arr.shape:
            buf.write(struct.pack("<Q", d))
        keys = []
        for off in range(0, max(len(payload), 1), chunk_size):
            key, _ = store.put(payload[off:off + chunk_size])
            keys.append(key)
        buf.write(struct.pack("<Q", len(payload)))
        buf.write(struct.pack("<I", len(keys)))
        for key in keys:
            buf.write(struct.pack("<Q", key))
    buf.write(struct.pack("<Q", table_hash(table)))
    stats = {"chunks": store.puts, "chunks_written": store.writes,
             "bytes_written": store.bytes_written, "bytes_total": total,
             "manifest_bytes": buf.tell()}
    return buf.getvalue(), stats


def restore_table_v2(data: bytes, store) -> Tuple[CodeTable, int]:
    """Reassemble a table manifest against its chunk store; every chunk's
    content hash and the whole-table hash are verified. Returns
    (table, cursor)."""
    from repro.core import snapshot as snap
    buf = io.BytesIO(data)
    if buf.read(4) != MAGIC_CODES:
        raise ValueError("not a Valori code-table manifest")
    (ver,) = struct.unpack("<I", buf.read(4))
    if ver != _FORMAT_VERSION:
        raise ValueError(f"unsupported code-table format {ver}")
    (cursor,) = struct.unpack("<Q", buf.read(8))
    buf.read(4)  # chunk_size: recorded for tooling; lengths self-describe
    (n_leaves,) = struct.unpack("<I", buf.read(4))
    if n_leaves != len(_LEAVES):
        raise ValueError(f"code-table manifest has {n_leaves} leaves")
    arrays = {}
    for name, dtype in _LEAVES:
        got = snap._read_str(buf)
        if got != name:
            raise ValueError(f"leaf {got!r} where {name!r} expected")
        (ndim,) = struct.unpack("<I", buf.read(4))
        shape = tuple(struct.unpack("<Q", buf.read(8))[0]
                      for _ in range(ndim))
        (nbytes,) = struct.unpack("<Q", buf.read(8))
        (n_chunks,) = struct.unpack("<I", buf.read(4))
        parts = [store.get(struct.unpack("<Q", buf.read(8))[0])
                 for _ in range(n_chunks)]
        payload = b"".join(parts)
        if len(payload) != nbytes:
            raise ValueError(f"leaf {name}: got {len(payload)} bytes, "
                             f"manifest says {nbytes}")
        arr = np.frombuffer(payload, dtype=np.dtype(dtype).newbyteorder("<"))
        arrays[name] = jnp.asarray(arr.astype(dtype).reshape(shape))
    (stored_hash,) = struct.unpack("<Q", buf.read(8))
    table = CodeTable(**arrays)
    actual = table_hash(table)
    if actual != stored_hash:
        raise ValueError(f"code-table hash mismatch: stored "
                         f"{stored_hash:#x}, got {actual:#x}")
    return table, cursor


def table_manifest_cursor(data: bytes) -> int:
    if data[:4] != MAGIC_CODES:
        raise ValueError("not a Valori code-table manifest")
    (cursor,) = struct.unpack("<Q", data[8:16])
    return cursor


def table_manifest_chunk_keys(data: bytes) -> list:
    """All chunk keys a code-table manifest references (retention sweeps)."""
    from repro.core import snapshot as snap
    buf = io.BytesIO(data)
    if buf.read(4) != MAGIC_CODES:
        raise ValueError("not a Valori code-table manifest")
    buf.read(16)  # version, cursor, chunk_size
    (n_leaves,) = struct.unpack("<I", buf.read(4))
    keys = []
    for _ in range(n_leaves):
        snap._read_str(buf)
        (ndim,) = struct.unpack("<I", buf.read(4))
        buf.read(8 * ndim + 8)
        (n_chunks,) = struct.unpack("<I", buf.read(4))
        for _ in range(n_chunks):
            (key,) = struct.unpack("<Q", buf.read(8))
            keys.append(key)
    return keys
