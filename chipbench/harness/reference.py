"""Plain references, written from the semantics and not from the program.

Nothing here imports ``repro``. Each function restates one guarantee of
the substrate in numpy:

* ``boundary``: Q16.16 encode (float32, round half away from zero,
  saturating) and exact integer L2 normalization;
* ``TopK``: the k nearest live rows by exact squared L2 on the raw
  integers, ties broken by id;
* ``Hnsw``: the deterministic HNSW insert that the bulk ingest path runs
  (id-hash levels, entry fixed at the first node, beam and prune ordered by
  (distance, slot));
* ``read_wal``: the records of a WAL directory, parsed from the byte
  layout of docs/wal-format.md.
"""
from __future__ import annotations

import os
import pathlib
import struct
from concurrent.futures import ThreadPoolExecutor

import numpy as np

INF = 1 << 62
M64 = (1 << 64) - 1


# --------------------------------------------------------------------------- #
# boundary
# --------------------------------------------------------------------------- #

def _isqrt(x: np.ndarray) -> np.ndarray:
    """Floor square root of non-negative int64 values, exact."""
    s = np.floor(np.sqrt(x.astype(np.float64))).astype(np.int64)
    for _ in range(2):
        s = np.where((s + 1) * (s + 1) <= x, s + 1, s)
        s = np.where(s * s > x, s - 1, s)
    return s


def boundary(x: np.ndarray, int_bits: int = 15, frac_bits: int = 16
             ) -> np.ndarray:
    """float32 rows -> unit-norm raw fixed-point rows (int32 for Q16.16,
    int16 for Q8.8).

    Each component is round(v * 2^f / isqrt(sum v^2)), halves away from
    zero, with v the saturated, rounded encoding. The quotient is taken in
    float64 and is exact: the numerator is below 2^52 (|v| < 2^31), so the
    float64 error stays below 1/(2 * norm), the least distance of a
    non-half quotient from a half, and an exact half is representable."""
    dtype = np.int16 if 1 + int_bits + frac_bits <= 16 else np.int32
    max_raw = (1 << (int_bits + frac_bits)) - 1
    min_raw = -(1 << (int_bits + frac_bits))
    hi = np.float32(max_raw)
    if int(hi) > max_raw:
        hi = np.nextafter(hi, np.float32(0), dtype=np.float32)
    lo = np.float32(min_raw)
    scaled = np.asarray(x, np.float32) * np.float32(1 << frac_bits)
    rounded = np.copysign(np.floor(np.abs(scaled) + np.float32(0.5)), scaled)
    wide = np.clip(rounded, lo, hi).astype(np.int64)
    norm = _isqrt(np.einsum("ij,ij->i", wide, wide))[:, None]
    t = np.abs(wide).astype(np.float64) * float(1 << frac_bits) \
        / np.where(norm == 0, 1, norm)
    q = np.floor(t)
    q += (t - q) >= 0.5
    out = np.where(norm == 0, wide, np.copysign(q, wide).astype(np.int64))
    return np.clip(out, min_raw, max_raw).astype(dtype)


def boundary_rows(x: np.ndarray, chunk: int = 1024, **kw) -> np.ndarray:
    """``boundary`` a chunk of rows at a time: numpy's temporaries then
    stay small enough to be reused instead of faulted in afresh."""
    if len(x) == 0:
        return boundary(x, **kw)
    return np.concatenate([boundary(x[a:a + chunk], **kw)
                           for a in range(0, len(x), chunk)])


def parallel_map(fn, items, workers: int = 0) -> list:
    """``fn`` over ``items`` on a few threads (numpy and the device fetch
    release the interpreter lock)."""
    workers = workers or min(8, os.cpu_count() or 1)
    with ThreadPoolExecutor(workers) as pool:
        return list(pool.map(fn, items))


# --------------------------------------------------------------------------- #
# exact k-NN
# --------------------------------------------------------------------------- #

def _dot_exact(q: np.ndarray, v: np.ndarray) -> np.ndarray:
    """Exact int64 q @ v.T. Float64 matrix products are exact here: each
    product is an integer of at most 2^32 and each sum of d of them stays
    below 2^53, so no partial sum rounds, whatever the summation order."""
    bound = int(np.abs(q).max(initial=0)) * int(np.abs(v).max(initial=0))
    if bound * q.shape[1] < (1 << 53):
        return (q.astype(np.float64) @ v.astype(np.float64).T).astype(
            np.int64)
    return q.astype(np.int64) @ v.astype(np.int64).T


def _smallest_k(scores: np.ndarray, ids: np.ndarray, k: int):
    """The k smallest (score, id) pairs of one row, in order."""
    if scores.shape[0] > 4 * k:
        cut = np.partition(scores, k - 1)[k - 1]
        keep = scores <= cut
        scores, ids = scores[keep], ids[keep]
    order = np.lexsort((ids, scores))[:k]
    return scores[order], ids[order]


class TopK:
    """Exact top-k of several queries over row blocks: ``block`` scores one
    block (thread-safe), ``merge`` folds a block's best into the total."""

    def __init__(self, q_raw: np.ndarray, k: int, precision: str = "exact"):
        if precision not in ("exact", "float32"):
            raise ValueError(f"unknown precision {precision!r}")
        self.q = np.asarray(q_raw)
        self.k = k
        self.precision = precision
        self.qq = np.sum(self.q.astype(np.int64) ** 2, axis=1)
        self.best_s = [np.zeros(0, np.int64) for _ in self.q]
        self.best_i = [np.zeros(0, np.int64) for _ in self.q]

    def block(self, rows: np.ndarray, ids: np.ndarray) -> list:
        """[(scores [<=k], ids [<=k])] per query over live rows [n, d]
        with external ids [n]."""
        if self.precision == "exact":
            nn = np.einsum("ij,ij->i", rows.astype(np.int64),
                           rows.astype(np.int64))
            scores = self.qq[:, None] - 2 * _dot_exact(self.q, rows) \
                + nn[None, :]
        else:
            # the lower-precision control: the same metric in float32
            qf, rf = self.q.astype(np.float32), rows.astype(np.float32)
            s32 = (np.sum(qf * qf, axis=1)[:, None] - 2 * (qf @ rf.T)
                   + np.sum(rf * rf, axis=1)[None, :])
            scores = s32.astype(np.int64)
        return [_smallest_k(row, ids, self.k) for row in scores]

    def merge(self, best: list) -> None:
        for j, (s, i) in enumerate(best):
            s = np.concatenate([self.best_s[j], s])
            i = np.concatenate([self.best_i[j], i])
            order = np.lexsort((i, s))[:self.k]
            self.best_s[j], self.best_i[j] = s[order], i[order]

    def add(self, rows: np.ndarray, ids: np.ndarray) -> None:
        self.merge(self.block(rows, ids))

    def result(self):
        """(ids [S, k], scores [S, k]); missing results are (-1, INF)."""
        ids = np.full((len(self.q), self.k), -1, np.int64)
        sc = np.full((len(self.q), self.k), INF, np.int64)
        for j in range(len(self.q)):
            n = len(self.best_s[j])
            ids[j, :n], sc[j, :n] = self.best_i[j], self.best_s[j]
        return ids, sc


# --------------------------------------------------------------------------- #
# deterministic HNSW insert
# --------------------------------------------------------------------------- #

def _splitmix64(x: int) -> int:
    z = (x + 0x9E3779B97F4A7C15) & M64
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & M64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & M64
    return z ^ (z >> 31)


def level_of_id(ext_id: int, levels: int) -> int:
    """Trailing one bits of the id's SplitMix64 hash, capped at
    levels - 1: P(level >= j) = 2^-j."""
    h = _splitmix64(ext_id & M64)
    tz = 0
    while tz < levels - 1 and (h >> tz) & 1:
        tz += 1
    return tz


class Hnsw:
    """The graph that inserting rows in slot order builds.

    Rows are inserted in the order they were written (slot 0, 1, ...).
    ``m`` = degree // 2 edges are made per level; a candidate's list keeps
    its ``degree`` nearest by (distance, slot). A beam search at a level
    expands the nearest unexpanded candidate up to 2 * ef + 8 times.
    """

    def __init__(self, capacity: int, vectors: np.ndarray, ids: np.ndarray,
                 *, levels: int = 4, degree: int = 16, ef: int = 32):
        self.cap, self.levels, self.degree, self.ef = capacity, levels, \
            degree, ef
        self.m = degree // 2
        self.v = np.asarray(vectors, np.int64)
        self.ids = np.asarray(ids, np.int64)
        self.nbrs = np.full((levels, len(self.v), degree), -1, np.int64)
        self.level = np.full(len(self.v), -1, np.int64)
        self.entry = -1

    def _dist(self, q: np.ndarray, slots) -> np.ndarray:
        slots = np.asarray(slots)
        ok = slots >= 0
        d = self.v[np.where(ok, slots, 0)] - q
        return np.where(ok, np.sum(d * d, axis=-1), INF)

    def _greedy(self, q, lvl: int, cur: int) -> int:
        cur_d = int(self._dist(q, [cur])[0])
        for _ in range(self.cap):
            row = self.nbrs[lvl, cur]
            nd = self._dist(q, row)
            b = int(np.argmin(nd))
            bd, bs = int(nd[b]), int(row[b])
            if not (bd < cur_d or (bd == cur_d and bs < cur)):
                break
            cur, cur_d = bs, bd
        return cur

    def _beam(self, q, entry: int, lvl: int):
        ef = self.ef
        beam = [(int(self._dist(q, [entry])[0]), entry, False)]
        seen = {entry}
        for _ in range(2 * ef + 8):
            pick = next((j for j, (d, _, done) in enumerate(beam)
                         if not done and d < INF), None)
            if pick is None:
                break
            d, s, _ = beam[pick]
            beam[pick] = (d, s, True)
            row = [int(x) for x in self.nbrs[lvl, s] if x >= 0]
            fresh = [x for x in row if x not in seen]
            if not fresh:
                continue
            seen.update(row)
            nd = self._dist(q, fresh)
            beam = sorted(beam + [(int(a), b, False)
                                  for a, b in zip(nd, fresh)])[:ef]
        return [(d, s) for d, s, _ in beam if d < INF]

    def _connect(self, lvl: int, new: int, cands) -> None:
        fwd = [s for _, s in cands[:self.m]]
        self.nbrs[lvl, new] = fwd + [-1] * (self.degree - len(fwd))
        updates = []
        for _, c in cands[:self.m]:
            if c == new:
                continue
            cur = [int(x) for x in self.nbrs[lvl, c] if x >= 0]
            pairs = sorted(zip(self._dist(self.v[c], cur + [new]).tolist(),
                               cur + [new]))[:self.degree]
            kept = [s for _, s in pairs]
            updates.append((c, kept + [-1] * (self.degree - len(kept))))
        for c, kept in updates:
            self.nbrs[lvl, c] = kept

    def insert(self, new: int) -> None:
        q = self.v[new]
        raw = level_of_id(int(self.ids[new]), self.levels)
        if self.entry < 0:
            self.entry, self.level[new] = new, raw
            return
        top = int(self.level[self.entry])
        node = min(raw, top)
        self.level[new] = node
        cur = self.entry
        for lvl in range(top, node, -1):
            cur = self._greedy(q, lvl, cur)
        for lvl in range(node, -1, -1):
            cands = [(d, s) for d, s in self._beam(q, cur, lvl) if s != new]
            self._connect(lvl, new, cands)
            if cands:
                cur = cands[0][1]


# --------------------------------------------------------------------------- #
# WAL records
# --------------------------------------------------------------------------- #

_FNV_OFFSET, _FNV_PRIME = 0xCBF29CE484222325, 0x100000001B3
_INSERT, _NOP_RUN = 1, 0xFFFFFFFE


def _fnv1a(data: bytes) -> int:
    h = _FNV_OFFSET
    for b in data:
        h = ((h ^ b) * _FNV_PRIME) & M64
    return h


def read_wal(wal_dir) -> list:
    """[(opcode, arg0, vec bytes or b""), ...] over the segments in order.

    Checks each segment header's magic and chain word and that segments
    continue one another; a record's own chain word is skipped, not
    verified (its digest is the program's bulk hash)."""
    records = []
    for path in sorted(pathlib.Path(wal_dir).glob("seg_*.wal")):
        data = path.read_bytes()
        if data[:4] != b"VWSG":
            raise ValueError(f"{path.name}: bad magic")
        _, dim, itemsize = struct.unpack_from("<III", data, 4)
        base_t, = struct.unpack_from("<Q", data, 16)
        n, = struct.unpack_from("<I", data, 24)
        end = 28 + n
        chain, = struct.unpack_from("<Q", data, end)
        if chain != _fnv1a(data[:end]):
            raise ValueError(f"{path.name}: header chain word does not verify")
        if base_t != len(records):
            raise ValueError(f"{path.name}: starts at t={base_t}, "
                             f"expected {len(records)}")
        off = end + 8
        while off + 36 <= len(data):
            op, a0 = struct.unpack_from("<Iq", data, off)
            off += 28
            vec = b""
            if op == _INSERT:
                vec = data[off:off + dim * itemsize]
                off += dim * itemsize
            off += 8
            if off > len(data):
                break  # torn tail: not durable
            if op == _NOP_RUN:
                records.extend([(0, 0, b"")] * a0)
            else:
                records.append((op, a0, vec))
    return records
