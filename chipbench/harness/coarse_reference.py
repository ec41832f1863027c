"""Plain reference of the int8 compressed tier's two-stage search.

Nothing here imports ``repro``. It restates DESIGN.md §10 in numpy:

* the code table: per dimension, lo / hi = min / max over the live rows,
  mid = floor((lo + hi) / 2), dev = max(hi - mid, mid - lo), scale = 2^e
  with e the number of i in [0, 16) for which 2^i < ceil(dev / 127), and
  offset = mid rounded down to a multiple of scale; a live row's code is
  (raw - offset) / scale rounded to nearest, halves away from zero,
  clipped to +-127, and its norm is sum_j (code_j * scale_j)^2; a dead
  slot has all-zero codes and a zero norm;
* the query weights w_j = (q_j - offset_j) * scale_j, clipped to +-2^28;
* the coarse score of a live row, norm - 2 * sum_j w_j * code_j, exact;
* the candidates: the ``ef`` best live rows by (coarse score, slot);
* the answer: the k best candidates by (exact squared L2 on the raw
  integers, id), missing results (-1, INF).

It also gives the exact top-k over every live row, so a run can report
the recall of the route.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from harness import reference

INF = reference.INF
MAX_EXP = 16
W_BOUND = 1 << 28
CHUNK = 8192   # rows scored at a time: numpy's temporaries stay small


class Table(NamedTuple):
    codes: np.ndarray    # [capacity, d] int8
    offset: np.ndarray   # [d] int64
    scale: np.ndarray    # [d] int64
    norms: np.ndarray    # [capacity] int64


class Result(NamedTuple):
    ids: np.ndarray          # [nq, k] the route's answer
    scores: np.ndarray       # [nq, k] its exact wide scores
    table: Table
    exact_ids: np.ndarray    # [nq, k] exact k-NN over every live row


def table_params(lo: np.ndarray, hi: np.ndarray):
    """(offset, scale), int64 [d], from the per-dimension min and max of
    the live rows (both 0 where there are none)."""
    lo, hi = lo.astype(np.int64), hi.astype(np.int64)
    mid = (lo + hi) >> 1
    dev = np.maximum(hi - mid, mid - lo)
    need = -(-dev // 127)
    e = np.sum((np.int64(1) << np.arange(MAX_EXP, dtype=np.int64))[None, :]
               < need[:, None], axis=1).astype(np.int64)
    scale = np.int64(1) << e
    return (mid >> e) << e, scale


def encode(rows: np.ndarray, offset: np.ndarray, scale: np.ndarray):
    """(codes int8 [n, d], norms int64 [n]) of live rows."""
    delta = rows.astype(np.int64) - offset[None, :]
    q, rem = np.divmod(np.abs(delta), scale[None, :])
    c = np.sign(delta) * (q + (2 * rem >= scale[None, :]))
    c = np.clip(c, -127, 127)
    deq = c * scale[None, :]
    return c.astype(np.int8), np.einsum("ij,ij->i", deq, deq)


def query_weights(q_raw: np.ndarray, offset: np.ndarray, scale: np.ndarray
                  ) -> np.ndarray:
    w = (q_raw.astype(np.int64) - offset[None, :]) * scale[None, :]
    return np.clip(w, -W_BOUND, W_BOUND)


def _best(keys: np.ndarray, slots: np.ndarray, m: int) -> np.ndarray:
    """Indices of the m smallest (key, slot) pairs of one row, in order."""
    idx = np.arange(len(keys))
    if len(keys) > 4 * m:
        cut = np.partition(keys, m - 1)[m - 1]
        idx = np.nonzero(keys <= cut)[0]
    return idx[np.lexsort((slots[idx], keys[idx]))[:m]]


def _exact(q_raw, qq, rows):
    """Exact squared L2 [nq, n] of the queries against raw rows."""
    nn = np.einsum("ij,ij->i", rows.astype(np.int64), rows.astype(np.int64))
    return qq[:, None] - 2 * reference._dot_exact(q_raw, rows) + nn[None, :]


def search(n_blocks: int, rows_of, capacity: int, q_raw: np.ndarray,
           k: int, ef: int, workers: int = 0) -> Result:
    """The route's answer over the live rows that ``rows_of(b)`` gives for
    b in range(n_blocks): (slots [n], ids [n], raw rows [n, d]) of the
    live rows of block b. Blocks run on a few threads
    (``reference.parallel_map``)."""
    q_raw = np.asarray(q_raw)
    nq, d = q_raw.shape
    blocks = reference.parallel_map(rows_of, range(n_blocks), workers)
    live = [b for b in blocks if len(b[0])]
    if live:
        lo = np.min([r.min(axis=0) for _, _, r in live], axis=0)
        hi = np.max([r.max(axis=0) for _, _, r in live], axis=0)
    else:
        lo = hi = np.zeros(d, np.int64)
    offset, scale = table_params(lo, hi)
    w = query_weights(q_raw, offset, scale)
    qq = np.einsum("ij,ij->i", q_raw.astype(np.int64), q_raw.astype(np.int64))
    codes = np.zeros((capacity, d), np.int8)
    norms = np.zeros(capacity, np.int64)

    def score(block):
        """Per query, this block's ef best by (coarse, slot), carrying
        their exact scores, and its k best by (exact, id)."""
        slots, ids, rows = block
        cand = [[] for _ in range(nq)]
        top = [[] for _ in range(nq)]
        for a in range(0, len(slots), CHUNK):
            s, i, r = slots[a:a + CHUNK], ids[a:a + CHUNK], rows[a:a + CHUNK]
            c, n = encode(r, offset, scale)
            codes[s], norms[s] = c, n
            coarse = n[None, :] - 2 * reference._dot_exact(w, c)
            exact = _exact(q_raw, qq, r)
            for j in range(nq):
                b = _best(coarse[j], s, ef)
                cand[j].append((s[b], i[b], coarse[j, b], exact[j, b]))
                t = _best(exact[j], i, k)
                top[j].append((i[t], exact[j, t]))
        return ([_cut(c, ef) for c in cand],
                [_top(t, k) for t in top])

    parts = reference.parallel_map(score, live, workers)
    ids = np.full((nq, k), -1, np.int64)
    scores = np.full((nq, k), INF, np.int64)
    exact_ids = np.full((nq, k), -1, np.int64)
    for j in range(nq):
        if not parts:
            break
        _, i, _, exact = _cut([cand[j] for cand, _ in parts], ef)
        a = _best(exact, i, k)                   # the re-rank
        ids[j, :len(a)], scores[j, :len(a)] = i[a], exact[a]
        ei, _ = _top([top[j] for _, top in parts], k)
        exact_ids[j, :len(ei)] = ei
    return Result(ids, scores, Table(codes, offset, scale, norms), exact_ids)


def _cut(pools, ef: int):
    """The ef best (slot, id, coarse, exact) by (coarse, slot) of several
    candidate pools."""
    s, i, coarse, exact = (np.concatenate(x) for x in zip(*pools))
    b = _best(coarse, s, ef)
    return s[b], i[b], coarse[b], exact[b]


def _top(pools, k: int):
    """The k best (id, exact) by (exact, id) of several pools."""
    i, exact = (np.concatenate(x) for x in zip(*pools))
    b = _best(exact, i, k)
    return i[b], exact[b]


def recall(ids: np.ndarray, exact_ids: np.ndarray) -> float:
    """Share of the exact top-k ids (those that exist) found among the
    served ids of the same query."""
    found = total = 0
    for got, want in zip(ids, exact_ids):
        want = want[want >= 0]
        found += np.isin(want, got).sum()
        total += len(want)
    return found / total if total else 1.0
