"""Exact deterministic k-NN over the fixed-point arena.

The throughput-oriented counterpart of hnsw.py (DESIGN.md §2): scoring is an
exact integer matmul built from int8 digit planes (``limbs.exact_dot`` in
XLA, or the Pallas qgemm kernel when enabled) and selection is a (score, id)
lexicographic top-k, so results — including tie order — are bit-identical
everywhere.

Scores are *wide* (unshifted Q(2f)) integers: exact, monotone in the true
metric, never rounded before ranking.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import limbs
from repro.core.state import MemoryState

INF = 1 << 62  # a Python int: importing this module creates no device array

METRIC_L2 = "l2"
METRIC_DOT = "dot"


def score_block(queries_raw: jax.Array, db_raw: jax.Array, metric: str = METRIC_L2,
                use_kernel: bool = False) -> jax.Array:
    """Wide integer scores [nq, nd]; lower = better for both metrics
    (dot scores are negated so selection logic is uniform). Its ops carry
    the ``scan`` scope in the compiled program."""
    if metric not in (METRIC_DOT, METRIC_L2):
        raise ValueError(f"unknown metric {metric!r}")
    with jax.named_scope("scan"):
        if use_kernel:
            from repro.kernels.qgemm import ops as qgemm_ops
            wide_dot = qgemm_ops.qgemm(queries_raw, db_raw)
        else:
            wide_dot = limbs.exact_dot(queries_raw, db_raw)
        if metric == METRIC_DOT:
            return -wide_dot
        qq = jnp.sum(queries_raw.astype(jnp.int64) ** 2, axis=-1)  # [nq]
        nn = jnp.sum(db_raw.astype(jnp.int64) ** 2, axis=-1)  # [nd]
        return qq[:, None] - 2 * wide_dot + nn[None, :]


def topk_by_score(scores: jax.Array, ids: jax.Array, k: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """Deterministic top-k smallest scores with (score, id) tie-break.

    scores [nq, n] int64, ids [n] int64 and unique → (scores [nq,k],
    ids [nq,k]). Its ops carry the ``select`` scope.

    One exact select, two ways to compute it, chosen from what the code
    observes and never from a flag:

    - lowered for a TPU, with ``k <= 128`` and ``n`` of at least one of
      qtopk's blocks: ``kernels.qtopk``, one streaming pass over the
      row's blocks that keeps a running k best;
    - anywhere else (any other platform, a larger k, a row shorter than a
      block): one two-key ``lax.sort`` of the whole row.

    Both return the same bits (tests/test_query_engine.py). The rule is
    fixed per compiled program: the shapes are static and the platform is
    the one the computation is lowered for (``lax.platform_dependent``).
    """
    from repro.kernels.qtopk import ops as qtopk_ops
    with jax.named_scope("select"):
        if not blockwise_applies(scores.shape[1], k):
            return _sort_topk(scores, ids, k)
        return jax.lax.platform_dependent(
            scores, ids, tpu=partial(qtopk_ops.qtopk, k=k),
            default=partial(_sort_topk, k=k))


# qtopk pays a threshold pass over every block and up to k min passes more:
# about one a block once the running k best settle where the row's order is
# unrelated to the query, k in every block where scores fall along the row
# (its worst case). On a TPU v5e, 128 rows of 2^20 (PERF.md, Findings): the
# sort takes 529 ms at any k; qtopk 13.8 ms (k = 10) to 58.5 ms (k = 1024)
# in random order, and in falling order 29, 223, 483, 1,172 and 3,215 ms at
# k = 10, 128, 256, 512, 1024. One row of 2^20: sort 22.1 ms, qtopk 2.0 ms
# random, 9.0 (k = 10) and 90.7 ms (k = 128) falling. At 1,024 to 16,384
# rows both take 0.9-4.5 ms, qtopk ahead at k = 10 in either order.
_BLOCKWISE_MAX_K = 128


def blockwise_applies(n: int, k: int) -> bool:
    """Whether :func:`topk_by_score` selects blockwise on a TPU for a row
    of ``n`` scores and ``k`` results (static shapes, plain Python)."""
    from repro.kernels.qtopk import ops as qtopk_ops
    return k <= _BLOCKWISE_MAX_K and n >= qtopk_ops.BLOCK_N


def _sort_topk(scores: jax.Array, ids: jax.Array, k: int
               ) -> Tuple[jax.Array, jax.Array]:
    nq, n = scores.shape
    ids_b = jnp.broadcast_to(ids[None, :], (nq, n))
    s_sorted, i_sorted = jax.lax.sort((scores, ids_b), num_keys=2,
                                      dimension=1)
    return s_sorted[:, :k], i_sorted[:, :k]


@partial(jax.jit, static_argnames=("k", "metric", "use_kernel"))
def exact_search(state: MemoryState, queries_raw: jax.Array, k: int,
                 *, metric: str = METRIC_L2, use_kernel: bool = False
                 ) -> Tuple[jax.Array, jax.Array]:
    """k-NN over all live rows. Returns (ids [nq,k] int64, scores [nq,k]).

    Missing results (fewer than k live rows) are (-1, INF).
    ``use_kernel=True`` scores through the Pallas qgemm kernel instead of
    ``limbs.exact_dot``, bit-identical either way
    (tests/test_query_engine.py::test_kernel_parity). The select is
    :func:`topk_by_score` on both, which picks its path from the platform
    and the shapes.
    """
    scores = score_block(queries_raw, state.vectors, metric, use_kernel)
    with jax.named_scope("scan"):
        scores = jnp.where(state.valid[None, :], scores, INF)
    with jax.named_scope("select"):
        # tombstoned ids are -1; give each dead slot an id of its own past
        # 2^62, so the ids the select breaks ties on stay unique
        slots = jnp.arange(state.ids.shape[0], dtype=jnp.int64)
        ids = jnp.where(state.valid, state.ids, (jnp.int64(1) << 62) + slots)
    s, i = topk_by_score(scores, ids, k)
    found = s < INF
    return jnp.where(found, i, jnp.int64(-1)), jnp.where(found, s, INF)


def merge_candidates(scores: jax.Array, ids: jax.Array, k: int
                     ) -> Tuple[jax.Array, jax.Array]:
    """Top-k of a [..., m] candidate pool by (score, id) — the one combine
    every fan-in path shares (pairwise merge, shard all-gather). A pure
    integer two-key sort, so the result is invariant to any permutation of
    the pool — the order-invariance the distributed paths lean on."""
    # re-mask tombstones so (-1) padding never wins ties
    i_key = jnp.where(scores < INF, ids, jnp.int64(1) << 62)
    s_sorted, i_sorted = jax.lax.sort(
        (scores, i_key), num_keys=2, dimension=scores.ndim - 1)
    s_out = s_sorted[..., :k]
    i_out = i_sorted[..., :k]
    return s_out, jnp.where(s_out < INF, i_out, jnp.int64(-1))


@partial(jax.jit, static_argnames=("k", "ef_coarse", "metric", "use_kernel"))
def coarse_search(state: MemoryState, table, queries_raw: jax.Array, k: int,
                  *, ef_coarse: int, metric: str = METRIC_L2,
                  use_kernel: bool = False
                  ) -> Tuple[jax.Array, jax.Array]:
    """Compressed-tier k-NN: int8 coarse scan, exact Q16.16 re-rank.

    Two stages (DESIGN.md §10):

    1. *Coarse scan*: approximate integer scores over the int8 code table
       (the qgemm kernel when ``use_kernel``, ``limbs.exact_dot``
       otherwise — bit-identical either way), candidates = the
       ``ef_coarse`` best by (approx score, slot).
    2. *Re-rank*: the survivors re-scored with the exact wide Q16.16
       ``score_block`` arithmetic and combined by ``merge_candidates`` —
       the same (score, id) tie-break every other read path uses.

    The served scores are therefore exact: quantization error can only
    cost *recall* (a true neighbor missing from the candidate set), never
    score fidelity. Coverage implies bit-exactness: whenever the candidate
    set contains every live row — by construction when
    ``ef_coarse >= live_count`` — the result equals ``exact_search``'s
    bit-for-bit, which is the conformance suite's coarse-route contract.

    Returns (ids [nq, k] int64, scores [nq, k] int64); missing results
    are (-1, INF), exactly like ``exact_search``. Its ops carry one of
    three scopes: ``code_scan`` (query weights, the int8 dot, norms and
    the validity mask), ``select`` (the ``ef_coarse`` cut) and ``rerank``
    (the gather of [nq, ef, d] rows, the exact re-score and the merge).
    """
    from repro.core import codes as codes_lib    # lazy: codes is leaf-level

    n = state.vectors.shape[0]
    ef = min(ef_coarse, n)
    if ef < k:
        raise ValueError(
            f"coarse route needs ef_coarse >= k (got ef_coarse={ef_coarse}, "
            f"k={k}, capacity={n}): a candidate set of {ef} cannot "
            f"yield {k} results")

    with jax.named_scope("code_scan"):
        w = codes_lib.query_weights(queries_raw, table, metric)
        if use_kernel:
            from repro.kernels.qgemm import ops as qgemm_ops
            s = qgemm_ops.qgemm(w, table.codes)
        else:
            s = limbs.exact_dot(w, table.codes)
        if metric == METRIC_L2:
            approx = table.norms[None, :] - 2 * s
        else:
            approx = -s
        approx = jnp.where(state.valid[None, :], approx, INF)

    # candidate selection by (approx score, slot): slots are unique, so the
    # set is deterministic; the *served* tie order is fixed later by the
    # exact (score, id) merge, the same combine every fan-in path shares
    with jax.named_scope("select"):
        slots = jnp.arange(n, dtype=jnp.int64)
        s_c, slot_c = topk_by_score(approx, slots, ef)

    # exact re-rank: the same wide integer arithmetic as score_block over
    # the full arena, gathered per query (integer sums are order-invariant,
    # so the values are bit-identical to the full scan's)
    with jax.named_scope("rerank"):
        slot_i = slot_c.astype(jnp.int32)                   # [nq, ef]
        rows = state.vectors[slot_i]                        # [nq, ef, d]
        exact = jax.vmap(
            lambda q, db: score_block(q[None, :], db, metric)[0]
        )(queries_raw, rows)                                # [nq, ef]
        live = state.valid[slot_i] & (s_c < INF)
        exact = jnp.where(live, exact, INF)
        cand_ids = jnp.where(live, state.ids[slot_i], jnp.int64(1) << 62)
        s_out, i_out = merge_candidates(exact, cand_ids, k)
    return i_out, s_out


def merge_topk(scores_a: jax.Array, ids_a: jax.Array,
               scores_b: jax.Array, ids_b: jax.Array, k: int
               ) -> Tuple[jax.Array, jax.Array]:
    """Merge two sorted top-k lists into one — the deterministic combine step
    used by the sharded memory. Associative, commutative, and permutation-
    invariant (tests/test_query_engine.py proves all three), which is what
    makes shard fan-in order a non-event."""
    s = jnp.concatenate([scores_a, scores_b], axis=-1)
    i = jnp.concatenate([ids_a, ids_b], axis=-1)
    return merge_candidates(s, i, k)
