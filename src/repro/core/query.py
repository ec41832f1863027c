"""Batched deterministic query engine — the read-path twin of bulk_apply.

``machine.bulk_apply`` made the write path fast under the equivalence
contract (DESIGN.md §3). This module is the same move for the read path
(DESIGN.md §4): every batched / planned / sharded search below is
bit-identical to the per-query reference loop over ``hnsw.hnsw_search`` /
``search.exact_search`` — same ids, same wide scores, same tie order.

Three layers:

* ``batched_hnsw_search`` — B queries through the HNSW graph under one jit:
  a ``vmap`` over the fixed-shape beam state in ``hnsw.py``. Every ranking
  decision inside the beam is the same ``(dist, slot)`` lexicographic
  integer compare, and a vmapped ``while_loop`` freezes each lane's carry
  once its own predicate goes false, so lane b computes exactly the values
  the single-query call computes.
* ``exact route`` — ``search.exact_search``, optionally kernel-backed
  (Pallas qgemm scoring + qtopk selection) with the pure-jnp path as both
  fallback and oracle.
* ``plan_query`` / ``execute_plan`` / ``sharded_query`` — a planner that
  picks exact-scan vs HNSW per request from *static host facts only*
  (live count, k, ef), so the route itself is replayable, and fans out
  across shards via ``distributed.py``, merging with the order-invariant
  ``merge_topk`` combine.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import hnsw as hnsw_lib
from repro.core import search
from repro.core.state import MemoryState

INF = search.INF

ROUTE_EXACT = "exact"
ROUTE_HNSW = "hnsw"
ROUTE_COARSE = "coarse"


# --------------------------------------------------------------------------- #
# batched HNSW: vmap over the fixed-shape beam
# --------------------------------------------------------------------------- #


@partial(jax.jit, static_argnames=("k", "ef"))
def batched_hnsw_search(state: MemoryState, queries_raw: jax.Array, k: int,
                        *, ef: int = 64
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """ANN for B queries under one jit: (ids [B,k], dists [B,k], slots [B,k]).

    Bit-identical to calling ``hnsw.hnsw_search`` once per row
    (tests/test_query_engine.py asserts this on randomized logs).
    """
    return jax.vmap(
        lambda q: hnsw_lib.hnsw_search(state, q, k, ef=ef)
    )(queries_raw)


# --------------------------------------------------------------------------- #
# query planner: static facts in, deterministic route out
# --------------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class QueryPlan:
    """A replayable routing decision. Pure data: two plans built from the
    same facts compare equal, and the facts are recorded for audit."""
    route: str               # ROUTE_EXACT | ROUTE_HNSW | ROUTE_COARSE
    k: int
    ef: int
    use_kernel: bool         # exact/coarse routes (HNSW gathers row-wise)
    live_count: int          # the fact the decision was made from
    reason: str
    # who answered: "primary", or "replica:<i>" when the serve engine's
    # read pool served this request at a proven cursor (DESIGN.md §9) —
    # recorded so replica-served answers are replayable audit artifacts
    # like every other planner choice
    served_by: str = "primary"
    # compressed-tier facts (DESIGN.md §10): candidate-set size for the
    # coarse route (0 = tier disabled) and the vector dimension the
    # decision was made from — recorded so a coarse answer is replayable
    # from (plan, log cursor, query) like every other route
    ef_coarse: int = 0
    dim: int = 0
    # churn audit (DESIGN.md §11): how many re-link passes the serving
    # graph has absorbed when this plan was made. A replayed plan is then
    # checkable against the engine's re-link schedule — the same log prefix
    # plus the same graph generation must reproduce this answer bit-exactly
    graph_gen: int = 0


def plan_query(live_count: int, k: int, ef: int, *,
               use_kernel: bool = False, exact_threshold: int = 1024,
               route: str = "auto", ef_coarse: int = 0,
               dim: int = 0, graph_gen: int = 0) -> QueryPlan:
    """Pick exact-scan vs HNSW vs the compressed coarse tier from static
    facts — host ints only, so the same request against the same memory
    plans identically everywhere.

    Rules (DESIGN.md §4, §10), first match wins:
      1. forced route (``route != "auto"``) — operator override (forcing
         "hnsw" with k > ef, or "coarse" with k > ef_coarse, raises: the
         candidate set cannot return k results);
      2. ``k > ef`` → exact (an ef-beam cannot return k results);
      3. ``live_count <= exact_threshold`` → exact (the scan is cheap and
         exact; no reason to pay graph traversal);
      4. ``ef >= live_count`` → exact (the beam would cover the whole
         corpus anyway — a scan does the same work without the gathers);
      5. ``0 < k <= ef_coarse`` and ``4 * ef_coarse <= 3 * live_count``
         and ``dim <= 8192`` → coarse: the int8 scan streams 1/4 the
         bytes of the exact scan, so bytes beat exact once the re-rank
         pool is under 3/4 of the corpus (the break-even of
         live*dim*1 + ef*dim*4 vs live*dim*4); the dim cap is the qgemm
         kernel's int32 exactness bound. The model counts bytes read
         only: not the cut, which at ``ef_coarse`` > 128 is one sort of
         the whole [nq, live] row (528.6 ms for [128, 2^20] on a TPU
         v5e), nor the int32 digit planes both scans write (DESIGN.md
         §10; the costs measured at 1M x 768: PERF.md §6);
      6. otherwise → HNSW — including under churn. Deletes no longer
         demote the graph to exact scan: entry-point repair keeps every
         layout's entry live and the scheduled re-link pass (recorded in
         ``graph_gen``) sweeps tombstoned waypoints, so ANN stays the
         production route on churny traffic (DESIGN.md §11).
    """
    def mk(r, why):
        return QueryPlan(route=r, k=k, ef=ef, use_kernel=use_kernel,
                         live_count=live_count, reason=why,
                         ef_coarse=ef_coarse, dim=dim, graph_gen=graph_gen)

    if route != "auto":
        if route not in (ROUTE_EXACT, ROUTE_HNSW, ROUTE_COARSE):
            raise ValueError(f"unknown route {route!r}")
        if route == ROUTE_HNSW and k > ef:
            # an ef-beam physically cannot return k results; truncating
            # silently would hand the caller [B, ef]-shaped arrays
            raise ValueError(f"route='hnsw' needs k <= ef, got k={k} ef={ef}")
        if route == ROUTE_COARSE and k > ef_coarse:
            raise ValueError(f"route='coarse' needs k <= ef_coarse, "
                             f"got k={k} ef_coarse={ef_coarse}")
        return mk(route, "forced")
    if k > ef:
        return mk(ROUTE_EXACT, f"k={k} > ef={ef}")
    if live_count <= exact_threshold:
        return mk(ROUTE_EXACT, f"live={live_count} <= {exact_threshold}")
    if ef >= live_count:
        return mk(ROUTE_EXACT, f"ef={ef} >= live={live_count}")
    if (0 < k <= ef_coarse and 4 * ef_coarse <= 3 * live_count
            and dim <= 8192):
        return mk(ROUTE_COARSE,
                  f"int8 scan + {ef_coarse}-rerank beats exact bytes at "
                  f"live={live_count}, dim={dim}")
    return mk(ROUTE_HNSW, f"live={live_count}, k={k}, ef={ef}")


def execute_plan(state: MemoryState, queries_raw: jax.Array, k: int,
                 plan: QueryPlan, *, metric: str = search.METRIC_L2,
                 codes=None) -> Tuple[jax.Array, jax.Array]:
    """Run the planned route: (ids [B,k] int64, wide scores [B,k] int64).

    All routes score with the same wide integer metric, so the planner can
    switch routes without changing a returned score's meaning. The coarse
    route takes the caller's maintained ``codes.CodeTable`` when given,
    and otherwise derives it from the state on the spot — the table is a
    pure function of the live rows, so both are bit-identical (the
    maintained table is a cost optimization, never a semantic one).
    """
    if plan.route == ROUTE_EXACT:
        return search.exact_search(state, queries_raw, k, metric=metric,
                                   use_kernel=plan.use_kernel)
    if plan.route == ROUTE_COARSE:
        from repro.core import codes as codes_lib  # lazy: leaf-level module
        table = codes if codes is not None else codes_lib.build(state)
        return search.coarse_search(state, table, queries_raw, k,
                                    ef_coarse=plan.ef_coarse, metric=metric,
                                    use_kernel=plan.use_kernel)
    ids, dists, _ = batched_hnsw_search(state, queries_raw, k, ef=plan.ef)
    return ids, dists


# --------------------------------------------------------------------------- #
# shard fan-out
# --------------------------------------------------------------------------- #


def sharded_query(mesh, axis: str, state: MemoryState, queries_raw: jax.Array,
                  k: int, plan: QueryPlan, *,
                  metric: str = search.METRIC_L2,
                  query_axis: Optional[str] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Fan the planned query out across shards (``distributed.py``).

    Every shard runs the planned route locally; candidates combine with the
    order-invariant integer ``merge_topk`` sort, so the answer is
    independent of shard count — and, for the exact route, bit-identical
    to the single-kernel scan.
    """
    from repro.core import distributed  # local import: avoids cycle at init

    if plan.route == ROUTE_EXACT:
        return distributed.distributed_search(
            mesh, axis, state, queries_raw, k, metric=metric,
            use_kernel=plan.use_kernel, query_axis=query_axis)
    return distributed.distributed_hnsw_search(
        mesh, axis, state, queries_raw, k, ef=plan.ef, query_axis=query_axis)


def sharded_host_query(state: MemoryState, n_shards: int,
                       queries_raw: jax.Array, k: int, plan: QueryPlan, *,
                       metric: str = search.METRIC_L2,
                       tables=None) -> Tuple[jax.Array, jax.Array]:
    """The planned route fanned out over a *host-side* sharded-layout state
    (no mesh): per-shard execution through the ``shard_wal`` twins, one
    order-invariant merge. This is the serve engine's sharded read path.

    Exact route: bit-identical to the single-kernel scan on the same live
    content (the merge is permutation- and layout-invariant). HNSW route:
    deterministic for a fixed shard count; bit-identical to the flat graph
    whenever every per-shard beam is exhaustive (``plan.ef`` >= per-shard
    live count) — the conformance regime DESIGN.md §7 pins. Coarse route:
    per-shard int8 scan + exact re-rank; bit-identical to flat exact
    whenever every shard's candidate set covers its slice
    (``plan.ef_coarse`` >= per-shard live count — DESIGN.md §10).
    ``tables`` optionally carries the engine's maintained per-shard code
    tables; absent, each shard derives its table from its slice.
    """
    from repro.core import shard_wal  # lazy: shard_wal imports us lazily

    if plan.route == ROUTE_EXACT:
        return shard_wal.exact_search_sharded(
            state, n_shards, queries_raw, k, metric=metric,
            use_kernel=plan.use_kernel)
    if plan.route == ROUTE_COARSE:
        return shard_wal.coarse_search_sharded(
            state, n_shards, queries_raw, k, ef_coarse=plan.ef_coarse,
            metric=metric, use_kernel=plan.use_kernel, tables=tables)
    return shard_wal.hnsw_search_sharded(state, n_shards, queries_raw, k,
                                         ef=plan.ef)


# --------------------------------------------------------------------------- #
# retrieval-set hash: the read path's audit artifact
# --------------------------------------------------------------------------- #


def retrieval_hash(ids: jax.Array, scores: jax.Array) -> int:
    """Platform-invariant hash of a retrieval set — the read-path analogue
    of the state hash: two runs agree iff every (id, score) bit agrees."""
    from repro.core import hashing
    return hashing.hash_pytree((jnp.asarray(ids).astype(jnp.int64),
                                jnp.asarray(scores).astype(jnp.int64)))
