"""Deterministic HNSW (paper §7), adapted from pointer-chasing to TPU form.

The paper removes the three stochastic ingredients of classic HNSW:
  1. *Fixed ordering* — batches are applied in sorted id order (see
     ``commands.canonicalize_batch``); the command log fixes the order.
  2. *Data-dependent level assignment* — instead of an RNG draw, a node's
     level is a pure function of its external id (trailing-zero count of a
     SplitMix64 avalanche), giving the same geometric(1/2) level profile with
     zero state.
  3. *Deterministic entry point* — the first inserted node is the entry
     until a DELETE tombstones it; then ``ensure_live_entry`` promotes the
     live node with the greatest *raw* (id-derived) level, lowest id first
     (DESIGN.md §11) — a pure integer rule, so every layout picks the same
     replacement. (Consequence: node levels are capped at the entry's
     stored level at insert time; higher levels would be unreachable from
     the entry. Recorded deviation: classic HNSW promotes the entry
     opportunistically, here promotion happens only on entry death and by
     integer order.)

TPU adaptation (DESIGN.md §2): the adjacency is a dense
``[levels, capacity, degree]`` int32 array; search is a ``lax.while_loop``
beam over gathered neighbor rows; all distance comparisons use *wide* integer
L2 scores with (distance, slot) lexicographic tie-breaks, so every decision
is a pure integer comparison — bit-identical everywhere.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.state import MemoryState

# large sentinel distance: safely above any real wide score, well below int64 max
INF = 1 << 62  # a Python int: importing this module creates no device array


# --------------------------------------------------------------------------- #
# level assignment: deterministic, data-dependent (paper §7.2)
# --------------------------------------------------------------------------- #


def splitmix64(x: jax.Array) -> jax.Array:
    """SplitMix64 avalanche — the stable 'randomness' source. uint64 wraps."""
    z = x.astype(jnp.uint64) + jnp.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return z ^ (z >> jnp.uint64(31))


def level_of_id(ext_id: jax.Array, max_levels: int) -> jax.Array:
    """Geometric(1/2) level from the id's hash: count trailing ones.

    P(level ≥ k) = 2^-k exactly, like HNSW's mL=1/ln(2) draw, but replayable.
    """
    h = splitmix64(ext_id)
    # trailing ones of h == trailing zeros of ~h
    tz = jnp.int32(0)

    def body(i, carry):
        tz, done = carry
        bit = (h >> jnp.uint64(i)) & jnp.uint64(1)
        take = jnp.logical_and(jnp.logical_not(done), bit == 1)
        tz = jnp.where(take, tz + 1, tz)
        done = jnp.logical_or(done, bit == 0)
        return tz, done

    tz, _ = jax.lax.fori_loop(0, max_levels - 1, body, (tz, jnp.bool_(False)))
    return jnp.minimum(tz, max_levels - 1).astype(jnp.int32)


# --------------------------------------------------------------------------- #
# distances
# --------------------------------------------------------------------------- #


def _wide_l2(state: MemoryState, q_raw: jax.Array, slots: jax.Array) -> jax.Array:
    """Exact wide squared-L2 from query to the given slots; invalid → INF."""
    rows = state.vectors[slots].astype(jnp.int64)  # [n, dim]
    d = rows - q_raw.astype(jnp.int64)[None, :]
    dist = jnp.sum(d * d, axis=-1)
    ok = (slots >= 0) & state.valid[jnp.clip(slots, 0, state.capacity - 1)]
    return jnp.where(ok, dist, INF)


def _wide_l2_traverse(state: MemoryState, q_raw: jax.Array,
                      slots: jax.Array) -> jax.Array:
    """Traversal distance: like ``_wide_l2`` but tombstoned rows keep their
    true score (their vectors are still stored). The query-time beam ranks
    dead nodes as waypoints — the classic soft-delete traversal — and the
    caller masks them out of the *answer*; masking them out of the frontier
    instead would strand every live node whose only paths run through a
    tombstone (DESIGN.md §11). On a tombstone-free state this is exactly
    ``_wide_l2``."""
    safe = jnp.clip(slots, 0, state.capacity - 1)
    rows = state.vectors[safe].astype(jnp.int64)  # [n, dim]
    d = rows - q_raw.astype(jnp.int64)[None, :]
    dist = jnp.sum(d * d, axis=-1)
    return jnp.where(slots >= 0, dist, INF)


def _lex_less(d_a, s_a, d_b, s_b):
    """(distance, slot) lexicographic less-than — the deterministic tie-break."""
    return (d_a < d_b) | ((d_a == d_b) & (s_a < s_b))


def _sort_by_dist(d: jax.Array, s: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Sort candidate arrays by (distance, slot): a single integer key sort.

    Key packs distance (< 2^62) and slot into a sortable composite via
    stable two-key lax.sort.
    """
    d_sorted, s_sorted = jax.lax.sort((d, s), num_keys=2)
    return d_sorted, s_sorted


def _sort_dedup(d: jax.Array, s: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Sort by (distance, slot) and blank duplicate slots.

    A duplicated slot has an identical (d, s) pair, so duplicates are
    adjacent post-sort; the second copy is replaced by the (INF, pad)
    sentinel and a re-sort pushes it to the tail. Pure integer ops.
    """
    pad = jnp.int32(2**31 - 1)
    d, s = jax.lax.sort((d, s), num_keys=2)
    dup = jnp.zeros_like(s, dtype=jnp.bool_).at[1:].set(
        (s[1:] == s[:-1]) & (s[1:] != pad))
    d = jnp.where(dup, INF, d)
    s = jnp.where(dup, pad, s)
    return jax.lax.sort((d, s), num_keys=2)


# --------------------------------------------------------------------------- #
# greedy descent (beam = 1) for upper levels
# --------------------------------------------------------------------------- #


def greedy_step_level(state: MemoryState, q_raw: jax.Array, level: jax.Array,
                      start_slot: jax.Array,
                      neighbors_full: jax.Array | None = None,
                      static_level: int | None = None) -> jax.Array:
    """Walk to the locally-nearest node at ``level`` starting from start_slot."""

    def cond(carry):
        cur, cur_d, moved, it = carry
        return moved & (it < jnp.int32(state.capacity))

    def body(carry):
        cur, cur_d, _, it = carry
        nbrs = (neighbors_full[static_level, cur]
                if neighbors_full is not None
                else jax.lax.dynamic_index_in_dim(
                    state.hnsw_neighbors, level, axis=0, keepdims=False
                )[cur])  # [degree]
        nd = _wide_l2(state, q_raw, nbrs)
        best = jnp.argmin(nd)  # ties → lowest index; nbr lists are sorted by (d,slot)
        best_d = nd[best]
        best_s = nbrs[best]
        better = _lex_less(best_d, best_s, cur_d, cur)
        nxt = jnp.where(better, best_s, cur)
        nxt_d = jnp.where(better, best_d, cur_d)
        return nxt.astype(jnp.int32), nxt_d, better, it + 1

    d0 = _wide_l2(state, q_raw, start_slot[None])[0]
    cur, _, _, _ = jax.lax.while_loop(
        cond, body, (start_slot.astype(jnp.int32), d0, jnp.bool_(True), jnp.int32(0))
    )
    return cur


# --------------------------------------------------------------------------- #
# beam search at one level
# --------------------------------------------------------------------------- #


def search_layer(
    state: MemoryState,
    q_raw: jax.Array,
    entry_slot: jax.Array,
    level: jax.Array,
    ef: int,
    max_iters: int | None = None,
    fast: bool = False,
    neighbors_l: jax.Array | None = None,
    neighbors_full: jax.Array | None = None,
    static_level: int | None = None,
    dead_ok: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """ef-beam search at ``level``; returns (dists[ef], slots[ef]) sorted.

    Carries fixed-size arrays + a capacity-sized expansion mask. Every merge
    is a (distance, slot) sort — deterministic including ties.

    ``dead_ok=True`` (the query path under churn, DESIGN.md §11) ranks and
    expands tombstoned nodes by their true stored-vector distance instead of
    INF, so they remain traversal waypoints; the caller filters them from
    the answer. Identical to the default on tombstone-free states.

    ``fast=True`` (the bulk-ingest construction path) computes the identical
    beam with less work per expansion: the merge is a single sort — the beam
    and the fresh-masked neighbor row are disjoint by construction (``seen``
    excludes every slot ever beamed; graph rows never repeat a slot), so the
    dedup pass of ``_sort_dedup`` can never fire — expansions yielding no
    fresh neighbors skip the merge entirely (merging an all-INF row is the
    identity on a sorted beam), and expansion state rides in an ef-sized
    flag vector permuted alongside the beam instead of a capacity-sized
    scatter mask.
    """
    capacity = state.capacity
    degree = state.hnsw_degree
    if max_iters is None:
        max_iters = 2 * ef + 8
    if fast and dead_ok:
        raise ValueError("dead_ok is a query-path knob; the fast "
                         "construction path never traverses tombstones")
    dist_of = _wide_l2_traverse if dead_ok else _wide_l2

    d0 = jnp.full((ef,), INF, dtype=jnp.int64)
    s0 = jnp.full((ef,), jnp.int32(2**31 - 1), dtype=jnp.int32)
    d0 = d0.at[0].set(dist_of(state, q_raw, entry_slot[None])[0])
    s0 = s0.at[0].set(entry_slot.astype(jnp.int32))
    seen0 = jnp.zeros((capacity,), jnp.bool_).at[entry_slot].set(True)

    if neighbors_full is not None:
        # bulk path: row gathers go straight into the full [levels, capacity,
        # degree] array at a static level — no per-call slice materialization
        def row_of(cur):
            return neighbors_full[static_level, cur]
    else:
        if neighbors_l is None:
            neighbors_l = jax.lax.dynamic_index_in_dim(
                state.hnsw_neighbors, level, axis=0, keepdims=False
            )  # [capacity, degree]
        _nl = neighbors_l

        def row_of(cur):
            return _nl[cur]

    if fast:
        exp0 = jnp.zeros((ef,), jnp.bool_)

        def fcond(carry):
            d, s, exp, seen, it = carry
            return jnp.any((~exp) & (d < INF)) & (it < max_iters)

        def fbody(carry):
            d, s, exp, seen, it = carry
            unexp = (~exp) & (d < INF)
            pick = jnp.argmax(unexp)  # beam sorted ⇒ first True is nearest
            cur = jnp.clip(s[pick], 0, capacity - 1)
            exp = exp.at[pick].set(True)
            nbrs = row_of(cur)  # [degree]
            nbr_safe = jnp.clip(nbrs, 0, capacity - 1)
            fresh = (nbrs >= 0) & (~seen[nbr_safe])

            def merge(ops):
                d, s, exp, seen = ops
                nd = _wide_l2(state, q_raw, nbrs)
                nd = jnp.where(fresh, nd, INF)
                ns = jnp.where(fresh, nbr_safe, jnp.int32(2**31 - 1))
                # -1 entries route to index `capacity` and are dropped: the
                # slow path's clip-to-0 scatter writes conflicting values at
                # slot 0 (its dedup pass absorbs the fallout); here the beam
                # must stay duplicate-free, so mark only real neighbors
                tgt = jnp.where(nbrs >= 0, nbr_safe, jnp.int32(capacity))
                seen = seen.at[tgt].set(True, mode="drop")
                md = jnp.concatenate([d, nd])
                ms = jnp.concatenate([s, ns])
                mf = jnp.concatenate([exp, jnp.zeros((degree,), jnp.bool_)])
                md, ms, mf = jax.lax.sort((md, ms, mf), num_keys=2)
                return md[:ef], ms[:ef], mf[:ef], seen

            d, s, exp, seen = jax.lax.cond(
                jnp.any(fresh), merge, lambda o: o, (d, s, exp, seen))
            return d, s, exp, seen, it + 1

        d, s, _, _, _ = jax.lax.while_loop(
            fcond, fbody, (d0, s0, exp0, seen0, jnp.int32(0)))
        return d, s

    expanded0 = jnp.zeros((capacity,), jnp.bool_)

    def cond(carry):
        d, s, seen, expanded, it = carry
        safe = jnp.clip(s, 0, capacity - 1)
        unexp = (~expanded[safe]) & (d < INF)
        return jnp.any(unexp) & (it < max_iters)

    def body(carry):
        d, s, seen, expanded, it = carry
        safe = jnp.clip(s, 0, capacity - 1)
        unexp = (~expanded[safe]) & (d < INF)
        # nearest unexpanded candidate (arrays are kept sorted, so argmax of
        # the first True is the nearest)
        pick = jnp.argmax(unexp)  # first True in sorted order
        cur = safe[pick]
        expanded = expanded.at[cur].set(True)
        nbrs = row_of(cur)  # [degree]
        nbr_safe = jnp.clip(nbrs, 0, capacity - 1)
        fresh = (nbrs >= 0) & (~seen[nbr_safe])
        nd = dist_of(state, q_raw, nbrs)
        nd = jnp.where(fresh, nd, INF)
        ns = jnp.where(fresh, nbr_safe, jnp.int32(2**31 - 1))
        seen = seen.at[nbr_safe].set(seen[nbr_safe] | (nbrs >= 0))
        # merge + keep ef best (deduped: rows may repeat a neighbor)
        md = jnp.concatenate([d, nd])
        ms = jnp.concatenate([s, ns])
        md, ms = _sort_dedup(md, ms)
        return md[:ef], ms[:ef], seen, expanded, it + 1

    d, s, _, _, _ = jax.lax.while_loop(cond, body, (d0, s0, seen0, expanded0, jnp.int32(0)))
    return d, s


# --------------------------------------------------------------------------- #
# insert
# --------------------------------------------------------------------------- #


def _add_bidirectional_edges(
    state_neighbors: jax.Array,  # [capacity, degree] at one level
    vectors: jax.Array,          # [capacity, dim] raw
    valid: jax.Array,
    new_slot: jax.Array,
    cand_d: jax.Array,           # [ef] sorted candidate distances to new node
    cand_s: jax.Array,           # [ef]
    m: int,
    active: jax.Array,           # bool: is this level active for the new node
) -> jax.Array:
    """Connect new_slot ↔ its M nearest candidates, pruning to degree by
    (distance-to-owner, slot). Pure integer ordering ⇒ deterministic."""
    capacity, degree = state_neighbors.shape
    pad = jnp.int32(2**31 - 1)

    # forward edges: M best candidates (already sorted by (d, slot)), -1 padded
    idx = jnp.arange(degree)
    src = jnp.clip(idx, 0, cand_s.shape[0] - 1)
    fwd_slots = jnp.where(
        (idx < m) & (cand_d[src] < INF), cand_s[src], jnp.int32(-1)
    ).astype(jnp.int32)
    fwd = jnp.where(active, fwd_slots, state_neighbors[new_slot])
    state_neighbors = state_neighbors.at[new_slot].set(fwd)

    # reverse edges: for each of the M candidates, insert new_slot and prune
    new_vec = vectors[new_slot].astype(jnp.int64)

    def rev_one(i, nbrs_arr):
        c = cand_s[i]
        is_real = active & (cand_d[i] < INF) & (i < m) & (c != new_slot)

        def do(nbrs_arr):
            owner_vec = vectors[c].astype(jnp.int64)
            cur = nbrs_arr[c]  # [degree]
            cur_safe = jnp.clip(cur, 0, capacity - 1)
            cur_vecs = vectors[cur_safe].astype(jnp.int64)
            dd = jnp.sum((cur_vecs - owner_vec[None, :]) ** 2, axis=-1)
            dd = jnp.where(cur >= 0, dd, INF)
            d_new = jnp.sum((new_vec - owner_vec) ** 2)
            alld = jnp.concatenate([dd, d_new[None]])
            alls = jnp.concatenate(
                [jnp.where(cur >= 0, cur, pad), new_slot[None].astype(jnp.int32)]
            )
            alld, alls = _sort_dedup(alld, alls)
            kept = jnp.where(alld[:degree] < INF, alls[:degree], jnp.int32(-1))
            return nbrs_arr.at[c].set(kept)

        return jax.lax.cond(is_real, do, lambda a: a, nbrs_arr)

    state_neighbors = jax.lax.fori_loop(0, cand_s.shape[0], rev_one, state_neighbors)
    return state_neighbors


def _add_edges_fast(neighbors: jax.Array, lvl: int, vectors: jax.Array,
                    new_slot: jax.Array, cand_d: jax.Array, cand_s: jax.Array,
                    m: int) -> jax.Array:
    """Bulk-path edge update on the full [levels, capacity, degree] array.

    Equivalent to ``_add_bidirectional_edges`` at one (static) level with
    ``active=True``, but with no per-level slice round-trip: the forward row
    and the m pruned reverse rows go in as direct (level, row) scatters, and
    the per-candidate loop is one batched prune — candidates are distinct
    rows (the fast-path beam is duplicate-free), so the sequential loop's
    iterations are independent."""
    _, capacity, degree = neighbors.shape
    pad = jnp.int32(2**31 - 1)

    idx = jnp.arange(degree)
    src = jnp.clip(idx, 0, cand_s.shape[0] - 1)
    fwd = jnp.where(
        (idx < m) & (cand_d[src] < INF), cand_s[src], jnp.int32(-1)
    ).astype(jnp.int32)
    neighbors = neighbors.at[lvl, new_slot].set(fwd)

    new_vec = vectors[new_slot].astype(jnp.int64)
    mm = min(m, cand_s.shape[0])
    c = cand_s[:mm]                  # [mm]
    is_real = (cand_d[:mm] < INF) & (c != new_slot)
    c_safe = jnp.clip(c, 0, capacity - 1)
    owner_vecs = vectors[c_safe].astype(jnp.int64)     # [mm, dim]
    cur = neighbors[lvl, c_safe]                       # [mm, degree]
    cur_safe = jnp.clip(cur, 0, capacity - 1)
    cur_vecs = vectors[cur_safe].astype(jnp.int64)     # [mm, degree, dim]
    dd = jnp.sum((cur_vecs - owner_vecs[:, None, :]) ** 2, axis=-1)
    dd = jnp.where(cur >= 0, dd, INF)
    d_new = jnp.sum((new_vec[None, :] - owner_vecs) ** 2, axis=-1)
    alld = jnp.concatenate([dd, d_new[:, None]], axis=1)
    alls = jnp.concatenate(
        [jnp.where(cur >= 0, cur, pad),
         jnp.broadcast_to(new_slot.astype(jnp.int32), (mm,))[:, None]],
        axis=1)
    alld, alls = jax.lax.sort((alld, alls), num_keys=2, dimension=1)
    kept = jnp.where(alld[:, :degree] < INF, alls[:, :degree], jnp.int32(-1))
    rows = jnp.where(is_real, c_safe, jnp.int32(capacity))
    return neighbors.at[lvl, rows].set(kept, mode="drop")


def hnsw_insert(state: MemoryState, new_slot: jax.Array, *, ef_construction: int = 32,
                m: int | None = None, fast: bool = False) -> MemoryState:
    """Incrementally insert the (already stored) row at ``new_slot``.

    Fully deterministic: level from id hash, entry fixed at first node,
    all selections tie-broken by slot id.

    ``fast=True`` selects the bulk-ingest variant used by
    ``machine.bulk_apply``: per-level work is gated behind ``lax.cond`` so
    inactive levels skip their beam search at runtime, and the reverse-edge
    loop visits only the M candidates that can actually connect. Both are
    pure control-flow changes — every value the default path would *use* is
    computed identically, so the resulting state is bit-identical
    (tests/test_bulk_apply.py proves this on randomized logs).
    """
    if m is None:
        m = state.hnsw_degree // 2
    if fast and m > ef_construction:
        # with more connectable candidates than beam slots, the default
        # path's forward-edge writer clip-repeats the last candidate,
        # producing duplicate row entries its dedup-sorts absorb — the
        # fast path's duplicate-free-beam invariant does not hold there,
        # so take the reference implementation (both args are static)
        fast = False
    max_levels = state.hnsw_max_levels
    q_raw = state.vectors[new_slot]
    ext_id = state.ids[new_slot]

    is_first = state.hnsw_entry < 0
    raw_level = level_of_id(ext_id, max_levels)
    entry = jnp.where(is_first, new_slot.astype(jnp.int32), state.hnsw_entry)
    entry_level = jnp.where(
        is_first, raw_level, state.hnsw_levels[jnp.clip(entry, 0, state.capacity - 1)]
    )
    # paper: entry fixed to first node ⇒ cap level so all nodes stay reachable
    node_level = jnp.minimum(raw_level, entry_level)

    state = dataclasses.replace(
        state,
        hnsw_levels=state.hnsw_levels.at[new_slot].set(node_level),
        hnsw_entry=entry.astype(jnp.int32),
    )

    if fast:
        # Unrolled static-level variant for bulk ingest. Identical values,
        # cheaper control flow: every lax.cond carries one [capacity, degree]
        # level slice instead of the whole [levels, capacity, degree] array,
        # inactive levels skip their beam search at runtime, and the
        # reverse-edge loop is batched over the m connectable candidates.
        def build(neighbors: jax.Array) -> jax.Array:
            # phase 1: greedy descent, entry's top level → node_level+1
            cur = entry.astype(jnp.int32)
            for lvl in range(max_levels - 1, 0, -1):
                do = (jnp.int32(lvl) <= entry_level) & (jnp.int32(lvl) > node_level)
                cur = jax.lax.cond(
                    do,
                    lambda c, lvl=lvl: greedy_step_level(
                        state, q_raw, jnp.int32(lvl), c,
                        neighbors_full=neighbors, static_level=lvl),
                    lambda c: c, cur)

            # phase 2: beam search + connect at levels node_level..0
            for lvl in range(max_levels - 1, -1, -1):
                active = jnp.int32(lvl) <= node_level

                def do_level(args, lvl=lvl):
                    nbrs, c = args
                    d, s = search_layer(state, q_raw, c, jnp.int32(lvl),
                                        ef_construction, fast=True,
                                        neighbors_full=nbrs,
                                        static_level=lvl)
                    # exclude self; the beam is duplicate-free, so a plain
                    # sort pushes the blanked entry back to the tail
                    d = jnp.where(s == new_slot, INF, d)
                    s = jnp.where(s == new_slot, jnp.int32(2**31 - 1), s)
                    d, s = jax.lax.sort((d, s), num_keys=2)
                    nbrs = _add_edges_fast(
                        nbrs, lvl, state.vectors, new_slot.astype(jnp.int32),
                        d, s, m)
                    nxt = jnp.where(d[0] < INF, s[0], c).astype(jnp.int32)
                    return nbrs, nxt

                neighbors, cur = jax.lax.cond(
                    active, do_level, lambda a: a, (neighbors, cur))
            return neighbors

        neighbors = jax.lax.cond(
            jnp.logical_not(is_first), build, lambda n: n,
            state.hnsw_neighbors)
        return dataclasses.replace(state, hnsw_neighbors=neighbors)

    def not_first_insert(state: MemoryState) -> MemoryState:
        # phase 1: greedy descent from the entry's top level to node_level+1
        def descend(lvl_rev, cur):
            lvl = jnp.int32(max_levels - 1 - lvl_rev)
            do = (lvl <= entry_level) & (lvl > node_level)
            return jnp.where(
                do, greedy_step_level(state, q_raw, lvl, cur), cur
            ).astype(jnp.int32)

        cur = jax.lax.fori_loop(0, max_levels, descend, entry.astype(jnp.int32))

        # phase 2: beam search + connect at levels node_level..0
        neighbors = state.hnsw_neighbors

        def connect(lvl_rev, carry):
            neighbors, cur = carry
            lvl = jnp.int32(max_levels - 1 - lvl_rev)
            active = lvl <= node_level
            # search against a state view with current neighbor arrays
            st = dataclasses.replace(state, hnsw_neighbors=neighbors)
            d, s = search_layer(st, q_raw, cur, lvl, ef_construction)
            # exclude self from candidates
            d = jnp.where(s == new_slot, INF, d)
            s = jnp.where(s == new_slot, jnp.int32(2**31 - 1), s)
            d, s = _sort_dedup(d, s)
            lvl_nbrs = jax.lax.dynamic_index_in_dim(neighbors, lvl, 0, keepdims=False)
            lvl_nbrs = _add_bidirectional_edges(
                lvl_nbrs, state.vectors, state.valid, new_slot.astype(jnp.int32),
                d, s, m, active
            )
            neighbors = jax.lax.dynamic_update_index_in_dim(neighbors, lvl_nbrs, lvl, 0)
            # next level starts from the best found here (when this level ran)
            nxt = jnp.where(active & (d[0] < INF), s[0], cur).astype(jnp.int32)
            return neighbors, nxt

        neighbors, _ = jax.lax.fori_loop(0, max_levels, connect, (neighbors, cur))
        return dataclasses.replace(state, hnsw_neighbors=neighbors)

    return jax.lax.cond(jnp.logical_not(is_first), not_first_insert, lambda s: s, state)


# --------------------------------------------------------------------------- #
# entry-point repair on delete (DESIGN.md §11)
# --------------------------------------------------------------------------- #


def raw_levels(state: MemoryState) -> jax.Array:
    """``level_of_id`` over the whole arena: [capacity] int32.

    The *raw* (uncapped) level is a pure function of each row's external id,
    so every layout holding the same live rows computes the same values.
    The repair and re-link orders below key on it instead of the stored
    (entry-capped) ``hnsw_levels``, whose values depend on each graph's own
    entry history and therefore differ across layouts."""
    return jax.vmap(lambda i: level_of_id(i, state.hnsw_max_levels))(state.ids)


def repair_entry(state: MemoryState) -> jax.Array:
    """The deterministic replacement entry after the current one dies: the
    live slot maximizing (raw level, then lowest id) — exactly the node a
    fresh build of the same live rows makes its entry (``fresh_build``
    inserts in this order, and a first insert is never level-capped).
    Returns -1 when nothing is live. Pure integer ordering: every layout
    picks the same replacement."""
    lv = jnp.where(state.valid, raw_levels(state), jnp.int32(-1))
    best = jnp.max(lv)
    id_key = jnp.where(state.valid & (lv == best), state.ids,
                       jnp.int64(1) << 62)
    slot = jnp.argmin(id_key).astype(jnp.int32)
    return jnp.where(jnp.any(state.valid), slot, jnp.int32(-1))


def ensure_live_entry(state: MemoryState) -> MemoryState:
    """Post-delete invariant: ``hnsw_entry`` is live, or -1 when the arena
    holds no live rows (the next insert then re-seeds the graph through the
    ordinary first-insert path). When a DELETE tombstones the entry, the
    promotion rule of ``repair_entry`` runs; the level-cap rule re-anchors
    to the promoted node's stored level automatically (``hnsw_insert``
    reads ``hnsw_levels[entry]``). Repair touches ONLY ``hnsw_entry`` —
    the tombstoned node keeps its edges and stays a traversal waypoint
    until a re-link sweeps it (``relink``)."""
    entry = state.hnsw_entry
    safe = jnp.clip(entry, 0, state.capacity - 1)
    dead = (entry >= 0) & jnp.logical_not(state.valid[safe])
    new_entry = jax.lax.cond(dead, repair_entry,
                             lambda s: s.hnsw_entry, state)
    return dataclasses.replace(state, hnsw_entry=new_entry)


# --------------------------------------------------------------------------- #
# deterministic re-link: graph compaction (DESIGN.md §11)
# --------------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class RelinkPolicy:
    """When the serve engine re-links (rebuilds) the HNSW graph from its
    live rows — the graph twin of ``wal.CompactionPolicy``. Every
    ``check_every`` ingested global commands (and only once at least
    ``min_deletes`` effective deletes have accrued since the last re-link),
    the pass fires when deletes reach ``dead_ratio`` of the graph's
    (dead + live) node population. All three facts derive from the global
    command stream, so flat and sharded engines fed the same batches fire
    at the same batch boundaries — the schedule itself is layout-invariant
    (per-shard cursors and per-slice tombstone counts are not, and are
    never consulted)."""
    dead_ratio: float = 0.5
    min_deletes: int = 64
    check_every: int = 64

    def __post_init__(self):
        if not 0.0 < self.dead_ratio <= 1.0:
            raise ValueError("dead_ratio must be in (0, 1]")
        if self.check_every < 1:
            raise ValueError("check_every must be >= 1")
        if self.min_deletes < 1:
            raise ValueError("min_deletes must be >= 1")


def relink_order(state: MemoryState) -> jax.Array:
    """Canonical re-insertion order over the live slots: (raw level desc,
    id asc), dead slots pushed to the tail as the ``capacity`` sentinel.
    Returns [capacity] int32 slot indices. A pure function of the arena's
    (ids, valid) — every holder of the same live rows derives the same
    order, and its head is exactly ``repair_entry``'s choice."""
    cap = state.capacity
    lv = raw_levels(state)
    big = jnp.int64(1) << 40
    k1 = jnp.where(state.valid,
                   (state.hnsw_max_levels - lv).astype(jnp.int64), big)
    k2 = jnp.where(state.valid, state.ids, jnp.int64(1) << 62)
    slots = jnp.arange(cap, dtype=jnp.int32)
    k1s, _, order = jax.lax.sort((k1, k2, slots), num_keys=2)
    return jnp.where(k1s < big, order, jnp.int32(cap))


def _blank_graph(state: MemoryState) -> MemoryState:
    return dataclasses.replace(
        state,
        hnsw_neighbors=jnp.full_like(state.hnsw_neighbors, -1),
        hnsw_levels=jnp.full_like(state.hnsw_levels, -1),
        hnsw_entry=jnp.asarray(-1, jnp.int32))


@partial(jax.jit, static_argnames=("ef_construction",))
def relink(state: MemoryState, *, ef_construction: int = 32) -> MemoryState:
    """Deterministic graph compaction: rebuild the HNSW arrays from the
    live rows only, in ``relink_order``, leaving the arena (vectors / ids /
    valid / meta / links and every scalar, ``version`` included) untouched.

    The bit-exact contract (tests/test_hnsw.py): ``hash_pytree(relink(S))
    == hash_pytree(fresh_build(S))`` — the jitted scan over the fast insert
    path must land on exactly the graph the reference per-row build lands
    on. Consequences of the canonical order: tombstoned waypoints vanish,
    the new entry is ``repair_entry``'s choice, and no node's level is
    capped (the first re-inserted node carries the maximal raw level), so a
    re-linked graph is also a *better* graph than the churned one."""
    blank = _blank_graph(state)
    order = relink_order(state)
    cap = state.capacity

    def body(carry, slot):
        def ins(c):
            nbrs, lvls, ent = c
            st = dataclasses.replace(
                blank, hnsw_neighbors=nbrs, hnsw_levels=lvls, hnsw_entry=ent)
            out = hnsw_insert(st, slot, ef_construction=ef_construction,
                              fast=True)
            return out.hnsw_neighbors, out.hnsw_levels, out.hnsw_entry

        return jax.lax.cond(slot < cap, ins, lambda c: c, carry), None

    carry0 = (blank.hnsw_neighbors, blank.hnsw_levels, blank.hnsw_entry)
    (nbrs, lvls, ent), _ = jax.lax.scan(body, carry0, order)
    return dataclasses.replace(
        state, hnsw_neighbors=nbrs, hnsw_levels=lvls, hnsw_entry=ent)


def fresh_build(state: MemoryState, *, ef_construction: int = 32
                ) -> MemoryState:
    """The definitional re-link reference: the same canonical order, one
    reference-path ``hnsw_insert`` per live row on the host. ``relink``
    must match it bit-for-bit — this is the oracle the contract test
    runs, never the production path."""
    out = _blank_graph(state)
    order = np.asarray(relink_order(state))
    for slot in order:
        if int(slot) >= state.capacity:
            break  # dead-slot sentinels are all at the tail
        out = hnsw_insert(out, jnp.int32(int(slot)),
                          ef_construction=ef_construction)
    return out


# --------------------------------------------------------------------------- #
# query
# --------------------------------------------------------------------------- #


def hnsw_search(state: MemoryState, q_raw: jax.Array, k: int, *, ef: int = 64
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """ANN search: returns (ids[k] int64, dists[k] wide int64, slots[k]).

    Missing results are (-1, INF, -1). Deterministic for a fixed state.
    """
    max_levels = state.hnsw_max_levels
    entry = state.hnsw_entry
    have_graph = entry >= 0
    entry_safe = jnp.clip(entry, 0, state.capacity - 1)
    entry_level = jnp.where(have_graph, state.hnsw_levels[entry_safe], 0)

    def descend(lvl_rev, cur):
        lvl = jnp.int32(max_levels - 1 - lvl_rev)
        do = (lvl <= entry_level) & (lvl > 0) & have_graph
        return jnp.where(do, greedy_step_level(state, q_raw, lvl, cur), cur).astype(jnp.int32)

    cur = jax.lax.fori_loop(0, max_levels, descend, entry_safe.astype(jnp.int32))
    # Level-0 beam traverses tombstones (dead_ok) so a churned graph stays
    # fully reachable; dead rows are then dropped from the *answer*, not the
    # frontier. On a tombstone-free state this is bit-identical to the
    # live-only beam (every beamed slot is valid), so insert-only goldens
    # are untouched.
    d, s = search_layer(state, q_raw, cur, jnp.int32(0), ef, dead_ok=True)
    safe = jnp.clip(s, 0, state.capacity - 1)
    live = (d < INF) & state.valid[safe]
    d = jnp.where(live, d, INF)
    s = jnp.where(live, s, jnp.int32(2 ** 31 - 1))
    d, s = jax.lax.sort((d, s), num_keys=2)
    d, s = d[:k], s[:k]
    ok = (d < INF) & have_graph
    slots = jnp.where(ok, s, jnp.int32(-1))
    ids = jnp.where(ok, state.ids[jnp.clip(s, 0, state.capacity - 1)], jnp.int64(-1))
    dists = jnp.where(ok, d, INF)
    return ids, dists, slots
