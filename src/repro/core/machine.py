"""The pure state-machine transition function F (paper §3.1, §5.2).

``S_{t+1} = F(S_t, C_t)``: a single jittable function dispatching on opcode
via ``lax.switch``. ``replay`` folds a whole command log with ``lax.scan`` —
the paper's replayability guarantee is literally this scan. Every branch
returns a full next-state so the switch is shape-stable.

Semantics (all deterministic, total — invalid commands are no-ops that still
advance logical time, so a log replays identically even past rejections):

* INSERT(id, vec): upsert. Existing id → overwrite row in place (graph edges
  and HNSW links for that slot are rebuilt from the new vector lazily via the
  next index touch; vector content is what distance math reads). New id →
  lowest free slot, claimed *clean*: the row's meta words and user links are
  reset, so a fresh id never inherits a tombstoned predecessor's metadata —
  slot-reuse order is layout-dependent, and leaked meta would break the
  cross-layout ``content_hash`` contract (DESIGN.md §7). HNSW incremental
  insert runs for new rows.
* DELETE(id): clear valid bit (tombstone). Slot becomes reusable; HNSW keeps
  the tombstoned node's edges so it stays a traversal waypoint (the query
  beam traverses tombstones via ``dead_ok`` and drops them from the answer,
  never the frontier), and when the delete kills the current entry point,
  ``hnsw.ensure_live_entry`` promotes the deterministic replacement — the
  live node with the greatest raw (id-derived) level, lowest id first
  (DESIGN.md §11) — so every layout repairs to the same entry.
* LINK(a, b) / UNLINK(a, b): typed user edges in ``links`` (first free /
  matching entry). Distinct from HNSW adjacency.
* SET_META(id, slot, value): write a metadata word.
"""
from __future__ import annotations

import dataclasses
import heapq
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import hnsw
from repro.core.commands import (DELETE, INSERT, LINK, NOP, NUM_OPCODES,
                                 SET_META, UNLINK, CommandLog)
from repro.core.state import MemoryState, slot_of_id


def _bump(state: MemoryState) -> MemoryState:
    return dataclasses.replace(state, version=state.version + 1)


# --------------------------------------------------------------------------- #
# opcode handlers — each: (state, rec) -> state
# --------------------------------------------------------------------------- #


def _op_nop(state: MemoryState, rec: CommandLog, ef_construction: int) -> MemoryState:
    return state


def _op_insert(state: MemoryState, rec: CommandLog, ef_construction: int) -> MemoryState:
    ext_id = rec.arg0
    existing = slot_of_id(state, ext_id)
    has_existing = existing >= 0
    free_mask = ~state.valid
    any_free = jnp.any(free_mask)
    free_slot = jnp.argmax(free_mask).astype(jnp.int32)  # lowest free slot
    slot = jnp.where(has_existing, existing, free_slot)
    can_write = has_existing | any_free  # full arena rejects new ids

    def write(state: MemoryState) -> MemoryState:
        vectors = state.vectors.at[slot].set(rec.vec)
        ids = state.ids.at[slot].set(ext_id)
        valid = state.valid.at[slot].set(True)
        count = state.count + jnp.where(has_existing, 0, 1).astype(jnp.int32)
        cursor = jnp.maximum(state.cursor, slot + 1)
        # a fresh id claims a CLEAN row: meta/links left by a tombstoned
        # predecessor must not leak (slot reuse is layout-dependent; leaked
        # meta breaks the cross-layout content_hash). Upserts keep theirs.
        meta = state.meta.at[slot].set(
            jnp.where(has_existing, state.meta[slot], 0))
        links = state.links.at[slot].set(
            jnp.where(has_existing, state.links[slot], -1))
        new_state = dataclasses.replace(
            state, vectors=vectors, ids=ids, valid=valid,
            count=count, cursor=cursor, meta=meta, links=links,
        )
        # fresh rows enter the HNSW graph; overwrites keep their links
        return jax.lax.cond(
            has_existing,
            lambda s: s,
            lambda s: hnsw.hnsw_insert(s, slot, ef_construction=ef_construction),
            new_state,
        )

    return jax.lax.cond(can_write, write, lambda s: s, state)


def _op_delete(state: MemoryState, rec: CommandLog, ef_construction: int) -> MemoryState:
    slot = slot_of_id(state, rec.arg0)
    found = slot >= 0
    safe = jnp.clip(slot, 0, state.capacity - 1)
    valid = state.valid.at[safe].set(jnp.where(found, False, state.valid[safe]))
    ids = state.ids.at[safe].set(jnp.where(found, jnp.int64(-1), state.ids[safe]))
    count = state.count - jnp.where(found, 1, 0).astype(jnp.int32)
    return hnsw.ensure_live_entry(
        dataclasses.replace(state, valid=valid, ids=ids, count=count))


def _op_link(state: MemoryState, rec: CommandLog, ef_construction: int) -> MemoryState:
    a = slot_of_id(state, rec.arg0)
    b = slot_of_id(state, rec.arg1)
    ok = (a >= 0) & (b >= 0)
    sa = jnp.clip(a, 0, state.capacity - 1)
    row = state.links[sa]  # [max_links]
    already = jnp.any(row == b)
    free = row < 0
    has_free = jnp.any(free)
    pos = jnp.argmax(free)
    do = ok & has_free & ~already
    new_row = jnp.where(
        do, row.at[pos].set(b.astype(jnp.int32)), row
    )
    return dataclasses.replace(state, links=state.links.at[sa].set(new_row))


def _op_unlink(state: MemoryState, rec: CommandLog, ef_construction: int) -> MemoryState:
    a = slot_of_id(state, rec.arg0)
    b = slot_of_id(state, rec.arg1)
    ok = (a >= 0) & (b >= 0)
    sa = jnp.clip(a, 0, state.capacity - 1)
    row = state.links[sa]
    new_row = jnp.where(ok & (row == b), jnp.int32(-1), row)
    return dataclasses.replace(state, links=state.links.at[sa].set(new_row))


def _op_set_meta(state: MemoryState, rec: CommandLog, ef_construction: int) -> MemoryState:
    slot = slot_of_id(state, rec.arg0)
    ok = slot >= 0
    safe = jnp.clip(slot, 0, state.capacity - 1)
    mslot = jnp.clip(rec.arg1, 0, state.meta.shape[1] - 1).astype(jnp.int32)
    cur = state.meta[safe, mslot]
    val = jnp.where(ok, rec.arg2, cur)
    return dataclasses.replace(state, meta=state.meta.at[safe, mslot].set(val))


_HANDLERS = [_op_nop, _op_insert, _op_delete, _op_link, _op_unlink, _op_set_meta]


# --------------------------------------------------------------------------- #
# F and replay
# --------------------------------------------------------------------------- #


def apply_command(state: MemoryState, rec: CommandLog,
                  *, ef_construction: int = 32) -> MemoryState:
    """S_{t+1} = F(S_t, C_t). Total function; always advances ``version``."""
    op = jnp.clip(rec.opcode, 0, NUM_OPCODES - 1)
    branches = [partial(h, ef_construction=ef_construction) for h in _HANDLERS]
    state = jax.lax.switch(op, branches, state, rec)
    return _bump(state)


@partial(jax.jit, static_argnames=("ef_construction",))
def replay(state: MemoryState, log: CommandLog,
           *, ef_construction: int = 32) -> MemoryState:
    """Apply a whole log: the paper's Apply(S_0, {C_i}). One lax.scan.

    Invariant: a pure function of (state, log) — the same inputs produce a
    bit-identical final state (same ``hashing.hash_pytree``) on any
    platform, in any chunking (``apply_chunked``), and under
    ``bulk_apply``'s batched form. This is the replayability guarantee
    every durability and audit contract reduces to."""

    def step(s, rec):
        return apply_command(s, rec, ef_construction=ef_construction), None

    final, _ = jax.lax.scan(step, state, log)
    return final


def apply_chunked(state: MemoryState, log: CommandLog, chunk: int,
                  *, ef_construction: int = 32) -> MemoryState:
    """Replay in host-driven chunks (used by tests to prove that batch
    boundaries cannot affect the final state)."""
    n = len(log)
    for start in range(0, n, chunk):
        state = replay(state, log.slice(start, min(start + chunk, n)),
                       ef_construction=ef_construction)
    return state


# --------------------------------------------------------------------------- #
# bulk apply: the vectorized ingestion fast path (DESIGN.md §3)
# --------------------------------------------------------------------------- #
#
# ``bulk_apply(S, log) == replay(S, log)`` bit-for-bit (hash-identical), but
# applies the log in batched segments instead of one lax.scan step per
# command. The host segments the log by opcode; each segment runs a batched
# kernel:
#
#   * clean INSERT runs (fresh, distinct ids): slots are allocated with ONE
#     prefix-scan over the free mask (the i-th fresh insert takes the i-th
#     lowest free slot — exactly the sequential "lowest free slot, in log
#     order" semantics), vectors/ids/valid are written with one batched
#     scatter, and only the HNSW graph construction remains a loop — over
#     fresh rows only, with inactive levels cond-skipped (hnsw_insert
#     ``fast=True``).
#   * DELETE / SET_META runs: slot resolution is one vmapped probe against
#     the segment-entry state plus a host-computed first/last-occurrence
#     mask, then one batched scatter.
#   * everything else (NOP-padded sequential segments: LINK/UNLINK order
#     within a row is semantic, and hazardous INSERTs — upserts or
#     duplicate ids — genuinely depend on interleaving): a plain scan of F,
#     which is the definitional semantics.
#
# Why pre-scattering whole INSERT runs cannot change the HNSW graph: every
# slot the construction searches, scores, or links is reachable only through
# the entry point and neighbor arrays, which mention exactly the rows already
# inserted. Rows scattered early but not yet graph-inserted have no incident
# edges, so no search can observe them — the graph build sees precisely the
# prefix state sequential replay would have shown it.


def _pad_log(log: CommandLog, target: int) -> CommandLog:
    """NOP-pad a sub-log to ``target`` records (pow2 buckets keep the number
    of distinct jit shapes logarithmic)."""
    n = len(log)
    if n == target:
        return log
    pad = target - n
    return CommandLog(
        opcode=jnp.concatenate([log.opcode, jnp.zeros((pad,), jnp.int32)]),
        arg0=jnp.concatenate([log.arg0, jnp.zeros((pad,), jnp.int64)]),
        arg1=jnp.concatenate([log.arg1, jnp.zeros((pad,), jnp.int64)]),
        arg2=jnp.concatenate([log.arg2, jnp.zeros((pad,), jnp.int64)]),
        vec=jnp.concatenate(
            [log.vec, jnp.zeros((pad, log.dim), log.vec.dtype)]),
    )


def _pow2(n: int) -> int:
    return 1 << (n - 1).bit_length() if n > 1 else 1


def _claim_slots(state: MemoryState, log: CommandLog, n_real: jax.Array
                 ) -> Tuple[MemoryState, jax.Array]:
    """Write a clean INSERT run's rows into free slots; returns the state
    and each command's slot (``capacity`` where rejected or padding).

    Slot allocation is one prefix scan: command i takes the i-th lowest free
    slot; commands past the free-slot supply are rejected, exactly like the
    sequential path."""
    m = len(log)
    cap = state.capacity
    free_mask = ~state.valid
    num_free = jnp.sum(free_mask).astype(jnp.int32)
    free_idx = jnp.nonzero(free_mask, size=m, fill_value=cap)[0].astype(jnp.int32)
    idx = jnp.arange(m, dtype=jnp.int32)
    present = idx < n_real                 # NOP padding guard
    accepted = present & (idx < num_free)  # full arena rejects the tail
    slots = jnp.where(accepted, free_idx, jnp.int32(cap))  # cap ⇒ dropped

    # no unique_indices promise: the rejected/padded tail repeats the `cap`
    # sentinel, and a false uniqueness promise is undefined behavior even
    # though those writes are dropped
    vectors = state.vectors.at[slots].set(
        log.vec, mode="drop", indices_are_sorted=True)
    ids = state.ids.at[slots].set(
        log.arg0, mode="drop", indices_are_sorted=True)
    valid = state.valid.at[slots].set(
        True, mode="drop", indices_are_sorted=True)
    # every id in a clean run is fresh: claimed rows start with zero meta
    # and no user links (see _op_insert — tombstone leftovers must not leak)
    meta = state.meta.at[slots].set(
        jnp.zeros((m, state.meta.shape[1]), state.meta.dtype),
        mode="drop", indices_are_sorted=True)
    links = state.links.at[slots].set(
        jnp.full((m, state.links.shape[1]), -1, state.links.dtype),
        mode="drop", indices_are_sorted=True)
    count = state.count + jnp.sum(accepted).astype(jnp.int32)
    cursor = jnp.maximum(
        state.cursor, jnp.max(jnp.where(accepted, slots + 1, 0)))
    state = dataclasses.replace(
        state, vectors=vectors, ids=ids, valid=valid, count=count,
        cursor=cursor, meta=meta, links=links,
        version=state.version + n_real,
    )
    return state, slots


@partial(jax.jit, static_argnames=("ef_construction",))
def _apply_insert_segment(state: MemoryState, log: CommandLog,
                          n_real: jax.Array, *, ef_construction: int
                          ) -> MemoryState:
    """Clean INSERT run: all ids fresh and distinct (host-verified). The
    rows go in by ``_claim_slots``, then the graph is built over them."""
    cap = state.capacity
    with jax.named_scope("slot_scatter"):
        state, slots = _claim_slots(state, log, n_real)

    # graph construction stays ordered over the fresh rows only; rejected and
    # padded entries carry slot == cap and skip at runtime. The scan carries
    # just the graph arrays — vectors/ids/valid are loop invariants, so they
    # stay out of the carried (and cond-copied) state.
    def body(carry, slot):
        def insert(c):
            nbrs, lvls, ent = c
            st = dataclasses.replace(
                state, hnsw_neighbors=nbrs, hnsw_levels=lvls, hnsw_entry=ent)
            out = hnsw.hnsw_insert(
                st, slot, ef_construction=ef_construction, fast=True)
            return out.hnsw_neighbors, out.hnsw_levels, out.hnsw_entry

        return jax.lax.cond(slot < cap, insert, lambda c: c, carry), None

    carry0 = (state.hnsw_neighbors, state.hnsw_levels, state.hnsw_entry)
    with jax.named_scope("hnsw_insert"):
        (nbrs, lvls, ent), _ = jax.lax.scan(body, carry0, slots)
    return dataclasses.replace(
        state, hnsw_neighbors=nbrs, hnsw_levels=lvls, hnsw_entry=ent)


def _probe_slots(state: MemoryState, arg0: jax.Array):
    """Batched ``slot_of_id``: (found[n], slots[n]) against one state — the
    shared slot-resolution core of the delete and meta kernels."""
    match = (state.ids[None, :] == arg0[:, None]) & state.valid[None, :]
    return jnp.any(match, axis=1), jnp.argmax(match, axis=1).astype(jnp.int32)


@jax.jit
def _apply_delete_segment(state: MemoryState, arg0: jax.Array,
                          first_occ: jax.Array, n_real: jax.Array
                          ) -> MemoryState:
    """DELETE run: one vmapped id→slot probe + one batched tombstone scatter.
    ``first_occ`` (host-computed) keeps only the first delete of each id —
    later duplicates are sequential no-ops."""
    cap = state.capacity
    idx = jnp.arange(arg0.shape[0])
    found, slots = _probe_slots(state, arg0)
    do = found & first_occ & (idx < n_real)
    tgt = jnp.where(do, slots, cap)
    valid = state.valid.at[tgt].set(False, mode="drop")
    ids = state.ids.at[tgt].set(jnp.int64(-1), mode="drop")
    count = state.count - jnp.sum(do).astype(jnp.int32)
    # One entry repair at batch end == per-command repair under replay: in a
    # pure-DELETE run, each sequential repair picks the max-(raw level, -id)
    # node over a superset of the batch's final live set, and the final
    # repair keys on that final set alone — so the last choice is the same
    # either way (and both land on -1 when nothing survives).
    return hnsw.ensure_live_entry(dataclasses.replace(
        state, valid=valid, ids=ids, count=count,
        version=state.version + n_real))


@jax.jit
def _apply_meta_segment(state: MemoryState, arg0: jax.Array, arg1: jax.Array,
                        arg2: jax.Array, last_occ: jax.Array,
                        n_real: jax.Array) -> MemoryState:
    """SET_META run: one probe + one scatter. ``last_occ`` (host-computed on
    the clipped (id, meta-slot) key) realizes last-write-wins."""
    cap = state.capacity
    idx = jnp.arange(arg0.shape[0])
    found, slots = _probe_slots(state, arg0)
    mslot = jnp.clip(arg1, 0, state.meta.shape[1] - 1).astype(jnp.int32)
    do = found & last_occ & (idx < n_real)
    row = jnp.where(do, slots, cap)
    meta = state.meta.at[row, mslot].set(arg2, mode="drop")
    return dataclasses.replace(
        state, meta=meta, version=state.version + n_real)


@partial(jax.jit, static_argnames=("ef_construction",))
def _apply_seq_segment(state: MemoryState, log: CommandLog, n_real: jax.Array,
                       *, ef_construction: int) -> MemoryState:
    """Order-sensitive remainder (LINK/UNLINK runs, hazardous INSERTs): the
    definitional scan of F, minus the per-command version bump — NOP padding
    must not advance logical time."""
    def step(s, rec):
        op = jnp.clip(rec.opcode, 0, NUM_OPCODES - 1)
        branches = [partial(h, ef_construction=ef_construction)
                    for h in _HANDLERS]
        return jax.lax.switch(op, branches, s, rec), None

    out, _ = jax.lax.scan(step, state, log)
    return dataclasses.replace(out, version=state.version + n_real)


_BATCH_CHUNK = 512  # caps the [run, capacity] probe matrix in delete/meta


class _HostAllocator:
    """Exact host mirror of F's slot allocator, driven during segmentation.

    Tracks the live id→slot map, the free-slot min-heap (lowest-slot-first,
    like the device argmax over the free mask) and per-slot graph virginity.
    A slot that ever held a graph node keeps its stale inbound HNSW edges
    after deletion (soft delete), so pre-scattering a whole INSERT run would
    make the reused row visible to earlier searches in the run — sequential
    replay would still see it invalid. Fresh inserts landing on such slots
    are therefore hazards and take the sequential path."""

    def __init__(self, state: MemoryState):
        ids_h = obs.to_host(state.ids)
        valid_h = obs.to_host(state.valid)
        levels_h = obs.to_host(state.hnsw_levels)
        self.id2slot = {int(i): int(s)
                        for s, i in enumerate(ids_h) if valid_h[s]}
        self.free = [int(s) for s in np.nonzero(~valid_h)[0]]  # already sorted
        self.virgin = (levels_h < 0)

    def next_slot_virgin(self) -> bool:
        return (not self.free) or bool(self.virgin[self.free[0]])

    def insert(self, ext_id: int) -> None:
        if ext_id in self.id2slot:     # upsert: no allocation
            return
        if self.free:
            slot = heapq.heappop(self.free)
            self.id2slot[ext_id] = slot
            self.virgin[slot] = False
        # else: arena full, rejected

    def delete(self, ext_id: int) -> None:
        slot = self.id2slot.pop(ext_id, None)
        if slot is not None:
            heapq.heappush(self.free, slot)


def _segment_log(opcode, arg0, alloc: _HostAllocator):
    """Host-side pass: split the log into batched-kernel segments while
    simulating exactly the allocation bookkeeping F would perform, so
    hazards are detected wherever sequential replay would behave differently
    from a batch."""
    segments = []  # (kind, start, stop, aux)
    n = len(opcode)
    i = 0
    while i < n:
        op = int(opcode[i])
        if op == NOP:
            j = i
            while j < n and opcode[j] == NOP:
                j += 1
            segments.append(("nop", i, j, None))
        elif op == INSERT:
            j = i
            seg_ids = set()
            while j < n and opcode[j] == INSERT:
                a = int(arg0[j])
                if a in alloc.id2slot or a in seg_ids:
                    break  # upsert or duplicate ⇒ order matters ⇒ hazard
                if not alloc.next_slot_virgin():
                    break  # reused slot has stale inbound edges ⇒ hazard
                alloc.insert(a)
                seg_ids.add(a)
                j += 1
            if j > i:  # clean run
                segments.append(("insert", i, j, None))
            else:      # hazardous single insert → sequential segment
                alloc.insert(int(arg0[i]))
                j = i + 1
                segments.append(("seq", i, j, None))
        elif op == DELETE:
            j = min(i + _BATCH_CHUNK, n)
            k = i
            seen = set()
            first_occ = []
            while k < j and opcode[k] == DELETE:
                a = int(arg0[k])
                first_occ.append(a not in seen)
                seen.add(a)
                alloc.delete(a)
                k += 1
            segments.append(("delete", i, k, np.asarray(first_occ, bool)))
            j = k
        elif op == SET_META:
            j = min(i + _BATCH_CHUNK, n)
            k = i
            while k < j and opcode[k] == op:
                k += 1
            segments.append(("run", i, k, op))
            j = k
        else:  # LINK / UNLINK: order-sensitive ⇒ sequential kernel
            k = i
            while k < n and opcode[k] == op:
                k += 1
            segments.append(("seq", i, k, None))
            j = k
        i = j

    # coalesce adjacent sequential segments: a reuse-heavy log (every fresh
    # insert landing on a non-virgin slot) otherwise degrades to one jit
    # dispatch per command; merged, it is a single padded scan like replay's
    merged = []
    for seg in segments:
        if merged and seg[0] == "seq" and merged[-1][0] == "seq":
            merged[-1] = ("seq", merged[-1][1], seg[2], None)
        else:
            merged.append(seg)
    return merged


def bulk_apply(state: MemoryState, log: CommandLog,
               *, ef_construction: int = 32) -> MemoryState:
    """Apply a whole command log in batched form.

    Bit-identical to ``replay(state, log)`` — same final hash under
    ``hashing.hash_pytree`` — including upserts, tombstone reuse, full-arena
    rejections and ``version`` accounting (tests/test_bulk_apply.py), but
    with the write path vectorized as described in DESIGN.md §3."""
    n = len(log)
    if n == 0:
        return state

    # plan on the host: fetch the log, mirror the allocator, segment
    with obs.span("bulk_apply.plan"):
        opcode, arg0, arg1, arg2 = (obs.to_host(a) for a in (
            log.opcode, log.arg0, log.arg1, log.arg2))
        segments = _segment_log(opcode, arg0, _HostAllocator(state))
    with obs.span("bulk_apply.dispatch"):
        return _dispatch(state, log, segments, arg0, arg1, arg2,
                         ef_construction)


def _dispatch(state: MemoryState, log: CommandLog, segments, arg0, arg1,
              arg2, ef_construction: int) -> MemoryState:
    """Run each segment's batched program, in log order."""
    for kind, a, b, aux in segments:
        m = b - a
        n_real = jnp.int32(m)
        if kind == "nop":
            state = dataclasses.replace(state, version=state.version + m)
        elif kind == "insert":
            sub = _pad_log(log.slice(a, b), _pow2(m))
            state = _apply_insert_segment(state, sub, n_real,
                                          ef_construction=ef_construction)
        elif kind == "delete":
            width = _pow2(m)
            a0 = np.zeros((width,), np.int64)
            a0[:m] = arg0[a:b]
            occ = np.zeros((width,), bool)
            occ[:m] = aux
            state = _apply_delete_segment(state, jnp.asarray(a0),
                                          jnp.asarray(occ), n_real)
        elif kind == "run" and aux == SET_META:
            width = _pow2(m)
            a0 = np.zeros((width,), np.int64)
            a1 = np.zeros((width,), np.int64)
            a2 = np.zeros((width,), np.int64)
            a0[:m] = arg0[a:b]
            a1[:m] = arg1[a:b]
            a2[:m] = arg2[a:b]
            mslots = np.clip(a1[:m], 0, state.meta.shape[1] - 1)
            occ = np.zeros((width,), bool)
            seen = set()
            for t in range(m - 1, -1, -1):  # last write per (id, slot) wins
                key = (int(a0[t]), int(mslots[t]))
                occ[t] = key not in seen
                seen.add(key)
            state = _apply_meta_segment(state, jnp.asarray(a0),
                                        jnp.asarray(a1), jnp.asarray(a2),
                                        jnp.asarray(occ), n_real)
        else:  # "seq" and LINK/UNLINK runs
            sub = _pad_log(log.slice(a, b), _pow2(m))
            state = _apply_seq_segment(state, sub, n_real,
                                       ef_construction=ef_construction)
    return state
