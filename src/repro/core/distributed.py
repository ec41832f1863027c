"""Sharded deterministic memory (DESIGN.md §2 — the paper's claim at pod scale).

The Rust kernel is single-node. At pod scale the arena is sharded row-wise
across the ``model`` mesh axis; queries are sharded across ``data``. The key
observation carried over from the paper: every cross-device combine here is
an *integer* collective (all-gather of wide scores + ids, then a sort-merge),
and integer collectives are exact and order-invariant — so the distributed
memory inherits bit-determinism from the arithmetic, not from scheduling.

Command routing is deterministic too: a command for external id ``i`` belongs
to shard ``splitmix64(i) mod n_shards``; each shard replays its own sub-log.
tests/test_distributed.py verifies that a multi-device shard_map run returns
search results bit-identical to the single-device kernel.

Layout: the distributed state reuses the MemoryState dataclass, with
* row arrays laid out shard-major: global row = shard * cap_per_shard + local;
* per-shard scalars (cursor/count/version/hnsw_entry) carried as [n_shards]
  arrays (each shard is its own little Valori kernel with its own clock).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import machine, search
from repro.core.commands import NOP, CommandLog
from repro.core.hnsw import splitmix64
from repro.core.state import MemoryState, init_state

INF = search.INF


# --------------------------------------------------------------------------- #
# deterministic command routing
# --------------------------------------------------------------------------- #


def shard_of_id(ext_id, n_shards: int):
    """Shard owner of an external id — pure integer hash, platform-invariant."""
    return (splitmix64(jnp.asarray(ext_id, jnp.int64).astype(jnp.uint64))
            % jnp.uint64(n_shards)).astype(jnp.int32)


def route_commands(log: CommandLog, n_shards: int) -> CommandLog:
    """Split a global log into per-shard logs, NOP-padded to equal length:
    fields gain a leading [n_shards] axis. Relative order within a shard is
    preserved, so per-shard replay equals filtering the global replay."""
    opcode = np.asarray(log.opcode)
    arg0 = np.asarray(log.arg0)
    n = len(opcode)
    owners = np.asarray(shard_of_id(jnp.asarray(arg0), n_shards))

    per_shard_idx = [[] for _ in range(n_shards)]
    for i in range(n):
        per_shard_idx[int(owners[i])].append(i)
    max_len = max([len(ix) for ix in per_shard_idx] + [1])

    def pad_take(arr: np.ndarray, idx) -> np.ndarray:
        taken = arr[idx] if len(idx) else arr[:0]
        pad_shape = (max_len - len(idx),) + arr.shape[1:]
        return np.concatenate([taken, np.zeros(pad_shape, arr.dtype)], axis=0)

    fields = {}
    for name in ("opcode", "arg0", "arg1", "arg2", "vec"):
        arr = np.asarray(getattr(log, name))
        fields[name] = jnp.asarray(np.stack([pad_take(arr, ix) for ix in per_shard_idx]))
    lengths = jnp.asarray([len(ix) for ix in per_shard_idx])
    fields["opcode"] = jnp.where(
        jnp.arange(max_len)[None, :] < lengths[:, None], fields["opcode"], NOP
    ).astype(jnp.int32)
    return CommandLog(**fields)


# --------------------------------------------------------------------------- #
# sharded state construction + specs
# --------------------------------------------------------------------------- #


def init_sharded_host(n_shards: int, capacity_per_shard: int, dim: int,
                      **kwargs) -> MemoryState:
    """Empty sharded-layout state (shard-major rows, [n_shards] per-shard
    scalars) as plain host/default-device arrays — no mesh required. This
    is the genesis a ``shard_wal.ShardedDurableStore`` slices per shard;
    ``init_sharded_state`` lays the same state out over a mesh."""
    proto = init_state(capacity_per_shard, dim, **kwargs)

    def rep(x):  # per-shard scalar → [n_shards]
        return jnp.broadcast_to(x[None], (n_shards,) + x.shape)

    return dataclasses.replace(
        proto,
        vectors=jnp.tile(proto.vectors, (n_shards, 1)),
        ids=jnp.tile(proto.ids, (n_shards,)),
        valid=jnp.tile(proto.valid, (n_shards,)),
        links=jnp.tile(proto.links, (n_shards, 1)),
        meta=jnp.tile(proto.meta, (n_shards, 1)),
        hnsw_neighbors=jnp.tile(proto.hnsw_neighbors, (1, n_shards, 1)),
        hnsw_levels=jnp.tile(proto.hnsw_levels, (n_shards,)),
        hnsw_entry=rep(proto.hnsw_entry),
        cursor=rep(proto.cursor),
        count=rep(proto.count),
        version=rep(proto.version),
    )


def init_sharded_state(mesh: Mesh, axis: str, capacity_per_shard: int, dim: int,
                       **kwargs) -> MemoryState:
    state = init_sharded_host(mesh.shape[axis], capacity_per_shard, dim,
                              **kwargs)
    specs = state_specs(axis, state.contract_name)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), state, specs
    )


def state_specs(axis: str, contract_name: str) -> MemoryState:
    """PartitionSpecs for the sharded MemoryState layout described above."""
    return MemoryState(
        vectors=P(axis, None),
        ids=P(axis),
        valid=P(axis),
        links=P(axis, None),
        meta=P(axis, None),
        hnsw_neighbors=P(None, axis, None),
        hnsw_levels=P(axis),
        hnsw_entry=P(axis),
        cursor=P(axis),
        count=P(axis),
        version=P(axis),
        contract_name=contract_name,
    )


def _log_specs(axis: str) -> CommandLog:
    return CommandLog(
        opcode=P(axis, None), arg0=P(axis, None), arg1=P(axis, None),
        arg2=P(axis, None), vec=P(axis, None, None),
    )


def _to_local(state: MemoryState) -> MemoryState:
    """Inside shard_map: strip the local leading shard dim from scalars."""
    return dataclasses.replace(
        state,
        hnsw_entry=state.hnsw_entry[0], cursor=state.cursor[0],
        count=state.count[0], version=state.version[0],
    )


def _to_shardview(state: MemoryState) -> MemoryState:
    return dataclasses.replace(
        state,
        hnsw_entry=state.hnsw_entry[None], cursor=state.cursor[None],
        count=state.count[None], version=state.version[None],
    )


# --------------------------------------------------------------------------- #
# sharded replay + search via shard_map
# --------------------------------------------------------------------------- #


def distributed_replay(mesh: Mesh, axis: str, state: MemoryState,
                       routed_log: CommandLog, *, ef_construction: int = 32
                       ) -> MemoryState:
    """Replay per-shard logs on their shards (no cross-shard traffic: ids are
    hash-routed, so shards never contend)."""
    specs = state_specs(axis, state.contract_name)

    @partial(jax.shard_map, mesh=mesh, in_specs=(specs, _log_specs(axis)),
             out_specs=specs, check_vma=False)
    def _replay(local_state: MemoryState, local_log: CommandLog) -> MemoryState:
        local_log = jax.tree.map(lambda a: a[0], local_log)  # drop shard dim
        out = machine.replay(_to_local(local_state), local_log,
                             ef_construction=ef_construction)
        return _to_shardview(out)

    return _replay(state, routed_log)


def shard_live_counts(state: MemoryState, n_shards: int) -> np.ndarray:
    """Per-shard live-row counts of a sharded-layout state, derived from the
    ``valid`` mask (cross-checkable against the per-shard ``count`` scalars)
    — the shard-balance diagnostic for the serve engine's sequential id
    allocation, and a planner-facing host fact."""
    return np.asarray(state.valid).reshape(n_shards, -1).sum(axis=1)


def shard_slice(state: MemoryState, s: int, n_shards: int) -> MemoryState:
    """Shard ``s`` of a shard-major sharded-layout state as a plain
    single-kernel MemoryState (host-side view; inverse of ``merge_shards``)."""
    cap = state.capacity // n_shards
    lo, hi = s * cap, (s + 1) * cap
    return dataclasses.replace(
        state,
        vectors=state.vectors[lo:hi], ids=state.ids[lo:hi],
        valid=state.valid[lo:hi], links=state.links[lo:hi],
        meta=state.meta[lo:hi],
        hnsw_neighbors=state.hnsw_neighbors[:, lo:hi],
        hnsw_levels=state.hnsw_levels[lo:hi],
        hnsw_entry=state.hnsw_entry[s], cursor=state.cursor[s],
        count=state.count[s], version=state.version[s],
    )


def merge_shards(shards) -> MemoryState:
    """Reassemble per-shard kernel states into the sharded layout (row
    arrays concatenated shard-major, per-shard scalars stacked)."""
    def cat(field):
        return jnp.concatenate([getattr(sh, field) for sh in shards], axis=0)

    def stack_scalar(field):
        return jnp.stack([getattr(sh, field) for sh in shards])

    return dataclasses.replace(
        shards[0],
        vectors=cat("vectors"), ids=cat("ids"), valid=cat("valid"),
        links=cat("links"), meta=cat("meta"),
        hnsw_neighbors=jnp.concatenate(
            [sh.hnsw_neighbors for sh in shards], axis=1),
        hnsw_levels=cat("hnsw_levels"),
        hnsw_entry=stack_scalar("hnsw_entry"), cursor=stack_scalar("cursor"),
        count=stack_scalar("count"), version=stack_scalar("version"),
    )


def distributed_bulk_apply(mesh: Mesh, axis: str, state: MemoryState,
                           routed_log: CommandLog, *, ef_construction: int = 32
                           ) -> MemoryState:
    """Apply routed per-shard logs through ``machine.bulk_apply``.

    Each shard is its own little Valori kernel, so bulk-apply runs per shard
    on its local slice — the segmentation driver is host-side, which is
    exactly where the routing table already lives. The result is
    hash-identical to ``distributed_replay`` on the same routed log, shard
    by shard (the per-shard equivalence is machine.bulk_apply's contract);
    the NOP padding ``route_commands`` adds folds into a version bump.

    Trade-off vs ``distributed_replay``: shards are processed sequentially
    on the host and the result is materialized unsharded (≈1 extra arena
    copy on the default device) before the final re-shard — the ingest win
    is per-shard vectorization, not cross-shard parallelism. For arenas too
    big to stage on one host, use ``distributed_replay``; for the mesh-free
    layout, ``shard_wal.apply_routed_device`` now runs the whole routed
    apply as one vmapped device scan with no per-shard host loop
    (DESIGN.md §11).
    """
    n_shards = mesh.shape[axis]

    shards = []
    for s in range(n_shards):
        local = shard_slice(state, s, n_shards)
        local_log = jax.tree.map(lambda a, s=s: a[s], routed_log)
        shards.append(machine.bulk_apply(local, local_log,
                                         ef_construction=ef_construction))

    out = merge_shards(shards)
    specs = state_specs(axis, state.contract_name)
    return jax.tree.map(
        lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)), out, specs)


def distributed_hnsw_search(mesh: Mesh, axis: str, state: MemoryState,
                            queries_raw: jax.Array, k: int, *, ef: int = 64,
                            query_axis: str | None = None
                            ) -> Tuple[jax.Array, jax.Array]:
    """ANN across shards: each shard runs its deterministic HNSW graph
    (vmapped beam search), candidates merge with the same exact integer sort
    as the flat path — the IVF-style latency configuration of the paper's
    index at pod scale. Per-shard graphs are built incrementally by
    distributed_replay, so replaying the same routed log on any mesh gives
    identical graphs and hence identical results."""
    specs = state_specs(axis, state.contract_name)
    qspec = P(query_axis, None)
    out_spec = P(query_axis, None)

    from repro.core import query as query_lib  # lazy: query imports us lazily

    @partial(jax.shard_map, mesh=mesh, in_specs=(specs, qspec),
             out_specs=(out_spec, out_spec), check_vma=False)
    def _search(local_state: MemoryState, q: jax.Array):
        local = _to_local(local_state)
        ids, dists, _ = query_lib.batched_hnsw_search(local, q, k, ef=ef)
        all_ids = jax.lax.all_gather(ids, axis)       # [n_shards, nq, k]
        all_d = jax.lax.all_gather(dists, axis)
        nq = q.shape[0]
        flat_ids = jnp.moveaxis(all_ids, 0, 1).reshape(nq, -1)
        flat_d = jnp.moveaxis(all_d, 0, 1).reshape(nq, -1)
        d_out, i_out = search.merge_candidates(flat_d, flat_ids, k)
        return i_out, d_out

    return _search(state, queries_raw)


# --------------------------------------------------------------------------- #
# per-shard snapshots under one merged manifest (DESIGN.md §5)
# --------------------------------------------------------------------------- #

SHARDED_MAGIC = b"VLRS"
SHARDED_FORMAT = 1


def snapshot_sharded(state: MemoryState, n_shards: int, store, *,
                     chunk_size: int | None = None) -> bytes:
    """Write one v2 snapshot per shard into ``store`` (a
    ``snapshot.ChunkStore``) and return a merged manifest whose combined
    hash is the hash of the whole sharded-layout state — the same value a
    single host computes over the assembled arenas, so a pod and a
    single-kernel holder of identical content agree on one number.

    Shards share the chunk store: identical chunks (e.g. untouched empty
    arena regions) are stored once across all shards."""
    import struct

    from repro.core import hashing as hashing_lib
    from repro.core import snapshot as snapshot_lib

    chunk_size = chunk_size or snapshot_lib.DEFAULT_CHUNK_SIZE
    parts = []
    for s in range(n_shards):
        manifest, _ = snapshot_lib.snapshot_v2(
            shard_slice(state, s, n_shards), store, chunk_size=chunk_size)
        parts.append(manifest)
    combined = hashing_lib.hash_pytree(state)
    out = [SHARDED_MAGIC, struct.pack("<II", SHARDED_FORMAT, n_shards),
           struct.pack("<Q", combined)]
    for m in parts:
        out.append(struct.pack("<Q", len(m)))
        out.append(m)
    return b"".join(out)


def restore_sharded(data: bytes, store) -> Tuple[MemoryState, int]:
    """Restore a merged manifest: per-shard v2 restores, reassembled with
    ``merge_shards``; verifies the combined hash. Returns (state, hash)."""
    import struct

    from repro.core import hashing as hashing_lib
    from repro.core import snapshot as snapshot_lib

    if data[:4] != SHARDED_MAGIC:
        raise ValueError("not a sharded Valori snapshot manifest")
    fmt, n_shards = struct.unpack_from("<II", data, 4)
    if fmt != SHARDED_FORMAT:
        raise ValueError(f"unsupported sharded manifest format {fmt}")
    (stored,) = struct.unpack_from("<Q", data, 12)
    off = 20
    shards = []
    for _ in range(n_shards):
        (n,) = struct.unpack_from("<Q", data, off)
        off += 8
        shard, _ = snapshot_lib.restore_v2(data[off:off + n], store)
        off += n
        shards.append(shard)
    state = merge_shards(shards)
    actual = hashing_lib.hash_pytree(state)
    if actual != stored:
        raise ValueError(
            f"sharded snapshot combined-hash mismatch: stored {stored:#x}, "
            f"got {actual:#x}")
    return state, actual


def distributed_search(mesh: Mesh, axis: str, state: MemoryState,
                       queries_raw: jax.Array, k: int, *,
                       metric: str = search.METRIC_L2, use_kernel: bool = False,
                       query_axis: str | None = None
                       ) -> Tuple[jax.Array, jax.Array]:
    """Exact k-NN across all shards: local top-k, all-gather, sort-merge.

    Integer-only combine ⇒ results (ids, scores, tie order) are independent
    of shard count and identical to the single-kernel answer.
    """
    specs = state_specs(axis, state.contract_name)
    qspec = P(query_axis, None)
    out_spec = P(query_axis, None)

    @partial(jax.shard_map, mesh=mesh, in_specs=(specs, qspec),
             out_specs=(out_spec, out_spec), check_vma=False)
    def _search(local_state: MemoryState, q: jax.Array):
        ids, scores = search.exact_search(
            _to_local(local_state), q, k, metric=metric, use_kernel=use_kernel
        )
        all_ids = jax.lax.all_gather(ids, axis)       # [n_shards, nq, k]
        all_scores = jax.lax.all_gather(scores, axis)
        nq = q.shape[0]
        flat_ids = jnp.moveaxis(all_ids, 0, 1).reshape(nq, -1)
        flat_scores = jnp.moveaxis(all_scores, 0, 1).reshape(nq, -1)
        s_out, i_out = search.merge_candidates(flat_scores, flat_ids, k)
        return i_out, s_out

    return _search(state, queries_raw)
