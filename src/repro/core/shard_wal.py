"""Per-shard WALs under one global clock (DESIGN.md §6).

PR 3 made a single-host log durable; this module makes *distributed*
ingest durable without that single-host log. Each shard owns a full
``durability.DurableStore`` (its own hash-chained WAL + chunked snapshots);
a ``ShardedDurableStore`` keeps the fleet in lockstep on one global
applied-command cursor ``t``:

  * every appended batch is routed with ``distributed.route_commands``
    (pure integer id hash) and NOP-padded to one common length, so every
    shard's WAL advances by exactly the same amount per batch — per-shard
    cursors are the global cursor;
  * a group commit (``append_many``, the sink ``wal.GroupCommitWriter``
    drives) flushes each shard's share of the group under one fsync per
    shard;
  * recovery reconciles: each shard recovers its own durable prefix, the
    global cursor is the *minimum* (a command is globally durable only
    when every shard's share of its batch is), and shards that got ahead —
    a crash landed between per-shard flushes — roll their never-globally-
    acked suffix back with ``DurableStore.rollback_to``. The torn-group
    contract thus extends across shards: recovery lands on the last
    globally-whole batch boundary prefix, never a partial group;
  * the merged restore verifies one number: ``hash_pytree`` of the merged
    sharded-layout state, the same whole-state hash ``snapshot_sharded``'s
    merged manifest carries — a pod and a single-kernel holder of the same
    content agree on it.

Shards share one content-addressed ``ChunkStore`` (identical chunks — e.g.
untouched arena regions — are stored once across shards); the sharded
store owns the cross-shard sweep, per-shard ``retain`` never deletes a
chunk another shard still references.

Layout of a store directory:
  store.json                 n_shards
  chunks/<key:016x>.chk      chunk store shared by all shards
  merged/t_<t:020d>.json     global-cursor records: {"t", "hash"}
  shard_<s:04d>/             a full DurableStore per shard (own WAL,
                             snapshots, store.json; chunks redirected up)
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import distributed, hashing, hnsw, machine, search, snapshot, wal
from repro.core.commands import CommandLog
from repro.core.durability import _RESTORE_ERRORS, DurableStore
from repro.core.state import MemoryState


class ShardedDurableStore:
    """n_shards lockstep ``DurableStore``s under one global cursor.

    Invariant (healthy store): every shard's durable cursor equals the
    global ``t``, and ``restore_at(t)`` merged across shards is hash-
    identical to applying the same routed batches to a fresh sharded
    genesis — the sharded twin of ``DurableStore``'s replay contract.
    """

    def __init__(self, directory: str | os.PathLike,
                 genesis: Optional[MemoryState] = None, *,
                 n_shards: Optional[int] = None,
                 chunk_size: int = snapshot.DEFAULT_CHUNK_SIZE,
                 segment_records: int = 1024,
                 compaction: Optional[wal.CompactionPolicy] = None,
                 backends: Optional[Sequence] = None):
        """``backends`` makes the store transport-pluggable: instead of
        creating local per-shard ``DurableStore``s, the coordinator drives
        the given shard handles — anything with the ``DurableStore``
        surface (``append_many`` / ``checkpoint`` / ``restore_at`` /
        ``recover`` / ``rollback_to`` / ``retain`` / ``t`` /
        ``wal.read_range``), in practice ``net.RemoteShardClient``s over
        subprocess shard hosts. The directory then holds only the
        coordinator's own artifacts (store.json, merged-hash records);
        each backend owns its chunks and sweeps them itself."""
        self.dir = pathlib.Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        meta_path = self.dir / "store.json"

        if backends is not None:
            if n_shards is not None and n_shards != len(backends):
                raise ValueError(
                    f"{len(backends)} backends given, n_shards={n_shards}")
            n_shards = len(backends)
        if meta_path.exists():
            meta = json.loads(meta_path.read_text())
            if n_shards is not None and n_shards != meta["n_shards"]:
                raise ValueError(
                    f"store has {meta['n_shards']} shards, {n_shards} given")
            n_shards = meta["n_shards"]
        else:
            if n_shards is None or (genesis is None and backends is None):
                raise ValueError(
                    f"{self.dir} is not a ShardedDurableStore and no "
                    "(genesis, n_shards) was given to create one")
            tmp = meta_path.with_suffix(".tmp")
            with open(tmp, "w") as f:  # tmp+fsync+rename: a crash leaves a
                f.write(json.dumps({"n_shards": n_shards}))  # stale .tmp,
                f.flush()                                    # never a torn
                os.fsync(f.fileno())                         # store.json
            tmp.rename(meta_path)

        self.n_shards = n_shards
        self._merged_dir = self.dir / "merged"
        self._merged_dir.mkdir(exist_ok=True)
        if backends is not None:
            self.chunks = None  # each backend owns (and sweeps) its chunks
            self.shards = list(backends)
        else:
            self.chunks = snapshot.ChunkStore(self.dir / "chunks")
            self.shards: List[DurableStore] = [
                DurableStore(
                    self.dir / f"shard_{s:04d}",
                    distributed.shard_slice(genesis, s, n_shards)
                    if genesis is not None else None,
                    chunk_size=chunk_size, segment_records=segment_records,
                    compaction=compaction, chunks=self.chunks)
                for s in range(n_shards)
            ]

    # ------------------------------------------------------------------ #
    # the global command stream
    # ------------------------------------------------------------------ #

    @property
    def t(self) -> int:
        """Globally durable logical time: the minimum shard cursor (a
        command counts only once every shard's share of its batch is on
        disk). Equal to every shard's cursor in a healthy store."""
        return min(s.t for s in self.shards)

    def shard_ts(self) -> List[int]:
        """Per-shard durable cursors (diagnostic; all equal when healthy)."""
        return [s.t for s in self.shards]

    def planned_advance(self, log: CommandLog) -> int:
        """Global-cursor advance appending ``log`` will cause: the batch's
        NOP-padded common per-shard length (its heaviest shard's share,
        min 1) — what ``GroupCommitWriter.target_t`` must add per batch
        instead of the raw command count."""
        if len(log) == 0:
            return 0
        owners = np.asarray(distributed.shard_of_id(
            jnp.asarray(np.asarray(log.arg0)), self.n_shards))
        counts = np.bincount(owners, minlength=self.n_shards)
        return max(int(counts.max()), 1)

    def append(self, log: CommandLog, *,
               routed: Optional[CommandLog] = None) -> int:
        """Route one global batch to the shards and durably append each
        share (one fsync per shard); returns the new global cursor. Every
        shard advances by the batch's common padded length. A caller that
        already routed the batch passes ``routed`` to skip re-routing."""
        if routed is not None and len(log):
            return self.append_many_routed([routed])
        return self.append_many([log])

    def append_many(self, logs: Sequence[CommandLog]) -> int:
        """Group commit across shards: each batch is routed exactly as
        ``append`` would route it (per-batch NOP padding, so cursors are
        identical whether or not batches were grouped), then each shard
        commits its whole share of the group under one fsync. Shards are
        flushed in shard order — a crash mid-flush leaves a *prefix* of
        shards with the group, which ``recover()`` rolls back to the last
        globally-complete point."""
        logs = [log for log in logs if len(log)]
        if not logs:
            return self.t
        return self.append_many_routed(
            [distributed.route_commands(log, self.n_shards) for log in logs])

    def append_many_routed(self, routed_logs: Sequence[CommandLog]) -> int:
        """``append_many`` minus the re-route: batches arrive as the
        ``[n_shards, L]`` shares ``distributed.route_commands`` emits (the
        serve engine routes once for audit + apply + durability). Same
        refusal discipline, same per-shard fsync, same bytes. Callers must
        route with this store's shard count and filter empty batches
        themselves (routing pads an empty batch to one NOP, which would
        advance the cursor)."""
        routed_logs = list(routed_logs)
        if not routed_logs:
            return self.t
        for r in routed_logs:
            if r.opcode.shape[0] != self.n_shards:
                raise ValueError(
                    f"routed batch has {r.opcode.shape[0]} shares, store "
                    f"has {self.n_shards} shards")
        # refuse BEFORE anything is fsynced: appending to an unreconciled
        # post-crash store would durably put different batches at the same
        # logical offset on different shards — run recover() first
        if len(set(self.shard_ts())) != 1:
            raise RuntimeError(
                f"shard cursors diverged ({self.shard_ts()}): the store "
                "needs recover() before it can accept new appends")
        per_shard: List[List[CommandLog]] = [
            [jax.tree.map(lambda a, s=s: a[s], r) for r in routed_logs]
            for s in range(self.n_shards)]
        ts = [self.shards[s].append_many(per_shard[s])
              for s in range(self.n_shards)]
        assert len(set(ts)) == 1, f"lockstep violated: {ts}"
        return ts[0]

    # ------------------------------------------------------------------ #
    # checkpoints + the merged-hash contract
    # ------------------------------------------------------------------ #

    def _merged_path(self, t: int) -> pathlib.Path:
        return self._merged_dir / f"t_{t:020d}.json"

    def merged_records(self) -> List[int]:
        """Cursors with a recorded merged whole-state hash, ascending."""
        return sorted(int(p.stem.split("_")[1])
                      for p in self._merged_dir.glob("t_*.json"))

    def checkpoint(self, state: MemoryState) -> Dict[str, int]:
        """Snapshot a sharded-layout state: one v2 snapshot per shard (into
        the shared chunk store) plus a merged record carrying the whole-
        state hash — the same combined-hash contract as
        ``distributed.snapshot_sharded``, so restore can verify the merge
        against one number. The state's per-shard cursors must agree (a
        mid-batch or diverged state is not a global checkpoint)."""
        host = jax.tree.map(np.asarray, state)
        versions = {int(v) for v in np.asarray(host.version)}
        if len(versions) != 1:
            raise ValueError(
                f"per-shard cursors disagree ({sorted(versions)}): "
                "checkpoint only at global batch boundaries")
        t = versions.pop()
        stats: Dict[str, int] = {"t": t, "bytes_written": 0}
        for s in range(self.n_shards):
            sh = self.shards[s].checkpoint(
                distributed.shard_slice(host, s, self.n_shards))
            stats["bytes_written"] += sh.get("bytes_written", 0)
        record = {"t": t, "hash": f"{hashing.hash_pytree(host):#018x}"}
        tmp = self._merged_path(t).with_suffix(".tmp")
        with open(tmp, "w") as f:
            f.write(json.dumps(record))
            f.flush()
            os.fsync(f.fileno())
        tmp.rename(self._merged_path(t))
        return stats

    def _verify_merged(self, t: int, h: int) -> None:
        path = self._merged_path(t)
        if not path.exists():
            return
        stored = int(json.loads(path.read_text())["hash"], 16)
        if stored != h:
            raise ValueError(
                f"merged-state hash mismatch at t={t}: manifest "
                f"{stored:#x}, restored {h:#x}")

    # ------------------------------------------------------------------ #
    # restore + recovery
    # ------------------------------------------------------------------ #

    def restore_at(self, t: int, *, ef_construction: int = 32
                   ) -> Tuple[MemoryState, int]:
        """The merged sharded-layout state as of global command ``t`` —
        each shard restores its own cursor-``t`` state (nearest snapshot +
        WAL tail), the merge is hash-verified against the merged record
        when one exists at ``t``. Returns (state, hash)."""
        parts = [s.restore_at(t, ef_construction=ef_construction)[0]
                 for s in self.shards]
        state = distributed.merge_shards(parts)
        h = hashing.hash_pytree(state)
        self._verify_merged(t, h)
        return state, h

    def recover(self, *, ef_construction: int = 32
                ) -> Tuple[MemoryState, int, int]:
        """Crash recovery with cross-shard reconciliation. Each shard
        recovers its own durable prefix; the global cursor is the minimum
        (commands beyond it were never globally acked); shards that got
        ahead — the crash hit between per-shard group flushes — roll back
        their unacked suffix so the fleet rejoins lockstep. Returns
        (merged state, hash, t); the hash is verified against the merged
        record when one exists at the reconciled cursor.

        Reconciliation is transport-agnostic: it drives only the backend
        surface (``recover`` / ``t`` / ``rollback_to``), and the wire
        client maps server refusals into the same exception families the
        local error envelopes catch (``net.RemoteError`` is a ValueError,
        ``net.TransportError`` an OSError — both in ``_RESTORE_ERRORS``).
        A remote shard reporting a stale cursor therefore rolls the ahead
        shards back exactly as a local one does (the regression
        tests/test_replication.py pins)."""
        ts = []
        for s, shard in enumerate(self.shards):
            try:
                ts.append(shard.recover(
                    ef_construction=ef_construction)[2])
            except _RESTORE_ERRORS as e:
                raise ValueError(
                    f"shard {s} has no recoverable state") from e
        t = min(ts)
        for s, shard in enumerate(self.shards):
            if shard.t > t:
                try:
                    shard.rollback_to(t)
                except ValueError as e:
                    raise ValueError(
                        f"shard {s} cannot rejoin the global cursor t={t} "
                        f"(its durable history has a hole there); the "
                        f"store is irreconcilable without that history"
                    ) from e
        state, h = self.restore_at(t, ef_construction=ef_construction)
        return state, h, t

    def rollback_to(self, t: int) -> None:
        """Drop every durable artifact above global time ``t`` on every
        shard (per-shard ``DurableStore.rollback_to``), then prune merged
        records above ``t`` — the sharded twin of the single-store
        rollback, used by the serve engine's time travel. A failure
        partway through the shard loop leaves cursors diverged exactly
        like a crash between per-shard flushes would; ``recover()``
        reconciles it the same way (min cursor, ahead shards roll back)."""
        if t > self.t:
            raise ValueError(f"rollback_to({t}) is ahead of the globally "
                             f"durable cursor {self.t}")
        for shard in self.shards:
            if shard.t > t:
                shard.rollback_to(t)
        for rec_t in self.merged_records():
            if rec_t > t:
                self._merged_path(rec_t).unlink()

    def shard_logs(self, t0: int, t1: int) -> List[CommandLog]:
        """Each shard's durable commands [t0, t1) — the per-shard audit
        logs (routed, NOP-padded to lockstep). Replaying shard ``s``'s log
        on its genesis slice re-derives its exact state: the sharded form
        of the single-host replay audit. Raises ValueError when retention
        dropped that history on any shard."""
        return [s.wal.read_range(t0, t1) for s in self.shards]

    # ------------------------------------------------------------------ #
    # retention
    # ------------------------------------------------------------------ #

    def retain(self, keep: int) -> Dict[str, int]:
        """Keep the newest ``keep`` snapshots per shard, then sweep shared
        chunks no *surviving manifest of any shard* references — the cross-
        shard gc a per-shard retain cannot safely do. Merged records below
        the new window are pruned with the snapshots they described."""
        stats = {"snapshots_dropped": 0, "wal_segments_dropped": 0,
                 "chunks_dropped": 0}
        oldest_parts = []
        for shard in self.shards:
            sh = shard.retain(keep)
            stats["snapshots_dropped"] += sh["snapshots_dropped"]
            stats["wal_segments_dropped"] += sh["wal_segments_dropped"]
            oldest_parts.append(sh["oldest_snapshot"])
            if self.chunks is None:
                # remote backends own their chunks and already swept them;
                # their per-shard counts roll up instead of a local sweep
                stats["chunks_dropped"] += sh.get("chunks_dropped", 0)
        if self.chunks is not None:
            referenced = set()
            for shard in self.shards:
                referenced |= shard.referenced_chunk_keys()
            for key in self.chunks.keys():
                if key not in referenced:
                    self.chunks.delete(key)
                    stats["chunks_dropped"] += 1
        oldest = min(oldest_parts, default=0)
        for t in self.merged_records():
            if t < oldest:
                self._merged_path(t).unlink()
        return stats


# --------------------------------------------------------------------------- #
# host-side sharded apply + search (the mesh-free twins of distributed.py)
# --------------------------------------------------------------------------- #


def live_count(state: MemoryState) -> int:
    """Total live rows of a MemoryState in either layout (flat scalar
    ``count`` or sharded ``[n_shards]`` counts) — the planner fact the
    serve engine feeds ``query.plan_query`` regardless of mode."""
    return int(obs.to_host(state.count).sum())


def bulk_apply_sharded(state: MemoryState, log: CommandLog, n_shards: int,
                       *, ef_construction: int = 32,
                       routed: Optional[CommandLog] = None,
                       device: Optional[bool] = None) -> MemoryState:
    """Route a global batch and apply each shard's share to its slice of a
    sharded-layout state — the in-memory reference for what a
    ``ShardedDurableStore`` ingest makes durable: applying the same batches
    here and recovering the store yield hash-identical merged states.
    Callers that already routed the batch (the serve engine routes once for
    audit + apply + append) pass ``routed`` to skip re-routing.

    ``device`` picks the apply driver. ``True`` runs every shard's share in
    one jitted vmapped scan on device (``apply_routed_device``); ``False``
    runs the host loop of per-shard ``machine.bulk_apply`` (whose
    segmentation planner is host-side but wins on long shares); ``None``
    (default) auto-selects: device for shares up to ``_DEVICE_APPLY_MAX``
    commands — the serve-traffic regime — host for bulk loads beyond it.
    All three are bit-identical (``bulk_apply == replay`` is proven by
    tests/test_bulk_apply.py; the device path IS the replay scan)."""
    if routed is None:
        routed = distributed.route_commands(log, n_shards)
    if device is None:
        device = int(routed.opcode.shape[1]) <= _DEVICE_APPLY_MAX
    if device:
        return apply_routed_device(state, routed, n_shards,
                                   ef_construction=ef_construction)
    parts = []
    for s in range(n_shards):
        local = distributed.shard_slice(state, s, n_shards)
        local_log = jax.tree.map(lambda a, s=s: a[s], routed)
        parts.append(machine.bulk_apply(local, local_log,
                                        ef_construction=ef_construction))
    return distributed.merge_shards(parts)


# --------------------------------------------------------------------------- #
# device-side routed apply (DESIGN.md §11): no host round-trip per shard
# --------------------------------------------------------------------------- #

# auto-route threshold: shares at or under this many commands take the
# vmapped device scan; longer shares amortize bulk_apply's host-side
# segmentation planner instead
_DEVICE_APPLY_MAX = 128


def shard_stack(state: MemoryState, n_shards: int) -> MemoryState:
    """Sharded layout → stacked layout: every array gains a leading
    [n_shards] axis whose lanes are exactly ``distributed.shard_slice``'s
    per-shard states (pure reshapes/transposes, no copies of row data).
    The result is a vmap-ready pytree, not a valid flat MemoryState —
    ``shard_unstack`` is the inverse."""
    cap = state.capacity // n_shards

    def rows(a):  # [n_shards*cap, ...] → [n_shards, cap, ...]
        return a.reshape((n_shards, cap) + a.shape[1:])

    nb = state.hnsw_neighbors  # [levels, n_shards*cap, degree]
    nb = jnp.moveaxis(
        nb.reshape(nb.shape[0], n_shards, cap, nb.shape[2]), 1, 0)
    return dataclasses.replace(
        state,
        vectors=rows(state.vectors), ids=rows(state.ids),
        valid=rows(state.valid), links=rows(state.links),
        meta=rows(state.meta), hnsw_neighbors=nb,
        hnsw_levels=rows(state.hnsw_levels),
        # hnsw_entry / cursor / count / version are already [n_shards]
    )


def shard_unstack(stacked: MemoryState, n_shards: int) -> MemoryState:
    """Inverse of ``shard_stack``: back to the shard-major sharded layout."""
    def rows(a):  # [n_shards, cap, ...] → [n_shards*cap, ...]
        return a.reshape((-1,) + a.shape[2:])

    nb = jnp.moveaxis(stacked.hnsw_neighbors, 0, 1)  # [lv, ns, cap, deg]
    nb = nb.reshape(nb.shape[0], -1, nb.shape[3])
    return dataclasses.replace(
        stacked,
        vectors=rows(stacked.vectors), ids=rows(stacked.ids),
        valid=rows(stacked.valid), links=rows(stacked.links),
        meta=rows(stacked.meta), hnsw_neighbors=nb,
        hnsw_levels=rows(stacked.hnsw_levels),
    )


def _pad_routed(routed: CommandLog, target: int) -> CommandLog:
    """NOP-pad every shard's share from its routed length to ``target``
    (pow2 buckets keep jit shapes logarithmic, exactly like
    ``machine._pad_log``). All-zero records are NOPs."""
    n = int(routed.opcode.shape[1])
    if n == target:
        return routed
    pad = target - n
    ns = int(routed.opcode.shape[0])

    def z(a):
        return jnp.concatenate(
            [a, jnp.zeros((ns, pad) + a.shape[2:], a.dtype)], axis=1)

    return CommandLog(opcode=z(routed.opcode), arg0=z(routed.arg0),
                      arg1=z(routed.arg1), arg2=z(routed.arg2),
                      vec=z(routed.vec))


@partial(jax.jit, static_argnames=("ef_construction",))
def _apply_routed_stacked(stacked: MemoryState, routed: CommandLog,
                          n_real: jax.Array, *, ef_construction: int
                          ) -> MemoryState:
    """vmap-of-scan: every shard replays its (padded) share in lockstep on
    device. ``n_real`` is the routed share length — the pow2 NOP padding
    must not advance logical time, so ``version`` is pinned to base +
    n_real afterwards (the ``_apply_seq_segment`` rule; the routing NOPs
    *inside* the share do advance it, as on every other path)."""
    def per_shard(local: MemoryState, share: CommandLog) -> MemoryState:
        def step(s, rec):
            return machine.apply_command(
                s, rec, ef_construction=ef_construction), None

        out, _ = jax.lax.scan(step, local, share)
        return dataclasses.replace(out, version=local.version + n_real)

    return jax.vmap(per_shard)(stacked, routed)


def apply_routed_device(state: MemoryState, routed: CommandLog,
                        n_shards: int, *, ef_construction: int = 32
                        ) -> MemoryState:
    """Apply an already-routed batch to a sharded-layout state entirely on
    device: one reshape in, one jitted vmapped scan, one reshape out — no
    per-shard host loop, no host-side segmentation round-trip. Bit-identical
    to the host ``bulk_apply`` driver (both equal per-shard ``replay``)."""
    n_real = int(routed.opcode.shape[1])
    padded = _pad_routed(routed, machine._pow2(n_real))
    stacked = shard_stack(state, n_shards)
    out = _apply_routed_stacked(
        stacked, padded, jnp.asarray(n_real, stacked.version.dtype),
        ef_construction=ef_construction)
    return shard_unstack(out, n_shards)


def relink_sharded(state: MemoryState, n_shards: int, *,
                   ef_construction: int = 32) -> MemoryState:
    """Re-link every shard's graph from its own live rows (DESIGN.md §11):
    the sharded twin of ``hnsw.relink``, applied slice-by-slice so each
    shard lands on exactly the graph ``hnsw.fresh_build`` of its slice
    lands on. Arena untouched; only the graph arrays and entries move."""
    parts = []
    for s in range(n_shards):
        local = distributed.shard_slice(state, s, n_shards)
        parts.append(hnsw.relink(local, ef_construction=ef_construction))
    return distributed.merge_shards(parts)


def exact_search_sharded(state: MemoryState, n_shards: int,
                         queries_raw: jax.Array, k: int, *,
                         metric: str = search.METRIC_L2,
                         use_kernel: bool = False
                         ) -> Tuple[jax.Array, jax.Array]:
    """Exact k-NN over a host-side sharded-layout state: per-shard top-k
    then the one shared (score, id) combine — bit-identical to
    ``distributed.distributed_search`` on a mesh and to a single kernel
    holding the same rows (the merge is permutation-invariant). Returns
    (ids [nq, k], scores [nq, k])."""
    ids_parts, score_parts = [], []
    for s in range(n_shards):
        local = distributed.shard_slice(state, s, n_shards)
        ids, scores = search.exact_search(local, queries_raw, k,
                                          metric=metric,
                                          use_kernel=use_kernel)
        ids_parts.append(ids)
        score_parts.append(scores)
    flat_ids = jnp.concatenate(ids_parts, axis=-1)
    flat_scores = jnp.concatenate(score_parts, axis=-1)
    s_out, i_out = search.merge_candidates(flat_scores, flat_ids, k)
    return i_out, s_out


def coarse_search_sharded(state: MemoryState, n_shards: int,
                          queries_raw: jax.Array, k: int, *,
                          ef_coarse: int, metric: str = search.METRIC_L2,
                          use_kernel: bool = False,
                          tables: Optional[Sequence] = None
                          ) -> Tuple[jax.Array, jax.Array]:
    """The compressed tier over a host-side sharded-layout state: each
    shard coarse-scans its own int8 code table and re-ranks exactly, the
    per-shard top-k candidates combine with the one shared (score, id)
    merge — the sharded twin of ``search.coarse_search``. Served scores
    are exact Q16.16 on every path, so whenever every shard's candidate
    set covers its slice (``ef_coarse`` >= per-shard live count) the
    answer is bit-identical to ``exact_search_sharded`` — and therefore
    to the flat scan (DESIGN.md §10). ``tables[s]``, when given, must be
    ``codes.build`` of shard s's slice (the engine maintains exactly
    that); otherwise each shard derives its table on the spot. Returns
    (ids [nq, k], scores [nq, k])."""
    from repro.core import codes as codes_lib  # lazy: leaf-level module

    ids_parts, score_parts = [], []
    for s in range(n_shards):
        local = distributed.shard_slice(state, s, n_shards)
        table = tables[s] if tables is not None else codes_lib.build(local)
        ids, scores = search.coarse_search(local, table, queries_raw, k,
                                           ef_coarse=ef_coarse,
                                           metric=metric,
                                           use_kernel=use_kernel)
        ids_parts.append(ids)
        score_parts.append(scores)
    flat_ids = jnp.concatenate(ids_parts, axis=-1)
    flat_scores = jnp.concatenate(score_parts, axis=-1)
    s_out, i_out = search.merge_candidates(flat_scores, flat_ids, k)
    return i_out, s_out


def hnsw_search_sharded(state: MemoryState, n_shards: int,
                        queries_raw: jax.Array, k: int, *, ef: int = 64
                        ) -> Tuple[jax.Array, jax.Array]:
    """ANN over a host-side sharded-layout state: each shard runs the
    vmapped deterministic beam search over its own graph, candidates
    combine with the one order-invariant (score, id) merge — the mesh-free
    twin of ``distributed.distributed_hnsw_search``. Deterministic for any
    shard count; bit-identical to a flat graph's answer whenever every
    beam is exhaustive over its slice (``ef`` >= per-shard live count),
    which is the regime the conformance suite pins (DESIGN.md §7).
    Returns (ids [nq, k], dists [nq, k])."""
    from repro.core import query as query_lib  # lazy: query imports us lazily

    ids_parts, dist_parts = [], []
    for s in range(n_shards):
        local = distributed.shard_slice(state, s, n_shards)
        ids, dists, _ = query_lib.batched_hnsw_search(local, queries_raw, k,
                                                      ef=ef)
        ids_parts.append(ids)
        dist_parts.append(dists)
    flat_ids = jnp.concatenate(ids_parts, axis=-1)
    flat_d = jnp.concatenate(dist_parts, axis=-1)
    d_out, i_out = search.merge_candidates(flat_d, flat_ids, k)
    return i_out, d_out
