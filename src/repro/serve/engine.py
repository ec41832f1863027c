"""Memory-augmented batched serving engine — the paper's deployment story.

The engine glues the LM stack to the Valori substrate exactly along the
paper's §5.3 boundary:

  embed (float, nondeterministic) ──boundary.normalize──▶ MemoryState
  query (float)                  ──boundary.admit_query──▶ deterministic k-NN

Request lifecycle:
  1. WRITE path: a document's pooled hidden state (mean of final-layer
     states) crosses the boundary and is INSERTed through the command log —
     the audit trail IS the memory (replayable, snapshot-able, hashable).
  2. READ path: a prompt is embedded the same way; deterministic k-NN
     returns neighbor ids; their stored token prefixes are prepended as
     retrieved context (classic RAG conditioning).
  3. GENERATE: batched prefill + greedy decode with the KV cache.

Everything after the boundary is bit-deterministic: the same request log
replayed on any host produces the same memory hash AND the same retrieval
sets, which is the property the paper's §8.1 snapshot-transfer test checks.

Two serving modes share this one class (DESIGN.md §7):

* ``ServeConfig(shards=1)`` — the single-host engine: flat MemoryState,
  ``DurableStore`` durability, planner-routed batched reads.
* ``ServeConfig(shards=N)`` — the sharded engine: shard-major sharded-layout
  MemoryState (mesh-free, ``distributed.init_sharded_host``), ingest routed
  and NOP-padded into lockstep per-shard application
  (``shard_wal.bulk_apply_sharded``), durability through a
  ``ShardedDurableStore`` (per-shard WALs + snapshots under one global
  cursor), reads fanned out per shard and merged with the one
  order-invariant (score, id) combine (``query.sharded_host_query``).
* ``ServeConfig(shards=N, hosts=[...])`` — the networked engine
  (DESIGN.md §8): the same sharded-layout machinery, but durability and
  retrieval fan out to per-process shard hosts over the deterministic wire
  protocol (``net/``); the engine's local sharded state stays as the audit
  twin, so every remote append, checkpoint and answer is checkable against
  it by hash.

The cross-mode conformance contract (tests/test_conformance.py): both modes
fed the same documents allocate the same ids, append the same command log,
and report one ``memory_hash()`` (the layout-invariant live-content hash)
and one ``retrieval_hash()`` — including after kill + ``recover()``.
``state_hash()`` stays the within-layout ``hash_pytree`` artifact that the
durable stores' snapshots and merged records verify.
"""
from __future__ import annotations

import dataclasses
import pathlib
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import boundary, commands, distributed, hnsw, machine, \
    query, shard_wal, snapshot
from repro.core import wal as wal_lib
from repro.core.contracts import DEFAULT_CONTRACT, PrecisionContract
from repro.core.durability import DurableStore, SideTable
from repro.core.shard_wal import ShardedDurableStore
from repro.core.state import MemoryState, init_state
from repro.models import transformer as tf
from repro.models.config import ModelConfig


@dataclasses.dataclass
class ServeConfig:
    capacity: int = 4096
    retrieve_k: int = 4
    max_new_tokens: int = 32
    s_cache: int = 512
    contract: PrecisionContract = DEFAULT_CONTRACT
    context_tokens: int = 32     # tokens of each retrieved doc to prepend
    # serving topology (DESIGN.md §7): shards=1 is the single-host engine;
    # shards=N runs the whole path — ingest, durability, retrieval — on a
    # shard-major sharded-layout state with per-shard WALs. ``capacity`` is
    # the TOTAL arena (split evenly across shards; a single shard filling up
    # rejects its inserts exactly like a full flat arena would).
    shards: int = 1
    # networked topology (DESIGN.md §8): "host:port" shard servers
    # (``python -m repro.net.server``), one per shard. Ingest routing,
    # grouped append, planned retrieval fan-in, checkpoint, recover and
    # rollback then run over the wire through ``net.RemoteShardClient``s,
    # while the engine keeps its local sharded-layout state as the audit
    # twin — every remote answer is checkable against it by hash. Requires
    # ``durable_dir`` (the coordinator's own metadata directory); when
    # ``shards`` is left at 1 it is inferred as ``len(hosts)``.
    hosts: Optional[List[str]] = None
    # read-path planning (DESIGN.md §4, §10): the planner picks exact-scan
    # vs HNSW vs the compressed coarse tier per request from static facts;
    # "auto" applies the planner rules, "exact"/"hnsw"/"coarse" force a
    # route
    route: str = "auto"
    ef: int = 64                 # HNSW beam width when that route is taken
    # compressed tier (DESIGN.md §10): candidate-set size for the coarse
    # route. 0 disables the tier; > 0 lets "auto" route through the int8
    # coarse scan + exact re-rank, and makes the engine maintain the code
    # tables incrementally on ingest (table == codes.build(state) always)
    ef_coarse: int = 0
    exact_threshold: int = 1024  # live count at/below which exact scan wins
    use_kernel: bool = False     # Pallas qgemm scan on the exact/coarse routes
    # durability (DESIGN.md §5): with a durable_dir, every ingested command
    # is WAL-appended before it is visible, incremental v2 snapshots are cut
    # every checkpoint_every commands (0 = manual only), and recover()
    # rebuilds the last durable prefix after a crash
    durable_dir: Optional[str] = None
    checkpoint_every: int = 0    # commands between background checkpoints
    retain_snapshots: int = 0    # keep newest N (snapshot, WAL) pairs; 0=all
    # high-QPS ingest (DESIGN.md §6): with a group-commit policy, ingested
    # batches buffer in a GroupCommitWriter and fsync once per group instead
    # of once per append; the read path flushes pending commands first (the
    # sync-on-read barrier), so retrieval never observes un-durable state,
    # and policy.timer_flush additionally bounds un-durability by wall clock.
    # A compaction policy schedules dead-ratio-driven WAL compaction.
    group_commit: Optional[wal_lib.GroupCommitPolicy] = None
    compaction: Optional[wal_lib.CompactionPolicy] = None
    # graph maintenance under churn (DESIGN.md §11): a RelinkPolicy
    # schedules the deterministic HNSW re-link pass the way ``compaction``
    # schedules WAL compaction — dead-ratio-driven from layout-invariant
    # facts (global commands ingested, effective deletes, live count), so
    # flat and sharded engines fed the same batches re-link at the same
    # batch boundaries. None = manual only (``relink_now()``).
    relink: Optional[hnsw.RelinkPolicy] = None
    # read scaling (DESIGN.md §9): replicas=k attaches k verified
    # log-shipping read replicas per shard (net.ReplicaStore followers of
    # the engine's own durable store(s), or of the shard hosts in
    # networked mode). ``retrieve()`` routes each request to a replica
    # chosen deterministically from the query bytes — but only when that
    # replica's acked cursor has reached the engine's flush cursor
    # (read-your-writes through the same sync-on-read barrier); otherwise
    # it falls back to the primary. The route lands on ``last_plan`` as
    # ``served_by``. Replicas advance via ``sync_replicas()`` (an
    # operator/cron concern, like checkpoints). Requires durable_dir —
    # a replica follows a WAL, and without one there is nothing to tail.
    replicas: int = 0
    # live followers (DESIGN.md §12): with a FollowerPolicy
    # (net.replica.FollowerPolicy), every attached replica runs a
    # background tailer — catch-up loops on a daemon thread, nudged by
    # ``flush()`` whenever the pool lags past ``max_lag_commands`` and
    # ticking at least every ``max_delay_s`` — so the read pool absorbs
    # traffic between barriers with NO manual sync_replicas(). Admission
    # is unchanged: a replica serves only at/past the flush cursor, so
    # liveness changes and correctness doesn't. Requires replicas > 0.
    follow: Optional[Any] = None


class MemoryAugmentedEngine:
    def __init__(self, cfg: ModelConfig, params: Any, serve_cfg: ServeConfig):
        self.cfg = cfg
        self.params = params
        self.sc = serve_cfg
        n = serve_cfg.shards
        if n < 1:
            raise ValueError(f"shards must be >= 1, got {n}")
        if serve_cfg.hosts is not None:
            if n == 1:
                n = len(serve_cfg.hosts)
            elif n != len(serve_cfg.hosts):
                raise ValueError(
                    f"shards={n} but {len(serve_cfg.hosts)} hosts given")
            if serve_cfg.durable_dir is None:
                raise ValueError(
                    "networked serving (hosts=[...]) needs durable_dir: the "
                    "coordinator keeps its merged-hash records there")
        if serve_cfg.capacity % n:
            raise ValueError(
                f"capacity {serve_cfg.capacity} must divide evenly across "
                f"{n} shards")
        self.n_shards = n
        # the layout switch: networked serving uses the sharded-layout
        # machinery even at one shard (its durable twin is a fleet of one)
        self._layout_sharded = (n > 1) or (serve_cfg.hosts is not None)
        if not self._layout_sharded:
            self.memory: MemoryState = init_state(
                serve_cfg.capacity, cfg.d_model, contract=serve_cfg.contract)
        else:
            self.memory = distributed.init_sharded_host(
                n, serve_cfg.capacity // n, cfg.d_model,
                contract=serve_cfg.contract)
        # the audit trail: the global command log, plus — in sharded mode —
        # its routed per-shard twin (what the per-shard WALs hold). After a
        # sharded recover() only the per-shard logs are reconstructible
        # (the global interleaving across shards is not durable by design).
        self.log = commands.empty_log(cfg.d_model, serve_cfg.contract)
        self._shard_logs: List[commands.CommandLog] = [
            commands.empty_log(cfg.d_model, serve_cfg.contract)
            for _ in range(n)]
        self.docs: Dict[int, np.ndarray] = {}   # id -> token prefix
        self._next_id = 0
        self.last_plan: Optional[query.QueryPlan] = None
        # churn audit (DESIGN.md §11): cursors at which the serving graph
        # was re-linked (``graph_gen == len(relink_ts)`` rides on every
        # plan), plus the layout-invariant scheduling counters — global
        # commands since the last schedule check and effective deletes
        # since the last re-link
        self.graph_gen = 0
        self.relink_ts: List[int] = []
        self._deletes_since_relink = 0
        self._cmds_since_relink_check = 0
        # compressed tier (DESIGN.md §10): one code table per shard slice
        # (one entry in flat mode), built on first coarse read and then
        # maintained incrementally on ingest; None until needed and after
        # recover/rollback (the table is a pure function of the state, so
        # a lazy rebuild is always bit-identical)
        self._code_tables: Optional[List[Any]] = None

        self.durable = None  # DurableStore | ShardedDurableStore | None
        self._group: Optional[wal_lib.GroupCommitWriter] = None
        self._doc_table: Optional[SideTable] = None
        self._clients = None  # net.RemoteShardClient fleet (hosts mode)
        self._ckpt_thread: Optional[threading.Thread] = None
        self._ckpt_error: Optional[BaseException] = None
        self._last_ckpt_t = 0
        if serve_cfg.durable_dir is not None:
            if serve_cfg.hosts is not None:
                # one RemoteShardClient per shard host; the sharded store
                # drives them through the exact surface local shards expose
                from repro.net.client import (RemoteShardClient,
                                              SocketTransport)
                self._clients = [
                    RemoteShardClient(
                        SocketTransport(h.rsplit(":", 1)[0],
                                        int(h.rsplit(":", 1)[1])),
                        contract=serve_cfg.contract)
                    for h in serve_cfg.hosts]
                self.durable = ShardedDurableStore(
                    serve_cfg.durable_dir, backends=self._clients)
            elif not self._layout_sharded:
                self.durable = DurableStore(
                    serve_cfg.durable_dir, self.memory,
                    compaction=serve_cfg.compaction)
            else:
                self.durable = ShardedDurableStore(
                    serve_cfg.durable_dir, self.memory, n_shards=n,
                    compaction=serve_cfg.compaction)
            # the doc cache's durable side table (DESIGN.md §7): token
            # prefixes ride beside the WAL so recover() starts warm; the
            # substrate never depends on it (it is a cache, not state).
            # Its records are written — and under group commit, synced via
            # the writer's pre_flush hook — BEFORE the commands they
            # describe become durable, so a live id can never outrun its
            # tokens (the rollback + id-reuse hazard)
            self._doc_table = SideTable(
                pathlib.Path(serve_cfg.durable_dir) / "docs.sdt")
            if serve_cfg.group_commit is not None:
                self._group = wal_lib.GroupCommitWriter(
                    self.durable, serve_cfg.group_commit,
                    pre_flush=self._doc_table.sync)
        elif (serve_cfg.group_commit is not None
              or serve_cfg.compaction is not None):
            # refuse the inconsistent config loudly: an operator who set a
            # durability policy believes ingest is durable — silently
            # running non-durable would be the worst possible reading
            raise ValueError(
                "group_commit/compaction policies need durable_dir set")

        # the read pool (DESIGN.md §9): read_replicas[s][i] is the i-th
        # verified follower of shard s (one list in flat mode)
        self.read_replicas: List[List[Any]] = []
        self._closed = False
        if serve_cfg.follow is not None and not serve_cfg.replicas:
            raise ValueError(
                "follow=FollowerPolicy(...) needs replicas > 0: a "
                "follower policy paces read replicas, and there are none")
        if serve_cfg.replicas:
            if self.durable is None:
                raise ValueError(
                    "replicas=k needs durable_dir: a read replica follows "
                    "a durable WAL, and without one there is nothing to "
                    "tail")
            self._spawn_replicas(serve_cfg.replicas)
            self._start_followers()

        self._embed_fn = jax.jit(self._embed_batch)
        self._prefill = jax.jit(
            lambda p, b: tf.prefill(p, b, cfg, self.sc.s_cache))
        self._decode = jax.jit(
            lambda p, c, t, pos: tf.decode_step(p, c, t, pos, cfg))

    # ------------------------------------------------------------------ #
    # embedding: pooled final hidden states (pre-head)
    # ------------------------------------------------------------------ #

    def _embed_batch(self, params, tokens: jax.Array) -> jax.Array:
        batch = {"tokens": tokens}
        h = tf._embed(params, batch, self.cfg)
        B, L = h.shape[:2]
        positions = jnp.broadcast_to(
            jnp.arange(L, dtype=jnp.int32)[None], (B, L))
        angles = tf._angles_for(batch, positions, self.cfg)
        h, _, _ = tf._run_stack(params, h, positions, self.cfg, "train",
                                None, angles)
        return jnp.mean(h.astype(jnp.float32), axis=1)  # [B, D]

    def _cursor(self) -> int:
        """The engine's applied-command cursor: flat ``version``, or the
        common per-shard padded cursor in sharded mode (always equal at
        the batch boundaries the engine operates at)."""
        v = np.asarray(self.memory.version).reshape(-1)
        return int(v[0])

    # ------------------------------------------------------------------ #
    # read pool: verified replicas behind the flush barrier (DESIGN.md §9)
    # ------------------------------------------------------------------ #

    def _spawn_replicas(self, k: int) -> None:
        """Attach ``k`` in-process verified followers per shard. Local
        modes follow the engine's own store(s) through ``LocalPrimary``
        (the replica-facing surface of a DurableStore); networked mode
        follows the shard hosts over their own wire connections. Genesis
        is the engine's t=0 state (its shard slice in sharded layouts) —
        replicas then catch up through the same verify-then-ack discipline
        any replica uses, so every cursor they report is proven."""
        from repro.net.replica import LocalPrimary, ReplicaStore
        if not self._layout_sharded:
            primaries = [lambda: LocalPrimary(
                self.durable, state_fn=lambda: self.memory,
                side_table=self._doc_table)]
            geneses = [self.memory]
        elif self._clients is not None:
            from repro.net.client import RemoteShardClient, SocketTransport
            def remote(h):
                addr, port = h.rsplit(":", 1)
                return lambda: RemoteShardClient(
                    SocketTransport(addr, int(port)),
                    contract=self.sc.contract)
            primaries = [remote(h) for h in self.sc.hosts]
            geneses = [distributed.shard_slice(self.memory, s, self.n_shards)
                       for s in range(self.n_shards)]
        else:
            def local(s):
                return lambda: LocalPrimary(
                    self.durable.shards[s],
                    state_fn=lambda: distributed.shard_slice(
                        self.memory, s, self.n_shards),
                    side_table=self._doc_table)
            primaries = [local(s) for s in range(self.n_shards)]
            geneses = [distributed.shard_slice(self.memory, s, self.n_shards)
                       for s in range(self.n_shards)]
        self.read_replicas = [
            [ReplicaStore(make_primary(), geneses[s],
                          replica_id=s * k + i)
             for i in range(k)]
            for s, make_primary in enumerate(primaries)]

    def _start_followers(self) -> None:
        """Start one background tailer per replica under the configured
        ``FollowerPolicy`` (DESIGN.md §12); a no-op without one — the
        pool then advances only on explicit ``sync_replicas()``."""
        if self.sc.follow is None:
            return
        for pool in self.read_replicas:
            for rep in pool:
                rep.start_following(self.sc.follow)

    def _reset_replicas(self) -> None:
        """Tear the read pool down and respawn it (recover/rollback):
        follower threads stop, transports close, and fresh replicas
        re-earn their cursors through the same verify-then-ack catch-up —
        a pool must never serve a state the *current* durable history
        cannot prove (rollback rewrites history; recovery may land on an
        older cursor)."""
        if not self.read_replicas:
            return
        for pool in self.read_replicas:
            for rep in pool:
                rep.close()  # close() stops the follower thread first
        self.read_replicas = []
        self._spawn_replicas(self.sc.replicas)
        self._start_followers()

    def _pick_replica(self, q_raw) -> Optional[int]:
        """Deterministic replica choice from the request bytes — the same
        query always lands on the same pool slot, so a served answer is
        replayable from (log cursor, query, plan). The slot must exist on
        EVERY shard's pool (the read fans out across shards at one slot),
        so the usable pool size is the min across shards: a ragged pool
        (a replica failed to spawn or was closed) shrinks the pool rather
        than routing to a missing slot, and an empty pool returns None —
        the primary serves."""
        from repro.core import hashing
        sizes = [len(pool) for pool in self.read_replicas]
        n = min(sizes) if sizes else 0
        if n == 0:
            return None
        return (hashing.digest_bytes(np.asarray(q_raw).tobytes()) % n)

    def sync_replicas(self, *, max_commands: int = 0) -> int:
        """Catch every attached replica up to the current flush cursor
        (each slice verified against the primary's hash before commit).
        Returns the **max residual lag** across the pool — 0 means every
        replica proved the flush cursor; a positive value means a hot
        primary outran at least one catch-up (the caller can tell "caught
        up" from "gave up"). Like checkpoints, replica advancement is an
        explicit serving-loop concern — ``retrieve()`` never blocks a
        read on it; a lagging replica just loses the route until it
        catches up."""
        self.flush()
        lag = 0
        for pool in self.read_replicas:
            for rep in pool:
                lag = max(lag, rep.catch_up(max_commands=max_commands))
        return lag

    # ------------------------------------------------------------------ #
    # compressed tier: per-slice code tables (DESIGN.md §10)
    # ------------------------------------------------------------------ #

    def _memory_slices(self) -> List[MemoryState]:
        if not self._layout_sharded:
            return [self.memory]
        return [distributed.shard_slice(self.memory, s, self.n_shards)
                for s in range(self.n_shards)]

    def _ensure_code_tables(self) -> None:
        """Build the per-slice code tables from the live state. Idempotent;
        the result is the same bits any other holder of this state would
        derive (``codes.build`` is pure in the live rows)."""
        if self._code_tables is not None:
            return
        from repro.core import codes as codes_lib
        self._code_tables = [codes_lib.build(sl)
                             for sl in self._memory_slices()]

    def _refresh_code_tables(self, inserted_ids: np.ndarray) -> None:
        """Incremental maintenance after an ingest batch: only the slots
        that received this batch's ids re-encode (engine writes are fresh
        INSERTs, so those are exactly the touched slots); a per-dim param
        drift falls back to a full rebuild inside ``codes.refresh`` —
        either way the invariant ``table == codes.build(slice)`` holds."""
        if self._code_tables is None:
            return
        from repro.core import codes as codes_lib
        tables = []
        for sl, tbl in zip(self._memory_slices(), self._code_tables):
            touched = np.nonzero(
                np.isin(np.asarray(sl.ids), inserted_ids)
                & np.asarray(sl.valid))[0].astype(np.int32)
            tables.append(codes_lib.refresh(tbl, sl, touched))
        self._code_tables = tables

    def _checkpoint_code_tables(self) -> None:
        """Cut the code tables' own content-addressed manifests beside the
        state snapshots (``<durable_dir>/codes/``): chunks dedup against
        the previous checkpoint, so a param-stable refresh costs only the
        touched rows' chunks. Recovery does NOT read these — the table is
        rebuilt from the recovered state (pure function, always correct);
        the manifests are the incremental audit/warm-start artifact, and
        tests verify a restored manifest equals the rebuild bit-for-bit."""
        if self.sc.durable_dir is None or not self._coarse_enabled():
            return
        from repro.core import codes as codes_lib
        self._ensure_code_tables()
        t = self._cursor()
        cdir = pathlib.Path(self.sc.durable_dir) / "codes"
        store = snapshot.ChunkStore(cdir / "chunks")
        keep_keys = set()
        for s, tbl in enumerate(self._code_tables):
            manifest, _ = codes_lib.snapshot_table_v2(tbl, t, store)
            path = cdir / f"codes_{s:04d}_t{t:020d}.mft"
            tmp = path.with_suffix(".tmp")
            tmp.write_bytes(manifest)
            tmp.replace(path)
            keep_keys.update(codes_lib.table_manifest_chunk_keys(manifest))
        # retain only the newest manifest set + the chunks it references
        for old in cdir.glob("codes_*.mft"):
            if not old.name.endswith(f"t{t:020d}.mft"):
                old.unlink()
        for key in store.keys():
            if key not in keep_keys:
                store.delete(key)

    def _coarse_enabled(self) -> bool:
        return self.sc.ef_coarse > 0 or self.sc.route == query.ROUTE_COARSE

    # ------------------------------------------------------------------ #
    # WRITE path
    # ------------------------------------------------------------------ #

    def insert_documents(self, token_batches: np.ndarray) -> List[int]:
        """token_batches [N, L] int32 → ids. Batched through the boundary.

        The WRITE path goes through ``machine.bulk_apply`` — hash-identical
        to scanning the log one command at a time — in flat mode, and
        through ``shard_wal.bulk_apply_sharded`` (route once, apply each
        shard's share to its slice) in sharded mode. Id allocation is
        sequential in BOTH modes: the same documents produce the same
        command log everywhere, which is what makes the two modes
        conformance-comparable (DESIGN.md §7)."""
        with obs.span("engine.insert"):
            return self._insert_documents(token_batches)

    def _insert_documents(self, token_batches: np.ndarray) -> List[int]:
        if len(token_batches) == 0:
            # routing pads an empty batch to one NOP per shard, which would
            # advance the sharded memory cursor while both durable paths
            # (correctly) skip empty logs — refuse the desync up front
            return []
        emb = self._embed_fn(self.params, jnp.asarray(token_batches))
        raw = boundary.normalize_embedding(emb, self.sc.contract)
        ids = np.arange(self._next_id, self._next_id + len(token_batches),
                        dtype=np.int64)
        self._next_id += len(token_batches)
        batch_log = commands.insert_batch(jnp.asarray(ids), raw,
                                          self.sc.contract)
        routed = None if not self._layout_sharded else \
            distributed.route_commands(batch_log, self.n_shards)

        # doc cache first: its side-table records must be durable no later
        # than the commands they describe, or a crash after a rollback-
        # then-reinsert could recover a live id with stale tokens. Under
        # group commit the writer's pre_flush hook syncs the table inside
        # every flush (foreground, policy or timer), before the sink commit
        for i, tid in enumerate(ids):
            doc = np.asarray(token_batches[i])
            self.docs[int(tid)] = doc
            if self._doc_table is not None:
                self._doc_table.put(
                    int(tid), doc.astype("<i4", copy=False).tobytes())

        if self._group is not None:
            # group commit: the batch buffers toward one fsync per group —
            # it is NOT yet durable, so it also must not be readable; the
            # read path's flush() barrier restores WAL-first ordering at
            # the moment of first observation (DESIGN.md §6)
            self._group.submit(batch_log, routed=routed)
        elif self.durable is not None:
            # WAL-first: the commands are durable before their effects are
            # visible, so a crash can lose at most un-acked work
            if self._doc_table is not None:
                self._doc_table.sync()
            if not self._layout_sharded:
                self.durable.append(batch_log)
            else:
                self.durable.append(batch_log, routed=routed)
        self.log = self.log.concat(batch_log)
        if not self._layout_sharded:
            self.memory = machine.bulk_apply(self.memory, batch_log)
        else:
            for s in range(self.n_shards):
                self._shard_logs[s] = self._shard_logs[s].concat(
                    jax.tree.map(lambda a, s=s: a[s], routed))
            self.memory = shard_wal.bulk_apply_sharded(
                self.memory, batch_log, self.n_shards, routed=routed)
        self._refresh_code_tables(ids)
        self._cmds_since_relink_check += len(batch_log)
        self._maybe_relink()
        self._maybe_checkpoint()
        return [int(i) for i in ids]

    def delete_documents(self, doc_ids) -> int:
        """Delete documents by id through the same durable path INSERTs
        take: one canonical DELETE batch is WAL-appended (or group-
        submitted) before its effects are visible, applied with the same
        bulk driver, and recorded on the same audit logs — a churny
        workload is just a log with more opcodes, not a different engine.
        Unknown ids are deterministic no-ops (they still advance logical
        time, like every rejected command). Returns the number of rows
        actually tombstoned.

        The HNSW graph survives: ``machine`` repairs a tombstoned entry
        point on the spot (DESIGN.md §11) and the scheduled re-link pass
        (``ServeConfig.relink``) sweeps dead waypoints, so the planner
        keeps the ANN route under churn."""
        if len(doc_ids) == 0:
            return 0
        ids = np.asarray(sorted(int(i) for i in doc_ids), dtype=np.int64)
        batch_log = commands.delete_batch(jnp.asarray(ids), self.cfg.d_model,
                                          self.sc.contract)
        routed = None if not self._layout_sharded else \
            distributed.route_commands(batch_log, self.n_shards)
        if self._group is not None:
            self._group.submit(batch_log, routed=routed)
        elif self.durable is not None:
            if self._doc_table is not None:
                self._doc_table.sync()
            if not self._layout_sharded:
                self.durable.append(batch_log)
            else:
                self.durable.append(batch_log, routed=routed)
        self.log = self.log.concat(batch_log)
        before = shard_wal.live_count(self.memory)
        if not self._layout_sharded:
            self.memory = machine.bulk_apply(self.memory, batch_log)
        else:
            for s in range(self.n_shards):
                self._shard_logs[s] = self._shard_logs[s].concat(
                    jax.tree.map(lambda a, s=s: a[s], routed))
            self.memory = shard_wal.bulk_apply_sharded(
                self.memory, batch_log, self.n_shards, routed=routed)
        removed = before - shard_wal.live_count(self.memory)
        for tid in ids:
            # the doc cache drops now; the side table's record stays — a
            # dead id is never retrieved, and the engine's sequential id
            # allocation never reuses it, so the stale bytes are inert
            self.docs.pop(int(tid), None)
        # deletes touch layout-dependent slots; the lazy rebuild is a pure
        # function of the live rows, so it is always bit-identical
        self._code_tables = None
        self._deletes_since_relink += removed
        self._cmds_since_relink_check += len(batch_log)
        self._maybe_relink()
        self._maybe_checkpoint()
        return removed

    # ------------------------------------------------------------------ #
    # graph maintenance: scheduled deterministic re-link (DESIGN.md §11)
    # ------------------------------------------------------------------ #

    def _maybe_relink(self) -> None:
        """The schedule of ``ServeConfig.relink``, checked at batch
        boundaries from layout-invariant facts only — flat and sharded
        engines fed the same batches fire at the same boundaries."""
        pol = self.sc.relink
        if pol is None or self._cmds_since_relink_check < pol.check_every:
            return
        self._cmds_since_relink_check = 0
        dead = self._deletes_since_relink
        live = shard_wal.live_count(self.memory)
        if dead < pol.min_deletes or dead < pol.dead_ratio * (dead + live):
            return
        self.relink_now()

    def relink_now(self) -> int:
        """Re-link the serving graph from its live rows right now (each
        shard's slice in sharded mode) and record the firing cursor on
        ``relink_ts`` — the pass mutates the graph without a logged
        command, so the audit trail must know where it fired for
        ``replay_log_fresh`` to reproduce the serving state. Returns the
        cursor. Arena, WAL and durable artifacts are untouched: a re-link
        changes how the graph routes, never what the memory contains."""
        t = self._cursor()
        if not self._layout_sharded:
            self.memory = hnsw.relink(self.memory)
        else:
            self.memory = shard_wal.relink_sharded(self.memory,
                                                   self.n_shards)
        self.relink_ts.append(t)
        self.graph_gen = len(self.relink_ts)
        self._deletes_since_relink = 0
        return t

    # ------------------------------------------------------------------ #
    # READ path
    # ------------------------------------------------------------------ #

    def retrieve(self, prompt_tokens: np.ndarray, k: Optional[int] = None
                 ) -> Tuple[np.ndarray, np.ndarray]:
        """[B, L] prompts → (ids [B, k], scores [B, k]) — deterministic.

        The whole batch runs on the route the query planner picks from
        static facts (live count, k, ef). Flat mode executes the plan under
        one jit; sharded mode fans it out per shard and merges with the
        order-invariant integer combine — bit-identical to the flat answer
        for the exact route, and for HNSW whenever the beam covers each
        shard (DESIGN.md §7). The decision is recorded on ``self.last_plan``
        for audit.

        With a read pool (``replicas=k``), the request picks a pool slot
        deterministically from its query bytes and is served from that
        replica's verified state — but only when every chosen replica's
        acked cursor has reached the flush cursor returned by the barrier
        above (read-your-writes: a replica may lag the log, never the
        reader). Otherwise the primary serves, and either way
        ``last_plan.served_by`` records who answered (DESIGN.md §9)."""
        with obs.span("engine.retrieve"):
            return self._retrieve(prompt_tokens, k)

    def _retrieve(self, prompt_tokens: np.ndarray, k: Optional[int]
                  ) -> Tuple[np.ndarray, np.ndarray]:
        k = k or self.sc.retrieve_k
        # sync-on-read barrier: nothing un-durable is observable, and the
        # cursor it returns is the read-your-writes floor for replica routes
        flush_t = self.flush()
        emb = self._embed_fn(self.params, jnp.asarray(prompt_tokens))
        q_raw = boundary.admit_query(emb, self.sc.contract)
        plan = query.plan_query(
            shard_wal.live_count(self.memory), k, self.sc.ef,
            use_kernel=self.sc.use_kernel,
            exact_threshold=self.sc.exact_threshold, route=self.sc.route,
            ef_coarse=self.sc.ef_coarse, dim=self.cfg.d_model,
            graph_gen=self.graph_gen)
        pool_states = None
        if self.read_replicas:
            slot = self._pick_replica(q_raw)
            if slot is not None:
                # consistent (state, hash, t) per replica: a live follower
                # may commit concurrently, and admission + serving must
                # read ONE proven pair, not a torn mix of two
                snaps = [shard_pool[slot].snapshot()
                         for shard_pool in self.read_replicas]
                if all(t >= flush_t for _, _, t in snaps):
                    pool_states = [state for state, _, _ in snaps]
                    plan = dataclasses.replace(plan,
                                               served_by=f"replica:{slot}")
        self.last_plan = plan
        if pool_states is not None:
            ids, scores = self._replica_query(pool_states, q_raw, k, plan)
        elif self._clients is not None:
            # the networked read: every shard host executes the same plan
            # on its applied state, candidates merge with the one
            # order-invariant combine — bit-identical to the local sharded
            # read on the same content (the conformance suite pins it)
            from repro.net.client import remote_sharded_query
            ids, scores = remote_sharded_query(self._clients, q_raw, k, plan)
        elif not self._layout_sharded:
            if plan.route == query.ROUTE_COARSE:
                self._ensure_code_tables()
                ids, scores = query.execute_plan(
                    self.memory, q_raw, k, plan, codes=self._code_tables[0])
            else:
                ids, scores = query.execute_plan(self.memory, q_raw, k, plan)
        else:
            tables = None
            if plan.route == query.ROUTE_COARSE:
                self._ensure_code_tables()
                tables = self._code_tables
            ids, scores = query.sharded_host_query(
                self.memory, self.n_shards, q_raw, k, plan, tables=tables)
        return np.asarray(ids), np.asarray(scores)

    def _replica_query(self, pool_states, q_raw, k: int,
                       plan: query.QueryPlan
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Execute the engine's plan on the chosen replicas' verified
        states: the flat state directly, per-shard states merged with the
        one order-invariant (score, id) combine — the same merge the
        networked read uses, so a replica-served answer is bit-identical
        to the primary's at the same cursor (the conformance suite pins
        it)."""
        from repro.core import search
        if not self._layout_sharded:
            return query.execute_plan(pool_states[0], q_raw, k, plan)
        ids_parts, score_parts = [], []
        for state in pool_states:
            ids_s, scores_s = query.execute_plan(state, q_raw, k, plan)
            ids_parts.append(jnp.asarray(ids_s, jnp.int64))
            score_parts.append(jnp.asarray(scores_s, jnp.int64))
        flat_ids = jnp.concatenate(ids_parts, axis=-1)
        flat_scores = jnp.concatenate(score_parts, axis=-1)
        s_out, i_out = search.merge_candidates(flat_scores, flat_ids, k)
        return i_out, s_out

    def retrieval_hash(self, prompt_tokens: np.ndarray,
                       k: Optional[int] = None) -> int:
        """Platform-invariant hash of the retrieval set for these prompts —
        the read-path audit artifact (paper §8.1 applied to queries)."""
        ids, scores = self.retrieve(prompt_tokens, k)
        return query.retrieval_hash(ids, scores)

    # ------------------------------------------------------------------ #
    # GENERATE
    # ------------------------------------------------------------------ #

    def generate(self, prompt_tokens: np.ndarray, *, augment: bool = True
                 ) -> np.ndarray:
        """Greedy decode a batch of prompts, optionally memory-augmented.
        Returns [B, max_new_tokens] int32."""
        B, L = prompt_tokens.shape
        if augment and shard_wal.live_count(self.memory) > 0:
            ids, _ = self.retrieve(prompt_tokens)
            ctx = np.zeros((B, self.sc.context_tokens), np.int32)
            for b in range(B):
                best = int(ids[b, 0])
                if best >= 0:
                    doc = self.docs.get(best)
                    if doc is not None:
                        n = min(len(doc), self.sc.context_tokens)
                        ctx[b, -n:] = doc[:n]
            prompt_tokens = np.concatenate([ctx, prompt_tokens], axis=1)
            L = prompt_tokens.shape[1]

        logits, caches = self._prefill(self.params,
                                       {"tokens": jnp.asarray(prompt_tokens)})
        out = np.zeros((B, self.sc.max_new_tokens), np.int32)
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        for t in range(self.sc.max_new_tokens):
            out[:, t] = np.asarray(tok)[:, 0]
            pos = jnp.full((B, 1), L + t, jnp.int32)
            logits, caches = self._decode(self.params, caches, tok, pos)
            tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        return out

    # ------------------------------------------------------------------ #
    # durability: background checkpoints + crash recovery (DESIGN.md §5, §7)
    # ------------------------------------------------------------------ #

    def flush(self) -> int:
        """Force any pending group-commit batch durable; returns the
        durable WAL cursor (== memory cursor afterwards). The read path
        calls this before serving — the sync-on-read barrier that keeps
        retrieval from ever observing un-durable commands — and it is the
        ack point for upstream callers under group commit. The doc side
        table syncs here too, so its durability never lags the barrier.
        With live followers, the barrier doubles as the staleness nudge:
        any follower lagging the new cursor past the policy's
        ``max_lag_commands`` is woken immediately (never waited on)."""
        if self._doc_table is not None:
            self._doc_table.sync()
        if self._group is not None:
            t = self._group.flush()
        else:
            t = self.durable.t if self.durable is not None \
                else self._cursor()
        if self.sc.follow is not None:
            lag_bound = self.sc.follow.max_lag_commands
            for pool in self.read_replicas:
                for rep in pool:
                    if t - rep.t > lag_bound:
                        rep.notify_writes()
        return t

    def close(self) -> None:
        """Flush pending ingest, join background work and release durable
        resources: the group-commit writer (and its timer thread, if
        ``timer_flush`` was set), the doc side table's file handle, every
        read replica (transports + any catch-up prefetch thread) and the
        shard-host connections. Long-lived processes that construct
        engines repeatedly must close them — daemon threads and fds do not
        collect themselves. Idempotent: benches and kill tests close
        engines repeatedly, and a double close is a no-op."""
        if self._closed:
            return
        self._closed = True
        self.flush()
        self.wait_durable()
        if self._group is not None:
            self._group.close()
        for pool in self.read_replicas:
            for rep in pool:
                rep.close()
        if self._doc_table is not None:
            self._doc_table.close()
        if self._clients is not None:
            for c in self._clients:
                c.close()

    def wait_durable(self) -> None:
        """Join any in-flight background checkpoint; re-raise its error —
        same no-silent-loss contract as checkpoint.CheckpointManager."""
        if self._ckpt_thread is not None:
            self._ckpt_thread.join()
            self._ckpt_thread = None
        if self._ckpt_error is not None:
            err, self._ckpt_error = self._ckpt_error, None
            raise RuntimeError("background checkpoint failed") from err

    def checkpoint(self) -> Dict[str, int]:
        """Synchronously cut an incremental snapshot at the current cursor
        (per-shard v2 snapshots + the merged whole-state-hash record in
        sharded mode); returns the snapshot stats."""
        if self.durable is None:
            raise RuntimeError("no durable_dir configured")
        self.flush()  # a snapshot may only cover durable commands
        self.wait_durable()
        stats = self.durable.checkpoint(
            jax.tree.map(np.asarray, self.memory))
        self._last_ckpt_t = self._cursor()
        if self.sc.retain_snapshots > 0:
            stats.update(self.durable.retain(self.sc.retain_snapshots))
        self._checkpoint_code_tables()
        return stats

    def _maybe_checkpoint(self) -> None:
        if (self.durable is None or self.sc.checkpoint_every <= 0
                or self._cursor() - self._last_ckpt_t
                < self.sc.checkpoint_every):
            return
        self.flush()  # a snapshot may only cover durable commands
        self.wait_durable()  # one in flight at a time; surfaces past errors
        host_state = jax.tree.map(np.asarray, self.memory)
        self._last_ckpt_t = self._cursor()
        if self._clients is not None:
            # synchronous over the wire: the shard host proves the cursor +
            # hash against its applied state at request time, so a
            # background thread would race the next append's cursor advance
            self.durable.checkpoint(host_state)
            if self.sc.retain_snapshots > 0:
                self.durable.retain(self.sc.retain_snapshots)
            return

        def work():
            try:
                self.durable.checkpoint(host_state)
                if self.sc.retain_snapshots > 0:
                    self.durable.retain(self.sc.retain_snapshots)
            except BaseException as e:  # noqa: BLE001 — re-raised on wait
                self._ckpt_error = e

        self._ckpt_thread = threading.Thread(target=work, daemon=True)
        self._ckpt_thread.start()

    def _reload_audit_logs(self, t: int) -> None:
        """Rebuild the in-memory audit trail from the durable WAL(s) after
        recover/rollback, if retention kept the full history."""
        empty = commands.empty_log(self.cfg.d_model, self.sc.contract)
        if not self._layout_sharded:
            try:
                self.log = self.durable.wal.read_range(0, t)
            except ValueError:
                self.log = empty
        else:
            # the global interleaving is not durable (per-shard WALs only);
            # the per-shard logs are the reconstructible audit trail
            self.log = empty
            try:
                self._shard_logs = self.durable.shard_logs(0, t)
            except ValueError:
                self._shard_logs = [empty for _ in range(self.n_shards)]

    def _reload_serving_caches(self) -> None:
        """Refresh next-id allocation and the doc cache from durable
        artifacts: ids from the live rows of the recovered state (works in
        both layouts), token prefixes from the side table — the recovered
        engine generates with warm retrieved context immediately instead
        of refilling lazily (DESIGN.md §7)."""
        ids = np.asarray(self.memory.ids)
        live = ids[np.asarray(self.memory.valid)]
        self._next_id = int(live.max()) + 1 if live.size else 0
        if self._doc_table is not None:
            self.docs = {
                int(key): np.frombuffer(payload, "<i4").astype(np.int32)
                for key, payload in self._doc_table.entries.items()}

    def recover(self) -> Tuple[int, int]:
        """Rebuild memory from the durable store after a crash: nearest
        snapshot(s) + WAL tail(s), bit-identical to replaying the durable
        prefix; in sharded mode the shards reconcile to one global cursor
        first (min over shards, ahead shards roll back — DESIGN.md §6).
        Returns (t, state hash). Retrieval serves immediately, and the doc
        cache reloads from its durable side table so generation is warm."""
        if self.durable is None:
            raise RuntimeError("no durable_dir configured")
        self.flush()  # a live engine recovering: don't drop acked-to-us work
        self.wait_durable()
        state, h, t = self.durable.recover()
        self.memory = state
        self._code_tables = None  # rebuilt from the recovered state on
        self._last_ckpt_t = t     # first coarse read (pure function of it)
        self._reload_audit_logs(t)
        self._reload_serving_caches()
        # recovery may land below the replicas' cursors (lost unflushed
        # suffix): respawn the pool so every served cursor re-earns its
        # proof against the recovered history (follower threads restart)
        self._reset_replicas()
        h = self._canonicalize_graph(t, h)
        return t, h

    def rollback_to(self, t: int) -> Tuple[int, int]:
        """Roll the durable history AND the serving state back to logical
        time ``t``: snapshots/WAL records above ``t`` are dropped (on every
        shard in sharded mode, with merged records pruned too) and memory
        is restored at ``t``. Returns (t, state hash)."""
        if self.durable is None:
            raise RuntimeError("no durable_dir configured")
        self.flush()
        self.wait_durable()
        self.durable.rollback_to(t)
        state, h = self.durable.restore_at(t)
        self.memory = state
        self._code_tables = None  # pure function of the restored state
        self._last_ckpt_t = t
        self._reload_audit_logs(t)
        self._reload_serving_caches()
        # rollback rewrites history: replicas ahead of ``t`` proved a
        # prefix that no longer exists — tear the pool down and re-earn
        self._reset_replicas()
        h = self._canonicalize_graph(t, h)
        return t, h

    def _canonicalize_graph(self, t: int, h: int) -> int:
        """Post-restore graph canonicalization (DESIGN.md §11). The durable
        WAL holds commands only — a restored graph is the pure-replay
        graph, not the re-linked one the engine was serving. With a re-link
        policy configured, one re-link of the restored state puts every
        recovered engine (and every layout) on the same canonical footing:
        ``relink_ts=[t]``, ``graph_gen=1``, counters reset — and the
        returned hash becomes the post-re-link ``state_hash()`` (the
        pre-re-link state was already verified against the durable records
        by the restore itself). Retrieval is unaffected either way in the
        beam-exhaustive regime; the canonical graph is simply the one whose
        provenance ``replay_log_fresh`` can restate. Without a policy the
        restore is returned untouched (graph audit state just resets)."""
        self._deletes_since_relink = 0
        self._cmds_since_relink_check = 0
        if self.sc.relink is None:
            self.relink_ts = []
            self.graph_gen = 0
            return h
        if not self._layout_sharded:
            self.memory = hnsw.relink(self.memory)
        else:
            self.memory = shard_wal.relink_sharded(self.memory,
                                                   self.n_shards)
        self.relink_ts = [t]
        self.graph_gen = 1
        return self.state_hash()

    # ------------------------------------------------------------------ #
    # audit / replay (paper §8.1, §9; DESIGN.md §7)
    # ------------------------------------------------------------------ #

    def memory_hash(self) -> int:
        """The layout-invariant live-content hash (DESIGN.md §7): flat and
        sharded engines fed the same command log report the same value —
        the cross-mode conformance artifact."""
        from repro.core import hashing
        return hashing.content_hash(self.memory)

    def state_hash(self) -> int:
        """``hash_pytree`` of the native-layout state — the within-layout
        artifact snapshots, merged records and replay audits verify."""
        from repro.core import hashing
        return hashing.hash_pytree(self.memory)

    def snapshot_bytes(self) -> bytes:
        if self._layout_sharded:
            raise ValueError(
                "sharded engines snapshot through checkpoint() (per-shard "
                "v2 snapshots + merged hash record), not one flat blob")
        return snapshot.snapshot_bytes(self.memory)

    def replay_log_fresh(self) -> int:
        """Re-apply the audit trail to S_0 with the one-command-at-a-time
        reference ``machine.replay``; returns the native-layout hash — must
        equal ``state_hash()`` (the paper's replayability guarantee). In
        sharded mode each shard's (routed, padded) log replays on its
        genesis slice and the merge is hashed — the sharded form of the
        same audit.

        Re-links mutate the graph without a logged command, so the replay
        interleaves ``hnsw.relink`` at the recorded ``relink_ts`` cursors —
        the flat cursor is the global log index, a per-shard cursor is the
        per-shard padded log offset, so slicing each log at the recorded
        cursors replays exactly the prefix each firing saw (DESIGN.md §11).
        """
        from repro.core import hashing
        if not self._layout_sharded:
            st = init_state(self.sc.capacity, self.cfg.d_model,
                            contract=self.sc.contract)
            pos = 0
            for t in self.relink_ts:
                st = machine.replay(st, self.log.slice(pos, t))
                st = hnsw.relink(st)
                pos = t
            st = machine.replay(st, self.log.slice(pos, len(self.log)))
            return hashing.hash_pytree(st)
        genesis = distributed.init_sharded_host(
            self.n_shards, self.sc.capacity // self.n_shards,
            self.cfg.d_model, contract=self.sc.contract)
        parts = []
        for s in range(self.n_shards):
            st = distributed.shard_slice(genesis, s, self.n_shards)
            log = self._shard_logs[s]
            pos = 0
            for t in self.relink_ts:
                st = machine.replay(st, log.slice(pos, t))
                st = hnsw.relink(st)
                pos = t
            parts.append(machine.replay(st, log.slice(pos, len(log))))
        return hashing.hash_pytree(distributed.merge_shards(parts))
