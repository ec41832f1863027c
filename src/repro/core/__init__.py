"""Valori deterministic memory substrate — the paper's primary contribution.

Public surface:
  contracts   — Q-format precision contracts (paper §6)
  fixedpoint  — exact integer arithmetic (paper §5.1)
  boundary    — the float→fixed determinism boundary (paper §5.3)
  state       — MemoryState arena pytree (paper §5.2)
  commands    — integer-encoded replayable command log (paper §3.1)
  machine     — the pure transition function F + replay (paper §3.1) and
                the hash-identical vectorized bulk_apply (DESIGN.md §3)
  hashing     — platform-invariant tree hashes (paper §8.1)
  snapshot    — serialize/restore with hash verification (paper §8.1):
                v1 blobs + v2 chunked content-addressed store (DESIGN.md §5)
  wal         — segmented, hash-chained write-ahead command log with
                replay-equivalent compaction (DESIGN.md §5), group commit
                and scheduled compaction policies (DESIGN.md §6)
  durability  — DurableStore: snapshots + WAL + restore_at time travel,
                crash recovery, retention (DESIGN.md §5)
  shard_wal   — ShardedDurableStore: per-shard WALs reconciled to one
                global cursor, durable distributed ingest (DESIGN.md §6)
  search      — exact deterministic k-NN (wide integer scores) and the
                compressed coarse tier's scan + exact re-rank
  codes       — deterministic int8 code table over Q16.16 rows: pure
                function of the live rows, incrementally maintained,
                chunk-snapshot-able (DESIGN.md §10)
  hnsw        — deterministic HNSW (paper §7), TPU-adapted
  query       — batched deterministic query engine: vmapped HNSW, planner,
                shard fan-out (DESIGN.md §4)
  distributed — pod-scale sharded memory over shard_map (DESIGN.md §2)

Most-used entry points (each docstring states the contract it promises):
  replay / bulk_apply      — Apply(S_0, {C_i}); bulk form is hash-identical
  DurableStore, restore_at — durable history; restore_at(t) ≡ replay(log[:t])
  GroupCommitPolicy, GroupCommitWriter — one fsync per group of commands
  CompactionPolicy         — dead-ratio-scheduled WAL compaction
  ShardedDurableStore      — per-shard WALs, one reconciled global cursor
  plan_query               — deterministic exact-vs-HNSW route from host ints
"""
from repro.core import (boundary, codes, commands, contracts, distributed,
                        durability, fixedpoint, hashing, hnsw, machine, query,
                        search, shard_wal, snapshot, state, wal)
from repro.core.contracts import (CONTRACTS, DEFAULT_CONTRACT, Q8_8, Q16_16,
                                  Q32_32, PrecisionContract, get_contract)
from repro.core.durability import DurableStore, SideTable, restore_at
from repro.core.hashing import content_hash
from repro.core.machine import apply_command, bulk_apply, replay
from repro.core.query import plan_query, retrieval_hash, sharded_host_query
from repro.core.shard_wal import ShardedDurableStore
from repro.core.state import MemoryState, init_state
from repro.core.wal import (CompactionPolicy, GroupCommitPolicy,
                            GroupCommitWriter, WriteAheadLog)

__all__ = [
    "boundary", "codes", "commands", "contracts", "distributed",
    "durability", "fixedpoint", "hashing", "hnsw", "machine", "query",
    "search", "shard_wal", "snapshot", "state", "wal",
    "CONTRACTS", "DEFAULT_CONTRACT", "Q8_8", "Q16_16", "Q32_32",
    "PrecisionContract", "get_contract", "MemoryState", "init_state",
    "apply_command", "bulk_apply", "replay", "content_hash",
    "DurableStore", "SideTable", "restore_at", "plan_query",
    "retrieval_hash", "sharded_host_query",
    "ShardedDurableStore", "WriteAheadLog",
    "CompactionPolicy", "GroupCommitPolicy", "GroupCommitWriter",
]
