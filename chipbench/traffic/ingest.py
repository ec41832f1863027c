"""Durable bulk load: one client writes batches of ``batch`` fresh rows
into an empty arena back to back. Each batch crosses the boundary, is
appended to the WAL and fsynced, applied (arena rows and HNSW graph), and
acknowledged once the device has finished; only then does the next one
start. The client draws each batch from the seed on the device just
before it sends it, so no batch is made that the window does not use."""
from __future__ import annotations

import shutil
import tempfile
import time

import jax
import numpy as np


class _NoWal:
    def append(self, log):
        pass


def run(r):
    import jax.numpy as jnp
    from harness import data, sut
    from repro.core import durability
    from repro.core.state import init_state

    cfg, t = r.config, r.traffic
    B, cap, dim = t["batch"], cfg["capacity"], cfg["dim"]
    key = data.seed_key(r.seed)
    store_dir = tempfile.mkdtemp(prefix="chipbench-wal-")
    try:
        state = init_state(cap, dim)
        store = durability.DurableStore(store_dir, genesis=state)
        # warm every program on the live arena's shapes; bulk apply leaves
        # its input as it was, so the warm result is dropped. The WAL
        # append is host code with nothing to compile: the warm batch
        # skips it
        warm = sut.ingest(_NoWal(), state, data.rows_block_jit(
            key, 1 << 30, B, dim), np.arange(B), r.spans)
        jax.block_until_ready(warm)
        del warm
        r.setup_done()

        done = 0
        with r.window() as w:
            while (time.perf_counter() - w.start < r.seconds
                   and (done + 1) * B <= cap):
                with r.spans("client"):
                    rows = data.rows_block_jit(key, done, B, dim)
                state = sut.ingest(store, state, rows,
                                   np.arange(done * B, (done + 1) * B),
                                   r.spans)
                done += 1
        n = done * B
        got = {
            "vectors": np.asarray(state.vectors[:n]),
            "ids": np.asarray(state.ids), "valid": np.asarray(state.valid),
            "nonzero_after": int(jnp.count_nonzero(state.vectors[n:])),
            "neighbors": np.asarray(state.hnsw_neighbors[:, :n]),
            "levels": np.asarray(state.hnsw_levels),
            "entry": int(state.hnsw_entry),
            "scalars": (int(state.count), int(state.cursor),
                        int(state.version)),
        }
        del state
        from harness import reference
        wal = reference.read_wal(f"{store_dir}/wal")
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)
    checks = check(key, cfg, t, n, got, wal, control=r.control)
    appends, append_s = r.spans.total("append", w.start)
    return {
        "metrics": {"ingest_rows_per_s": n / w.seconds},
        "attempted": done, "failed": 0, "checks": checks,
        "counts": {"batches": done, "rows": n, "dim": dim, "capacity": cap,
                   "appends": appends, "append_s": append_s},
    }


def check(key, cfg, t, n, got, wal, control=False):
    """Compare the arena, the graph and the WAL with the plain reference:
    every acknowledged row, in slot order, under its id. With ``control``
    the reference one precision lower (Q8.8) takes the program's place."""
    from harness import data, reference
    B, cap, dim = t["batch"], cfg["capacity"], cfg["dim"]
    floats = np.concatenate([np.asarray(data.rows_block_jit(key, b, B, dim))
                             for b in range(n // B)]) if n else \
        np.zeros((0, dim), np.float32)
    ref = reference.boundary_rows(floats) if n else \
        np.zeros((0, dim), np.int32)
    ext = np.arange(n, dtype=np.int64)
    g = reference.Hnsw(cap, ref, ext, levels=cfg["hnsw_levels"],
                       degree=cfg["hnsw_degree"], ef=cfg["ef_construction"])
    if control:
        low = reference.boundary_rows(floats, int_bits=7, frac_bits=8) \
            if n else ref
        c = reference.Hnsw(cap, low, ext, levels=cfg["hnsw_levels"],
                           degree=cfg["hnsw_degree"],
                           ef=cfg["ef_construction"])
        for s in range(n):
            c.insert(s)
        ids = np.full(cap, -1, np.int64)
        ids[:n] = ext
        got = {"vectors": low.astype(np.int32), "ids": ids,
               "valid": np.arange(cap) < n, "nonzero_after": 0,
               "neighbors": c.nbrs.astype(np.int32),
               "levels": np.concatenate([c.level, np.full(cap - n, -1)]),
               "entry": c.entry, "scalars": (n, n, n)}
        wal = [(1, int(i), low[i].astype("<i4").tobytes()) for i in range(n)]
    for s in range(n):
        g.insert(s)
    ref_ids = np.full(cap, -1, np.int64)
    ref_ids[:n] = ext
    rows_off = int(np.sum(np.any(got["vectors"] != ref, axis=1))) \
        + int(np.sum(got["ids"] != ref_ids)) \
        + int(np.sum(got["valid"] != (np.arange(cap) < n))) \
        + got["nonzero_after"]
    levels = np.concatenate([g.level, np.full(cap - n, -1)])
    graph_off = int(np.sum(got["neighbors"] != g.nbrs)) \
        + int(np.sum(got["levels"] != levels)) \
        + int(got["entry"] != (g.entry if n else -1))
    want = [(1, i, ref[i].astype("<i4").tobytes()) for i in range(n)]
    wal_off = abs(len(wal) - n) + sum(a != b for a, b in zip(wal, want))
    return [
        ("arena_rows_differing", rows_off, 0),
        ("arena_counters_differing",
         sum(int(v != n) for v in got["scalars"]), 0),
        ("graph_entries_differing", graph_off, 0),
        ("wal_records_differing", wal_off, 0),
    ]
