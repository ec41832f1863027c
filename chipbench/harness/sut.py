"""The calls into the system under test, in the order the serve engine
makes them in flat mode (``MemoryAugmentedEngine.retrieve`` and
``insert_documents``), each inside a host span.

The program has no vector-in serve entry yet, so the window drives the
substrate's public functions directly. Settings a cell does not name take
``ServeConfig``'s defaults: what a user of the engine gets. One departure:
the boundary runs under ``jax.jit``. Called eagerly, as the engine calls
it, ``fixedpoint.isqrt``'s ``fori_loop`` body is a new closure each call,
so every call traces and compiles again, and nothing may compile inside
the measured window.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time

import jax
import numpy as np


@functools.cache
def _boundary():
    from repro.core import boundary
    return (jax.jit(boundary.admit_query),
            jax.jit(boundary.normalize_embedding))


class Spans:
    """Host spans: a ``TraceAnnotation`` in the profiler's trace (when one
    is running) and a (name, start, seconds) record kept in memory."""

    def __init__(self):
        self.records = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.records.append((name, t0, time.perf_counter() - t0))

    def total(self, name: str, since: float = 0.0) -> tuple:
        """(count, seconds) of the spans called ``name`` that started at
        or after ``since``."""
        d = [s for n, t, s in self.records if n == name and t >= since]
        return len(d), float(sum(d))


def serve_defaults() -> dict:
    from repro.serve.engine import ServeConfig
    return {f.name: f.default for f in dataclasses.fields(ServeConfig)}


def plan(live: int, k: int, dim: int, serve: dict):
    """``query.plan_query`` with the cell's settings over the defaults."""
    from repro.core import query
    s = {**serve_defaults(), **serve}
    return query.plan_query(live, k, s["ef"], use_kernel=s["use_kernel"],
                            exact_threshold=s["exact_threshold"],
                            route=s["route"], ef_coarse=s["ef_coarse"],
                            dim=dim)


def read(state, q, k: int, serve: dict, spans: Spans):
    """One retrieve: float32 queries [nq, d] in, answers on the host out.

    Returns (admitted queries, still on the device; ids [nq, k]; wide
    scores [nq, k]). An answer counts once it is on the host."""
    from repro.core import query, shard_wal
    with spans("admit"):
        q_raw = _boundary()[0](q)
    with spans("live_count"):
        live = shard_wal.live_count(state)
    with spans("plan"):
        p = plan(live, k, state.dim, serve)
    with spans("execute"):
        ids, scores = query.execute_plan(state, q_raw, k, p)
    with spans("fetch"):
        ids, scores = np.asarray(ids), np.asarray(scores)
    return q_raw, ids, scores


def ingest(store, state, rows, ids: np.ndarray, spans: Spans):
    """One durable batch: boundary, command log, WAL append (fsynced),
    bulk apply. The batch is acknowledged when this returns."""
    import jax.numpy as jnp
    from repro.core import commands, machine
    with spans("boundary"):
        raw = _boundary()[1](rows)
    with spans("commands"):
        log = commands.insert_batch(jnp.asarray(ids, jnp.int64), raw)
    with spans("append"):
        store.append(log)
    with spans("apply"):
        state = machine.bulk_apply(state, log)
    with spans("ack"):
        jax.block_until_ready(state)
    return state
