"""The program's spans, counters and device scopes (``repro.obs``).

Spans are kept in memory only while a profiler session collects, and sit
in the trace at a constant offset from their records; host fetches and
compiles are counted under the span open at the time; the compiled
programs carry the ``scan``, ``select``, ``slot_scatter`` and
``hnsw_insert`` scopes on the ops that do that work; and none of it
changes a result.
"""
import contextlib
import glob
import re
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro  # noqa: F401  (x64, as every caller has it)
from repro import obs
from repro.core import (boundary, commands, durability, hashing, machine,
                        search)
from repro.core.state import init_state

CAP, D = 64, 8


@contextlib.contextmanager
def _profiling(directory):
    jax.profiler.start_trace(str(directory))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _batch(n: int, seed: int = 0) -> commands.CommandLog:
    rows = boundary.normalize_embedding(np.random.default_rng(seed).normal(
        size=(n, D)).astype(np.float32))
    return commands.insert_batch(jnp.arange(n, dtype=jnp.int64), rows)


def test_spans_are_kept_only_while_the_profiler_collects(tmp_path):
    obs.reset()
    with obs.span("test.before"):
        pass
    assert obs.records() == []
    with _profiling(tmp_path):
        with obs.span("test.during"):
            pass
    with obs.span("test.after"):
        pass
    (r,) = obs.records()
    assert r.name == "test.during" and 0 < r.start_ns <= r.end_ns
    assert (r.parent, r.n) == (-1, 0)


def test_parents_and_requests(tmp_path):
    obs.reset()
    with _profiling(tmp_path):
        with obs.span("a.root"):
            with obs.span("a.child"):
                with obs.span("a.leaf"):
                    obs.count("test_count", 2)
            with obs.span("a.second"):
                pass
        with obs.span("b.root"):
            pass
    recs = obs.records()
    assert [r.name for r in recs] == ["a.root", "a.child", "a.leaf",
                                      "test_count", "a.second", "b.root"]
    assert [r.parent for r in recs] == [-1, 0, 1, 2, 0, -1]
    assert len({r.request for r in recs[:5]}) == 1
    assert recs[5].request != recs[0].request
    for r in recs:
        if r.parent >= 0:
            p = recs[r.parent]
            assert p.start_ns <= r.start_ns <= r.end_ns <= p.end_ns
    count = recs[3]
    assert (count.n, count.start_ns == count.end_ns) == (2, True)
    assert obs.counters()["test_count"] == {"a.leaf": 2}


def test_spans_sit_in_the_cpu_trace_at_a_constant_offset(tmp_path):
    from jax.profiler import ProfileData
    obs.reset()
    with _profiling(tmp_path):
        for _ in range(20):
            with obs.span("test.aligned"):
                time.sleep(0.001)
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))[-1]
    starts = sorted(e.start_ns for plane in ProfileData.from_file(path).planes
                    if plane.name.startswith("/host:")
                    for line in plane.lines for e in line.events
                    if e.name == "test.aligned")
    recs = obs.records()
    assert len(starts) == len(recs) == 20
    offsets = [s - r.start_ns for s, r in zip(starts, recs)]
    q1, _, q3 = statistics.quantiles(offsets, n=4)
    assert q3 - q1 <= 50_000


def test_one_durable_batch_fetches_twelve_times(tmp_path):
    state = init_state(CAP, D)
    store = durability.DurableStore(tmp_path / "store", genesis=state)
    log = _batch(10)
    obs.reset()
    store.append(log)
    machine.bulk_apply(state, log)
    # the WAL's five log fields; bulk_apply's four log fields and the
    # allocator mirror's ids, valid and levels
    assert obs.counters()["host_syncs"] == {"wal.append": 5,
                                            "bulk_apply.plan": 7}


def test_a_fresh_jit_compiles_under_its_span():
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        obs.reset()
        with obs.span("test.compile"):
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0)).block_until_ready()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    assert obs.counters()["compiles"]["test.compile"] >= 1


def test_bulk_apply_is_the_same_with_the_profiler_on(tmp_path):
    state, log = init_state(CAP, D), _batch(20, seed=3)
    off = hashing.hash_pytree(machine.bulk_apply(state, log))
    obs.reset()
    with _profiling(tmp_path):
        on = hashing.hash_pytree(machine.bulk_apply(state, log))
    assert on == off
    assert [r.name for r in obs.records() if r.n == 0] == [
        "bulk_apply.plan", "bulk_apply.dispatch"]


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%\S+ = .*? ([a-z][a-z0-9-]*)\(")


def _scopes_by_opcode(text: str) -> dict:
    """{opcode: [named scopes of each such op's op_name]}."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            path = re.search(r'op_name="([^"]*)"', line)
            parts = path.group(1).split("/")[:-1] if path else []
            out.setdefault(m.group(1), []).append(parts)
    return out


@pytest.mark.parametrize("use_kernel", [False, True])
def test_search_program_carries_scan_and_select(use_kernel):
    state = machine.bulk_apply(init_state(CAP, D), _batch(40))
    q = boundary.admit_query(np.ones((4, D), np.float32))
    ops = _scopes_by_opcode(search.exact_search.lower(
        state, q, 5, use_kernel=use_kernel).compile().as_text())
    assert ops["dot"] and all("scan" in p for p in ops["dot"])
    assert ops["sort"] and all("select" in p for p in ops["sort"])


def test_insert_program_carries_slot_scatter_and_hnsw_insert():
    log = machine._pad_log(_batch(10), 16)
    ops = _scopes_by_opcode(machine._apply_insert_segment.lower(
        init_state(CAP, D), log, jnp.int32(10),
        ef_construction=32).compile().as_text())
    scatters = ops["scatter"]
    assert sum("slot_scatter" in p for p in scatters) >= 5
    assert all("slot_scatter" in p or "hnsw_insert" in p for p in scatters)
    assert ops["while"] and all("hnsw_insert" in p for p in ops["while"])
    assert all("hnsw_insert" in p for p in ops["sort"])
