"""The plain references agree with the program at small sizes, and the
work functions count what they say."""
from __future__ import annotations

import dataclasses
import importlib.util
import sys

import numpy as np
import pytest

from benchfix import BENCH, REPO

sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))

from harness import data, reference  # noqa: E402


def _floats(n, dim, seed=0):
    return np.array(data.rows_block_jit(data.seed_key(seed), 0, n, dim))


def test_generator_is_exact_and_the_same_in_any_program():
    import jax
    key = data.seed_key(2 ** 34 + 5)
    a = np.asarray(data.rows_block_jit(key, 3, 256, 32))
    b = np.asarray(jax.jit(lambda k: jax.vmap(
        lambda i: data.rows_block(k, i, 256, 32))(np.arange(4)))(key))[3]
    assert (a == b).all()
    assert (a * 2 ** 22 == np.round(a * 2 ** 22)).all()
    gaps = data.poisson_gaps(10.0, 5000, 1)
    assert (np.sort(gaps) == np.sort(data.poisson_gaps(10.0, 5000, 2))).all()
    assert np.mean(gaps) == pytest.approx(0.1, rel=0.01)


@pytest.mark.parametrize("int_bits,frac_bits", [(15, 16), (7, 8)])
def test_boundary_matches_the_program(int_bits, frac_bits):
    from repro.core import boundary
    from repro.core.contracts import PrecisionContract
    x = _floats(512, 48)
    x[0] = 0.0                                        # zero row
    x[1, :4] = np.float32(2.0 ** -17) * np.array([1, -1, 3, -3])  # halves
    x[2, 0] = 1e9                                      # saturates
    c = PrecisionContract("t", int_bits=int_bits, frac_bits=frac_bits)
    want = np.asarray(boundary.normalize_embedding(x, c))
    got = reference.boundary(x, int_bits=int_bits, frac_bits=frac_bits)
    assert (got == want).all()
    assert (reference.boundary_rows(x, chunk=100, int_bits=int_bits,
                                        frac_bits=frac_bits) == want).all()


def test_exact_topk_matches_the_program_and_the_control_does_not():
    import jax.numpy as jnp
    from repro.core import search
    from repro.core.state import init_state
    rows = reference.boundary(_floats(600, 64, 1))
    rows[5] = rows[4]                                  # a tie, broken by id
    q = reference.boundary(_floats(9, 64, 2))
    q[0] = rows[4]
    st = init_state(1024, 64)
    ids = np.arange(600, dtype=np.int64)[::-1].copy()
    st = dataclasses.replace(st, vectors=st.vectors.at[:600].set(rows),
                             ids=st.ids.at[:600].set(ids),
                             valid=st.valid.at[:600].set(True))
    want_i, want_s = search.exact_search(st, jnp.asarray(q), 10)
    ref = reference.TopK(q, 10)
    for a in range(0, 600, 256):
        ref.add(rows[a:a + 256], ids[a:a + 256])
    got_i, got_s = ref.result()
    assert (got_i == np.asarray(want_i)).all()
    assert (got_s == np.asarray(want_s)).all()
    ctl = reference.TopK(q, 10, "float32")
    ctl.add(rows, ids)
    assert (ctl.result()[1] != got_s).any()


def test_hnsw_reference_matches_bulk_apply():
    import jax.numpy as jnp
    from repro.core import commands, machine
    from repro.core.state import init_state
    rows = reference.boundary(_floats(300, 16, 3))
    st = init_state(512, 16)
    for a in range(0, 300, 64):
        b = min(a + 64, 300)
        st = machine.bulk_apply(st, commands.insert_batch(
            jnp.arange(a, b, dtype=jnp.int64), jnp.asarray(rows[a:b])))
    g = reference.Hnsw(512, rows, np.arange(300))
    for s in range(300):
        g.insert(s)
    assert (np.asarray(st.hnsw_neighbors)[:, :300] == g.nbrs).all()
    assert (np.asarray(st.hnsw_levels)[:300] == g.level).all()
    assert int(st.hnsw_entry) == g.entry


def test_wal_reader_reads_a_durable_store(tmp_path):
    import jax.numpy as jnp
    from repro.core import commands, durability
    from repro.core.state import init_state
    rows = reference.boundary(_floats(40, 8, 4))
    store = durability.DurableStore(tmp_path, genesis=init_state(64, 8),
                                    segment_records=16)
    for a in range(0, 40, 10):
        store.append(commands.insert_batch(
            jnp.arange(a, a + 10, dtype=jnp.int64), jnp.asarray(rows[a:a + 10])))
    recs = reference.read_wal(tmp_path / "wal")
    assert recs == [(1, i, rows[i].astype("<i4").tobytes())
                    for i in range(40)]


def test_scan_work_counts_one_pass_over_the_rows():
    spec = importlib.util.spec_from_file_location("scan", BENCH / "work" /
                                                  "scan.py")
    scan = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(scan)
    ops, nbytes = scan.exact(1, 1_000_000, 768)
    assert ops == 2 * 768 * 1_000_000
    assert nbytes == 4 * 768 * 1_000_001
    peaks = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
    assert scan.bound_s(1, 1_000_000, 768, peaks) == pytest.approx(
        nbytes / 819e9)
    assert scan.bound_s(128, 1_000_000, 128, peaks) == pytest.approx(
        4 * 128 * 1_000_128 / 819e9)
