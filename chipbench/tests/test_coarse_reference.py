"""The plain reference of the coarse route against the program: the same
table as ``codes.build`` and the same answers as ``search.coarse_search``,
bit for bit, on seeded rows with tombstones, ids unlike their slots and,
on one seed, planted ties; and with ``ef`` >= live rows, the exact k-NN."""
from __future__ import annotations

import dataclasses
import sys

import numpy as np
import pytest

from benchfix import BENCH, REPO

sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))

from harness import coarse_reference, reference  # noqa: E402

CAP, DIM, NQ, K, EF = 4096, 64, 16, 10, 256


def _arena(seed: int, ties: bool):
    """(raw rows [CAP, DIM], ids [CAP], valid [CAP], queries [NQ, DIM]):
    about one slot in 16 dead (its stale vector left in place), ids a
    shuffle of the slots. With ``ties``, rows repeat in runs of four and
    some queries equal stored rows, so coarse and exact scores tie."""
    rng = np.random.default_rng(seed)
    centres = rng.standard_normal((8, DIM)).astype(np.float32)
    floats = (centres[rng.integers(0, 8, CAP)]
              + 0.02 * rng.standard_normal((CAP, DIM))).astype(np.float32)
    if ties:
        floats = np.repeat(floats[::4], 4, axis=0)
    rows = reference.boundary(floats)
    valid = rng.random(CAP) >= 1 / 16
    ids = np.where(valid, rng.permutation(CAP).astype(np.int64), -1)
    q = (centres[rng.integers(0, 8, NQ)]
         + 0.02 * rng.standard_normal((NQ, DIM))).astype(np.float32)
    q = reference.boundary(q)
    if ties:
        q[: NQ // 2] = rows[rng.choice(np.nonzero(valid)[0], NQ // 2)]
    return rows, ids, valid, q


def _state(rows, ids, valid):
    import jax.numpy as jnp
    from repro.core.state import init_state
    n = int(valid.sum())
    return dataclasses.replace(
        init_state(CAP, DIM), vectors=jnp.asarray(rows),
        ids=jnp.asarray(ids), valid=jnp.asarray(valid),
        cursor=jnp.asarray(CAP, jnp.int32), count=jnp.asarray(n, jnp.int32))


def _reference(rows, ids, valid, q, ef):
    live = np.nonzero(valid)[0].astype(np.int64)

    def rows_of(b):
        return live, ids[live], rows[live]
    return coarse_reference.search(1, rows_of, CAP, q, K, ef)


@pytest.mark.parametrize("ef", [K, EF])
@pytest.mark.parametrize("seed,ties", [(1, False), (2, False), (3, True)])
def test_reference_equals_the_program(seed, ties, ef):
    import jax.numpy as jnp
    from repro.core import codes, search

    rows, ids, valid, q = _arena(seed, ties)
    state = _state(rows, ids, valid)
    table = codes.build(state)
    got_ids, got_scores = search.coarse_search(state, table, jnp.asarray(q),
                                               K, ef_coarse=ef)
    ref = _reference(rows, ids, valid, q, ef)
    np.testing.assert_array_equal(ref.table.codes, np.asarray(table.codes))
    np.testing.assert_array_equal(ref.table.offset, np.asarray(table.offset))
    np.testing.assert_array_equal(ref.table.scale, np.asarray(table.scale))
    np.testing.assert_array_equal(ref.table.norms, np.asarray(table.norms))
    np.testing.assert_array_equal(ref.ids, np.asarray(got_ids))
    np.testing.assert_array_equal(ref.scores, np.asarray(got_scores))
    if ef == K:   # the cut drops some of the exact top-k here
        assert coarse_reference.recall(ref.ids, ref.exact_ids) < 1.0


@pytest.mark.parametrize("seed,ties", [(4, False), (5, True)])
def test_covering_cut_is_exact_knn(seed, ties):
    rows, ids, valid, q = _arena(seed, ties)
    ref = _reference(rows, ids, valid, q, int(valid.sum()))
    top = reference.TopK(q, K)
    live = np.nonzero(valid)[0]
    top.add(rows[live], ids[live])
    want_ids, want_scores = top.result()
    np.testing.assert_array_equal(ref.ids, want_ids)
    np.testing.assert_array_equal(ref.scores, want_scores)
    np.testing.assert_array_equal(ref.exact_ids, want_ids)
    assert coarse_reference.recall(ref.ids, ref.exact_ids) == 1.0
