"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (exact equality —
integer kernels admit no tolerance)."""
import jax.numpy as jnp
import numpy as np
import pytest
from _pbt import given, settings
from _pbt import strategies as st

import repro  # noqa: F401
from repro.kernels.qgemm import ops as qgemm_ops
from repro.kernels.qgemm import ref as qgemm_ref
from repro.kernels.qtopk import ops as qtopk_ops
from repro.kernels.qtopk import ref as qtopk_ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("nq,nn,d", [
    (1, 1, 8), (4, 16, 32), (8, 128, 64), (128, 256, 512),
    (7, 100, 384), (130, 257, 640), (16, 1000, 768), (3, 33, 8192),
])
def test_qgemm_exact_vs_oracle(nq, nn, d):
    q = RNG.integers(-65536, 65537, size=(nq, d)).astype(np.int32)
    db = RNG.integers(-65536, 65537, size=(nn, d)).astype(np.int32)
    got = qgemm_ops.qgemm(jnp.asarray(q), jnp.asarray(db))
    want = qgemm_ref.qgemm_ref(jnp.asarray(q), jnp.asarray(db))
    assert (np.asarray(got) == np.asarray(want)).all()


def test_qgemm_extreme_values():
    """Boundary raws (±2^16) at max dim: the overflow-freedom proof, tested."""
    d = 8192
    q = np.full((2, d), 65536, np.int32)
    q[1] = -65536
    db = np.concatenate([np.full((1, d), 65536, np.int32),
                         np.full((1, d), -65536, np.int32)])
    got = qgemm_ops.qgemm(jnp.asarray(q), jnp.asarray(db))
    want = qgemm_ref.qgemm_ref(jnp.asarray(q), jnp.asarray(db))
    assert (np.asarray(got) == np.asarray(want)).all()
    assert int(got[0, 0]) == d * 65536 * 65536


def test_qgemm_rejects_oversized_dim():
    q = np.zeros((2, 16384), np.int32)
    with pytest.raises(ValueError, match="dim"):
        qgemm_ops.qgemm(jnp.asarray(q), jnp.asarray(q))


@given(st.integers(1, 6), st.integers(4, 200), st.integers(1, 16))
@settings(max_examples=25, deadline=None)
def test_qtopk_property(nq, n, k):
    k = min(k, n)
    s = RNG.integers(-2**45, 2**45, size=(nq, n)).astype(np.int64)
    keys = np.arange(n, dtype=np.int32)
    got_s, got_k = qtopk_ops.qtopk(jnp.asarray(s), jnp.asarray(keys), k)
    want_s, want_k = qtopk_ref.qtopk_ref(jnp.asarray(s), jnp.asarray(keys), k)
    assert (np.asarray(got_s) == np.asarray(want_s)).all()
    assert (np.asarray(got_k) == np.asarray(want_k)).all()


def test_qtopk_tie_break_by_key():
    s = np.zeros((1, 64), np.int64)  # ALL tied
    keys = np.arange(64, dtype=np.int32)[::-1].copy()  # reversed keys
    got_s, got_k = qtopk_ops.qtopk(jnp.asarray(s), jnp.asarray(keys), 5)
    assert np.asarray(got_k)[0].tolist() == [0, 1, 2, 3, 4]


def test_qtopk_rejects_k_past_n():
    s = np.zeros((2, 8), np.int64)
    with pytest.raises(ValueError, match="k <= n"):
        qtopk_ops.qtopk(jnp.asarray(s), jnp.arange(8), 9)


def test_qtopk_big_block_sweep():
    for n in (1024, 2048, 4096, 5000):
        s = RNG.integers(-2**40, 2**40, size=(4, n)).astype(np.int64)
        keys = np.arange(n, dtype=np.int32)
        got = qtopk_ops.qtopk(jnp.asarray(s), jnp.asarray(keys), 16)
        want = qtopk_ref.qtopk_ref(jnp.asarray(s), jnp.asarray(keys), 16)
        assert (np.asarray(got[0]) == np.asarray(want[0])).all()
        assert (np.asarray(got[1]) == np.asarray(want[1])).all()


# --------------------------------------------------------------------------- #
# qboundary: the fused determinism boundary (quantize + integer normalize)
# --------------------------------------------------------------------------- #

from repro.core.contracts import Q8_8, Q16_16  # noqa: E402
from repro.kernels.qboundary import ops as qb_ops  # noqa: E402
from repro.kernels.qboundary import ref as qb_ref  # noqa: E402


@pytest.mark.parametrize("n,d", [(1, 8), (4, 16), (128, 384), (257, 768),
                                 (100, 64)])
def test_qboundary_bitwise_vs_oracle(n, d):
    x = RNG.normal(size=(n, d)).astype(np.float32) * 2
    got = qb_ops.qboundary(jnp.asarray(x), Q16_16)
    want = qb_ref.qboundary_ref(jnp.asarray(x), Q16_16)
    assert (np.asarray(got) == np.asarray(want)).all()


def test_qboundary_no_norm_and_saturation():
    x = np.asarray([[0.5, -1.0, 40000.0, -40000.0]], np.float32)
    got = qb_ops.qboundary(jnp.asarray(x), Q16_16, unit_norm=False)
    want = qb_ref.qboundary_ref(jnp.asarray(x), Q16_16, unit_norm=False)
    assert (np.asarray(got) == np.asarray(want)).all()
    assert int(got[0, 2]) == Q16_16.max_raw  # saturating convert


def test_qboundary_narrow_contract_falls_back():
    x = RNG.normal(size=(8, 16)).astype(np.float32)
    got = qb_ops.qboundary(jnp.asarray(x), Q8_8)       # int16 storage → ref path
    want = qb_ref.qboundary_ref(jnp.asarray(x), Q8_8)
    assert (np.asarray(got) == np.asarray(want)).all()


def test_qboundary_unit_norm_property():
    x = RNG.normal(size=(32, 128)).astype(np.float32) * 3
    raw = np.asarray(qb_ops.qboundary(jnp.asarray(x), Q16_16))
    norms = (raw.astype(np.float64) / Q16_16.one)
    lens = np.sqrt((norms ** 2).sum(-1))
    assert np.abs(lens - 1.0).max() < 1e-3


# --------------------------------------------------------------------------- #
# the compressed tier's coarse scan: int32 weights x int8 codes through the
# same qgemm kernel (DESIGN.md §10)
# --------------------------------------------------------------------------- #

from repro.core import codes as codes_lib  # noqa: E402
from repro.core import commands, machine, search  # noqa: E402
from repro.core.state import init_state  # noqa: E402

W = codes_lib.W_BOUND


@pytest.mark.parametrize("nq,nn,d", [
    (1, 1, 8), (4, 16, 32), (8, 128, 64), (128, 256, 512),
    (7, 100, 384), (130, 257, 640), (3, 33, 8192),
])
def test_qcoarse_exact_vs_oracle(nq, nn, d):
    """Odd/prime/padded shapes: the Pallas planes + combine == direct i64."""
    w = RNG.integers(-W, W + 1, size=(nq, d)).astype(np.int32)
    c = RNG.integers(-127, 128, size=(nn, d)).astype(np.int8)
    got = qgemm_ops.qgemm(jnp.asarray(w), jnp.asarray(c))
    want = qgemm_ref.qgemm_ref(jnp.asarray(w), jnp.asarray(c))
    assert (np.asarray(got) == np.asarray(want)).all()


def test_qcoarse_extreme_values():
    """|w| = W_BOUND, |c| = 127 at max dim: the overflow-freedom proof."""
    d = 8192
    w = np.full((2, d), W, np.int32)
    w[1] = -W
    c = np.concatenate([np.full((1, d), 127, np.int8),
                        np.full((1, d), -127, np.int8)])
    got = qgemm_ops.qgemm(jnp.asarray(w), jnp.asarray(c))
    want = qgemm_ref.qgemm_ref(jnp.asarray(w), jnp.asarray(c))
    assert (np.asarray(got) == np.asarray(want)).all()
    assert int(got[0, 0]) == d * W * 127


def test_qcoarse_rejects_oversized_dim():
    w = np.zeros((2, 16384), np.int32)
    c = np.zeros((2, 16384), np.int8)
    with pytest.raises(ValueError, match="dim"):
        qgemm_ops.qgemm(jnp.asarray(w), jnp.asarray(c))


@given(st.integers(1, 5), st.integers(1, 140), st.integers(8, 96))
@settings(max_examples=20, deadline=None)
def test_qcoarse_property(nq, nn, d):
    w = RNG.integers(-W, W + 1, size=(nq, d)).astype(np.int32)
    c = RNG.integers(-127, 128, size=(nn, d)).astype(np.int8)
    got = qgemm_ops.qgemm(jnp.asarray(w), jnp.asarray(c))
    want = qgemm_ref.qgemm_ref(jnp.asarray(w), jnp.asarray(c))
    assert (np.asarray(got) == np.asarray(want)).all()


def _coarse_state(n_live, d, n_dead=0, duplicate_rows=0, seed=7):
    """A flat state with n_live fresh rows, optionally some tombstones and
    duplicated vectors (ids stay unique — ties must break on id)."""
    rng = np.random.default_rng(seed)
    cap = max(64, n_live + n_dead + duplicate_rows)
    vecs = rng.integers(-65536, 65537, (n_live, d)).astype(np.int32)
    if duplicate_rows:
        vecs = np.concatenate([vecs, vecs[:duplicate_rows]], axis=0)
    n = len(vecs)
    ids = np.arange(n, dtype=np.int64)
    st_ = machine.bulk_apply(
        init_state(cap, d),
        commands.insert_batch(jnp.asarray(ids), jnp.asarray(vecs)))
    if n_dead:
        dead = np.arange(0, n, max(1, n // n_dead))[:n_dead].tolist()
        log = commands.delete_cmd(dead[0], d)
        for i in dead[1:]:
            log = log.concat(commands.delete_cmd(i, d))
        st_ = machine.bulk_apply(st_, log)
    return st_


@pytest.mark.parametrize("metric", ["l2", "dot"])
def test_coarse_search_kernel_parity(metric):
    """use_kernel=True (Pallas qgemm + qtopk) == jnp path, bit for bit."""
    st_ = _coarse_state(37, 24)
    tbl = codes_lib.build(st_)
    q = RNG.integers(-65536, 65537, (5, 24)).astype(np.int32)
    for ef in (8, 16, 64):
        a = search.coarse_search(st_, tbl, jnp.asarray(q), 5,
                                 ef_coarse=ef, metric=metric)
        b = search.coarse_search(st_, tbl, jnp.asarray(q), 5,
                                 ef_coarse=ef, metric=metric,
                                 use_kernel=True)
        assert (np.asarray(a[0]) == np.asarray(b[0])).all()
        assert (np.asarray(a[1]) == np.asarray(b[1])).all()


@pytest.mark.parametrize("metric", ["l2", "dot"])
def test_coarse_search_tombstones(metric):
    """Dead rows never surface, in either kernel mode, and coverage over
    the survivors still reproduces exact_search bit-for-bit."""
    st_ = _coarse_state(30, 16, n_dead=9)
    tbl = codes_lib.build(st_)
    q = RNG.integers(-65536, 65537, (4, 16)).astype(np.int32)
    want = search.exact_search(st_, jnp.asarray(q), 6, metric=metric)
    dead = set(np.arange(0, 30, max(1, 30 // 9))[:9].tolist())
    for uk in (False, True):
        ids, scores = search.coarse_search(st_, tbl, jnp.asarray(q), 6,
                                           ef_coarse=64, metric=metric,
                                           use_kernel=uk)
        assert not (set(np.asarray(ids).ravel().tolist()) & dead)
        assert (np.asarray(ids) == np.asarray(want[0])).all()
        assert (np.asarray(scores) == np.asarray(want[1])).all()


@pytest.mark.parametrize("metric", ["l2", "dot"])
def test_coarse_search_duplicate_vectors_tie_break(metric):
    """Identical vectors under different ids: the served tie order is the
    exact (score, id) order, identical across kernel modes and identical
    to exact_search under coverage."""
    st_ = _coarse_state(20, 12, duplicate_rows=10)
    tbl = codes_lib.build(st_)
    q = RNG.integers(-65536, 65537, (3, 12)).astype(np.int32)
    want = search.exact_search(st_, jnp.asarray(q), 8, metric=metric)
    for uk in (False, True):
        ids, scores = search.coarse_search(st_, tbl, jnp.asarray(q), 8,
                                           ef_coarse=64, metric=metric,
                                           use_kernel=uk)
        assert (np.asarray(ids) == np.asarray(want[0])).all()
        assert (np.asarray(scores) == np.asarray(want[1])).all()
