"""Batched deterministic query engine (DESIGN.md §4).

The read-path equivalence contract: every batched / planned / sharded search
is bit-identical — ids, wide scores, tie order — to the per-query reference
loop over ``hnsw.hnsw_search`` / ``search.exact_search``. Randomized logs
(inserts, deletes, duplicate vectors, non-contiguous ids) drive the checks;
``merge_topk``'s algebraic properties get a property test via ``_pbt``.
"""
import os
import re
import subprocess
import sys
import textwrap

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _pbt import given, settings
from _pbt import strategies as st

import repro  # noqa: F401
from repro.core import boundary, commands, hnsw, machine, query, search
from repro.core.state import init_state
from repro.kernels.qtopk import ops as qtopk_ops

D = 20
INF = int(search.INF)


def _random_state(seed: int, n: int = 120, capacity: int = 192,
                  n_delete: int = 10, n_dup: int = 0):
    """Replay a randomized log: shuffled non-contiguous ids, optional runs of
    duplicate vectors, a sprinkle of deletes."""
    rng = np.random.default_rng(seed)
    raw = rng.normal(size=(n, D)).astype(np.float32)
    if n_dup:
        raw[n // 3:n // 3 + n_dup] = raw[n // 3]
    vecs = boundary.normalize_embedding(raw)
    ids = rng.permutation(n).astype(np.int64) * 7 + 3
    log = commands.insert_batch(jnp.asarray(ids), vecs)
    for i in rng.choice(n, size=n_delete, replace=False):
        log = log.concat(commands.delete_cmd(int(ids[i]), D))
    return machine.replay(init_state(capacity, D), log), vecs


def _queries(seed: int, b: int = 8):
    rng = np.random.default_rng(seed)
    return boundary.admit_query(rng.normal(size=(b, D)).astype(np.float32))


# --------------------------------------------------------------------------- #
# tentpole: batched == per-query reference loop
# --------------------------------------------------------------------------- #


def test_batched_hnsw_equals_per_query_loop():
    for seed, k, ef in ((0, 5, 32), (1, 10, 64), (2, 3, 16)):
        state, _ = _random_state(seed, n_dup=4 if seed == 1 else 0)
        q = _queries(100 + seed)
        bi, bd, bs = query.batched_hnsw_search(state, q, k, ef=ef)
        for b in range(q.shape[0]):
            ri, rd, rs = hnsw.hnsw_search(state, q[b], k, ef=ef)
            assert (np.asarray(bi[b]) == np.asarray(ri)).all(), (seed, b)
            assert (np.asarray(bd[b]) == np.asarray(rd)).all(), (seed, b)
            assert (np.asarray(bs[b]) == np.asarray(rs)).all(), (seed, b)


def test_executed_plan_equals_reference_loop():
    """Whatever route the planner picks, the batched answer equals running
    that route's single-query reference one row at a time."""
    state, _ = _random_state(3)
    q = _queries(103)
    live = int(state.count)
    for plan in (
        query.plan_query(live, 5, 32),                     # → exact (small)
        query.plan_query(live, 5, 32, route="hnsw"),       # forced hnsw
        query.plan_query(live, 5, 32, use_kernel=True),    # exact via Pallas
    ):
        ids, scores = query.execute_plan(state, q, 5, plan)
        for b in range(q.shape[0]):
            if plan.route == query.ROUTE_EXACT:
                ri, rs = search.exact_search(state, q[b][None], 5)
                ri, rs = ri[0], rs[0]
            else:
                ri, rs, _ = hnsw.hnsw_search(state, q[b], 5, ef=plan.ef)
            assert (np.asarray(ids[b]) == np.asarray(ri)).all(), plan
            assert (np.asarray(scores[b]) == np.asarray(rs)).all(), plan


def test_planner_rules_are_static_and_deterministic():
    p = query.plan_query(100, 5, 32)
    assert p.route == query.ROUTE_EXACT and "live" in p.reason
    assert query.plan_query(100, 5, 32) == p  # pure data, replayable
    # k > ef can never come out of an ef-beam
    assert query.plan_query(50_000, 128, 64).route == query.ROUTE_EXACT
    # beam covers the whole corpus → scan
    assert query.plan_query(2_000, 5, 4_096).route == query.ROUTE_EXACT
    # big corpus, sane beam → graph
    assert query.plan_query(50_000, 10, 64).route == query.ROUTE_HNSW
    # operator override wins over every rule
    assert query.plan_query(10, 5, 32, route="hnsw").route == query.ROUTE_HNSW
    try:
        query.plan_query(10, 5, 32, route="scan")
        assert False, "unknown route must raise"
    except ValueError:
        pass
    # forcing hnsw with k > ef must raise, not hand back [B, ef] arrays
    try:
        query.plan_query(10, 48, 32, route="hnsw")
        assert False, "forced hnsw with k > ef must raise"
    except ValueError:
        pass


# --------------------------------------------------------------------------- #
# satellite: merge_topk algebra (property test via _pbt)
# --------------------------------------------------------------------------- #


def _random_topk_list(rng, m: int, k: int):
    """A sorted top-k-style list [k]: real (score, id) pairs up-front, then
    (INF, -1) padding; occasional tombstone score collisions."""
    n_real = int(rng.integers(0, k + 1))
    scores = np.sort(rng.integers(0, 2**40, size=n_real)).astype(np.int64)
    ids = rng.choice(m, size=n_real, replace=False).astype(np.int64)
    # sort the block the way a real top-k emits it: (score, id)
    order = np.lexsort((ids, scores))
    s = np.full(k, INF, np.int64)
    i = np.full(k, -1, np.int64)
    s[:n_real], i[:n_real] = scores[order], ids[order]
    return jnp.asarray(s), jnp.asarray(i)


def _eq(a, b):
    return all((np.asarray(x) == np.asarray(y)).all() for x, y in zip(a, b))


@given(st.integers(0, 2**31 - 1), st.integers(1, 12))
@settings(max_examples=30, deadline=None)
def test_merge_topk_is_associative_commutative_perm_invariant(seed, k):
    rng = np.random.default_rng(seed)
    a_s, a_i = _random_topk_list(rng, 10_000, k)
    b_s, b_i = _random_topk_list(rng, 10_000, k)
    c_s, c_i = _random_topk_list(rng, 10_000, k)

    ab = search.merge_topk(a_s, a_i, b_s, b_i, k)
    ba = search.merge_topk(b_s, b_i, a_s, a_i, k)
    assert _eq(ab, ba), "commutativity"

    ab_c = search.merge_topk(*ab, c_s, c_i, k)
    bc = search.merge_topk(b_s, b_i, c_s, c_i, k)
    a_bc = search.merge_topk(a_s, a_i, *bc, k)
    assert _eq(ab_c, a_bc), "associativity"

    # permutation invariance: shuffle the pooled candidates, merge again
    pool_s = jnp.concatenate([a_s, b_s])
    pool_i = jnp.concatenate([a_i, b_i])
    perm = rng.permutation(2 * k)
    pm = search.merge_candidates(pool_s[perm], pool_i[perm], k)
    assert _eq((pm[0], pm[1]), ab), "permutation invariance"


def test_merge_topk_tombstones_never_beat_real_results():
    k = 4
    real_s = jnp.asarray([7, 9, INF, INF], jnp.int64)
    real_i = jnp.asarray([42, 3, -1, -1], jnp.int64)
    pad_s = jnp.full((k,), INF, jnp.int64)
    pad_i = jnp.full((k,), -1, jnp.int64)
    s, i = search.merge_topk(pad_s, pad_i, real_s, real_i, k)
    assert np.asarray(i).tolist() == [42, 3, -1, -1]
    assert np.asarray(s).tolist() == [7, 9, INF, INF]


# --------------------------------------------------------------------------- #
# satellite: duplicate vectors tie-break identically on every path
# --------------------------------------------------------------------------- #


def test_duplicate_vectors_tie_break_by_id_on_all_paths():
    n, n_dup, k = 48, 6, 8
    rng = np.random.default_rng(11)
    raw = rng.normal(size=(n, D)).astype(np.float32)
    raw[16:16 + n_dup] = raw[16]            # duplicates under ids 16..24
    vecs = boundary.normalize_embedding(raw)
    ids = jnp.arange(n, dtype=jnp.int64)    # insert order == id order
    state = machine.replay(init_state(96, D), commands.insert_batch(ids, vecs))

    q = vecs[16][None]                      # the duplicated vector itself
    e_ids, e_s = search.exact_search(state, q, k)
    # the k nearest are the duplicates at distance 0, in ascending id order
    assert np.asarray(e_ids)[0, :n_dup].tolist() == list(range(16, 16 + n_dup))
    assert (np.asarray(e_s)[0, :n_dup] == 0).all()

    ke_ids, ke_s = search.exact_search(state, q, k, use_kernel=True)
    assert (np.asarray(ke_ids) == np.asarray(e_ids)).all()
    assert (np.asarray(ke_s) == np.asarray(e_s)).all()

    h_ids, h_d, _ = hnsw.hnsw_search(state, q[0], k, ef=64)
    assert (np.asarray(h_ids) == np.asarray(e_ids)[0]).all()
    assert (np.asarray(h_d) == np.asarray(e_s)[0]).all()

    b_ids, b_d, _ = query.batched_hnsw_search(state, q, k, ef=64)
    assert (np.asarray(b_ids) == np.asarray(e_ids)).all()
    assert (np.asarray(b_d) == np.asarray(e_s)).all()


# --------------------------------------------------------------------------- #
# satellite: exact_search kernel parity (Pallas interpret mode on CPU)
# --------------------------------------------------------------------------- #


def test_kernel_parity_l2_and_dot_odd_shapes():
    for seed, nq, n, dim, k, n_del in (
        (0, 1, 7, 5, 3, 0), (1, 3, 37, 19, 7, 5),
        (2, 5, 130, 33, 11, 17), (3, 2, 200, 24, 200, 40),
    ):
        rng = np.random.default_rng(seed)
        vecs = boundary.normalize_embedding(
            rng.normal(size=(n, dim)).astype(np.float32))
        ids = rng.permutation(n).astype(np.int64) * 11 + 2  # rank ≠ slot order
        log = commands.insert_batch(jnp.asarray(ids), vecs)
        for i in rng.choice(n, size=n_del, replace=False):
            log = log.concat(commands.delete_cmd(int(ids[i]), dim))
        state = machine.replay(init_state(n, dim), log)
        q = boundary.admit_query(rng.normal(size=(nq, dim)).astype(np.float32))
        for metric in (search.METRIC_L2, search.METRIC_DOT):
            ref = search.exact_search(state, q, k, metric=metric)
            got = search.exact_search(state, q, k, metric=metric,
                                      use_kernel=True)
            assert (np.asarray(got[0]) == np.asarray(ref[0])).all(), \
                (seed, metric)
            assert (np.asarray(got[1]) == np.asarray(ref[1])).all(), \
                (seed, metric)


# --------------------------------------------------------------------------- #
# the one exact select: its blockwise branch (qtopk, interpreted on CPU) ==
# the full sort it takes on the CPU, bit for bit
# --------------------------------------------------------------------------- #

BLOCK = qtopk_ops.BLOCK_N


def _select_inputs(seed: int, nq: int, n: int, metric: str, n_dead: int = 0,
                   n_live: int | None = None):
    """Scores and ids as ``exact_search`` hands them to its select.

    Rows come from a 5-letter alphabet in 4 dims (625 distinct vectors), so
    exact score ties are everywhere; twelve equal rows straddle the first
    block boundary and the first query is their vector, so under L2 its
    ties at score 0 straddle the k-th place for k <= 10. Ids are a shuffled
    stride (id order != slot order); dead rows score INF under 2^62 + slot.
    """
    rng = np.random.default_rng(seed)
    vecs = rng.integers(-2, 3, size=(n, 4)).astype(np.int32) * 4096
    edge = min(BLOCK, n) - 6
    vecs[edge:edge + 12] = vecs[edge]
    q = rng.integers(-2, 3, size=(nq, 4)).astype(np.int32) * 4096
    q[0] = vecs[edge]
    valid = np.ones(n, bool)
    if n_live is not None:
        valid[rng.permutation(n)[n_live:]] = False
    valid[rng.choice(n, size=n_dead, replace=False)] = False
    ids = rng.permutation(n).astype(np.int64) * 13 + 5
    scores = search.score_block(jnp.asarray(q), jnp.asarray(vecs), metric)
    scores = jnp.where(jnp.asarray(valid)[None, :], scores, search.INF)
    ids = jnp.where(jnp.asarray(valid), jnp.asarray(ids),
                    (jnp.int64(1) << 62) + jnp.arange(n, dtype=jnp.int64))
    return scores, ids


@pytest.mark.parametrize("metric", [search.METRIC_L2, search.METRIC_DOT])
@pytest.mark.parametrize("seed,nq,n,k,n_dead,n_live", [
    (0, 13, 2500, 10, 0, None),      # n not a multiple of the block
    (1, 5, 2500, 1, 200, None),      # k = 1, tombstones
    (2, 3, 1500, 128, 100, None),    # k = 128 over two blocks, one partial
    (3, 13, 2100, 128, 0, 50),       # fewer live rows than k
    (4, 8, 3 * 1024, 10, 40, None),  # n a multiple of the block
    (5, 1, 700, 10, 7, None),        # one short block
    (6, 130, 2100, 10, 30, None),    # two row blocks, the second padded
])
def test_blockwise_select_equals_full_sort(metric, seed, nq, n, k, n_dead,
                                          n_live):
    scores, ids = _select_inputs(seed, nq, n, metric, n_dead, n_live)
    want = search.topk_by_score(scores, ids, k)  # on CPU: the full sort
    got = qtopk_ops.qtopk(scores, ids, k)
    assert (np.asarray(got[0]) == np.asarray(want[0])).all()
    assert (np.asarray(got[1]) == np.asarray(want[1])).all()
    if seed == 0 and metric == search.METRIC_L2:  # ties cut at the k-th place
        assert (np.asarray(want[0])[0] == 0).all()


@pytest.mark.parametrize("n,k,blockwise", [
    (1 << 20, 10, True),      # the batch cell's select
    (4 * BLOCK, 128, True),
    (1 << 20, 129, False),    # k past 128
    (1 << 20, 1024, False),   # the coarse route's candidate cut
    (4 * BLOCK - 1, 10, True),  # the last block partial
    (BLOCK, 10, True),        # one block
    (BLOCK - 1, 10, False),   # a row shorter than a block
    (64, 5, False),           # tiny n
])
def test_select_path_follows_shapes(n, k, blockwise):
    """The blockwise branch is staged only where the shape rule admits it;
    everywhere else the select is the full sort on every platform."""
    assert search.blockwise_applies(n, k) == blockwise
    jaxpr = jax.make_jaxpr(functools.partial(search.topk_by_score, k=k))(
        jax.ShapeDtypeStruct((2, n), jnp.int64),
        jax.ShapeDtypeStruct((n,), jnp.int64))
    assert ("platform_index" in str(jaxpr)) == blockwise


# --------------------------------------------------------------------------- #
# the coarse route: its scopes and the count of whole-table builds
# --------------------------------------------------------------------------- #

_COARSE_SCOPES = ("code_scan", "select", "rerank")


@pytest.mark.parametrize("use_kernel", [False, True])
def test_coarse_program_ops_lie_under_one_scope_each(use_kernel):
    """Every op of the compiled coarse program that comes from
    ``coarse_search`` lies under exactly one of its three scopes."""
    from repro.core import codes
    state, _ = _random_state(4)
    table = codes.build(state)
    text = search.coarse_search.lower(
        state, table, _queries(4), 5, ef_coarse=32,
        use_kernel=use_kernel).compile().as_text()
    paths = re.findall(r'op_name="(jit\(coarse_search\)/[^"]*)"', text)
    assert paths
    for scope in _COARSE_SCOPES:
        assert any(scope in p.split("/")[:-1] for p in paths), scope
    for p in paths:
        parts = p.split("/")[:-1]
        assert sum(s in parts for s in _COARSE_SCOPES) == 1, p


def test_code_table_builds_counts_whole_table_builds():
    """``execute_plan`` without a table builds one and counts it; with the
    caller's table it builds none."""
    from repro import obs
    from repro.core import codes

    def builds():
        return sum(obs.counters().get("code_table_builds", {}).values())

    state, _ = _random_state(5)
    q = _queries(5)
    plan = query.plan_query(110, 5, 16, route="coarse", ef_coarse=32, dim=D)
    table = codes.build(state)
    before = builds()
    with_table = query.execute_plan(state, q, 5, plan, codes=table)
    assert builds() == before
    on_the_spot = query.execute_plan(state, q, 5, plan)
    assert builds() == before + 1
    for a, b in zip(with_table, on_the_spot):
        assert (np.asarray(a) == np.asarray(b)).all()


# --------------------------------------------------------------------------- #
# shard fan-out: planner-driven sharded query == single kernel, bitwise
# (multi-device → subprocess, per the dry-run isolation rule)
# --------------------------------------------------------------------------- #

_SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    import repro
    from repro.core import (boundary, commands, distributed, hnsw,
                            machine, query, search)
    from repro.core.state import init_state

    mesh = jax.make_mesh((4, 2), ("model", "data"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
    D, N, K = 16, 56, 8
    rng = np.random.default_rng(0)
    raw = rng.normal(size=(N, D)).astype(np.float32)
    raw[20:26] = raw[20]                       # duplicates under distinct ids
    vecs = boundary.normalize_embedding(raw)
    ids = jnp.arange(N, dtype=jnp.int64)
    log = commands.insert_batch(ids, vecs)

    ref = machine.replay(init_state(128, D), log)
    q = jnp.concatenate([vecs[20][None],       # ties: id-ordered duplicates
        boundary.admit_query(rng.normal(size=(7, D)).astype(np.float32))])
    ref_ids, ref_scores = search.exact_search(ref, q, K)
    assert np.asarray(ref_ids)[0, :6].tolist() == list(range(20, 26))

    routed = distributed.route_commands(log, 4)
    st = distributed.init_sharded_state(mesh, "model", 32, D)
    st = distributed.distributed_replay(mesh, "model", st, routed)

    # exact route: bit-identical to the single kernel, duplicates included
    plan = query.plan_query(int(np.asarray(st.count).sum()), K, 64)
    assert plan.route == query.ROUTE_EXACT
    d_ids, d_scores = query.sharded_query(mesh, "model", st, q, K, plan,
                                          query_axis="data")
    assert (np.asarray(d_ids) == np.asarray(ref_ids)).all(), "ids diverged"
    assert (np.asarray(d_scores) == np.asarray(ref_scores)).all()

    # hnsw route: per-shard beams cover each tiny shard fully (ef >= n_local),
    # so the merge_topk fan-in must reproduce the exact answer — duplicates
    # tie-break by id across shard boundaries
    hplan = query.plan_query(N, K, 64, route="hnsw")
    h_ids, h_scores = query.sharded_query(mesh, "model", st, q, K, hplan,
                                          query_axis="data")
    assert (np.asarray(h_ids) == np.asarray(ref_ids)).all(), "hnsw ids"
    assert (np.asarray(h_scores) == np.asarray(ref_scores)).all()
    print("SHARDED_QUERY_OK")
""")


def test_sharded_query_equals_single_kernel():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    repo_src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(repo_src)
    proc = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SHARDED_QUERY_OK" in proc.stdout
