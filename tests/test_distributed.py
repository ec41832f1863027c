"""Distributed memory: shard_map run must equal the single kernel bitwise.

Needs >1 device → runs itself in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the parent test process
must keep seeing 1 device, per the dry-run isolation rule).
"""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    import repro
    from repro.core import boundary, commands, distributed, hashing, machine, search
    from repro.core.state import init_state

    mesh = jax.make_mesh((4, 2), ("model", "data"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
    D, N, K = 16, 96, 5
    rng = np.random.default_rng(0)
    vecs = boundary.normalize_embedding(rng.normal(size=(N, D)).astype(np.float32))
    ids = jnp.arange(N, dtype=jnp.int64) * 3 + 1
    log = commands.insert_batch(ids, vecs)

    ref = machine.replay(init_state(256, D), log)
    q = boundary.admit_query(rng.normal(size=(8, D)).astype(np.float32))
    ref_ids, ref_scores = search.exact_search(ref, q, K)

    routed = distributed.route_commands(log, 4)
    st = distributed.init_sharded_state(mesh, "model", 64, D)
    st = distributed.distributed_replay(mesh, "model", st, routed)
    d_ids, d_scores = distributed.distributed_search(
        mesh, "model", st, q, K, query_axis="data")
    assert (np.asarray(d_ids) == np.asarray(ref_ids)).all(), "ids diverged"
    assert (np.asarray(d_scores) == np.asarray(ref_scores)).all(), "scores diverged"

    # replay determinism across different shard counts: 2 vs 4 shards give
    # identical search answers
    mesh2 = jax.make_mesh((2, 4), ("model", "data"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
    st2 = distributed.init_sharded_state(mesh2, "model", 128, D)
    st2 = distributed.distributed_replay(mesh2, "model", st2,
                                         distributed.route_commands(log, 2))
    d2_ids, d2_scores = distributed.distributed_search(
        mesh2, "model", st2, q, K, query_axis="data")
    assert (np.asarray(d2_ids) == np.asarray(ref_ids)).all()
    assert (np.asarray(d2_scores) == np.asarray(ref_scores)).all()

    # sharded HNSW: deterministic across runs + high recall vs sharded exact
    h_ids, h_d = distributed.distributed_hnsw_search(
        mesh, "model", st, q, K, ef=48, query_axis="data")
    h_ids2, h_d2 = distributed.distributed_hnsw_search(
        mesh, "model", st, q, K, ef=48, query_axis="data")
    assert (np.asarray(h_ids) == np.asarray(h_ids2)).all()
    assert (np.asarray(h_d) == np.asarray(h_d2)).all()
    hits = sum(len(set(np.asarray(h_ids)[i].tolist())
                   & set(np.asarray(d_ids)[i].tolist()))
               for i in range(q.shape[0]))
    recall = hits / (q.shape[0] * K)
    assert recall >= 0.85, f"sharded hnsw recall {recall}"
    print("DISTRIBUTED_OK", recall)
""")


def test_sharded_memory_equals_single_kernel():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    repo_src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(repo_src)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "DISTRIBUTED_OK" in proc.stdout
