"""Data pipeline, checkpointing, optimizer, gradient compression, elastic."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro  # noqa: F401
from repro.checkpoint.manager import (CheckpointManager, load_checkpoint,
                                      save_checkpoint)
from repro.core import hashing
from repro.data.pipeline import DataConfig, DeterministicPipeline, feistel_permute
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.runtime.elastic import plan_remesh


# --------------------------------------------------------------------------- #
# data pipeline
# --------------------------------------------------------------------------- #


def test_feistel_is_a_permutation():
    for n in (10, 100, 1000, 4096, 10_001):
        idx = np.arange(n)
        out = feistel_permute(idx, n, seed=3)
        assert sorted(out.tolist()) == list(range(n)), n
        assert not (out == idx).all()  # actually shuffles


def test_pipeline_deterministic_and_rank_consistent():
    cfg = DataConfig(seq_len=16, global_batch=8, vocab_size=101, seed=5)
    p = DeterministicPipeline(cfg)
    a = p.batch(3)
    b = p.batch(3)
    assert (a["tokens"] == b["tokens"]).all()
    # dp_size invariance: concatenating rank shards == the dp=1 batch
    parts = [p.batch(3, dp_rank=r, dp_size=4)["tokens"] for r in range(4)]
    assert (np.concatenate(parts) == a["tokens"]).all()


def test_pipeline_resume_mid_epoch():
    cfg = DataConfig(seq_len=8, global_batch=4, vocab_size=33, seed=1,
                     num_documents=64)
    p = DeterministicPipeline(cfg)
    trace_a = [p.batch(s)["tokens"] for s in range(40)]   # crosses epochs
    p2 = DeterministicPipeline(cfg)                        # "restarted" host
    trace_b = [p2.batch(s)["tokens"] for s in range(40)]
    for a, b in zip(trace_a, trace_b):
        assert (a == b).all()


def test_labels_are_shifted_tokens():
    p = DeterministicPipeline(DataConfig(seq_len=12, global_batch=2,
                                         vocab_size=50, seed=0))
    b = p.batch(0)
    assert (b["tokens"][:, 1:] == b["labels"][:, :-1]).all()


# --------------------------------------------------------------------------- #
# checkpointing
# --------------------------------------------------------------------------- #


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"w": jax.random.normal(k, (8, 8)),
            "b": jnp.arange(5, dtype=jnp.int32),
            "nested": {"s": jnp.float32(3.5)}}


def test_checkpoint_roundtrip_hash_verified(tmp_path):
    t = _tree()
    h = save_checkpoint(tmp_path / "c1", t, step=7)
    t2, step, h2 = load_checkpoint(tmp_path / "c1", jax.eval_shape(lambda: t))
    assert step == 7 and h == h2 == hashing.hash_pytree(t2)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(t2)):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_checkpoint_detects_tamper(tmp_path):
    t = _tree()
    save_checkpoint(tmp_path / "c1", t, step=1)
    # corrupt one leaf file
    target = tmp_path / "c1" / "0.npy"
    raw = bytearray(target.read_bytes())
    raw[-1] ^= 0xFF
    target.write_bytes(bytes(raw))
    with pytest.raises(ValueError, match="hash mismatch"):
        load_checkpoint(tmp_path / "c1", jax.eval_shape(lambda: t))


def test_manager_rotation_and_restore(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ckpts"), keep=2, async_save=False)
    for s in (10, 20, 30):
        mgr.save(_tree(s), s)
    assert mgr.steps() == [20, 30]  # rotated
    restored = mgr.restore_latest(jax.eval_shape(lambda: _tree()))
    assert restored is not None and restored[1] == 30


# --------------------------------------------------------------------------- #
# optimizer
# --------------------------------------------------------------------------- #


def test_adamw_reduces_quadratic_loss():
    optc = AdamWConfig(lr=0.1, warmup_steps=1, total_steps=100,
                       weight_decay=0.0)
    params = {"x": jnp.asarray([5.0, -3.0])}
    state = adamw_init(params)

    def loss(p):
        return jnp.sum(p["x"] ** 2)

    for _ in range(60):
        g = jax.grad(loss)(params)
        params, state, _ = adamw_update(optc, params, g, state)
    assert float(loss(params)) < 0.5


def test_adamw_deterministic():
    optc = AdamWConfig()
    params = {"x": jnp.ones((4, 4))}

    def run():
        p, s = params, adamw_init(params)
        for i in range(5):
            g = jax.tree.map(lambda a: a * 0.1 * (i + 1), p)
            p, s, _ = adamw_update(optc, p, g, s)
        return hashing.hash_pytree(p)

    assert run() == run()


# --------------------------------------------------------------------------- #
# elastic planning
# --------------------------------------------------------------------------- #


def test_plan_remesh_shrinks_data_axis():
    full = plan_remesh(512, model=16, prefer_pods=2)
    assert full.shape == (2, 16, 16) and full.dropped_chips == 0
    # lose 5 chips from one pod → biggest valid mesh
    degraded = plan_remesh(507, model=16)
    assert degraded.size <= 507
    assert degraded.shape[-1] == 16  # TP width preserved
    assert degraded.size >= 256      # still uses most of the fleet


def test_plan_remesh_keeps_pow2_data():
    p = plan_remesh(300, model=16)
    data = p.shape[-2]
    assert data & (data - 1) == 0  # power of two


# --------------------------------------------------------------------------- #
# gradient compression (needs a 'pod' axis → subprocess with 4 devices)
# --------------------------------------------------------------------------- #

_COMPRESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import functools
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    import repro
    from repro.optim import compress

    mesh = jax.make_mesh((4,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    grads = {"w": jnp.asarray(np.random.default_rng(0).normal(
        size=(4, 16, 16)).astype(np.float32) * 1e-3)}

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P("pod"),),
                       out_specs=P(), check_vma=False)
    def reduce_q(g):
        g = jax.tree.map(lambda a: a[0], g)
        mean, _ = compress.integer_psum_grads(g, "pod", "Q2.13")
        return mean

    got = reduce_q(grads)
    want = jnp.mean(grads["w"], axis=0)
    err = float(jnp.max(jnp.abs(got["w"] - want)))
    scale = float(jnp.max(jnp.abs(grads["w"])))
    # quantization error bounded by contract resolution * scale
    assert err <= scale / (1 << 13) + 1e-9, (err, scale)

    # determinism: run twice, bit-identical
    a = np.asarray(reduce_q(grads)["w"])
    b = np.asarray(reduce_q(grads)["w"])
    assert (a == b).all()
    print("COMPRESS_OK", err)
""")


def test_integer_gradient_allreduce():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    repo_src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(repo_src)
    proc = subprocess.run([sys.executable, "-c", _COMPRESS], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "COMPRESS_OK" in proc.stdout


# --------------------------------------------------------------------------- #
# cross-substrate agreement on a bulk-applied log (needs >1 device →
# subprocess, per the dry-run isolation rule)
# --------------------------------------------------------------------------- #

_CROSS_SUBSTRATE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    import repro
    from repro.core import (boundary, commands, distributed, hashing,
                            hnsw, machine, search)
    from repro.core.state import init_state

    D, N, K = 16, 48, 5
    rng = np.random.default_rng(0)
    vecs = boundary.normalize_embedding(
        rng.normal(size=(N, D)).astype(np.float32))
    ids = jnp.arange(N, dtype=jnp.int64) * 7 + 3
    log = commands.insert_batch(ids, vecs)
    q = boundary.admit_query(rng.normal(size=(4, D)).astype(np.float32))

    # substrate 1: flat kernel, bulk-applied — exact search
    flat = machine.bulk_apply(init_state(128, D), log)
    e_ids, _ = search.exact_search(flat, q, K)

    # substrate 2: deterministic HNSW on the same bulk-applied state
    # (ef > N ⇒ the beam covers the whole connected graph ⇒ exact answers)
    h_ids = np.stack([
        np.asarray(hnsw.hnsw_search(flat, qq, K, ef=64)[0]) for qq in q])

    # substrate 3: sharded memory, routed log bulk-applied per shard
    def sharded_ids(n_shards, mesh_shape):
        mesh = jax.make_mesh(mesh_shape, ("model", "data"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
        st = distributed.init_sharded_state(mesh, "model", 128 // n_shards, D)
        st = distributed.distributed_bulk_apply(
            mesh, "model", st, distributed.route_commands(log, n_shards))
        d_ids, _ = distributed.distributed_search(
            mesh, "model", st, q, K, query_axis="data")
        return st, np.asarray(d_ids)

    st4, ids4 = sharded_ids(4, (4, 2))
    st2, ids2 = sharded_ids(2, (2, 4))

    for b in range(q.shape[0]):
        exact_set = set(np.asarray(e_ids)[b].tolist())
        assert set(h_ids[b].tolist()) == exact_set, ("hnsw", b)
        assert set(ids4[b].tolist()) == exact_set, ("sharded4", b)
        assert set(ids2[b].tolist()) == exact_set, ("sharded2", b)

    # shard count must not change the memory content union: the sorted live
    # (id, vector, meta) rows hash identically for 1, 2 and 4 shards
    def content_hash(state):
        ids_h = np.asarray(state.ids)
        valid = np.asarray(state.valid)
        order = np.argsort(ids_h[valid])
        return hashing.hash_pytree({
            "ids": jnp.asarray(ids_h[valid][order]),
            "vecs": jnp.asarray(np.asarray(state.vectors)[valid][order]),
            "meta": jnp.asarray(np.asarray(state.meta)[valid][order]),
        })

    h_flat, h2, h4 = content_hash(flat), content_hash(st2), content_hash(st4)
    assert h_flat == h2 == h4, (hex(h_flat), hex(h2), hex(h4))
    print("CROSS_SUBSTRATE_OK", hex(h_flat))
""")


def test_cross_substrate_agreement_on_bulk_applied_log():
    """exact, HNSW and sharded search agree on a bulk-applied log, and the
    memory content union is invariant to shard count."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    repo_src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(repo_src)
    proc = subprocess.run([sys.executable, "-c", _CROSS_SUBSTRATE], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "CROSS_SUBSTRATE_OK" in proc.stdout
