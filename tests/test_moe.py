"""MoE layer: determinism, capacity semantics, shard_map == dense equality.

Repro note (jax 0.8.2 / XLA CPU): grad(scan(shard_map)) with fully-manual dp
specs needs explicit jit out_shardings (KeyError in parse_flatten_op_sharding
otherwise), and bf16 psum inside partial-manual shard_map aborts in XLA's
AllReducePromotion. Both worked around in moe.py / train/step.py; the
subprocess test below covers the working configuration end to end.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

import repro  # noqa: F401
from repro.configs import get_reduced_config
from repro.models.layers import moe as moe_lib


def _cfg():
    return get_reduced_config("phi3_5_moe_42b_a6_6b")


def test_dense_moe_deterministic():
    cfg = _cfg()
    params = moe_lib.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
    y1, a1 = moe_lib._moe_dense(params, x, cfg)
    y2, a2 = moe_lib._moe_dense(params, x, cfg)
    assert (np.asarray(y1) == np.asarray(y2)).all()
    assert float(a1) == float(a2)


def test_expert_padding_never_routed():
    import dataclasses
    cfg = dataclasses.replace(_cfg(), num_experts=40, expert_d_ff=16,
                              num_experts_per_tok=4)
    assert cfg.padded_experts == 48
    params = moe_lib.init_moe(jax.random.PRNGKey(0), cfg)
    assert params["w_gate"].shape[0] == 48
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    xt = x.reshape(-1, cfg.d_model)
    probs, top_p, top_e = moe_lib._route(params, xt, cfg)
    assert int(jnp.max(top_e)) < 40  # padded experts unreachable


def test_capacity_drops_overflow_deterministically():
    import dataclasses
    cfg = dataclasses.replace(_cfg(), moe_capacity_factor=0.25)
    params = moe_lib.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    y1, _ = moe_lib._moe_dense(params, x, cfg)
    y2, _ = moe_lib._moe_dense(params, x, cfg)
    assert (np.asarray(y1) == np.asarray(y2)).all()
    # some tokens dropped → some rows equal zero contribution is fine; just
    # require finiteness and shape
    assert np.isfinite(np.asarray(y1, np.float32)).all()


_SMAP = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    import repro
    from repro.configs import get_reduced_config
    from repro.models import sharding as shd, transformer as tf
    from repro.models.layers import moe as moe_lib
    from repro.optim.adamw import AdamWConfig, adamw_init
    from repro.train.step import make_train_step

    mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = get_reduced_config('phi3_5_moe_42b_a6_6b')
    params = moe_lib.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model), jnp.float32)
    y_dense, _ = moe_lib._moe_dense(params, x, cfg)
    with jax.set_mesh(mesh):
        y_smap, _ = jax.jit(lambda p, x: moe_lib.moe_ffn(p, x, cfg))(params, x)
    err = float(jnp.max(jnp.abs(y_dense - y_smap)))
    assert err == 0.0, f"shard_map EP diverged from dense: {err}"

    # full train step with explicit out_shardings
    full = tf.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(full)
    batch = {'tokens': jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, cfg.vocab_size),
             'labels': jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0, cfg.vocab_size)}
    step = make_train_step(cfg, AdamWConfig())
    with jax.set_mesh(mesh):
        p_sh = shd.param_shardings(jax.eval_shape(lambda: full), cfg, mesh)
        rep = NamedSharding(mesh, P())
        o_sh = {"m": p_sh, "v": p_sh, "step": rep}
        m_sh = {k: rep for k in ("loss", "ce", "aux", "grad_norm", "lr")}
        jitted = jax.jit(step, out_shardings=(p_sh, o_sh, m_sh))
        p2, o2, m = jitted(full, opt, batch)
        assert np.isfinite(float(m["loss"]))
    print("MOE_SMAP_OK")
""")


def test_shardmap_moe_equals_dense_and_trains():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    repo_src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(repo_src)
    proc = subprocess.run([sys.executable, "-c", _SMAP], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MOE_SMAP_OK" in proc.stdout
