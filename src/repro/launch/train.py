"""End-to-end training driver.

Usage (host-scale example; production would launch the same file per pod):
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m --reduced \
      --steps 100 --batch 8 --seq 128

Wires together: config → params/optimizer init → deterministic pipeline →
pjit'd train step with FSDP/TP shardings → fault-tolerant coordinator
(checkpoint/restart) → metrics log.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro  # noqa: F401
from repro.configs import get_config, get_reduced_config
from repro.data.pipeline import DataConfig, DeterministicPipeline
from repro.launch.mesh import batch_axes, make_host_mesh
from repro.models import sharding as shd
from repro.models import transformer as tf
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.runtime.coordinator import Coordinator, RunConfig
from repro.train.step import make_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if cfg.external_embeddings:
        raise SystemExit(
            f"{cfg.name} takes stub embeddings; use examples/train_lm.py "
            "with a token arch instead")

    mesh = make_host_mesh()
    print(f"mesh: {dict(mesh.shape)} devices={mesh.size}")
    optc = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                       total_steps=args.steps)
    data = DeterministicPipeline(DataConfig(
        seq_len=args.seq, global_batch=args.batch, vocab_size=cfg.vocab_size,
        seed=args.seed))

    step_fn = make_train_step(cfg, optc)

    def init_state_fn():
        params = tf.init_params(cfg, jax.random.PRNGKey(args.seed))
        opt = adamw_init(params)
        return {"params": params, "opt": opt}

    with jax.set_mesh(mesh):
        params_shapes = jax.eval_shape(
            lambda: tf.init_params(cfg, jax.random.PRNGKey(0)))
        p_sh = shd.param_shardings(params_shapes, cfg, mesh)

        jitted = jax.jit(
            lambda s, b: _wrap_step(step_fn, s, b), donate_argnums=(0,))

        def train_one(state, batch):
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            return jitted(state, batch)

        coord = Coordinator(
            RunConfig(total_steps=args.steps,
                      checkpoint_every=args.checkpoint_every,
                      checkpoint_dir=args.checkpoint_dir),
            train_step=_logging_step(train_one, args.log_every),
            batch_fn=lambda step: data.batch(step),
            init_state_fn=init_state_fn,
        )
        t0 = time.time()
        state = coord.train()
        dt = time.time() - t0
    print(f"done: {args.steps} steps in {dt:.1f}s "
          f"({args.steps / max(dt, 1e-9):.2f} steps/s); "
          f"events={len(coord.events)}")


def _wrap_step(step_fn, state, batch):
    params, opt, metrics = step_fn(state["params"], state["opt"], batch)
    return {"params": params, "opt": opt}, metrics


def _logging_step(fn, every: int):
    def wrapped(state, batch):
        state, metrics = fn(state, batch)
        step = int(np.asarray(state["opt"]["step"]))
        if step % every == 0 or step == 1:
            loss = float(np.asarray(metrics["loss"]))
            gn = float(np.asarray(metrics["grad_norm"]))
            print(f"step {step:5d}  loss {loss:8.4f}  gnorm {gn:8.3f}",
                  flush=True)
        return state, metrics
    return wrapped


if __name__ == "__main__":
    main()
