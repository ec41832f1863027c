"""Train / serve step builders (pjit-ready pure functions)."""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import transformer as tf
from repro.models.config import ModelConfig
from repro.optim.adamw import AdamWConfig, adamw_update


def make_train_step(cfg: ModelConfig, optc: AdamWConfig):
    """(params, opt_state, batch) → (params, opt_state, metrics)."""

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            tf.loss_fn, has_aux=True)(params, batch, cfg)
        params, opt_state, om = adamw_update(optc, params, grads, opt_state)
        return params, opt_state, {**metrics, **om}

    return train_step


def make_compressed_train_step(cfg: ModelConfig, optc: AdamWConfig, mesh,
                               contract: str = "Q2.13",
                               error_feedback: bool = True):
    """Pod-DP train step with deterministic integer cross-pod gradient sync.

    shard_map over the `pod` axis only; `data`/`model` stay GSPMD-auto inside.
    opt_state gains a `residual` tree when error feedback is on.
    """
    from jax.sharding import PartitionSpec as P

    from repro.optim import compress

    inner_axes = frozenset(n for n in mesh.axis_names if n != "pod")

    def step(params, opt_state, batch):
        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P(), P(), P("pod")),
            out_specs=(P(), P(), P()),
            axis_names={"pod"}, check_vma=False,
        )
        def pod_step(params, opt_state, batch):
            (loss, metrics), grads = jax.value_and_grad(
                tf.loss_fn, has_aux=True)(params, batch, cfg)
            residual = opt_state.get("residual")
            grads, new_res = compress.integer_psum_grads(
                grads, "pod", contract, residual)
            params, new_opt, om = adamw_update(optc, params, grads,
                                               {k: v for k, v in opt_state.items()
                                                if k != "residual"})
            if new_res is not None:
                new_opt["residual"] = new_res
            metrics = {**metrics, **om}
            metrics = jax.tree.map(lambda x: jax.lax.pmean(x, "pod"), metrics)
            return params, new_opt, metrics

        return pod_step(params, opt_state, batch)

    return step


def make_prefill_step(cfg: ModelConfig, s_cache: int):
    def prefill_step(params, batch):
        return tf.prefill(params, batch, cfg, s_cache)
    return prefill_step


def make_decode_step(cfg: ModelConfig):
    def decode_step(params, caches, tokens, positions, embeds=None):
        return tf.decode_step(params, caches, tokens, positions, cfg,
                              embeds=embeds)
    return decode_step
