"""Token-choice top-k MoE with deterministic sort-based capacity dispatch.

Design goals (in priority order):
  1. determinism — routing uses stable integer sorts (ties by token index);
     no RNG, no atomics, so the same batch routes identically everywhere,
     matching the framework's replayability story;
  2. EP-shardability — the expert buffer [E, C, D] carries the expert axis,
     which the sharding rules place on the ``model`` mesh axis; GSPMD turns
     the scatter/gather into all-to-alls;
  3. O(T·k) memory — no [T, E, C] one-hot dispatch tensors (those explode at
     32k-token microbatches); instead tokens are sorted by expert and
     scattered into per-expert capacity slots.

Overflow tokens (rank ≥ capacity) are dropped, standard for capacity-factor
routing; their combine weight is zero so the residual passes through.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models import pspec
from repro.models.config import ModelConfig
from repro.models.initializers import dense_init


def init_moe(key, cfg: ModelConfig) -> dict:
    D, E, Fe = cfg.d_model, cfg.padded_experts, cfg.expert_d_ff
    pd = cfg.params_dtype
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "router": dense_init(k1, (D, E), pd, fan_in=D),
        "w_gate": dense_init(k2, (E, D, Fe), pd, fan_in=D),
        "w_up": dense_init(k3, (E, D, Fe), pd, fan_in=D),
        "w_down": dense_init(k4, (E, Fe, D), pd, fan_in=Fe),
    }


def capacity_of(tokens: int, cfg: ModelConfig) -> int:
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    c = int(tokens * k * cfg.moe_capacity_factor / E)
    return max(8, ((c + 7) // 8) * 8)  # pad to lane multiple


def moe_ffn(params: dict, x: jax.Array, cfg: ModelConfig
            ) -> Tuple[jax.Array, jax.Array]:
    """x [B, L, D] → (y [B, L, D], aux_loss scalar f32).

    Two implementations:
      * shard_map EP (production): trunk activations are replicated across
        `model`, so every model rank recomputes the (cheap) routing
        identically and runs ONLY its expert shard on the tokens routed
        there — dispatch needs zero communication and combine is a single
        bf16 psum over `model` per layer. Measured 9.09e12 → 1.4e11 wire
        bytes on phi3.5-moe train_4k vs the GSPMD-scatter version
        (EXPERIMENTS.md §Perf).
      * dense fallback (no mesh / non-divisible experts): sort-based
        capacity dispatch under plain GSPMD.

    aux = load-balancing loss (Switch-style mean(f_e · p_e) · E).
    """
    mesh = pspec._mesh()
    E = cfg.padded_experts
    if (mesh is not None and "model" in mesh.axis_names
            and E % mesh.shape["model"] == 0
            and x.shape[0] % _dp_size(mesh) == 0):
        return _moe_shardmap(params, x, cfg, mesh)
    return _moe_dense(params, x, cfg)


def _dp_size(mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n


def _route(params, xt, cfg: ModelConfig):
    """Shared routing: top-k probs/experts + load-balance aux (f32)."""
    E, E_real, K = (cfg.padded_experts, cfg.num_experts,
                    cfg.num_experts_per_tok)
    logits = xt.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    if E != E_real:
        eidx = jnp.arange(E, dtype=jnp.int32)
        logits = jnp.where(eidx[None, :] < E_real, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)
    return probs, top_p, top_e


def _expert_mlp(params, buf, cfg: ModelConfig, dtype):
    gate = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"].astype(dtype))
    up = jnp.einsum("ecd,edf->ecf", buf, params["w_up"].astype(dtype))
    act = jax.nn.silu(gate) if cfg.activation == "swiglu" else jax.nn.gelu(gate)
    return jnp.einsum("ecf,efd->ecd", act * up, params["w_down"].astype(dtype))


def _moe_shardmap(params: dict, x: jax.Array, cfg: ModelConfig, mesh
                  ) -> Tuple[jax.Array, jax.Array]:
    from functools import partial

    from jax.sharding import PartitionSpec as P

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_model = mesh.shape["model"]
    E, K = cfg.padded_experts, cfg.num_experts_per_tok
    E_loc = E // n_model

    param_specs = {
        "router": P(None, None),
        "w_gate": P("model", None, None),
        "w_up": P("model", None, None),
        "w_down": P("model", None, None),
    }

    # fully-manual shard_map: `model` carries EP; the dp axes shard the batch
    # dim explicitly. (Partial-manual psum crashes XLA CPU's
    # AllReducePromotion; fully-manual works but requires the caller's jit to
    # pass explicit out_shardings — see train/step.py.)
    @partial(jax.shard_map, mesh=mesh,
             in_specs=(param_specs, P(dp, None, None)),
             out_specs=(P(dp, None, None), P()),
             check_vma=False)
    def fn(p, x_loc):
        B_loc, L, D = x_loc.shape  # local batch (dp-sharded)
        T = B_loc * L
        C = capacity_of(T, cfg)
        dtype = x_loc.dtype
        xt = x_loc.reshape(T, D)
        my = jax.lax.axis_index("model")

        probs, top_p, top_e = _route(p, xt, cfg)  # router replicated

        # identical on every model rank (same tokens, same router) — each
        # rank then takes only its expert slice. Deterministic by symmetry.
        flat_e = top_e.reshape(T * K).astype(jnp.int32)
        pair_idx = jnp.arange(T * K, dtype=jnp.int32)
        sorted_e, sorted_pair = jax.lax.sort((flat_e, pair_idx), num_keys=2)
        counts = jnp.bincount(flat_e, length=E)
        starts = jnp.cumsum(counts) - counts
        rank = (jnp.arange(T * K, dtype=jnp.int32)
                - starts[sorted_e].astype(jnp.int32))
        mine = (sorted_e // E_loc) == my
        keep = (rank < C) & mine
        dest = jnp.where(keep, (sorted_e % E_loc) * C + rank, E_loc * C)

        src_token = sorted_pair // K
        buf = jnp.zeros((E_loc * C, D), dtype)
        buf = buf.at[dest].set(xt[src_token], mode="drop")
        out_buf = _expert_mlp(p, buf.reshape(E_loc, C, D), cfg, dtype)
        out_flat = out_buf.reshape(E_loc * C, D)

        # combine locally then ONE psum over the expert shards
        pair_dest = jnp.full((T * K,), -1, jnp.int32).at[sorted_pair].set(
            jnp.where(keep, dest, -1))
        safe = jnp.clip(pair_dest, 0, E_loc * C - 1)
        gathered = out_flat[safe]
        w = jnp.where(pair_dest >= 0, top_p.reshape(T * K), 0.0).astype(dtype)
        y = jnp.sum((gathered * w[:, None]).reshape(T, K, D), axis=1)
        # psum in f32: XLA CPU's AllReducePromotion pass crashes on bf16
        # all-reduce inside partially-manual shard_map (checked 0.8.2);
        # f32 avoids the pass. TPU would take the bf16 path.
        y = jax.lax.psum(y.astype(jnp.float32), "model").astype(dtype)

        frac_tokens = counts.astype(jnp.float32) / jnp.float32(T * K)
        frac_probs = jnp.mean(probs, axis=0)
        aux = jnp.sum(frac_tokens * frac_probs) * E
        if dp:
            aux = jax.lax.pmean(aux, dp)
        return y.reshape(B_loc, L, D), aux

    moe_params = {k: params[k] for k in
                  ("router", "w_gate", "w_up", "w_down")}
    return fn(moe_params, x)


def _moe_dense(params: dict, x: jax.Array, cfg: ModelConfig
               ) -> Tuple[jax.Array, jax.Array]:
    """Fallback: sort-based capacity dispatch under plain GSPMD."""
    B, L, D = x.shape
    T = B * L
    E, K = cfg.padded_experts, cfg.num_experts_per_tok
    E_real = cfg.num_experts
    C = capacity_of(T, cfg)
    dtype = x.dtype
    xt = pspec.constrain(x.reshape(T, D), "batch", None)

    # ---- routing (f32 for numerics) ----------------------------------- #
    logits = (xt.astype(jnp.float32) @ params["router"].astype(jnp.float32))
    if E != E_real:
        # padded experts are unroutable (deterministically -inf)
        eidx = jnp.arange(E, dtype=jnp.int32)
        logits = jnp.where(eidx[None, :] < E_real, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)                     # [T, E]
    top_p, top_e = jax.lax.top_k(probs, K)                      # [T, K]
    top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)

    # ---- deterministic dispatch: stable sort by expert ----------------- #
    flat_e = top_e.reshape(T * K).astype(jnp.int32)             # pair -> expert
    pair_idx = jnp.arange(T * K, dtype=jnp.int32)
    # two-key sort (expert, pair index) — deterministic ties by construction
    sorted_e, sorted_pair = jax.lax.sort((flat_e, pair_idx), num_keys=2)
    # rank of each pair within its expert = position - segment start
    counts = jnp.bincount(flat_e, length=E)                     # [E]
    starts = jnp.cumsum(counts) - counts                        # exclusive
    rank = jnp.arange(T * K, dtype=jnp.int32) - starts[sorted_e].astype(jnp.int32)
    keep = rank < C
    # overflow pairs scatter out of bounds → dropped by mode="drop"
    dest = jnp.where(keep, sorted_e * C + rank, E * C)          # [T*K]

    src_token = sorted_pair // K                                 # token of pair
    buf = jnp.zeros((E * C, D), dtype)
    buf = buf.at[dest].set(xt[src_token], mode="drop")
    # EP: expert axis over `model` (no-op when E is TP-indivisible)
    buf = pspec.constrain(buf.reshape(E, C, D), "model", None, None)

    # ---- expert computation (batched over E; EP shards this axis) ------ #
    gate = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"].astype(dtype))
    up = jnp.einsum("ecd,edf->ecf", buf, params["w_up"].astype(dtype))
    act = jax.nn.silu(gate) if cfg.activation == "swiglu" else jax.nn.gelu(gate)
    out_buf = jnp.einsum("ecf,efd->ecd", act * up, params["w_down"].astype(dtype))
    out_buf = pspec.constrain(out_buf, "model", None, None)
    out_flat = out_buf.reshape(E * C, D)

    # ---- combine: gather each pair's expert output, weight, sum over K - #
    # invert the sort: pair -> dest slot (or -1 if dropped)
    pair_dest = jnp.full((T * K,), -1, jnp.int32).at[sorted_pair].set(
        jnp.where(keep, dest, -1)
    )
    safe = jnp.clip(pair_dest, 0, E * C - 1)
    gathered = out_flat[safe]                                    # [T*K, D]
    w = jnp.where(pair_dest >= 0, top_p.reshape(T * K), 0.0).astype(dtype)
    y = jnp.sum((gathered * w[:, None]).reshape(T, K, D), axis=1)
    y = pspec.constrain(y, "batch", None)

    # ---- aux load-balance loss ----------------------------------------- #
    frac_tokens = counts.astype(jnp.float32) / jnp.float32(T * K)
    frac_probs = jnp.mean(probs, axis=0)
    aux = jnp.sum(frac_tokens * frac_probs) * E

    return y.reshape(B, L, D), aux
