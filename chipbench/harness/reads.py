"""Read cells: the arena a configuration describes, and the check of the
answers the window returned against the plain reference."""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from harness import data, reference

BLOCK = 1 << 16  # rows per generator block; the reference regenerates by it


@partial(jax.jit, static_argnames=("capacity", "live", "dim", "block"))
def _build(key, *, capacity: int, live: int, dim: int, block: int):
    from repro.core import boundary
    from repro.core.state import init_state
    state = init_state(capacity, dim)

    def body(b, vec):
        raw = boundary.normalize_embedding(data.rows_block(key, b, block, dim))
        return jax.lax.dynamic_update_slice(vec, raw, (b * block, 0))

    vec = jax.lax.fori_loop(0, -(-live // block), body, state.vectors)
    slot = jnp.arange(capacity)
    valid = slot < live
    n = jnp.asarray(live, jnp.int32)
    return dataclasses.replace(
        state, vectors=jnp.where(valid[:, None], vec, 0),
        ids=jnp.where(valid, slot.astype(jnp.int64), -1), valid=valid,
        cursor=n, count=n, version=jnp.asarray(live, jnp.int64))


def _block(cfg: dict) -> int:
    block = min(BLOCK, cfg["capacity"])
    if cfg["capacity"] % block:
        raise ValueError("capacity must be a multiple of the generator block")
    return block


def build_arena(seed: int, cfg: dict):
    """The arena that inserting rows 0..live-1 (external id = slot) through
    the boundary gives, with the HNSW graph left empty, made on the device
    in one call."""
    return _build(data.seed_key(seed), capacity=cfg["capacity"],
                  live=cfg["rows"], dim=cfg["dim"], block=_block(cfg))


def check(seed: int, cfg: dict, floats: np.ndarray, admitted: np.ndarray,
          ids: np.ndarray, scores: np.ndarray, control: bool = False):
    """Compare sampled answers with the plain reference.

    floats [S, d] are the queries as sent, admitted [S, d] the program's
    admitted integers, ids / scores [S, k] its answers. With ``control``
    the reference computed one precision lower (Q8.8 at the boundary,
    float32 scores) takes the program's place. Returns
    [(name, value, limit)]."""
    ref_q = reference.boundary(floats)
    k = ids.shape[1]
    tops = [reference.TopK(ref_q, k)]
    if control:
        tops.append(reference.TopK(ref_q, k, "float32"))
    block, key, live = _block(cfg), data.seed_key(seed), cfg["rows"]

    def score(b):
        n = min(block, live - b * block)
        rows = reference.boundary_rows(np.asarray(
            data.rows_block_jit(key, b, block, cfg["dim"]))[:n])
        slots = np.arange(b * block, b * block + n, dtype=np.int64)
        return [t.block(rows, slots) for t in tops]

    for parts in reference.parallel_map(score, range(-(-live // block))):
        for t, best in zip(tops, parts):
            t.merge(best)
    ref, ctl = tops[0], (tops[1] if control else None)
    ref_ids, ref_s = ref.result()
    if ctl is not None:
        admitted = reference.boundary(floats, int_bits=7, frac_bits=8)
        ids, scores = ctl.result()
    return [
        ("admitted_ints_differing", int(np.sum(ref_q != admitted)), 0),
        ("answer_ids_differing", int(np.sum(ref_ids != ids)), 0),
        ("answer_scores_differing", int(np.sum(ref_s != scores)), 0),
    ]
