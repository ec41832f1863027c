"""Closed loop on the int8 compressed tier: one client sends a batch of
``batch`` queries, waits for the answers, and sends the next (ANN-Benchmarks
batch mode), cycling through ``pool`` distinct batches drawn from the seed.

Set-up builds the arena as ``closed_batch`` does and the code table as the
serve engine builds it (``codes.build``, ``_ensure_code_tables``); every
read of the window is the engine's flat-mode retrieve with that table
handed to ``query.execute_plan``. The cell's ``serve`` settings must plan
the coarse route with the configuration's ``ef_coarse``, or the kind
refuses to run.

The check compares the sampled batches' admitted integers, ids and scores,
and the whole table, with ``harness/coarse_reference.py``, and counts the
whole-table builds (``code_table_builds``) made inside the window. The
recall of the route against exact k-NN is printed, not checked.
"""
from __future__ import annotations

import sys
import time
from functools import partial

import numpy as np


def _builds() -> int:
    """Whole-table builds the program has counted so far (0 where it
    counts none)."""
    from repro import obs
    return sum(obs.counters().get("code_table_builds", {}).values())


def read(state, table, q, k: int, serve: dict, spans):
    """One retrieve on the coarse route, as ``sut.read`` makes it with the
    maintained table passed on."""
    from repro.core import query, shard_wal

    from harness import sut
    with spans("admit"):
        q_raw = sut._boundary()[0](q)
    with spans("live_count"):
        live = shard_wal.live_count(state)
    with spans("plan"):
        p = sut.plan(live, k, state.dim, serve)
    with spans("execute"):
        ids, scores = query.execute_plan(state, q_raw, k, p, codes=table)
    with spans("fetch"):
        ids, scores = np.asarray(ids), np.asarray(scores)
    return q_raw, ids, scores


def run(r):
    import jax
    from repro.core import codes

    from harness import data, reads, sut

    cfg, t = r.config, r.traffic
    nq, k, pool, ef = t["batch"], cfg["k"], t["pool"], cfg["ef_coarse"]
    plan = sut.plan(cfg["rows"], k, cfg["dim"], r.serve)
    if plan.route != "coarse" or plan.ef_coarse != ef:
        raise SystemExit(f"chipbench: the cell plans route {plan.route!r} "
                         f"with ef_coarse {plan.ef_coarse}; its "
                         f"configuration asks for the coarse route with "
                         f"ef_coarse {ef}")
    state = reads.build_arena(r.seed, cfg)
    table = codes.build(state)
    qs = list(data.queries(data.seed_key(r.seed), pool + 1, nq, cfg["dim"]))
    jax.block_until_ready((state, table))
    for _ in range(2):
        read(state, table, qs[pool], k, r.serve, r.spans)
    r.setup_done()

    answers, builds = [], _builds()
    with r.window() as w:
        while time.perf_counter() - w.start < r.seconds:
            i = len(answers)
            answers.append(read(state, table, qs[i % pool], k, r.serve,
                                r.spans))
    builds = _builds() - builds
    done = len(answers)

    pick = np.sort(np.random.default_rng(r.seed).choice(
        min(done, pool), min(t["check_batches"], done, pool), replace=False))
    floats = np.asarray(jax.numpy.stack([qs[i] for i in pick]))
    admitted = np.stack([np.asarray(answers[i][0]) for i in pick])
    ids = np.stack([answers[i][1] for i in pick])
    scores = np.stack([answers[i][2] for i in pick])
    served = [np.asarray(x) for x in (table.codes, table.offset,
                                      table.scale, table.norms)]
    del state, table, qs, answers
    checks, got = check(r.seed, cfg, floats.reshape(-1, cfg["dim"]),
                        admitted.reshape(-1, cfg["dim"]), ids.reshape(-1, k),
                        scores.reshape(-1, k), served, builds,
                        control=r.control)
    print(f"recall@{k}: {got:.6f} over {len(pick) * nq} queries "
          f"(ef_coarse {ef})", file=sys.stderr)
    return {
        "metrics": {"retrieve_qps": done * nq / w.seconds},
        "attempted": done, "failed": 0, "checks": checks,
        "counts": {"requests": done, "nq": nq, "live": cfg["rows"],
                   "dim": cfg["dim"], "ef_coarse": ef, "recall": got,
                   "code_table_builds": builds},
    }


def check(seed: int, cfg: dict, floats, admitted, ids, scores, table,
          builds: int, control: bool = False):
    """Compare the sampled answers and the served table (codes, offset,
    scale, norms as numpy arrays) with the plain reference. With
    ``control`` the reference computed one precision lower (Q8.8 at the
    boundary) takes the program's place. Returns ([(name, value, limit)],
    the recall of the answers checked against exact k-NN)."""
    from harness import coarse_reference, data, reads, reference

    block, key, live = reads._block(cfg), data.seed_key(seed), cfg["rows"]
    k, ef = ids.shape[1], cfg["ef_coarse"]

    def rows_of(b, **precision):
        n = min(block, live - b * block)
        rows = reference.boundary_rows(np.asarray(
            data.rows_block_jit(key, b, block, cfg["dim"]))[:n], **precision)
        slots = np.arange(b * block, b * block + n, dtype=np.int64)
        return slots, slots, rows

    def two_stage(q_raw, **precision):
        return coarse_reference.search(
            -(-live // block), partial(rows_of, **precision),
            cfg["capacity"], q_raw, k, ef)

    ref_q = reference.boundary(floats)
    ref = two_stage(ref_q)
    if control:
        low = dict(int_bits=7, frac_bits=8)
        admitted = reference.boundary(floats, **low)
        ctl = two_stage(admitted, **low)
        ids, scores, table = ctl.ids, ctl.scores, ctl.table
    want = ref.table
    codes, offset, scale, norms = table
    checks = [
        ("admitted_ints_differing", int(np.sum(ref_q != admitted)), 0),
        ("answer_ids_differing", int(np.sum(ref.ids != ids)), 0),
        ("answer_scores_differing", int(np.sum(ref.scores != scores)), 0),
        ("table_params_differing", int(np.sum(want.offset != offset)
                                       + np.sum(want.scale != scale)), 0),
        ("table_codes_differing", int(np.sum(want.codes != codes)), 0),
        ("table_norms_differing", int(np.sum(want.norms != norms)), 0),
        ("table_builds_in_window", int(builds), 0),
    ]
    return checks, coarse_reference.recall(ids, ref.exact_ids)
