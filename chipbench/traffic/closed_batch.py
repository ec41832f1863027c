"""Closed loop: one client sends a batch of ``batch`` queries, waits for
the answers, and sends the next (ANN-Benchmarks batch mode). The client
cycles through ``pool`` distinct batches drawn from the seed."""
from __future__ import annotations

import time

import numpy as np


def run(r):
    import jax
    from harness import data, reads, sut

    cfg, t = r.config, r.traffic
    nq, k, pool = t["batch"], cfg["k"], t["pool"]
    state = reads.build_arena(r.seed, cfg)
    qs = list(data.queries(data.seed_key(r.seed), pool + 1, nq, cfg["dim"]))
    jax.block_until_ready(state)
    for _ in range(2):
        sut.read(state, qs[pool], k, r.serve, r.spans)
    r.setup_done()

    answers = []
    with r.window() as w:
        while time.perf_counter() - w.start < r.seconds:
            i = len(answers)
            answers.append(sut.read(state, qs[i % pool], k, r.serve, r.spans))
    done = len(answers)

    pick = np.sort(np.random.default_rng(r.seed).choice(
        min(done, pool), min(t["check_batches"], done, pool), replace=False))
    floats = np.asarray(jax.numpy.stack([qs[i] for i in pick]))
    admitted = np.stack([np.asarray(answers[i][0]) for i in pick])
    ids = np.stack([answers[i][1] for i in pick])
    scores = np.stack([answers[i][2] for i in pick])
    del state, qs, answers
    checks = reads.check(r.seed, cfg, floats.reshape(-1, cfg["dim"]),
                         admitted.reshape(-1, cfg["dim"]),
                         ids.reshape(-1, k), scores.reshape(-1, k),
                         control=r.control)
    return {
        "metrics": {"retrieve_qps": done * nq / w.seconds},
        "attempted": done, "failed": 0, "checks": checks,
        "counts": {"requests": done, "queries": done * nq, "nq": nq,
                   "live": cfg["rows"], "dim": cfg["dim"],
                   "capacity": cfg["capacity"]},
    }
