"""Open loop: requests of ``queries_per_request`` vectors arrive as a
Poisson stream at ``rate_per_s`` (the arrival order fixed by the mix's
``schedule_seed``), and one server answers them in order.

Latency runs from a request's due time to its answer on the host, so a
request that waits behind a slow one is charged the wait. Every request
due in the window is answered and counted, also after the window closes.
"""
from __future__ import annotations

import time

import numpy as np


def run(r):
    import jax
    from harness import data, reads, sut

    cfg, t = r.config, r.traffic
    nq, k = t["queries_per_request"], cfg["k"]
    state = reads.build_arena(r.seed, cfg)
    n = int(np.ceil(t["rate_per_s"] * r.seconds * 1.5)) + 16
    gaps = data.poisson_gaps(t["rate_per_s"], n, t["schedule_seed"])
    due = np.cumsum(gaps) - gaps[0]
    n_due = int(np.sum(due < r.seconds))
    warm = 3
    # a request's vectors arrive on the host, as from the network
    qs = np.asarray(data.queries(data.seed_key(r.seed), n_due + warm, nq,
                                 cfg["dim"]))
    jax.block_until_ready(state)
    for q in qs[n_due:]:
        sut.read(state, q, k, r.serve, r.spans)
    r.setup_done()

    if n_due < t["check_sample"]:
        raise RuntimeError(f"only {n_due} requests fall in the window; "
                           f"the check samples {t['check_sample']}")
    # the admitted queries stay on the device only for the checked sample
    pick = np.sort(np.random.default_rng(r.seed).choice(
        n_due, t["check_sample"], replace=False))
    keep = set(pick.tolist())
    answers, latency = [], []
    with r.window() as w:
        for i in range(n_due):
            t_due = w.start + due[i]
            wait = t_due - time.perf_counter()
            if wait > 0:
                with r.spans("idle"):
                    time.sleep(wait)
            q_raw, ids, scores = sut.read(state, qs[i], k, r.serve, r.spans)
            latency.append(time.perf_counter() - t_due)
            answers.append((q_raw if i in keep else None, ids, scores))
    lat_ms = 1e3 * np.asarray(latency)

    floats = qs[pick]
    admitted = np.stack([np.asarray(answers[i][0]) for i in pick])
    ids = np.stack([answers[i][1] for i in pick])
    scores = np.stack([answers[i][2] for i in pick])
    del state, qs, answers
    checks = reads.check(r.seed, cfg, floats.reshape(-1, cfg["dim"]),
                         admitted.reshape(-1, cfg["dim"]),
                         ids.reshape(-1, k), scores.reshape(-1, k),
                         control=r.control)
    return {
        "metrics": {
            "retrieve_p50_ms": float(np.percentile(lat_ms, 50)),
            "retrieve_p95_ms": float(np.percentile(lat_ms, 95)),
        },
        "attempted": n_due, "failed": 0, "checks": checks,
        "counts": {"requests": n_due, "queries": n_due * nq,
                   "nq": nq, "live": cfg["rows"], "dim": cfg["dim"],
                   "capacity": cfg["capacity"]},
    }
