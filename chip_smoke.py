#!/usr/bin/env python3
"""Drive the serve path once on a TPU, compiled, and check what comes out.

    python3 chip_smoke.py              # one chip: engine, substrate, platforms
    python3 chip_smoke.py --chips 4    # only the pod-scale phase, 4-device mesh
    python3 chip_smoke.py --rehearse   # the same phases at tiny sizes on CPU

Phases on one chip:

* engine — ``MemoryAugmentedEngine`` with mamba2-130m at full width (24
  layers, d_model 768), random params from ``--seed``, a durable directory
  with group commit: ingest 2,048 docs x 64 tokens, retrieve 8 prompts on
  the auto (HNSW), exact and coarse routes (coarse == exact bit for bit),
  generate 16 tokens, audit replay == state hash, and a fresh engine's
  ``recover()`` serving the same retrieval hash.
* substrate — a 2^17 x 768 arena filled through ``boundary`` and
  ``machine.bulk_apply`` in batches of 4,096 clustered rows; exact top-10
  of 128 queries through the compiled Pallas kernels and through the int8
  digit-plane XLA path must agree, and the kernel route must lower to a
  ``tpu_custom_call``.
* platforms — the paper's claim: the first 4,096 commands and 64 queries
  run on the TPU and on the host CPU in this process; state, content and
  retrieval hashes must be equal, and the exact answers must equal a numpy
  int64 reference.

Every check prints a line; any failure exits non-zero at once. Each phase
ends with its seconds and the seconds XLA spent compiling. The last line of
stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``. With no
TPU (and no ``--rehearse``) the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SIZES = {  # full size on the chip, tiny for the CPU rehearsal
    False: dict(docs=2048, doc_len=64, doc_batch=256, ef=64, prompts=8,
                prompt_len=16, max_new=16, rows=1 << 17, dim=768,
                batch=4096, nq=128, prefix=4096, prefix_nq=64),
    True: dict(docs=48, doc_len=16, doc_batch=16, ef=16, prompts=8,
               prompt_len=8, max_new=4, rows=1 << 10, dim=64, batch=256, nq=16,
               prefix=256, prefix_nq=16),
}
K = 10
CLUSTERS = 64
# stop filling the arena early (and say so) rather than miss the run's limit
INGEST_BUDGET_S = 480.0


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; proves no chip")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the pod-scale phase on a 4-device mesh")
    return ap.parse_args()


class Phase:
    """Prints a phase's checks, then its wall and compile seconds."""

    compile_s = 0.0  # running total of XLA backend compile seconds

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), Phase.compile_s
        print(f"[{self.name}] start", flush=True)
        return self

    def note(self, msg: str) -> None:
        print(f"[{self.name}] {msg}", flush=True)

    def check(self, what: str, ok: bool, detail: str = "") -> None:
        print(f"[{self.name}] check {what}: {'ok' if ok else 'FAIL'}"
              f"{' ' + detail if detail else ''}", flush=True)
        if not ok:
            raise SystemExit(f"chip_smoke: {self.name}: {what} failed")

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            print(f"[{self.name}] done: {time.perf_counter() - self.t0:.3f} s,"
                  f" compile {Phase.compile_s - self.c0:.3f} s", flush=True)


def _count_compile_time():
    from jax import monitoring

    def on_event(event, duration, **_):
        # the backend compile only: tracing events nest and would overcount
        if event == "/jax/core/compile/backend_compile_duration":
            Phase.compile_s += duration
    monitoring.register_event_duration_secs_listener(on_event)


def clustered(key, n: int, dim: int, centers):
    """n float32 rows near seeded cluster centers (ANN-Benchmarks-style)."""
    import jax
    ka, kn = jax.random.split(key)
    assign = jax.random.randint(ka, (n,), 0, centers.shape[0])
    return centers[assign] + 0.25 * jax.random.normal(kn, (n, dim))


def phase_engine(sz, seed: int, rehearse: bool) -> None:
    import jax
    import numpy as np
    from repro.configs import get_config, get_reduced_config
    from repro.core import wal
    from repro.models import transformer as tf
    from repro.serve.engine import MemoryAugmentedEngine, ServeConfig

    with Phase("engine") as ph:
        cfg = (get_reduced_config if rehearse else get_config)("mamba2-130m")
        ph.note(f"model {cfg.name}: {cfg.num_layers} layers, d_model "
                f"{cfg.d_model}, vocab {cfg.vocab_size}, seed {seed}")
        params = tf.init_params(cfg, jax.random.PRNGKey(seed))
        rng = np.random.default_rng(seed)
        docs = rng.integers(0, cfg.vocab_size, (sz["docs"], sz["doc_len"]),
                            dtype=np.int32)
        prompts = rng.integers(0, cfg.vocab_size,
                               (sz["prompts"], sz["prompt_len"]),
                               dtype=np.int32)
        with tempfile.TemporaryDirectory(prefix="chip-smoke-") as durable:
            sc = ServeConfig(
                capacity=2 * sz["docs"], retrieve_k=K,
                max_new_tokens=sz["max_new"], context_tokens=sz["doc_len"],
                s_cache=sz["doc_len"] + sz["prompt_len"] + sz["max_new"] + 32,
                durable_dir=durable, ef=sz["ef"], ef_coarse=sz["docs"],
                exact_threshold=sz["docs"] // 2,
                group_commit=wal.GroupCommitPolicy(max_batch=sz["doc_batch"],
                                                   max_delay_s=60.0))
            eng = MemoryAugmentedEngine(cfg, params, sc)
            t0 = time.perf_counter()
            for a in range(0, sz["docs"], sz["doc_batch"]):
                eng.insert_documents(docs[a:a + sz["doc_batch"]])
            eng.flush()
            ph.note(f"ingested {sz['docs']} docs x {sz['doc_len']} tokens in "
                    f"{time.perf_counter() - t0:.3f} s (compile included)")

            answers = {}
            for route in ("auto", "exact", "coarse"):
                eng.sc = dataclasses.replace(eng.sc, route=route)
                t0 = time.perf_counter()
                answers[route] = eng.retrieve(prompts)
                ph.note(f"retrieve route={route} -> {eng.last_plan.route} "
                        f"({eng.last_plan.reason}) in "
                        f"{time.perf_counter() - t0:.3f} s")
                if route == "auto":
                    ph.check("auto route is HNSW above exact_threshold",
                             eng.last_plan.route == "hnsw")
            eng.sc = dataclasses.replace(eng.sc, route="auto")
            (ids_e, s_e), (ids_c, s_c) = answers["exact"], answers["coarse"]
            ph.check("coarse == exact (ids and scores, bit for bit)",
                     (ids_e == ids_c).all() and (s_e == s_c).all())
            ph.check("exact route returns k live ids per prompt",
                     ids_e.shape == (sz["prompts"], K) and (ids_e >= 0).all())
            ids_h = answers["auto"][0]
            overlap = np.mean([len(set(a) & set(b)) / K
                               for a, b in zip(ids_h, ids_e)])
            ph.note(f"HNSW recall@{K} against exact: {overlap:.4f}")

            t0 = time.perf_counter()
            out = eng.generate(prompts)
            ph.note(f"generated {out.shape[0]}x{out.shape[1]} tokens in "
                    f"{time.perf_counter() - t0:.3f} s (compile included)")
            ph.check("generate shape and vocab range",
                     out.shape == (sz["prompts"], sz["max_new"])
                     and (out >= 0).all() and (out < cfg.vocab_size).all())

            h_live = eng.state_hash()
            h_replay = eng.replay_log_fresh()
            ph.check("replay_log_fresh() == state_hash()", h_replay == h_live,
                     f"{h_live:#018x}")
            rh = eng.retrieval_hash(prompts)
            eng.close()
            eng2 = MemoryAugmentedEngine(cfg, params, sc)
            t, h = eng2.recover()
            ph.check("recover() restores the state hash",
                     t == sz["docs"] and h == h_live, f"t={t}")
            rh2 = eng2.retrieval_hash(prompts)
            ph.check("recover() serves the same retrieval_hash", rh2 == rh,
                     f"{rh:#018x}")
            eng2.close()


def row_source(sz, seed: int):
    """(centers, make): ``make(b)`` is batch ``b`` of seeded clustered rows,
    already across the boundary (raw Q16.16 int32)."""
    import jax
    from repro.core import boundary

    key = jax.random.PRNGKey(seed)
    centers = jax.random.normal(jax.random.fold_in(key, 1 << 30),
                                (CLUSTERS, sz["dim"]))
    make = jax.jit(lambda i: boundary.normalize_embedding(clustered(
        jax.random.fold_in(key, i), sz["batch"], sz["dim"], centers)))
    return centers, make


def fill_arena(ph, sz, seed: int):
    """Fill a rows x dim arena through boundary + bulk_apply in batches,
    stopping early (and saying so) when the measured rate projects past
    INGEST_BUDGET_S. Returns (state, rows ingested, first batch's log,
    centers)."""
    import jax
    import jax.numpy as jnp
    from repro.core import commands, machine
    from repro.core.state import init_state

    rows, batch = sz["rows"], sz["batch"]
    centers, make = row_source(sz, seed)
    state = init_state(rows, sz["dim"])
    first_log = None
    t_start = time.perf_counter()
    done = 0
    for b in range(rows // batch):
        ids = jnp.arange(b * batch, (b + 1) * batch, dtype=jnp.int64)
        log = commands.insert_batch(ids, make(b))
        if first_log is None:
            first_log = log
        state = machine.bulk_apply(state, log)
        jax.block_until_ready(state.vectors)
        done += batch
        spent = time.perf_counter() - t_start
        if done < rows and spent * rows / done > INGEST_BUDGET_S:
            ph.note(f"rows cut to {done} of {rows}: {spent:.3f} s for "
                    f"{done} rows projects {spent * rows / done:.0f} s, past "
                    f"the {INGEST_BUDGET_S:.0f} s ingest budget")
            break
    seconds = time.perf_counter() - t_start
    ph.note(f"ingested {done} rows x {sz['dim']} into a {rows}-row arena in "
            f"{seconds:.3f} s: {done / seconds:.1f} rows/s (batches of "
            f"{batch}, the first batch's compile included)")
    return state, done, first_log, centers


def queries(seed: int, n: int, dim: int, centers):
    import jax
    from repro.core import boundary
    return boundary.admit_query(
        clustered(jax.random.PRNGKey(seed + 1), n, dim, centers))


def timed(fn, *args, **kw):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t0


def phase_substrate(sz, seed: int, on_tpu: bool):
    import numpy as np
    from repro.core import search

    with Phase("substrate") as ph:
        state, _, first_log, centers = fill_arena(ph, sz, seed)
        q = queries(seed, sz["nq"], sz["dim"], centers)
        results = {}
        for use_kernel in (True, False):
            name = "kernels" if use_kernel else "limbs"
            (ids, s), first = timed(search.exact_search, state, q, K,
                                    use_kernel=use_kernel)
            _, warm = timed(search.exact_search, state, q, K,
                            use_kernel=use_kernel)
            results[name] = (np.asarray(ids), np.asarray(s))
            ph.note(f"exact_search {sz['nq']}x{sz['rows']} k={K} via {name}: "
                    f"first call {first:.3f} s, warm {warm:.6f} s")
        (ik, sk), (il, sl) = results["kernels"], results["limbs"]
        ph.check("kernel route == limb route (ids and scores)",
                 (ik == il).all() and (sk == sl).all())
        ph.check("every query finds k live rows", (il >= 0).all())
        text = search.exact_search.lower(state, q, K,
                                         use_kernel=True).as_text()
        has_kernel = "tpu_custom_call" in text
        if on_tpu:
            ph.check("kernel route lowers to tpu_custom_call", has_kernel)
        else:
            ph.note(f"kernel route lowered for cpu: interpret mode "
                    f"(tpu_custom_call present: {has_kernel})")
    return first_log, centers


def numpy_topk(vectors, ids, q, k: int):
    """Plain reference: int64 squared L2 on the host, (score, id) order."""
    import numpy as np
    v = vectors.astype(np.int64)
    qq = q.astype(np.int64)
    scores = ((qq * qq).sum(1)[:, None] - 2 * (qq @ v.T)
              + (v * v).sum(1)[None, :])
    out_i, out_s = [], []
    for row in scores:
        order = np.lexsort((ids, row))[:k]
        out_i.append(ids[order])
        out_s.append(row[order])
    return np.stack(out_i), np.stack(out_s)


def phase_platforms(sz, seed: int, first_log, centers) -> None:
    import jax
    import numpy as np
    from repro.core import hashing, machine, query, search
    from repro.core.state import init_state

    with Phase("platforms") as ph:
        n = sz["prefix"]
        log = jax.tree.map(lambda a: np.asarray(a)[:n], first_log)
        q = np.asarray(queries(seed, sz["prefix_nq"], sz["dim"], centers))

        def run(device):
            with jax.default_device(device):
                st = machine.bulk_apply(init_state(2 * n, sz["dim"]),
                                        jax.device_put(log, device))
                qd = jax.device_put(q, device)
                ids, s = search.exact_search(st, qd, K)
                h_ids, h_d, _ = query.batched_hnsw_search(st, qd, K, ef=64)
                dev_hash = int(jax.jit(hashing.hash_state_device)(st))
                return dict(
                    state_hash=hashing.hash_pytree(st),
                    device_hash=dev_hash,
                    content_hash=hashing.content_hash(st),
                    exact_hash=query.retrieval_hash(ids, s),
                    hnsw_hash=query.retrieval_hash(h_ids, h_d),
                ), (np.asarray(ids), np.asarray(s)), st

        t0 = time.perf_counter()
        chip, chip_exact, chip_state = run(jax.devices()[0])
        ph.note(f"{n} commands + {len(q)} queries on "
                f"{jax.devices()[0].platform}: "
                f"{time.perf_counter() - t0:.3f} s")
        t0 = time.perf_counter()
        host, _, _ = run(jax.devices("cpu")[0])
        ph.note(f"same work on cpu: {time.perf_counter() - t0:.3f} s")
        for name in chip:
            ph.check(f"{name} chip == cpu", chip[name] == host[name],
                     f"{chip[name]:#018x}")
        ph.check("hash_state_device == hash_pytree on the chip",
                 chip["device_hash"] == chip["state_hash"])
        ref_ids, ref_s = numpy_topk(np.asarray(chip_state.vectors)[:n],
                                    np.asarray(chip_state.ids)[:n], q, K)
        ph.check("exact answers == numpy int64 reference",
                 (chip_exact[0] == ref_ids).all()
                 and (chip_exact[1] == ref_s).all())


def phase_mesh(sz, seed: int, devices) -> None:
    """Pod scale (``core/distributed``): a 2^17-row arena sharded over a
    4-device mesh, filled by ``distributed_bulk_apply`` and searched with
    integer collectives, against ``exact_search`` on one device over the
    same log. The log is one batch: ``bulk_apply``'s HNSW insert costs
    O(capacity) per row on the chip, so a full arena takes hours."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import commands, distributed, machine, search
    from repro.core.state import init_state

    with Phase("mesh") as ph:
        n_dev = len(devices)
        mesh = jax.make_mesh((n_dev,), ("model",), devices=devices,
                             axis_types=(jax.sharding.AxisType.Auto,))
        centers, make = row_source(sz, seed)
        n = sz["batch"]
        log = commands.insert_batch(jnp.arange(n, dtype=jnp.int64), make(0))
        q = queries(seed, sz["nq"], sz["dim"], centers)

        t0 = time.perf_counter()
        routed = distributed.route_commands(log, n_dev)
        # hash routing is not an even split: give each shard 25% headroom
        per_shard = sz["rows"] // n_dev + sz["rows"] // (4 * n_dev)
        st = distributed.init_sharded_state(mesh, "model", per_shard,
                                            sz["dim"])
        st = distributed.distributed_bulk_apply(mesh, "model", st, routed)
        jax.block_until_ready(st.vectors)
        counts = distributed.shard_live_counts(st, n_dev)
        ph.note(f"distributed_bulk_apply of {n} rows into {n_dev} x "
                f"{per_shard} rows x {sz['dim']}: "
                f"{time.perf_counter() - t0:.3f} s (compile included)")
        ph.check("the shards hold every row", int(counts.sum()) == n,
                 f"per shard {counts.tolist()}")
        (d_ids, d_s), first = timed(distributed.distributed_search, mesh,
                                    "model", st, q, K)
        ph.note(f"distributed_search {sz['nq']} queries: {first:.3f} s "
                f"with its compile")

        t0 = time.perf_counter()
        single = machine.bulk_apply(init_state(2 * n, sz["dim"]), log)
        r_ids, r_s = search.exact_search(single, q, K)
        ph.note(f"single-device bulk_apply + exact_search on "
                f"{devices[0]}: {time.perf_counter() - t0:.3f} s")
        ph.check(f"distributed_search over {n_dev} devices == single-device "
                 f"exact_search (ids and scores)",
                 (np.asarray(d_ids) == np.asarray(r_ids)).all()
                 and (np.asarray(d_s) == np.asarray(r_s)).all())


def main() -> int:
    args = parse_args()
    if args.rehearse and args.chips == 4:
        os.environ.setdefault("XLA_FLAGS",
                              "--xla_force_host_platform_device_count=4")
    try:
        import repro
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package from "
              f"{ROOT / 'src'}: {e}", file=sys.stderr)
        return 2
    import jax
    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    cache = repro.use_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if not args.rehearse and platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found {platform} devices only; "
              f"--rehearse runs the phases at tiny sizes on the CPU",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    used = devices[:args.chips]
    print(f"devices: {len(devices)} x {devices[0].device_kind} "
          f"({platform}), using {len(used)}; jax {jax.__version__}; "
          f"compile cache {cache}", flush=True)
    _count_compile_time()
    sz = SIZES[args.rehearse]

    if args.chips == 4:
        phase_mesh(sz, args.seed, used)
    else:
        phase_engine(sz, args.seed, args.rehearse)
        first_log, centers = phase_substrate(sz, args.seed,
                                             on_tpu=platform == "tpu")
        phase_platforms(sz, args.seed, first_log, centers)

    result = {"ok": True, "device": {"platform": platform,
                                     "kind": devices[0].device_kind,
                                     "count": len(used)}}
    if args.rehearse:
        result["rehearse"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
