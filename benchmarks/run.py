"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (stdout). Mapping to the paper:
  bench_divergence  — Table 1 / §4  (bit-level divergence; float vs Q16.16)
  bench_contracts   — Table 2 / §6  (precision contracts ladder)
  bench_recall      — Table 3 / §8.3 (Recall@10 f32 vs Q16.16 HNSW)
  bench_snapshot    — §8.1          (snapshot transfer, H_A == H_B, 10k rows)
  bench_latency     — §8.2          (retrieval latency, exact + HNSW + boundary)
  bench_wal         — DESIGN.md §6  (group commit vs fsync-per-command;
                                     sharded ingest + kill + recover)
  bench_serve       — DESIGN.md §7  (sharded vs single-host serve engine,
                                     memory/retrieval hashes cross-checked)
  bench_coarse      — DESIGN.md §10 (int8 coarse scan + exact re-rank vs
                                     planner-exact and HNSW; bytes-scanned
                                     model, coverage hash asserted)
  bench_churn       — DESIGN.md §11 (ANN under churn: planner stays on
                                     HNSW, exhaustive hash == exact,
                                     re-link amortization, all asserted)
  bench_replication — DESIGN.md §8  (ingest with 0/1/2 verified replicas,
                                     cold-replica catch-up lag, hash-checked)
  bench_roofline    — EXPERIMENTS.md §Roofline (reads dry-run artifacts)
"""
import sys
import traceback


def main() -> None:
    import repro
    repro.use_compile_cache()
    from benchmarks import (bench_churn, bench_coarse, bench_contracts,
                            bench_divergence, bench_ingest, bench_latency,
                            bench_recall, bench_replication, bench_roofline,
                            bench_serve, bench_snapshot, bench_wal)
    print("name,us_per_call,derived")
    failures = 0
    for mod in (bench_divergence, bench_contracts, bench_recall,
                bench_snapshot, bench_latency, bench_ingest, bench_wal,
                bench_serve, bench_replication, bench_coarse, bench_churn,
                bench_roofline):
        try:
            mod.run()
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{mod.__name__},0.0,ERROR:{type(e).__name__}:{e}",
                  flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(f"{failures} benchmark modules failed")


if __name__ == '__main__':
    main()
