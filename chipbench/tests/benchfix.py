"""Helpers for the harness tests: a copy of the benchmark at tiny sizes,
and the harness's entry point loaded in this process."""
from __future__ import annotations

import importlib.util
import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parent

TINY = {
    "configs": {
        "cohere-768d-1m": {"dim": 64, "rows": 3000, "capacity": 4096},
        "sift-128d-1m": {"dim": 32, "rows": 3000, "capacity": 4096},
    },
    "traffic": {
        "open-poisson-1q": {"rate_per_s": 40, "check_sample": 8},
        "closed-batch-128": {"batch": 16, "pool": 4, "check_batches": 2},
        "durable-bulk-100": {"batch": 32},
    },
}

# A cell whose files the benchmark keeps, though BENCHMARK.json leaves it
# out until a stall of its tail is understood: the open-loop read cell.
# The tiny checkout adds these entries, so the harness keeps running it.
LATER = {
    "configs": [{"name": "cohere-768d-1m", "source": "VectorDBBench "
                 "Performance768D1M", "file": "chipbench/configs/"
                 "cohere-768d-1m.json", "reduced": [], "why": "RAG corpus"}],
    "workloads": [{"name": "cohere768-exact-open", "config": "cohere-768d-1m",
                   "traffic": "open-poisson-1q", "chips": 1,
                   "why": "RAG front end, open loop, exact route"}],
    "end_to_end": [{"name": n, "unit": "ms", "better": "lower",
                    "bound": 0.25, "source": "host_clock",
                    "workloads": ["cohere768-exact-open"]}
                   for n in ("retrieve_p50_ms", "retrieve_p95_ms")],
    "per_layer": [{"name": f"{n}.open", "unit": u, "better": b,
                   "source": "device_trace", "layer": n,
                   "moves": "retrieve_p95_ms",
                   "workloads": ["cohere768-exact-open"]}
                  for n, u, b in (("scan_roofline_pct", "%", "higher"),
                                  ("select_ms", "ms", "lower"),
                                  ("device_idle_pct", "%", "lower"))],
}


def make_tiny_root(dest: pathlib.Path) -> pathlib.Path:
    """A checkout holding BENCHMARK.json with the ``LATER`` entries added,
    the benchmark's files with every configuration and traffic mix cut to
    a CPU-sized shape, and a link to the program's sources."""
    shutil.copytree(BENCH, dest / BENCH.name, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for key, entries in LATER.items():
        bench[key] += entries
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    (dest / "src").symlink_to(REPO / "src")
    for folder, files in TINY.items():
        for name, changes in files.items():
            path = dest / BENCH.name / folder / f"{name}.json"
            path.write_text(json.dumps({**json.loads(path.read_text()),
                                        **changes}))
    return dest


def load_run(root: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_run_under_test", root / BENCH.name / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
