"""Discovery by name, the refusal to run without a chip or without the
program, and the shape of BENCHMARK.json against the files it names."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

from benchfix import BENCH, REPO, load_run

DUMMY_KIND = '''
def run(r):
    import jax.numpy as jnp
    x = jnp.arange(8.0)
    r.setup_done()
    n = 0
    with r.window() as w:
        while n < 3:
            x = (x * 2).block_until_ready()
            n += 1
    return {"metrics": {"dummy_rate": n / w.seconds}, "attempted": n,
            "failed": 0, "checks": [("dummy_off", 0, 0)],
            "counts": {"n": n}}
'''


def _add_dummy(root):
    """A new configuration, traffic kind, traffic mix, cell, end-to-end
    metric and per-layer metric, added as files and entries only."""
    d = root / BENCH.name
    (d / "configs" / "dummy-config.json").write_text('{"dim": 8}')
    (d / "traffic" / "dummy_kind.py").write_text(DUMMY_KIND)
    (d / "traffic" / "dummy-mix.json").write_text('{"kind": "dummy_kind"}')
    (d / "workloads" / "dummy-cell.json").write_text(json.dumps(
        {"config": "dummy-config", "traffic": "dummy-mix", "serve": {}}))
    (d / "metrics" / "dummy_count.py").write_text(
        "def read(ctx):\n    return float(ctx.counts['n'])\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "dummy-cell", "config": "dummy-config",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "a cell added by files alone"})
    bench["end_to_end"].append({"name": "dummy_rate", "unit": "1/s",
                                "better": "higher", "bound": 0.25,
                                "source": "host_clock",
                                "workloads": ["dummy-cell"]})
    bench["per_layer"].append({"name": "dummy_count.cell", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "dummy", "moves": "dummy_rate"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_new_cell_kind_and_metric_are_found_by_name(tiny_root, run_cell):
    _add_dummy(tiny_root)
    line = run_cell("dummy-cell", 5, 1)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"dummy_rate", "setup_s"}
    assert list(line)[-1] == "checks"
    assert line["checks"] == {"dummy_off": {"value": 0, "limit": 0}}
    traced = run_cell("dummy-cell", 5, 1, trace=1)
    assert traced["metrics"] == {"dummy_count.cell": {"value": 3.0,
                                                      "unit": "1"}}
    assert traced["device"]["window_s"] > 0
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_chip_exits_nonzero_and_prints_no_result(tiny_root, capsys):
    run = load_run(tiny_root)
    rc = run.main(["--workload", "sift128-ingest", "--seed", "1",
                   "--seconds", "1"], root=tiny_root)
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""


def test_checkout_without_the_program_exits_nonzero(tmp_path):
    shutil.copytree(BENCH, tmp_path / BENCH.name,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, f"{BENCH.name}/run.py", "--workload",
         "sift128-ingest", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_names_files_that_exist():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    assert bench["command"] == ["python3", f"{BENCH.name}/run.py"]
    configs = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        assert NAME.match(c["name"])
        cfg = json.loads((REPO / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    used = set()
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        cell = json.loads((BENCH / "workloads" / f"{w['name']}.json")
                          .read_text())
        assert (cell["config"], cell["traffic"]) == (w["config"],
                                                     w["traffic"])
        mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
        assert (BENCH / "traffic" / f"{mix['kind']}.py").is_file()
        used.add(w["config"])
    assert used == configs
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and all(m["bound"] <= 0.25 for m in e2e.values())
    for m in bench["per_layer"]:
        assert set(m["workloads"]) <= cells
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", [cell])
        base = m["name"].split(".")[0]
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file() or \
            (BENCH / "metrics" / f"{base}.py").is_file()
