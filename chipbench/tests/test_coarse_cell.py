"""The coarse-route cell's whole run at a tiny size on the CPU, with the
look for a chip skipped: a sound run is correct, and the control and each
fault planted in the timed path come out not correct. A traced run reports
the cell's five per-layer metrics, read from device ops planted in the
trace under the names of the program's own compiled CPU instructions (the
CPU trace has no TPU plane)."""
from __future__ import annotations

import json
import sys

import jax
import jax.numpy as jnp
import pytest

from benchfix import BENCH, REPO, load_run, make_tiny_root

sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))

CELL = "cohere768-coarse-batch"
METRICS = {"code_scan_ms.coarse", "code_scan_roofline_pct.coarse",
           "coarse_select_ms.coarse", "rerank_ms.coarse",
           "device_idle_pct.coarse"}
TINY = {"configs/cohere-768d-1m-sq8.json": {"dim": 64, "rows": 3000,
                                            "capacity": 4096},
        "traffic/closed-batch-128-coarse.json": {"batch": 16, "pool": 4}}


@pytest.fixture
def coarse_root(tmp_path):
    root = make_tiny_root(tmp_path)
    for name, changes in TINY.items():
        path = root / BENCH.name / name
        path.write_text(json.dumps({**json.loads(path.read_text()),
                                    **changes}))
    return root


@pytest.fixture
def run_coarse(coarse_root, capsys):
    """run_coarse(seed, seconds, trace=0, control=False) -> the result line
    and the run's error output."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    run = load_run(coarse_root)

    def go(seed, seconds, trace=0, control=False):
        argv = ["--workload", CELL, "--seed", str(seed), "--seconds",
                str(seconds), "--trace", str(trace)]
        rc = run.main(argv + (["--control"] if control else []),
                      root=coarse_root, require_chip=False)
        out = capsys.readouterr()
        assert rc == 0, out.err
        return json.loads(out.out.strip().splitlines()[-1]), out.err

    yield go
    for k, v in saved.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_sound_run_is_correct(run_coarse):
    line, err = run_coarse(2 ** 33 + 7, 1.5)
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"retrieve_qps", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["compiles_in_window"] == 0
    assert set(line["checks"]) == {
        "admitted_ints_differing", "answer_ids_differing",
        "answer_scores_differing", "table_params_differing",
        "table_codes_differing", "table_norms_differing",
        "table_builds_in_window"}
    assert all(c["value"] == 0 for c in line["checks"].values())
    assert "recall@10: " in err


def test_lower_precision_control_is_not_correct(run_coarse):
    line, _ = run_coarse(11, 1.0, control=True)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def _altered_code(monkeypatch):
    """One code changed in the table handed to ``execute_plan``."""
    from repro.core import codes
    real = codes.build

    def broken(state):
        t = real(state)
        return codes.CodeTable(codes=t.codes.at[0, 0].add(1),
                               offset=t.offset, scale=t.scale, norms=t.norms)
    monkeypatch.setattr(codes, "build", broken)


def _approximate_answers(monkeypatch):
    """The k best by approx score served, with their approx scores."""
    from repro.core import codes, limbs, search

    def broken(state, table, q, k, *, ef_coarse, metric, use_kernel):
        w = codes.query_weights(q, table, metric)
        approx = table.norms[None, :] - 2 * limbs.exact_dot(w, table.codes)
        approx = jnp.where(state.valid[None, :], approx, search.INF)
        s, slot = search.topk_by_score(
            approx, jnp.arange(approx.shape[1], dtype=jnp.int64), k)
        return state.ids[slot], s
    monkeypatch.setattr(search, "coarse_search", broken)


def _altered_id(monkeypatch):
    from repro.core import query
    real = query.execute_plan

    def broken(state, q, k, plan, **kw):
        ids, scores = real(state, q, k, plan, **kw)
        return ids.at[0, 0].add(1), scores
    monkeypatch.setattr(query, "execute_plan", broken)


@pytest.mark.parametrize("fault", [_altered_code, _approximate_answers,
                                   _altered_id], ids=lambda f: f.__name__)
def test_fault_in_the_timed_path_is_not_correct(run_coarse, monkeypatch,
                                                fault):
    fault(monkeypatch)
    line, _ = run_coarse(23, 1.0)
    assert line["correct"] is False


def _scoped_ops(root):
    """Names of the coarse program's instructions under one of its scopes,
    compiled as the reader compiles it for the tiny cell."""
    from types import SimpleNamespace

    from harness import coarse_program, program
    d = root / BENCH.name
    cfg = json.loads((d / "configs" / "cohere-768d-1m-sq8.json").read_text())
    mix = json.loads((d / "traffic" / "closed-batch-128-coarse.json")
                     .read_text())
    cell = json.loads((d / "workloads" / f"{CELL}.json").read_text())
    ctx = SimpleNamespace(config=cfg, serve=cell["serve"],
                          counts={"live": cfg["rows"], "nq": mix["batch"]})
    table = program.op_scopes(coarse_program._lower(ctx).compile().as_text())
    return [n for n, p in table.items()
            if any(program.under(p, s) for s in coarse_program.SCOPES)]


def test_traced_run_reports_the_coarse_metrics(coarse_root, run_coarse,
                                               monkeypatch):
    """The CPU trace has no device plane, so each ``execute`` span gets the
    compiled program's scoped instructions as device ops, in order, spread
    over it; the CPU's device kind gets the v5e's peaks in the tiny copy."""
    from harness import coarse_program, trace

    peaks_path = coarse_root / BENCH.name / "peaks.json"
    peaks = json.loads(peaks_path.read_text())
    peaks[jax.devices()[0].device_kind] = peaks["TPU v5 lite"]
    peaks_path.write_text(json.dumps(peaks))
    names, real_load = _scoped_ops(coarse_root), trace.load

    def load(trace_dir, span_names):
        view = real_load(trace_dir, span_names)
        ops = []
        for name, start, dur in view.spans:
            if name == "execute":
                step = dur / len(names)
                ops += [trace.op_from_event(
                    f"%{n} = s64[] fusion()", coarse_program.PROGRAM,
                    "/device:TPU:0", start + i * step, step)
                    for i, n in enumerate(names)]
        trace.set_self_times(ops)
        view.ops = ops
        return view

    monkeypatch.setattr(trace, "load", load)
    line, _ = run_coarse(5, 1.0, trace=1)
    assert line["correct"] is True
    assert METRICS <= set(line["metrics"]), line["metrics"]
    assert all(line["metrics"][m]["value"] > 0 for m in METRICS)
