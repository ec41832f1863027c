"""Each cell's whole run at a tiny size on the CPU, with the look for a
chip skipped: sound runs are correct, and the control and each fault the
cell can have come out not correct."""
from __future__ import annotations

import sys

import jax.numpy as jnp
import pytest

from benchfix import REPO

sys.path.insert(0, str(REPO / "src"))

CELLS = {
    "cohere768-exact-open": {"retrieve_p50_ms", "retrieve_p95_ms"},
    "sift128-exact-batch": {"retrieve_qps"},
    "sift128-ingest": {"ingest_rows_per_s"},
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(run_cell, cell):
    line = run_cell(cell, 2 ** 33 + 7, 1.5)
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == CELLS[cell] | {"setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["compiles_in_window"] == 0
    assert all(c["value"] == 0 for c in line["checks"].values())


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_lower_precision_control_is_not_correct(run_cell, cell):
    line = run_cell(cell, 11, 1.0, control=True)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def _altered_answer(monkeypatch):
    from repro.core import query
    real = query.execute_plan

    def broken(state, q, k, plan, **kw):
        ids, scores = real(state, q, k, plan, **kw)
        return ids.at[0, 0].add(1), scores
    monkeypatch.setattr(query, "execute_plan", broken)


def _half_batch(monkeypatch):
    from repro.core import query
    real = query.execute_plan

    def broken(state, q, k, plan, **kw):
        h = q.shape[0] // 2
        ids, scores = real(state, q[:h], k, plan, **kw)
        return jnp.concatenate([ids, ids]), jnp.concatenate([scores, scores])
    monkeypatch.setattr(query, "execute_plan", broken)


def _state_unchanged(monkeypatch):
    from repro.core import machine
    monkeypatch.setattr(machine, "bulk_apply", lambda state, log, **kw: state)


def _altered_row(monkeypatch):
    from repro.core import commands
    real = commands.insert_batch

    def broken(ids, raw, *a, **kw):
        log = real(ids, raw, *a, **kw)
        return log.__class__(log.opcode, log.arg0, log.arg1, log.arg2,
                             log.vec.at[0, 0].add(1))
    monkeypatch.setattr(commands, "insert_batch", broken)


@pytest.mark.parametrize("cell,fault", [
    ("cohere768-exact-open", _altered_answer),
    ("sift128-exact-batch", _altered_answer),
    ("sift128-exact-batch", _half_batch),
    ("sift128-ingest", _state_unchanged),
    ("sift128-ingest", _altered_row),
], ids=lambda x: getattr(x, "__name__", x))
def test_fault_in_the_timed_path_is_not_correct(run_cell, monkeypatch, cell,
                                                fault):
    fault(monkeypatch)
    line = run_cell(cell, 23, 1.0)
    assert line["correct"] is False


def test_traced_run_reports_per_layer_metrics(run_cell):
    line = run_cell("sift128-ingest", 5, 1.0, trace=1)
    assert line["correct"] is True
    # the CPU trace has no TPU plane: only the host-span metric reads
    assert set(line["metrics"]) == {"wal_append_ms.ingest"}
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
