"""The program's spans moved onto the trace's clock, op scopes from the
compiled text, and the readers built on them, on synthetic windows: the
numbers where everything matches, None (never a guess) where it does
not."""
from __future__ import annotations

import importlib.util
import sys

import pytest

from benchfix import BENCH, REPO

sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))

from harness import program, trace  # noqa: E402
from repro import obs  # noqa: E402

CHIP = "/device:TPU:0"
T0 = 100.0              # harness clock, seconds
OFF = 5_000_000_000     # trace clock - record clock, ns
BATCHES = 4
SEARCH_TEXT = """HloModule jit_exact_search
ENTRY %main.9 (p: s32[8,16]) -> (s64[8,4]) {
  %fusion.2 = s64[8,64]{1,0} fusion(s32[8,16]{1,0} %p), kind=kLoop, metadata={op_name="jit(exact_search)/scan/add"}
  %sort.1 = (s64[8,64]{1,0}) sort(s64[8,64]{1,0} %fusion.2), dimensions={1}, metadata={op_name="jit(exact_search)/select/sort"}
  ROOT %copy.3 = s64[8,4]{1,0} copy(s64[8,64]{1,0} %sort.1)
}
"""


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _Spans:
    def __init__(self, records):
        self.records = records


class _Ctx:
    def __init__(self, view, harness_records, counts):
        self.view, self.counts = view, counts
        self.spans = _Spans(harness_records)
        self.config, self.traffic, self.serve = {}, {}, {}


def _ns(seconds: float) -> int:
    return int(round(seconds * 1e9))


def _window(jitter_ns=(0, 300, 600, 900), ops=None):
    """Batches of 1 s: a harness ``apply`` span at the start of each; the
    device busy from 0.1 s to 0.9 s into it (or ``ops``)."""
    harness = [("apply", T0 + i, 0.5) for i in range(BATCHES)]
    view_spans = [("window", _ns(T0) + OFF, _ns(BATCHES))] + [
        ("apply", _ns(t) + OFF + jitter_ns[i % len(jitter_ns)], _ns(d))
        for i, (_, t, d) in enumerate(harness)]
    if ops is None:
        ops = [("%fusion.7 = s32[8] fusion()", "jit__apply_insert_segment",
                _ns(T0 + i + 0.1) + OFF, _ns(0.8)) for i in range(BATCHES)]
    ops = [trace.op_from_event(t, m, CHIP, s, d) for t, m, s, d in ops]
    trace.set_self_times(ops)
    view = trace.View(ops, view_spans, (_ns(T0) + OFF,
                                        _ns(T0 + BATCHES) + OFF))
    return view, harness


def _program_records():
    """Per batch: the plan span over the first 0.1 s (the device idle), 12
    host fetches in it, and a 3 ms fsync inside a WAL append."""
    recs = []
    for i in range(BATCHES):
        t = _ns(T0 + i)
        plan = len(recs)
        recs.append(obs.Record("bulk_apply.plan", t, t + _ns(0.1), -1, i))
        recs += [obs.Record("host_syncs", t + 10 + j, t + 10 + j, plan, i, 1)
                 for j in range(12)]
        wal = len(recs)
        recs.append(obs.Record("wal.append", t + _ns(0.95), t + _ns(0.96),
                               -1, 100 + i))
        recs.append(obs.Record("wal.fsync", t + _ns(0.951), t + _ns(0.954),
                               wal, 100 + i))
    return recs


@pytest.fixture
def kept(monkeypatch):
    recs = _program_records()
    monkeypatch.setattr(obs, "records", lambda: list(recs))
    return recs


def test_spans_move_onto_the_trace_clock(kept):
    view, harness = _window()
    ctx = _Ctx(view, harness, {"batches": BATCHES})
    off, spread = program.offset_ns(ctx)
    assert off == pytest.approx(OFF + 450, abs=1)
    assert spread <= 900
    got = program.spans(ctx)
    assert [r.name for r in got] == [r.name for r in kept if r.n == 0]
    assert got[0].start_ns == pytest.approx(_ns(T0) + OFF + 450, abs=1)
    assert program.counted(ctx, "host_syncs") == 12 * BATCHES


@pytest.mark.parametrize("why", ["two matches", "wide spread", "no device op",
                                 "no program records"])
def test_spans_are_none_when_they_cannot_be_placed(monkeypatch, kept, why):
    if why == "two matches":
        view, harness = _window()
        view.spans = view.spans[:3]
    elif why == "wide spread":
        view, harness = _window(jitter_ns=(0, 200_000))
    elif why == "no device op":
        view, harness = _window(ops=[])
    else:       # a program from before it kept records of its own
        import repro
        view, harness = _window()
        monkeypatch.delattr(repro, "obs")
        monkeypatch.setitem(sys.modules, "repro.obs", None)
    ctx = _Ctx(view, harness, {"batches": BATCHES})
    assert program.spans(ctx) is None
    assert program.counted(ctx, "host_syncs") is None
    for name in ("apply_plan_ms", "wal_fsync_ms", "host_syncs_per_batch",
                 "idle_unattributed_pct"):
        assert _reader(name)(ctx) is None, name


def test_span_readers_on_a_window_that_aligns(kept):
    view, harness = _window()
    ctx = _Ctx(view, harness, {"batches": BATCHES})
    assert _reader("apply_plan_ms")(ctx) == pytest.approx(100.0)
    assert _reader("wal_fsync_ms")(ctx) == pytest.approx(3.0)
    assert _reader("host_syncs_per_batch")(ctx) == 12
    # idle: 0.1 s under the plan span, 0.1 s after the device's work of
    # which the WAL append covers 0.01 s
    assert _reader("idle_unattributed_pct")(ctx) == pytest.approx(
        100 * 0.09 / 0.2, rel=1e-4)


def _search_window():
    t = _ns(T0) + OFF
    ops = [("%fusion.2 = s64[8,64] fusion()", "jit_exact_search(7)",
            t + _ns(0.1), _ns(0.02)),
           ("%sort.1 = (s64[8,64]) sort()", "jit_exact_search(7)",
            t + _ns(0.2), _ns(0.5)),
           ("%copy.3 = s64[8,4] copy()", "jit_exact_search(7)",
            t + _ns(0.8), _ns(0.001))]
    view, harness = _window(ops=ops)
    return _Ctx(view, harness, {"requests": 2})


def test_scope_readers_from_the_compiled_text(monkeypatch):
    monkeypatch.setattr(program, "_compiled_text",
                        lambda ctx, prog, fresh=False: SEARCH_TEXT)
    ctx = _search_window()
    table = program.scopes(ctx, program.SEARCH)
    assert table == {"fusion.2": "jit(exact_search)/scan/add",
                     "sort.1": "jit(exact_search)/select/sort",
                     "copy.3": ""}
    assert _reader("select_scope_ms")(ctx) == pytest.approx(250.0)
    assert _reader("scan_scope_ms")(ctx) == pytest.approx(10.0)
    assert program.scopes(ctx, program.APPLY) is None


def test_a_cached_program_without_scopes_is_compiled_again(monkeypatch):
    """op_name is not part of the compile cache's key, so the window may
    have run a program cached by a checkout without the scopes: its text
    is read again from a fresh compile."""
    stale = SEARCH_TEXT.replace("/scan/", "/").replace("/select/", "/")
    calls = []

    def compiled(ctx, prog, fresh=False):
        calls.append(fresh)
        return SEARCH_TEXT if fresh else stale
    monkeypatch.setattr(program, "_compiled_text", compiled)
    ctx = _search_window()
    assert _reader("select_scope_ms")(ctx) == pytest.approx(250.0)
    assert _reader("scan_scope_ms")(ctx) == pytest.approx(10.0)
    assert calls == [False, True]


def test_graph_insert_reader_from_the_compiled_text(monkeypatch):
    text = SEARCH_TEXT.replace("/scan/", "/hnsw_insert/while/body/")
    monkeypatch.setattr(program, "_compiled_text",
                        lambda ctx, prog, fresh=False: text)
    ctx = _search_window()
    for o in ctx.view.ops:
        o.module = program.APPLY
    ctx.counts = {"rows": 100}
    assert _reader("graph_insert_ms_per_row")(ctx) == pytest.approx(0.2)


@pytest.mark.parametrize("reader,prog", [
    ("select_scope_ms", program.SEARCH), ("scan_scope_ms", program.SEARCH),
    ("graph_insert_ms_per_row", program.APPLY)])
def test_scope_readers_are_none_when_an_op_has_no_entry(monkeypatch, reader,
                                                         prog):
    cut = "\n".join(line for line in SEARCH_TEXT.splitlines()
                    if "%sort.1" not in line)
    monkeypatch.setattr(program, "_compiled_text",
                        lambda ctx, p, fresh=False: cut)
    ctx = _search_window()
    if prog == program.APPLY:
        for o in ctx.view.ops:
            o.module = program.APPLY
        ctx.counts = {"rows": 100}
    assert program.scopes(ctx, prog) is None
    assert _reader(reader)(ctx) is None


def test_under_reads_named_scopes_not_the_primitive():
    assert program.under("jit(f)/select/sort", "select")
    assert program.under("jit(f)/hnsw_insert/while/body/scan/x", "scan")
    assert not program.under("jit(f)/while/scan", "scan")
    assert not program.under("", "scan")


@pytest.fixture
def compile_cache(tmp_path):
    """A persistent compile cache of its own that keeps every program, as
    run.py sets it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cc.reset_cache()
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    cc.reset_cache()


@pytest.mark.parametrize("prog", [program.SEARCH, program.APPLY])
def test_compiled_text_is_the_windows_program_from_the_cache(compile_cache,
                                                             prog):
    """Lowered from the cell's files alone, the program gives the ops and
    scopes that the call the window makes compiles to, and JAX's caches
    hold it from that call: nothing compiles again. Fresh, it reads the
    same."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import boundary, commands, machine, search
    from repro.core.state import init_state

    ctx = _Ctx(None, [], {"live": 200, "nq": 4})
    ctx.config = {"capacity": 256, "dim": 16, "k": 5, "ef_construction": 32}
    ctx.traffic, ctx.serve = {"batch": 10}, {"route": "exact"}
    state = init_state(256, 16)
    if prog == program.SEARCH:
        q = jax.jit(boundary.admit_query)(np.ones((4, 16), np.float32))
        search.exact_search(state, q, 5, metric=search.METRIC_L2,
                            use_kernel=False)
        real = search.exact_search.lower(state, q, 5, use_kernel=False)
    else:
        rows = boundary.normalize_embedding(np.ones((10, 16), np.float32))
        log = commands.insert_batch(jnp.arange(10, dtype=jnp.int64), rows)
        machine.bulk_apply(state, log)
        real = machine._apply_insert_segment.lower(
            state, machine._pad_log(log, 16), jnp.int32(10),
            ef_construction=32)
    obs.reset()
    with obs.span("test.read"):
        text = program._compiled_text(ctx, prog)
    assert "test.read" not in obs.counters().get("compiles", {})
    table = program.op_scopes(text)
    assert table == program.op_scopes(real.compile().as_text())
    assert table == program.op_scopes(program._compiled_text(ctx, prog,
                                                             fresh=True))
    scope = "select" if prog == program.SEARCH else "hnsw_insert"
    assert any(program.under(p, scope) for p in table.values())


def test_fresh_compile_reads_past_a_cached_program_without_scopes(
        compile_cache, monkeypatch):
    """The compile cache's key leaves op_name out, so a program compiled
    without the scopes (by an older checkout) is what the cache returns
    for the scoped one; a fresh compile reads the scopes, op for op."""
    import contextlib

    import jax
    import numpy as np
    from repro.core import boundary, search
    from repro.core.state import init_state

    ctx = _Ctx(None, [], {"live": 200, "nq": 4})
    ctx.config = {"capacity": 256, "dim": 16, "k": 5, "ef_construction": 32}
    ctx.serve = {"route": "exact"}
    state = init_state(256, 16)
    q = jax.jit(boundary.admit_query)(np.ones((4, 16), np.float32))
    scoped = jax.named_scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    search.exact_search(state, q, 5, metric=search.METRIC_L2,
                        use_kernel=False)
    monkeypatch.setattr(jax, "named_scope", scoped)
    jax.clear_caches()
    stale = program.op_scopes(program._compiled_text(ctx, program.SEARCH))
    assert not any(program.under(p, "select") for p in stale.values())
    fresh = program.op_scopes(program._compiled_text(ctx, program.SEARCH,
                                                     fresh=True))
    assert set(fresh) == set(stale)
    assert any(program.under(p, "select") for p in fresh.values())
