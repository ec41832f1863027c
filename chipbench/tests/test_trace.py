"""Trace reduction: busy time, idle gaps charged to host spans, op
attribution, and the per-layer readers on a small recorded trace."""
from __future__ import annotations

import importlib.util
import json
import sys

import pytest

from benchfix import BENCH, REPO

sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))

from harness import trace  # noqa: E402

CHIP = "/device:TPU:0"


def _view():
    ops = [trace.op_from_event(text, mod, CHIP, start, dur)
           for text, mod, start, dur in [
               ("%while.3 = (s32[]) while(s32[] %x)", "jit_x(12)", 0, 15),
               ("%fusion.1 = s32[8] fusion(s32[8] %y)", "jit_x(12)", 2, 6),
               ("%sort.23 = (u32[8]) sort(u32[8] %z)", "jit_y(34)", 20, 10),
               ("%sort.24 = (u32[8]) sort(u32[8] %z)", "jit_y(34)", 38, 5)]]
    trace.set_self_times(ops)
    spans = [("window", 0, 40), ("admit", 14, 7), ("idle", 30, 8)]
    return trace.View(ops, spans, (0, 40))


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    v = _view()
    assert v.busy_intervals() == [[0, 15], [20, 30], [38, 43]]
    assert v.busy_s == pytest.approx(30e-9)
    assert v.window_s == pytest.approx(40e-9)
    assert v.seconds(lambda o: o.kind == "sort") == pytest.approx(15e-9)
    assert [o.self_ns for o in v.ops] == [9, 6, 10, 5]
    assert (v.ops[2].name, v.ops[2].kind, v.ops[2].module) == \
        ("sort.23", "sort", "jit_y")


def test_idle_gaps_are_charged_to_the_overlapping_host_span():
    assert _view().idle_by_host() == [["idle", pytest.approx(8e-9)],
                                      ["admit", pytest.approx(5e-9)]]


def test_top_ops_group_by_program_and_kind():
    assert _view().top_ops() == [["jit_y:sort", pytest.approx(15e-9)],
                                 ["jit_x:while", pytest.approx(9e-9)],
                                 ["jit_x:fusion", pytest.approx(6e-9)]]


def _recorded(tag):
    """Four single-query reads over 2^20 x 768 ("open") and two 128-query
    batches over 2^20 x 128 ("batch") on the default exact route, traced
    on one TPU v5e; op texts cut after their name."""
    d = json.loads((BENCH / "tests" / "data" / f"trace_{tag}.json")
                   .read_text())
    return trace.build({CHIP: (d["ops"], d["modules"])}, d["spans"])


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _Ctx:
    def __init__(self, view, counts):
        self.view, self.counts = view, counts
        self.peaks = json.loads((BENCH / "peaks.json").read_text())[
            "TPU v5 lite"]

    def work(self, layer):
        spec = importlib.util.spec_from_file_location(
            layer, BENCH / "work" / f"{layer}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


@pytest.mark.parametrize("tag,counts,select_ms,scan_pct", [
    ("open", {"requests": 4, "nq": 1, "live": 1_000_000, "dim": 768},
     20.87, 10.0),
    ("batch", {"requests": 2, "nq": 128, "live": 1_000_000, "dim": 128},
     518.9, 1.8),
])
def test_readers_on_a_recorded_chip_trace(tag, counts, select_ms, scan_pct):
    from harness import ops
    v = _recorded(tag)
    ctx = _Ctx(v, counts)
    assert {o.module for o in v.ops} == {"jit_exact_search",
                                         "jit_admit_query"}
    assert v.seconds(ops.in_search) == pytest.approx(
        sum(o.dur_ns for o in v.ops if ops.in_search(o)
            and o.kind != "while") / 1e9, rel=0.01)
    assert _reader("select_ms")(ctx) == pytest.approx(select_ms, rel=0.01)
    assert _reader("scan_roofline_pct")(ctx) == pytest.approx(scan_pct,
                                                              rel=0.1)
    idle = _reader("device_idle_pct")(ctx)
    assert 0 < idle < 10
    assert _reader("apply_ms_per_row")(_Ctx(v, {"rows": 10})) is None


# Op texts as the profiler names them (HLO text of the compiled program,
# attributes after the custom-call target cut), taken from the search
# program compiled for a v5e on its kernel route, and from lax.top_k and
# lax.approx_max_k compiled for a v5e.
KERNEL_ROUTE = [
    ('%branch_0_fun.2 = s32[7,32,4096]{2,1,0:T(8,128)S(1)} custom-call('
     's32[32,128]{1,0} %pad.2, s32[4096,128]{1,0} %copy-done), '
     'custom_call_target="tpu_custom_call"', "scan"),
    ('%fusion.18 = s32[8,4096]{1,0:T(8,128)S(1)} fusion(s32[7,32,4096]'
     '{2,1,0:T(8,128)S(1)} %branch_0_fun.2), kind=kLoop', "scan"),
    ('%branch_0_fun.3 = (s32[8,512]{1,0:T(8,128)S(1)}, s32[8,512]'
     '{1,0:T(8,128)S(1)}, s32[8,512]{1,0:T(8,128)S(1)}) custom-call('
     's32[8,4096]{1,0:T(8,128)S(1)} %fusion.18), '
     'custom_call_target="tpu_custom_call"', "select"),
    ('%sort.42 = (u32[8,40]{0,1:T(8,128)S(1)}, s32[8,40]{0,1:T(8,128)}) '
     'sort(u32[8,40]{0,1:T(8,128)S(1)} %copy.3, s32[8,40]{0,1:T(8,128)} '
     '%iota.2), dimensions={1}, is_stable=true', "select"),
    ('%custom-call.3 = s64[8,10]{0,1:T(8,128)S(1)} custom-call('
     'u32[8,10]{0,1:T(8,128)S(1)} %get-tuple-element.145), '
     'custom_call_target="X64Combine"', "scan"),
    ('%custom-call = (f32[8,10]{1,0:T(8,128)}, s32[8,10]{1,0:T(8,128)}) '
     'custom-call(f32[8,4096]{1,0:T(8,128)} %x.1), '
     'custom_call_target="TopK"', "select"),
    ('%approx_top_k.15 = (f32[8,256]{1,0:T(8,128)S(1)}, s32[8,256]'
     '{1,0:T(8,128)S(1)}) custom-call(f32[8,4096]{1,0:T(8,128)} %param_0), '
     'custom_call_target="PartialReduce"', "select"),
    ('%topk.1 = (f32[8,10]{1,0}, s32[8,10]{1,0}) topk(f32[8,4096]{1,0} '
     '%x.1), k=10, largest=true', "select"),
]


@pytest.mark.parametrize("text,layer", KERNEL_ROUTE,
                         ids=[t.split(" ")[0][1:] for t, _ in KERNEL_ROUTE])
def test_select_is_found_by_op_not_only_by_sort(text, layer):
    """The qtopk kernel, XLA's top-k ops and the candidate sort are
    select; the qgemm kernel and the combines are scan; outside the search
    program neither."""
    from harness import ops
    op = trace.op_from_event(text, "jit_exact_search(7)", CHIP, 0, 5)
    assert (ops.is_select(op), ops.is_scan(op)) == (layer == "select",
                                                    layer == "scan")
    other = trace.op_from_event(text, "jit_other", CHIP, 0, 5)
    assert not ops.is_select(other) and not ops.is_scan(other)


def test_op_text_gives_opcode_target_and_tuple():
    op = trace.op_from_event(KERNEL_ROUTE[2][0], "jit_exact_search", CHIP,
                             0, 5)
    assert (op.name, op.kind, op.opcode, op.target, op.tuple_out) == (
        "branch_0_fun.3", "branch_0_fun", "custom-call", "tpu_custom_call",
        True)
    cut = trace.op_from_event("%fusion.404 = ", "jit_exact_search", CHIP,
                              0, 5)
    assert (cut.name, cut.kind, cut.opcode, cut.target) == (
        "fusion.404", "fusion", "", "")
