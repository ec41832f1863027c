"""Reduce a profiler trace of the window to device ops, host spans, busy
time and idle gaps.

The window is one ``TraceAnnotation("window")`` span; the profiler runs
for the window alone, so every device op in the trace is the window's.
Device ops are the events of the "XLA Ops" line of each
``/device:TPU:<n>`` plane. An event's name is its HLO text
(``%sort.23 = (...) sort(...)``): the op is ``sort.23``, its kind
``sort``, its opcode ``sort``; a custom call also carries its target
(``custom_call_target="TopK"``). Ops nest (a ``while`` holds its body's ops), so each op carries
its self time, its duration less that of the ops inside it, and layer
times sum self times. Each op is given the program ("XLA Modules" event,
``jit_exact_search``) whose interval holds it. Busy time is the union of
op intervals, averaged over the chips that ran anything. An idle gap is
charged to the host span that overlaps it most. The device clock in the
trace may sit a few milliseconds off the host's, so that charge is
approximate to that.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import re

_DEVICE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\.\d+$")
_HASH = re.compile(r"\(\d+\)$")
# the opcode is the first lower-case word before "(" after the result
# shape; layouts inside the shape ("T(8,128)", "S(1)") are upper case
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')


@dataclasses.dataclass
class Op:
    name: str       # "sort.23"
    kind: str       # "sort"
    module: str     # "jit_exact_search"
    chip: str
    start_ns: float
    dur_ns: float
    self_ns: float = 0.0
    opcode: str = ""     # "sort", "custom-call"; "" where the text is cut
    target: str = ""     # a custom call's target: "TopK", "tpu_custom_call"
    tuple_out: bool = False   # the op returns a tuple of arrays

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def op_from_event(text: str, module: str, chip: str, start: float,
                  dur: float) -> Op:
    if text.startswith("%") and " = " in text:
        name, rest = text[1:].split(" = ", 1)
    else:
        name, rest = text.split(" ")[0], ""
    opcode = _OPCODE.search(" " + rest)
    target = _TARGET.search(rest)
    return Op(name, _SUFFIX.sub("", name), _HASH.sub("", module), chip,
              start, dur, dur, opcode.group(1) if opcode else "",
              target.group(1) if target else "", rest.startswith("("))


def set_self_times(ops: list) -> None:
    """Self time = duration less the durations of the ops directly inside
    (same chip, interval within)."""
    by_chip = {}
    for o in ops:
        by_chip.setdefault(o.chip, []).append(o)
    for chip_ops in by_chip.values():
        stack = []
        for o in sorted(chip_ops, key=lambda o: (o.start_ns, -o.dur_ns)):
            o.self_ns = o.dur_ns
            while stack and stack[-1].end_ns <= o.start_ns:
                stack.pop()
            if stack:
                stack[-1].self_ns -= o.dur_ns
            stack.append(o)


@dataclasses.dataclass
class View:
    """What the per-layer readers see of one traced window."""
    ops: list            # [Op], all chips
    spans: list          # [(name, start_ns, dur_ns)] host spans
    window_ns: tuple     # (start, end) of the host's window span

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    def busy_intervals(self, chip: str = "") -> list:
        """Merged op intervals of one chip (the first by default)."""
        chip = chip or min((o.chip for o in self.ops), default="")
        merged = []
        for a, b in sorted((o.start_ns, o.end_ns) for o in self.ops
                           if o.chip == chip):
            if merged and a <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], b)
            else:
                merged.append([a, b])
        return merged

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips that ran."""
        chips = sorted({o.chip for o in self.ops})
        return sum(sum(b - a for a, b in self.busy_intervals(c))
                   for c in chips) / 1e9 / max(len(chips), 1)

    def seconds(self, pred) -> float:
        """Device seconds (self time) of the ops for which ``pred(op)``
        holds, summed over chips."""
        return sum(o.self_ns for o in self.ops if pred(o)) / 1e9

    def top_ops(self, n: int = 10) -> list:
        """[[program:op kind, self seconds]], the largest first."""
        agg = {}
        for o in self.ops:
            key = f"{o.module}:{o.kind}"
            agg[key] = agg.get(key, 0.0) + o.self_ns / 1e9
        return sorted(([k, v] for k, v in agg.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_by_host(self, n: int = 10) -> list:
        """Idle seconds inside the window, summed by the host span that
        overlaps each gap most ("no span" where none does)."""
        w0, w1 = self.window_ns
        edges = [w0]
        for a, b in self.busy_intervals():
            a, b = max(a, w0), min(b, w1)
            if b > a:
                edges += [a, b]
        edges.append(w1)
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        spans = sorted((s, s + d, name) for name, s, d in self.spans
                       if name != "window")
        starts = [s[0] for s in spans]
        longest = max((e - s for s, e, _ in spans), default=0)
        agg = {}
        for a, b in gaps:
            best, label = 0.0, "no span"
            lo = bisect.bisect_left(starts, a - longest)
            for s, e, name in spans[lo:]:
                if s >= b:
                    break
                ov = min(e, b) - max(s, a)
                if ov > best:
                    best, label = ov, name
            agg[label] = agg.get(label, 0.0) + (b - a) / 1e9
        return sorted(([k, v] for k, v in agg.items()),
                      key=lambda kv: -kv[1])[:n]


def build(devices: dict, spans: list) -> View:
    """A view from raw events: ``devices`` maps a chip to (ops, programs),
    each a list of (HLO text or name, start_ns, dur_ns); ``spans`` are the
    host spans, one of them "window"."""
    ops = []
    for chip, (raw_ops, programs) in devices.items():
        mods = sorted((s, s + d, name) for name, s, d in programs)
        starts = [m[0] for m in mods]
        for text, start, dur in raw_ops:
            i = bisect.bisect_right(starts, start) - 1
            mod = mods[i][2] if i >= 0 and start < mods[i][1] else "?"
            ops.append(op_from_event(text, mod, chip, start, dur))
    win = [s for s in spans if s[0] == "window"]
    if not win:
        raise RuntimeError("the trace holds no window span")
    set_self_times(ops)
    return View(ops, spans, (win[-1][1], win[-1][1] + win[-1][2]))


def load(trace_dir: str, span_names) -> View:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise RuntimeError(f"no profiler trace under {trace_dir}")
    spans, devices = [], {}
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.duration_ns)
                             for e in line.events if e.name in span_names)
        elif _DEVICE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines:
                devices[plane.name] = tuple(
                    [(e.name, e.start_ns, e.duration_ns)
                     for e in lines[name].events] if name in lines else []
                    for name in ("XLA Ops", "XLA Modules"))
    return build(devices, spans)
