#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--control]

Everything about a cell is found by name, in files of its own:

* ``BENCHMARK.json`` (repository root): the cell's configuration and
  traffic, and which metrics it reports;
* ``chipbench/workloads/<cell>.json``: serving settings (route, ...);
* ``chipbench/configs/<config>.json``: the deployment's sizes;
* ``chipbench/traffic/<traffic>.json``: the traffic mix's parameters, whose
  ``kind`` names the generator ``chipbench/traffic/<kind>.py``;
* ``chipbench/metrics/<metric>.py`` (else ``<metric up to the first
  dot>.py``): the reader of one per-layer metric.

A run loads the cell, warms up every shape it uses (set-up, timed from
process start), measures for ``--seconds`` (``--trace 1``: a traced window
of at most the traffic's ``trace_seconds``), checks what the window
returned against the plain reference, and prints one JSON line last on
stdout. The numbers compared, each with its limit, are the last lines on
stderr and the last key of that line. ``--control`` puts the reference
computed one precision lower in the program's place: its run must come out
not correct. With no TPU, or fewer chips than the cell asks for, it exits
1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up runs from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
BENCH_DIR = HERE.name
SPAN_NAMES = ("window", "idle", "admit", "live_count", "plan", "execute",
              "fetch", "client", "boundary", "commands", "append", "apply",
              "ack")


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    return ap.parse_args(argv)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise SystemExit(f"chipbench: {path} not found")
    return json.loads(path.read_text())


def find_cell(root: pathlib.Path, name: str) -> dict:
    """Everything one cell needs, found by name under ``root``."""
    bench = _json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json")
    d = root / BENCH_DIR
    cell = _json(d / "workloads" / f"{name}.json")
    for key in ("config", "traffic"):
        if cell[key] != entry[key]:
            raise SystemExit(f"chipbench: {name}: {key} is {cell[key]!r} in "
                             f"its file, {entry[key]!r} in BENCHMARK.json")
    traffic = _json(d / "traffic" / f"{entry['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    readers = {}
    for m in per_layer:
        own = d / "metrics" / f"{m['name']}.py"
        base = d / "metrics" / f"{m['name'].split('.')[0]}.py"
        readers[m["name"]] = load_module(own if own.is_file() else base)
    return {
        "entry": entry, "cell": cell, "traffic": traffic,
        "config": _json(d / "configs" / f"{entry['config']}.json"),
        "kind": load_module(d / "traffic" / f"{traffic['kind']}.py"),
        "end_to_end": e2e, "per_layer": per_layer, "readers": readers,
        "peaks": _json(d / "peaks.json"),
    }


class Window:
    start = end = seconds = 0.0


class Run:
    """What a traffic kind is handed: the cell's files, the run's
    arguments, host spans, and the set-up and window hooks."""

    def __init__(self, found: dict, args, spans, t_start: float):
        self.cell, self.config = found["cell"], found["config"]
        self.traffic, self.serve = found["traffic"], found["cell"]["serve"]
        self.seed, self.control = args.seed, args.control
        self.trace = bool(args.trace)
        self.seconds = args.seconds
        if self.trace:
            self.seconds = min(args.seconds,
                               self.traffic.get("trace_seconds", args.seconds))
        self.spans, self.t_start = spans, t_start
        self.setup_s = None
        self.trace_dir = None
        self.compiles = 0               # backend compiles so far
        self.compiles_in_window = None
        self.memory_peak = 0

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t_start

    @contextlib.contextmanager
    def window(self):
        import jax
        w = Window()
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(self.trace_dir)
        c0 = self.compiles
        with jax.profiler.TraceAnnotation("window"):
            w.start = time.perf_counter()
            yield w
            w.end = time.perf_counter()
        w.seconds = w.end - w.start
        self.window_start, self.window_end = w.start, w.end
        if self.trace:
            jax.profiler.stop_trace()
        self.window_s = w.seconds
        self.compiles_in_window = self.compiles - c0
        self.memory_peak = max((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0) for d in jax.local_devices())


def main(argv=None, root: pathlib.Path = HERE.parent,
         require_chip: bool = True) -> int:
    args = parse_args(argv)
    t_start = T_START if argv is None else time.perf_counter()
    sys.path.insert(0, str(root / BENCH_DIR))
    sys.path.insert(0, str(root / "src"))
    found = find_cell(root, args.workload)
    try:
        import repro
    except ImportError as e:
        print(f"chipbench: the system under test is missing "
              f"({root / 'src'}): {e}", file=sys.stderr)
        return 2
    import jax
    from jax import monitoring
    from harness import sut, trace

    repro.use_compile_cache()
    # small programs too: every eager op of the window's path is cached
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    chips = found["entry"]["chips"]
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < chips):
        print(f"chipbench: {args.workload} needs {chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    kind = devices[0].device_kind
    if require_chip and kind not in found["peaks"]:
        print(f"chipbench: no peaks for device kind {kind!r} in peaks.json",
              file=sys.stderr)
        return 1

    spans = sut.Spans()
    r = Run(found, args, spans, t_start)

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            r.compiles += 1
    monitoring.register_event_duration_secs_listener(on_event)

    try:
        res = found["kind"].run(r)
    except BaseException:
        if r.trace_dir is not None:
            shutil.rmtree(r.trace_dir, ignore_errors=True)
        raise
    after_window_s = time.perf_counter() - r.window_end

    device = {"platform": devices[0].platform, "kind": kind,
              "count": chips, "memory_peak_bytes": int(r.memory_peak)}
    out = {"correct": None, "attempted": res["attempted"],
           "failed": res["failed"], "metrics": {}, "device": device}
    if args.trace:
        view = trace.load(r.trace_dir, SPAN_NAMES)
        shutil.rmtree(r.trace_dir, ignore_errors=True)
        device["busy_s"] = view.busy_s
        device["window_s"] = view.window_s
        ctx = Reading(view, res["counts"], r, found["peaks"].get(kind, {}),
                      root / BENCH_DIR / "work")
        for m in found["per_layer"]:
            value = found["readers"][m["name"]].read(ctx)
            if value is not None:
                out["metrics"][m["name"]] = {"value": value,
                                             "unit": m["unit"]}
        out["breakdown"] = {"device_ops": view.top_ops(),
                            "idle_gaps": view.idle_by_host()}
    else:
        values = dict(res["metrics"], setup_s=r.setup_s)
        for m in found["end_to_end"]:
            out["metrics"][m["name"]] = {"value": values[m["name"]],
                                         "unit": m["unit"]}
    checks = list(res["checks"])
    out["correct"] = res["failed"] == 0 and all(v <= lim
                                                for _, v, lim in checks)
    out["compiles_in_window"] = r.compiles_in_window
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    sys.stdout.flush()
    print(f"timing: setup_s {r.setup_s:.3f}, window_s {r.window_s:.3f}, "
          f"after the window {after_window_s:.3f} s (check included)",
          file=sys.stderr)
    for n, v, lim in checks:
        print(f"check {n}: {v} (limit {lim})", file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


class Reading:
    """What a per-layer metric's reader gets: the trace of the window, the
    traffic's counts, the cell's files and the device's peaks."""

    def __init__(self, view, counts: dict, r: Run, peaks: dict,
                 work_dir: pathlib.Path):
        self.view, self.counts, self.peaks = view, counts, peaks
        self.config, self.traffic, self.serve = r.config, r.traffic, r.serve
        self.spans = r.spans
        self.work_dir = work_dir

    def work(self, layer: str):
        """The work functions of one layer: ``chipbench/work/<layer>.py``."""
        return load_module(self.work_dir / f"{layer}.py")


if __name__ == "__main__":
    sys.exit(main())
