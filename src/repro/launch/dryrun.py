import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production mesh and extract memory/cost/collective artifacts.

The two lines above MUST precede any jax import (device count locks at init).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all --mesh both
Artifacts land in experiments/dryrun/<arch>__<shape>__<mesh>.json.
"""
import argparse
import json
import pathlib
import time
import traceback

import jax

import repro  # noqa: F401  (x64 for the memory substrate)
from repro.configs import ARCH_IDS, CANONICAL
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_cell
from repro.models.config import SHAPES
from repro.roofline import analysis as roofline

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: pathlib.Path,
             verbose: bool = True) -> dict:
    mesh_name = "multi" if multi_pod else "single"
    tag = f"{arch}__{shape_name}__{mesh_name}"
    out_path = out_dir / f"{tag}.json"
    t0 = time.time()
    record = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        chips = mesh.size
        cell = build_cell(arch, shape_name, mesh)
        if cell.skip_reason:
            record.update(status="skip", reason=cell.skip_reason)
        else:
            with jax.set_mesh(mesh):
                jitted = jax.jit(cell.step_fn, donate_argnums=cell.donate,
                                 out_shardings=cell.out_shardings)
                lowered = jitted.lower(*cell.args)
                compiled = lowered.compile()
            mem = compiled.memory_analysis()
            mem_rec = {}
            for field in ("generated_code_size_in_bytes",
                          "argument_size_in_bytes", "output_size_in_bytes",
                          "alias_size_in_bytes", "temp_size_in_bytes"):
                v = getattr(mem, field, None)
                if v is not None:
                    mem_rec[field] = int(v)
            cost = compiled.cost_analysis() or {}
            rf = roofline.analyze(compiled, chips)
            record.update(
                status="ok",
                chips=chips,
                memory_analysis=mem_rec,
                cost={k: float(v) for k, v in cost.items()
                      if isinstance(v, (int, float))},
                roofline=rf.to_dict(),
                compile_seconds=round(time.time() - t0, 1),
            )
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:])
    out_dir.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(record, indent=2))
    if verbose:
        status = record["status"]
        extra = ""
        if status == "ok":
            rl = record["roofline"]
            extra = (f" dominant={rl['dominant']}"
                     f" compute={rl['compute_s']:.2e}s"
                     f" memory={rl['memory_s']:.2e}s"
                     f" coll={rl['collective_s']:.2e}s"
                     f" compile={record['compile_seconds']}s")
        elif status == "error":
            extra = " " + record["error"][:200]
        print(f"[dryrun] {tag}: {status}{extra}", flush=True)
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id (canonical or module name) or 'all'")
    ap.add_argument("--shape", default="all", help="shape name or 'all'")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default=str(OUT_DIR))
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else [
        CANONICAL.get(args.arch, args.arch.replace("-", "_").replace(".", "_"))
    ]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    out_dir = pathlib.Path(args.out)

    failures = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                rec = run_cell(arch, shape, multi, out_dir)
                failures += rec["status"] == "error"
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
