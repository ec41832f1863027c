"""The chip entry points, rehearsed on the CPU.

``chip_smoke.py --rehearse`` drives every one-chip phase at tiny sizes, and
importing the package must not claim a device: a chip belongs to one
process, and a module that created an array at import would take it.
"""
import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src")] + [p for p in [env.get("PYTHONPATH")] if p])
    return env


def test_import_initializes_no_backend():
    code = (
        "import repro.core, repro.serve.engine, repro.kernels.qgemm.ops, "
        "repro.kernels.qtopk.ops, "
        "repro.net.server, repro.launch.serve\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized(), 'backend claimed'\n"
        "print('no backend')\n")
    out = subprocess.run([sys.executable, "-c", code], env=_env(),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "no backend" in out.stdout


def test_chip_smoke_rehearsal_runs_every_phase():
    out = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py"), "--rehearse"],
        env=_env(), capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    for phase in ("engine", "substrate", "platforms"):
        assert any(l.startswith(f"[{phase}] done: ") for l in lines), phase
    checks = [l for l in lines if " check " in l]
    assert len(checks) >= 15, checks
    assert not any(l.endswith(": FAIL") or ": FAIL " in l for l in checks)
    for want in ("coarse == exact", "kernel route == limb route",
                 "state_hash chip == cpu", "retrieval_hash",
                 "replay_log_fresh() == state_hash()"):
        assert any(want in l for l in checks), want
    last = json.loads(lines[-1])
    assert last["ok"] is True and last["rehearse"] is True
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}


def test_chip_smoke_refuses_without_a_tpu():
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=_env(), capture_output=True, text=True,
                         timeout=300, cwd=ROOT)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr
