"""Fixtures for the harness tests, run on the CPU at tiny sizes."""
from __future__ import annotations

import json

import pytest

from benchfix import load_run, make_tiny_root


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)


@pytest.fixture
def run_cell(tiny_root, capsys):
    """run_cell(cell, seed, seconds, trace=0, control=False) -> the parsed
    result line, from the harness's main() in this process, with the look
    for a chip skipped. JAX settings the run changes are put back."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    run = load_run(tiny_root)

    def go(cell, seed, seconds, trace=0, control=False):
        argv = ["--workload", cell, "--seed", str(seed),
                "--seconds", str(seconds), "--trace", str(trace)]
        rc = run.main(argv + (["--control"] if control else []),
                      root=tiny_root, require_chip=False)
        out = capsys.readouterr().out.strip().splitlines()
        assert rc == 0
        return json.loads(out[-1])

    yield go
    for k, v in saved.items():
        jax.config.update(k, v)
    cc.reset_cache()
