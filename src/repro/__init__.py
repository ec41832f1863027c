"""repro: a deterministic-memory JAX framework reproducing the Valori paper.

x64 note: the Valori substrate is built on exact integer arithmetic with
64-bit accumulators (paper §5.1). JAX disables 64-bit types by default, which
would silently truncate our accumulators to int32 and break the overflow-
freedom argument — so we enable x64 here, before any array is created.
All model/training code keeps explicit dtypes (bf16/f32/int32) so the wider
defaults never leak into compute graphs.
"""
import os
import pathlib

import jax

jax.config.update("jax_enable_x64", True)

__version__ = "1.0.0"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing else is set. Otherwise the cache lives at
    ``<checkout>/.jax_cache``: a fixed path, because the path is part of the
    cache key, so a per-run directory would never hit. Called by the entry
    points (``chip_smoke.py``, ``launch/serve.py``, ``net/server.py``,
    ``benchmarks/run.py``), never on import. Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
