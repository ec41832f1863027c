"""HLO walker validation: must match XLA cost_analysis on loop-free modules
and correctly multiply loop bodies by trip count."""
import jax
import jax.numpy as jnp
import numpy as np

import repro  # noqa: F401
from repro.roofline.analysis import Roofline
from repro.roofline.hlo_walk import HloModule, walk_hlo


def _compiled(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _cost(compiled):
    return compiled.cost_analysis() or {}


def test_matches_cost_analysis_single_matmul():
    x = jnp.zeros((256, 512), jnp.float32)
    w = jnp.zeros((512, 128), jnp.float32)
    c = _compiled(lambda a, b: a @ b, x, w)
    t = walk_hlo(c.as_text())
    ca = _cost(c)
    assert t.flops == ca["flops"] == 2 * 256 * 512 * 128
    assert t.bytes == ca["bytes accessed"]


def test_scan_multiplies_trip_count():
    x = jnp.zeros((128, 128), jnp.float32)
    ws = jnp.zeros((7, 128, 128), jnp.float32)

    def scanned(x, ws):
        def step(h, w):
            return h @ w, None
        return jax.lax.scan(step, x, ws)[0]

    c = _compiled(scanned, x, ws)
    t = walk_hlo(c.as_text())
    per_step = 2 * 128 ** 3
    assert abs(t.flops - 7 * per_step) / (7 * per_step) < 0.05


def test_nested_scan_multiplies():
    x = jnp.zeros((64, 64), jnp.float32)
    ws = jnp.zeros((3, 4, 64, 64), jnp.float32)

    def nested(x, ws):
        def outer(h, wgroup):
            def inner(h, w):
                return h @ w, None
            h, _ = jax.lax.scan(inner, h, wgroup)
            return h, None
        return jax.lax.scan(outer, x, ws)[0]

    c = _compiled(nested, x, ws)
    t = walk_hlo(c.as_text())
    per_step = 2 * 64 ** 3
    assert abs(t.flops - 12 * per_step) / (12 * per_step) < 0.05


def test_elementwise_flops_counted():
    x = jnp.zeros((1024,), jnp.float32)
    c = _compiled(lambda a: jnp.tanh(a) + a * 2.0, x)
    t = walk_hlo(c.as_text())
    assert 2 * 1024 <= t.flops <= 4 * 1024


def test_dominant_term_logic():
    r = Roofline(flops=1e15, hbm_bytes=1e9, wire_bytes=1e9, chips=256,
                 collectives={})
    assert r.dominant == "compute"
    r = Roofline(flops=1e12, hbm_bytes=1e14, wire_bytes=0, chips=256,
                 collectives={})
    assert r.dominant == "memory"
    r = Roofline(flops=1e12, hbm_bytes=1e9, wire_bytes=1e13, chips=256,
                 collectives={})
    assert r.dominant == "collective"


def test_bytes_min_leq_bytes():
    x = jnp.zeros((256, 256), jnp.float32)

    def f(a):
        h = jnp.tanh(a @ a)
        return jnp.sum(h * 3.0)

    t = walk_hlo(_compiled(f, x).as_text())
    assert 0 < t.bytes_min <= t.bytes
