"""The serving kernels and read routes compile for a TPU v5e at real width.

Nothing here runs on a chip: the TPU compiler is asked to compile for a
described (not attached) ``v5e:2x2`` topology, which refuses what Mosaic or
XLA would refuse on the chip — an s64 ``dot``, an unsupported operand dtype,
a block that breaks the lane tiling, more VMEM than a kernel may use.
Shapes are the substrate's real ones: d=768 rows, 2^17 arena rows, a batch
of 128 queries, k=10.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and under several test
workers every worker imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro  # noqa: F401  (x64, as every caller has it)
from repro.core import codes, search
from repro.core.state import init_state
from repro.kernels.qgemm import kernel as qgemm_kernel
from repro.kernels.qgemm import ops as qgemm_ops
from repro.kernels.qtopk import kernel as qtopk_kernel

DIM = 768
ROWS = 1 << 17
NQ = 128
K = 10


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A sharding on one described v5e chip, with the persistent compile
    cache off: a TPU executable written here could never be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on_chip(sharding, tree):
    return jax.tree.map(
        lambda a: _spec(sharding, a.shape, a.dtype), tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


@pytest.mark.parametrize("b_dtype", [jnp.int32, jnp.int8],
                         ids=["qgemm", "qcoarse"])
def test_digit_kernel_compiles(one_chip, b_dtype):
    """The exact scan (int32 rows) and the coarse scan (int8 codes) share
    the digit-plane kernel; compiled, not interpreted."""
    bq, bn, bk, dp = qgemm_ops._pick_blocks(NQ, ROWS, DIM)
    fn = functools.partial(qgemm_kernel.digit_planes_pallas, block_q=bq,
                           block_n=bn, block_k=bk, interpret=False)
    text = _compile(fn, _spec(one_chip, (NQ, dp), jnp.int32),
                    _spec(one_chip, (ROWS, dp), b_dtype))
    assert "tpu_custom_call" in text


def test_qtopk_kernel_compiles(one_chip):
    fn = functools.partial(qtopk_kernel.qtopk_pallas, k=K, block_q=NQ,
                           block_n=1024, interpret=False)
    plane = _spec(one_chip, (NQ, ROWS), jnp.int32)
    text = _compile(fn, plane, plane, _spec(one_chip, (1, ROWS), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["limbs", "kernels"])
def test_exact_route_compiles(one_chip, use_kernel):
    """The default exact route (int8 digit dots in XLA) and the kernel
    route; the kernel route lowers to Mosaic, not to the interpreter."""
    state = _on_chip(one_chip, jax.eval_shape(lambda: init_state(ROWS, DIM)))
    q = _spec(one_chip, (NQ, DIM), jnp.int32)
    fn = functools.partial(search.exact_search, k=K, use_kernel=use_kernel)
    text = _compile(fn, state, q)
    assert ("tpu_custom_call" in text) == use_kernel


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["limbs", "kernels"])
def test_coarse_route_compiles(one_chip, use_kernel):
    state = _on_chip(one_chip, jax.eval_shape(lambda: init_state(ROWS, DIM)))
    table = _on_chip(one_chip, jax.eval_shape(
        codes.build, jax.eval_shape(lambda: init_state(ROWS, DIM))))
    q = _spec(one_chip, (NQ, DIM), jnp.int32)
    fn = functools.partial(search.coarse_search, k=K, ef_coarse=256,
                           use_kernel=use_kernel)
    text = _compile(fn, state, table, q)
    assert ("tpu_custom_call" in text) == use_kernel
