"""The serving kernels and read routes compile for a TPU v5e at real width.

Nothing here runs on a chip: the TPU compiler is asked to compile for a
described (not attached) ``v5e:2x2`` topology, which refuses what Mosaic or
XLA would refuse on the chip — an s64 ``dot``, an unsupported operand dtype,
a block that breaks the lane tiling, more VMEM than a kernel may use.
Shapes are the substrate's real ones: d=768 rows, 2^17 arena rows, a batch
of 128 queries, k=10; and the batch cell's exact route, 2^20 rows of d=128.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and under several test
workers every worker imports this file.
"""
import functools
import re

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
from repro.kernels.qtopk import ops as qtopk_ops

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


def _kernels(text):
    """Names of the Pallas kernels (``tpu_custom_call``s) in compiled HLO:
    the instruction takes the ``pallas_call``'s name."""
    return {m.group(1) for m in re.finditer(
        r"%([A-Za-z_]+)(?:\.\d+)? = .*custom_call_target=\"tpu_custom_call\"",
        text)}


def _sort_rows(text):
    """The length along the sorted dimension of every sort in compiled
    HLO, read from its first result's shape."""
    rows = []
    for line in text.splitlines():
        m = re.search(r"= \(?[a-z0-9]+\[([0-9,]*)\].* sort\(.*"
                      r"dimensions=\{([0-9]+)\}", line)
        if m:
            rows.append(int(m.group(1).split(",")[int(m.group(2))]))
    return rows


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
    key = _spec(one_chip, (1, ROWS), jnp.int32)
    text = _compile(fn, plane, plane, key, key)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["limbs", "kernels"])
def test_exact_route_compiles(one_chip, use_kernel):
    """The default exact route (int8 digit dots in XLA) and the qgemm
    route; both select blockwise through qtopk on a TPU (k <= 128 over many
    blocks), and the kernels lower to Mosaic, not to the interpreter."""
    state = _on_chip(one_chip, jax.eval_shape(lambda: init_state(ROWS, DIM)))
    q = _spec(one_chip, (NQ, DIM), jnp.int32)
    fn = functools.partial(search.exact_search, k=K, use_kernel=use_kernel)
    kernels = _kernels(_compile(fn, state, q))
    assert "qtopk" in kernels
    assert ("qgemm" in kernels) == use_kernel


def test_batch_cell_select_sorts_no_full_row(one_chip):
    """The batch cell's exact route, 128 queries over 2^20 rows of d=128,
    k=10: no sort of the compiled program is longer than the blockwise
    merge's row bound, n_blocks * 128 (the full [128, 2^20] sort is gone)."""
    rows, dim = 1 << 20, 128
    state = _on_chip(one_chip, jax.eval_shape(lambda: init_state(rows, dim)))
    q = _spec(one_chip, (NQ, dim), jnp.int32)
    text = _compile(functools.partial(search.exact_search, k=K), state, q)
    assert "qtopk" in _kernels(text)
    sorts = _sort_rows(text)
    assert sorts and max(sorts) <= (rows // qtopk_ops.BLOCK_N) * 128, sorts


def test_open_cell_select_sorts_no_full_row(one_chip):
    """The open cell's exact route, one query over 2^20 rows of d=768,
    k=10: the row block pads to 8 and still selects through qtopk, with no
    sort of the full row."""
    rows = 1 << 20
    state = _on_chip(one_chip, jax.eval_shape(lambda: init_state(rows, DIM)))
    q = _spec(one_chip, (1, DIM), jnp.int32)
    text = _compile(functools.partial(search.exact_search, k=K), state, q)
    assert "qtopk" in _kernels(text)
    sorts = _sort_rows(text)
    assert sorts and max(sorts) <= (rows // qtopk_ops.BLOCK_N) * 128, sorts


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


def test_coarse_cell_cuts_with_one_sort_of_the_full_row(one_chip):
    """The coarse cell's program, 128 queries over 2^20 rows of d=768 with
    ef_coarse 1,024: the cut takes the sort side of the select rule (k past
    128), one sort of the whole row of 2^20; the only other sort is the
    re-rank's merge of [128, 1,024]. Its ops carry the three scopes the
    benchmark reads."""
    rows = 1 << 20
    shapes = jax.eval_shape(lambda: init_state(rows, DIM))
    state = _on_chip(one_chip, shapes)
    table = _on_chip(one_chip, jax.eval_shape(codes.build, shapes))
    q = _spec(one_chip, (NQ, DIM), jnp.int32)
    fn = functools.partial(search.coarse_search, k=K, ef_coarse=1024)
    text = _compile(fn, state, table, q)
    assert sorted(_sort_rows(text)) == [1024, rows]
    for scope in ("code_scan", "select", "rerank"):
        assert re.search(
            rf'op_name="[^"]*jit\(coarse_search\)/([^"]*/)?{scope}/', text), scope
