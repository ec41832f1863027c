"""Sharded deterministic memory: the paper's kernel at pod scale.

Builds a (model, data) mesh from the devices JAX finds — up to 4 shards
on the model axis, the rest of the devices on the data axis — shards the
arena over it, and proves the distributed kernel returns results
bit-identical to the single-device kernel: integer collectives make
sharding invisible.

Run: PYTHONPATH=src python examples/distributed_memory.py
On a CPU-only host it asks XLA for 8 virtual CPU devices (a flag the CPU
backend alone reads), so run it in a fresh interpreter; on a 4-chip TPU
host the mesh is the 4 chips.
"""
import os

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import numpy as np  # noqa: E402

import repro  # noqa: F401,E402
from repro.core import boundary, commands, distributed, machine, search  # noqa: E402
from repro.core.state import init_state  # noqa: E402

n_dev = len(jax.devices())
shards = max(m for m in (4, 2, 1) if n_dev % m == 0)
mesh = jax.make_mesh((shards, n_dev // shards), ("model", "data"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)

D, N, K = 32, 512, 7
rng = np.random.default_rng(0)
vecs = boundary.normalize_embedding(rng.normal(size=(N, D)).astype(np.float32))
ids = np.arange(N, dtype=np.int64) * 13 + 5
log = commands.insert_batch(jax.numpy.asarray(ids), vecs)

# reference: single kernel
ref_state = machine.replay(init_state(1024, D), log)
queries = boundary.admit_query(rng.normal(size=(16, D)).astype(np.float32))
ref_ids, ref_scores = search.exact_search(ref_state, queries, K)

# distributed: shards on the model axis, queries on data
routed = distributed.route_commands(log, shards)
state = distributed.init_sharded_state(mesh, "model", 1024 // shards, D)
state = distributed.distributed_replay(mesh, "model", state, routed)
d_ids, d_scores = distributed.distributed_search(
    mesh, "model", state, queries, K, query_axis="data")

assert (np.asarray(d_ids) == np.asarray(ref_ids)).all()
assert (np.asarray(d_scores) == np.asarray(ref_scores)).all()
print(f"sharded({shards}x over {mesh.devices.size} "
      f"{jax.devices()[0].platform} devices) == single kernel, bit-for-bit, "
      f"for {N} vectors / {queries.shape[0]} queries ✓")
print("first query neighbors:", np.asarray(d_ids)[0].tolist())
