"""Pallas TPU kernels for the substrate's compute hot-spots.

Each kernel ships a kernel.py (pl.pallas_call + BlockSpec VMEM tiling), an
ops.py (jit'd public wrapper: padding, bounds, int64 combine) and a ref.py
(the pure-jnp test oracle), and is validated BITWISE against its oracle
across shape sweeps — integer kernels admit no tolerance.

  qgemm     — exact integer scoring matmul: int8 digit planes on the MXU,
              accumulated in int32 and combined in int64 outside the kernel
              (a TPU has no native int64 and no int32 matmul). It serves
              the exact scan (int32 rows) and the compressed tier's coarse
              scan (int32 query weights x int8 codes, 1/4 the bytes
              streamed)
  qtopk     — deterministic k smallest (score, key) per row, streaming a
              running k best over int32 planes: the exact select on a
              TPU (``core.search.topk_by_score``)
  qboundary — fused float→Q-encode→integer-L2-normalize (the paper's §5.3
              determinism boundary); on no serving path, kept with its
              interpret-mode tests

``on_platform`` runs the serving kernels compiled by Mosaic wherever the
computation is lowered for a TPU and in interpret mode elsewhere (exact
semantics on the CPU), so no caller chooses a mode.
"""
import functools

import jax


def on_platform(kernel_call, *args):
    """``kernel_call(*args, interpret=...)``: compiled on a TPU, interpreted
    on any other platform. The choice follows the platform the computation
    is lowered for — where its arrays live — not a flag or a default."""
    return jax.lax.platform_dependent(
        *args,
        tpu=functools.partial(kernel_call, interpret=False),
        default=functools.partial(kernel_call, interpret=True))
