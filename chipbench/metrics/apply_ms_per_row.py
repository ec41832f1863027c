"""Bulk apply: device milliseconds of the apply programs (slot scatter and
HNSW insert loop) per row applied in the traced window."""
from harness import ops


def read(ctx):
    s = ctx.view.seconds(ops.in_apply)
    n = ctx.counts.get("rows", 0)
    return 1e3 * s / n if s > 0 and n else None
