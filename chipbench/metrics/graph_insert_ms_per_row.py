"""HNSW insert loop: device milliseconds per row applied of the insert
program's ops under the program's ``hnsw_insert`` scope (the ``lax.scan``
over a batch's slots)."""
from harness import program


def read(ctx):
    s = program.scope_seconds(ctx, program.APPLY, "hnsw_insert")
    n = ctx.counts.get("rows", 0)
    return 1e3 * s / n if s and n else None
