"""Exact scan by scope: device milliseconds per request of the search
program's ops under the program's ``scan`` scope (``search.score_block``
and the tombstone score mask)."""
from harness import program


def read(ctx):
    s = program.scope_seconds(ctx, program.SEARCH, "scan")
    n = ctx.counts.get("requests", 0)
    return 1e3 * s / n if s and n else None
