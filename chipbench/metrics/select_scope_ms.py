"""Top-k select by scope: device milliseconds per request of the search
program's ops under the program's ``select`` scope (the tombstone id mask
and ``search.topk_by_score`` or its qtopk kernel)."""
from harness import program


def read(ctx):
    s = program.scope_seconds(ctx, program.SEARCH, "select")
    n = ctx.counts.get("requests", 0)
    return 1e3 * s / n if s and n else None
