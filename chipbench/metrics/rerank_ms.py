"""Coarse route's exact re-rank: device milliseconds per request of
``jit_coarse_search``'s ops under the program's ``rerank`` scope (the
gather of [nq, ef_coarse, d] rows, their exact re-score and the merge)."""
from harness import coarse_program


def read(ctx):
    s = coarse_program.scope_seconds(ctx, "rerank")
    n = ctx.counts.get("requests", 0)
    return 1e3 * s / n if s and n else None
