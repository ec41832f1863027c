"""Coarse route's candidate cut: device milliseconds per request of
``jit_coarse_search``'s ops under the program's ``select`` scope
(``search.topk_by_score`` of the ``ef_coarse`` best by approx score)."""
from harness import coarse_program


def read(ctx):
    s = coarse_program.scope_seconds(ctx, "select")
    n = ctx.counts.get("requests", 0)
    return 1e3 * s / n if s and n else None
