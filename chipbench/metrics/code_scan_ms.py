"""Coarse route's code scan: device milliseconds per request of
``jit_coarse_search``'s ops under the program's ``code_scan`` scope (query
weights, the int8 dot over the code table, norms, the validity mask)."""
from harness import coarse_program


def read(ctx):
    s = coarse_program.scope_seconds(ctx, "code_scan")
    n = ctx.counts.get("requests", 0)
    return 1e3 * s / n if s and n else None
