"""Top-k select: device milliseconds of the selection ops per request."""
from harness import ops


def read(ctx):
    s = ctx.view.seconds(ops.is_select)
    n = ctx.counts.get("requests", 0)
    return 1e3 * s / n if s > 0 and n else None
