"""Bulk apply's host plan: milliseconds per batch of the program's
``bulk_apply.plan`` span (fetching the log, the host allocator mirror,
segmenting)."""
from harness import program


def read(ctx):
    ms = program.span_ms(ctx, "bulk_apply.plan")
    n = ctx.counts.get("batches", 0)
    return ms / n if ms and n else None
