"""WAL: host milliseconds per durable append (encode, write, fsync), from
the spans the harness puts around ``DurableStore.append``."""


def read(ctx):
    n, s = ctx.counts.get("appends", 0), ctx.counts.get("append_s", 0.0)
    return 1e3 * s / n if n else None
