"""WAL fsync: host milliseconds per batch of the program's ``wal.fsync``
spans (each ``os.fsync`` on the append path)."""
from harness import program


def read(ctx):
    ms = program.span_ms(ctx, "wal.fsync")
    n = ctx.counts.get("batches", 0)
    return ms / n if ms and n else None
