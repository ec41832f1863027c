"""Fetches from the device per batch: the program's ``host_syncs`` count
(every ``obs.to_host``) inside the window over the batches."""
from harness import program


def read(ctx):
    syncs = program.counted(ctx, "host_syncs")
    n = ctx.counts.get("batches", 0)
    return syncs / n if syncs and n else None
