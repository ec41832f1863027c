"""Op scopes of the coarse route's search program, ``jit_coarse_search``.

``search.coarse_search`` puts each of its ops under one of three scopes:
``code_scan`` (query weights, the int8 dot over the codes, norms, the
validity mask), ``select`` (the ``ef_coarse`` cut) and ``rerank`` (the
gather of the candidates' rows, their exact re-score and the merge). The
map from op to scope comes from ``compiled.as_text()`` of the program
lowered with the window's shapes and static arguments, made from the
cell's files as the window makes its calls, and is read with
``program.op_scopes`` / ``program.under``. As ``program.scopes`` does, it
compiles afresh past the caches when the cached program carries not all
three scopes, and it gives None where an op of the program in the trace
has no entry: it never guesses. A program without these scopes reads 0
under each, which the readers report as None.
"""
from __future__ import annotations

import contextlib

from harness import program

PROGRAM = "jit_coarse_search"
SCOPES = ("code_scan", "select", "rerank")


def _lower(ctx):
    """``search.coarse_search`` lowered as ``query.execute_plan`` calls it
    in the window, or None where the cell does not plan the coarse
    route."""
    import jax
    import jax.numpy as jnp
    from repro.core import boundary, codes, search
    from repro.core.state import init_state

    from harness import sut

    cfg, c = ctx.config, ctx.counts
    dim = cfg["dim"]
    plan = sut.plan(c["live"], cfg["k"], dim, ctx.serve)
    if plan.route != "coarse":
        return None
    # no sharding on the abstract arguments, as in program._lower: the
    # compile cache then holds the window's program
    state = jax.eval_shape(lambda: init_state(cfg["capacity"], dim))
    table = jax.eval_shape(codes.build, state)
    q = jax.eval_shape(boundary.admit_query, jax.ShapeDtypeStruct(
        (c["nq"], dim), jnp.float32))
    return search.coarse_search.lower(
        state, table, q, cfg["k"], ef_coarse=plan.ef_coarse,
        metric=search.METRIC_L2, use_kernel=plan.use_kernel)


def _table(ctx, fresh: bool):
    with program._no_cached_code() if fresh else contextlib.nullcontext():
        lowered = _lower(ctx)
        if lowered is None:
            return None
        return program.op_scopes(lowered.compile().as_text())


def _scoped(table) -> bool:
    return all(any(program.under(p, s) for p in table.values())
               for s in SCOPES)


def scopes(ctx):
    """{op name: ``op_name`` path} for ``jit_coarse_search``, or None
    where the window ran none of its ops or one is missing from the map."""
    ops = [o for o in ctx.view.ops if o.module == PROGRAM]
    if not ops:
        return None
    cache = vars(ctx).setdefault("_program_scopes", {})
    if PROGRAM not in cache:
        table = _table(ctx, fresh=False)
        if table is not None and not _scoped(table):
            # op_name is not part of the persistent cache's key
            table = _table(ctx, fresh=True)
        cache[PROGRAM] = table
    table = cache[PROGRAM]
    if table is None or any(o.name not in table for o in ops):
        return None
    return table


def scope_seconds(ctx, scope: str):
    """Device seconds (self time) of ``jit_coarse_search``'s ops under
    ``scope`` in the window, or None."""
    table = scopes(ctx)
    if table is None:
        return None
    return ctx.view.seconds(lambda o: o.module == PROGRAM
                            and program.under(table[o.name], scope))
