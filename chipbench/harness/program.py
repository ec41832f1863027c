"""The program's own spans and scopes, for the per-layer readers.

* ``spans(ctx)``: the span records the program keeps (``repro.obs``) while
  a profiler session collects, moved onto the trace's clock. The offset is
  the median, over the harness's host spans, of (start in the trace) -
  (start in the harness's own record): the last N records of each name
  match the N spans of that name in the trace, in order. None with fewer
  than ``MIN_MATCHES`` matches, when the matched offsets spread (Q3 - Q1)
  by more than ``MAX_SPREAD_NS``, when the program keeps no records (it
  predates them), or when the trace holds no device op: the spans are read
  against the device's timeline, and a trace without one has nothing to
  place them on.
* ``counted(ctx, name)``: what the program counted under ``name`` inside
  the window, from the same records.
* ``scopes(ctx, program)``: {op name: ``op_name`` path} for one program of
  the window, from ``compiled.as_text()`` of the same function lowered with
  the window's shapes and static arguments, made from the cell's files as
  ``sut.py`` makes the calls. The window compiled the same program, so
  JAX's caches hold it and nothing compiles again, unless the cached
  program carries none of the program's scopes (see ``scopes``). None
  where an op of that program in the trace has no entry: it never
  guesses.
"""
from __future__ import annotations

import contextlib
import re
import statistics

MIN_MATCHES = 3
MAX_SPREAD_NS = 50_000
SEARCH = "jit_exact_search"
APPLY = "jit__apply_insert_segment"
SCOPES = {SEARCH: ("scan", "select"), APPLY: ("slot_scatter", "hnsw_insert")}

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def offset_ns(ctx):
    """(median offset, spread) from record to trace clock, in ns, or None
    with fewer than ``MIN_MATCHES`` matched harness spans."""
    in_trace, kept = {}, {}
    for name, start, _ in ctx.view.spans:
        in_trace.setdefault(name, []).append(start)
    for name, t0, _ in ctx.spans.records:
        kept.setdefault(name, []).append(t0)
    offsets = []
    for name, starts in in_trace.items():
        mine = kept.get(name, [])
        if len(mine) < len(starts):
            continue
        mine = mine[len(mine) - len(starts):]
        offsets += [s - 1e9 * t for s, t in zip(sorted(starts), mine)]
    if len(offsets) < MIN_MATCHES:
        return None
    q1, _, q3 = statistics.quantiles(offsets, n=4)
    return statistics.median(offsets), q3 - q1


def _aligned(ctx):
    """The program's records inside the window, on the trace's clock."""
    try:
        from repro import obs
    except ImportError:
        return None
    found = offset_ns(ctx) if ctx.view.ops else None
    if found is None or found[1] > MAX_SPREAD_NS:
        return None
    off, _ = found
    w0, w1 = ctx.view.window_ns
    out = []
    for r in obs.records():
        if r.end_ns < 0:
            continue
        r = r._replace(start_ns=r.start_ns + off, end_ns=r.end_ns + off)
        if w0 <= r.start_ns and r.end_ns <= w1:
            out.append(r)
    return out


def spans(ctx):
    """The program's spans inside the window, on the trace's clock, or
    None (see the module docstring)."""
    recs = _aligned(ctx)
    return None if recs is None else [r for r in recs if r.n == 0]


def span_ms(ctx, name: str):
    """Host milliseconds of the program's spans called ``name`` in the
    window, or None."""
    found = spans(ctx)
    if found is None:
        return None
    return sum(r.end_ns - r.start_ns for r in found if r.name == name) / 1e6


def counted(ctx, name: str):
    """What the program counted under ``name`` inside the window, or
    None."""
    recs = _aligned(ctx)
    return None if recs is None else sum(r.n for r in recs
                                         if r.n and r.name == name)


def op_scopes(hlo_text: str) -> dict:
    """{instruction name: its ``op_name`` metadata ("" without one)} of
    every instruction of a compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            path = _OP_NAME.search(line)
            out[m.group(1)] = path.group(1) if path else ""
    return out


def under(path: str, scope: str) -> bool:
    """Whether an ``op_name`` path lies under a named scope: the scope is
    one of its parts, the last part (the primitive) aside."""
    return scope in path.split("/")[:-1]


@contextlib.contextmanager
def _no_cached_code():
    """JAX's in-memory caches emptied and its persistent cache off."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.clear_caches()
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()    # else the cache stays in use as first decided
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _compiled_text(ctx, program: str, fresh: bool = False):
    """The compiled text of ``program`` at the window's shapes; with
    ``fresh``, compiled anew past every cache of compiled code."""
    with _no_cached_code() if fresh else contextlib.nullcontext():
        lowered = _lower(ctx, program)
        return None if lowered is None else lowered.compile().as_text()


def _lower(ctx, program: str):
    """``program`` lowered with the window's shapes and static arguments,
    or None for a program this module does not map."""
    import jax
    import jax.numpy as jnp
    from repro.core import boundary, commands, machine, search
    from repro.core.state import init_state

    from harness import sut

    cfg, c = ctx.config, ctx.counts
    dim = cfg["dim"]
    # no sharding on the abstract arguments: the window's arrays are
    # uncommitted, and a sharding would change the compile options and so
    # miss the compile cache
    state = jax.eval_shape(lambda: init_state(cfg["capacity"], dim))
    if program == SEARCH:
        plan = sut.plan(c["live"], cfg["k"], dim, ctx.serve)
        if plan.route != "exact":
            return None
        q = jax.eval_shape(boundary.admit_query, jax.ShapeDtypeStruct(
            (c["nq"], dim), jnp.float32))
        return search.exact_search.lower(
            state, q, cfg["k"], metric=search.METRIC_L2,
            use_kernel=plan.use_kernel)   # as query.execute_plan calls it
    if program == APPLY:
        b = ctx.traffic["batch"]

        def log():
            raw = boundary.normalize_embedding(jnp.zeros((b, dim),
                                                         jnp.float32))
            batch = commands.insert_batch(jnp.zeros((b,), jnp.int64), raw)
            return machine._pad_log(batch, machine._pow2(b))

        return machine._apply_insert_segment.lower(
            state, jax.eval_shape(log), jax.ShapeDtypeStruct((), jnp.int32),
            ef_construction=cfg["ef_construction"])
    return None


def _table(text):
    return None if text is None else op_scopes(text)


def scopes(ctx, program: str):
    """{op name: ``op_name`` path} for ``program`` ("jit_exact_search" or
    "jit__apply_insert_segment"), or None where the window ran none of
    its ops or one of them is missing from the map."""
    ops = [o for o in ctx.view.ops if o.module == program]
    if not ops:
        return None
    cache = vars(ctx).setdefault("_program_scopes", {})
    if program not in cache:
        table = _table(_compiled_text(ctx, program))
        if table is not None and not any(
                under(p, s) for p in table.values() for s in SCOPES[program]):
            # op_name is not part of the persistent cache's key: a program
            # cached by a checkout without these scopes reads without them
            table = _table(_compiled_text(ctx, program, fresh=True))
        cache[program] = table
    table = cache[program]
    if table is None or any(o.name not in table for o in ops):
        return None
    return table


def scope_seconds(ctx, program: str, scope: str):
    """Device seconds (self time) of ``program``'s ops under ``scope`` in
    the window, or None."""
    table = scopes(ctx, program)
    if table is None:
        return None
    return ctx.view.seconds(lambda o: o.module == program
                            and under(table[o.name], scope))
