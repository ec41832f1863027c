"""Spans and counters of the program, for operators and the chip benchmark.

* ``span(name)``: a host span. It always enters
  ``jax.profiler.TraceAnnotation(name)``, so a profiler trace shows it next
  to the device ops. While a profiler session collects
  (``TraceAnnotation.is_enabled()``), it also keeps a :class:`Record` in
  memory, timed by ``time.perf_counter_ns``. A span opened with no
  recorded span around it starts a new request id; the spans inside it
  carry that id and their parent's index in :func:`records`. No setting
  turns recording on: it runs exactly while someone takes a trace.
* ``count(name, n)``: integer counters, always on, kept by the innermost
  open span (``""`` outside any). While a profiler session collects, each
  count is also a record (start == end, ``n`` > 0), so a reader can count
  inside a time window.
* ``to_host(x)``: ``np.asarray(x)`` counted as ``host_syncs``. Every fetch
  from the device on the hot path goes through it.
* ``compiles``: a count made by a ``jax.monitoring`` listener on every
  backend compile (or load of one from the persistent compile cache),
  kept by the span that was open when it compiled.

Span names are dotted (``bulk_apply.plan``, ``wal.fsync``); counter names
are not (``host_syncs``, ``compiles``). Importing this module claims no
device.
"""
from __future__ import annotations

import contextvars
import itertools
import threading
import time
from typing import Dict, List, NamedTuple

import jax
import numpy as np

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Record(NamedTuple):
    """A span (``n`` == 0), or a count made while a trace collected
    (``n`` > 0, ``start_ns`` == ``end_ns``). Times are
    ``time.perf_counter_ns``; a span still open has ``end_ns`` == -1."""
    name: str
    start_ns: int
    end_ns: int
    parent: int    # index in records() of the enclosing span, -1 at a root
    request: int   # shared by a root span and everything recorded inside it
    n: int = 0


_lock = threading.Lock()
_records: List[Record] = []
_counts: Dict[tuple, int] = {}           # (counter, span) -> n
_requests = itertools.count()
# the open spans of this thread or task: ((name, record index, request), ...)
# with index and request -1 where the span was opened before recording began
_open: contextvars.ContextVar = contextvars.ContextVar("repro_obs_open",
                                                       default=())
_collecting = jax.profiler.TraceAnnotation.is_enabled
_clock = time.perf_counter_ns


def _reserve(name: str, t: int, end: int, n: int, top) -> tuple:
    """Append a record under the innermost open span; returns (the list
    it went to, its index, its request)."""
    if top is not None and top[1] >= 0:
        parent, request = top[1], top[2]
    else:
        parent, request = -1, next(_requests)
    with _lock:
        _records.append(Record(name, t, end, parent, request, n))
        return _records, len(_records) - 1, request


class span:
    """``with span("bulk_apply.plan"): ...``: see the module docstring."""

    __slots__ = ("name", "_ann", "_token", "_kept", "_index", "_start")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "span":
        stack = _open.get()
        kept, index, request = None, -1, -1
        if _collecting():
            kept, index, request = _reserve(self.name, 0, -1, 0,
                                            stack[-1] if stack else None)
        self._kept, self._index = kept, index
        self._token = _open.set(stack + ((self.name, index, request),))
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._start = _clock()
        return self

    def __exit__(self, *exc) -> None:
        end = _clock()
        self._ann.__exit__(*exc)
        _open.reset(self._token)
        # fill in the record where it went, though a reset() since may
        # have dropped that list
        if self._kept is not None:
            with _lock:
                self._kept[self._index] = self._kept[self._index]._replace(
                    start_ns=self._start, end_ns=end)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` under the innermost open span."""
    stack = _open.get()
    key = (name, stack[-1][0] if stack else "")
    with _lock:
        _counts[key] = _counts.get(key, 0) + n
    if n and _collecting():
        t = _clock()
        _reserve(name, t, t, n, stack[-1] if stack else None)


def to_host(x) -> np.ndarray:
    """``np.asarray(x)``: a fetch from the device, counted as
    ``host_syncs``."""
    out = np.asarray(x)
    count("host_syncs")
    return out


def records() -> List[Record]:
    """Every record kept so far, in the order the spans opened."""
    with _lock:
        return list(_records)


def counters() -> Dict[str, Dict[str, int]]:
    """``{counter: {span: n}}``, the span being the innermost one open at
    each count (``""`` for none)."""
    out: Dict[str, Dict[str, int]] = {}
    with _lock:
        for (name, where), n in _counts.items():
            out.setdefault(name, {})[where] = n
    return out


def reset() -> None:
    """Forget every record and counter."""
    global _records
    with _lock:
        _records = []
        _counts.clear()


def _on_duration(event: str, duration_secs: float, **_) -> None:
    if event == COMPILE_EVENT:
        count("compiles")


jax.monitoring.register_event_duration_secs_listener(_on_duration)
