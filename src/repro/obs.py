"""Program spans and counters: where an entry call spends its host time, and
what JAX traced and compiled and the garbage collector swept meanwhile.

``span(name)`` marks one stage of an entry call on the host.  It shows in
any profile as the annotation ``repro.<name>`` and is kept, with its ids, in
a bounded in-memory ring that :func:`spans` reads.  Spans opened inside
another on the same thread are its children and share its root id, so every
span of one entry call carries that call's id.  Times are
``time.time_ns()``, the profiler's own clock.

Counters are kept where the work happens, from listeners this module
installs once, on import:

* ``traces``: functions traced to a jaxpr (nested jits each count);
* ``compiles``, ``compile_s``: backend compiles, a persistent-cache load
  included, and their seconds;
* ``cache_hits``, ``cache_misses``: persistent compilation cache lookups
  (only where the cache is enabled);
* ``gc_collections``, ``gc_s``: Python garbage collections and their seconds.

Each trace, compile and collection is also a span (``trace``, ``compile``,
``gc``), the child of whatever span was open on its thread.  A trace or
compile span ends when JAX reports it and starts its duration earlier.
There is no switch: a span costs a few microseconds, and there are a
handful in a solve.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import itertools
import threading
import time
from typing import NamedTuple

import jax

PREFIX = "repro."
RING = 65536


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    span_id: int
    parent_id: int | None       # None for a root span
    root_id: int                # the entry call's span (its own for a root)


_ring: collections.deque[Span] = collections.deque(maxlen=RING)
_ids = itertools.count(1)
_local = threading.local()
# reentrant: a collection's callback may run while this thread holds it
_lock = threading.RLock()
_counters: dict[str, float] = {
    "traces": 0, "compiles": 0, "compile_s": 0.0, "cache_hits": 0,
    "cache_misses": 0, "gc_collections": 0, "gc_s": 0.0}

_DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": ("traces", None, "trace"),
    "/jax/core/compile/backend_compile_duration":
        ("compiles", "compile_s", "compile"),
}
_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
           "/jax/compilation_cache/cache_misses": "cache_misses"}


def _open() -> list[tuple[int, int]]:
    """This thread's open spans, innermost last, as (span id, root id)."""
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _count(deltas: dict[str, float]) -> None:
    with _lock:
        for k, v in deltas.items():
            _counters[k] += v


def _closed(name: str, start_ns: int, end_ns: int) -> None:
    """Record a finished span under the innermost span open here."""
    stack = _open()
    sid = next(_ids)
    parent, root = stack[-1] if stack else (None, sid)
    _ring.append(Span(name, start_ns, end_ns, sid, parent, root))


@contextlib.contextmanager
def span(name: str):
    """Time the body as span ``name`` (profile annotation ``repro.<name>``)."""
    stack = _open()
    sid = next(_ids)
    parent, root = stack[-1] if stack else (None, sid)
    stack.append((sid, root))
    start = time.time_ns()
    try:
        with jax.profiler.TraceAnnotation(PREFIX + name):
            yield
    finally:
        end = time.time_ns()
        stack.pop()
        _ring.append(Span(name, start, end, sid, parent, root))


def snapshot() -> dict[str, float]:
    """The counters as they stand."""
    with _lock:
        return dict(_counters)


def spans(since_ns: int | None = None) -> list[Span]:
    """The recorded spans, in the order they ended; with ``since_ns``, only
    those that ended at or after it.  The ring keeps the newest 65536."""
    out = list(_ring)
    if since_ns is None:
        return out
    return [s for s in out if s.end_ns >= since_ns]


def _on_duration(event: str, secs: float, **_) -> None:
    hit = _DURATION_EVENTS.get(event)
    if hit is None:
        return
    count, total, name = hit
    _count({count: 1, total: secs} if total else {count: 1})
    end = time.time_ns()
    _closed(name, end - int(secs * 1e9), end)


def _on_event(event: str, **_) -> None:
    count = _EVENTS.get(event)
    if count is not None:
        _count({count: 1})


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        note = jax.profiler.TraceAnnotation(PREFIX + "gc")
        note.__enter__()
        _local.gc = (time.time_ns(), note)
        return
    opened = getattr(_local, "gc", None)
    if opened is None:                  # installed mid-collection
        return
    _local.gc = None
    start, note = opened
    end = time.time_ns()
    note.__exit__(None, None, None)
    _count({"gc_collections": 1, "gc_s": (end - start) * 1e-9})
    _closed("gc", start, end)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
gc.callbacks.append(_on_gc)
