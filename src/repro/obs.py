"""Spans and counters at the program's layer boundaries.

    with obs.span("engine.dispatch"):
        out = fused(...)
    obs.count("engine.dispatch.fused")

Recording is off by default: `span` then returns one shared no-op context
(one check, no allocation, no clock read). It is on inside
`obs.recording()` and while a JAX profiler session is active
(`jax.profiler.start_trace` ... `stop_trace`). When on, a span

- enters `jax.profiler.TraceAnnotation(name)`, so it lands in the
  profiler's `.xplane.pb` on the same clock as the device ops, and
- appends `Span(name, t0_ns, t1_ns, span_id, parent_id)` to a bounded
  in-memory buffer, on `time.perf_counter_ns()`. The parent is the span
  open around it on the same thread (0 for a root span); a root span
  (`engine.run`, `partition.run`) is what the spans of one call share.

`count(name, n)` always adds to a process-wide counter; when recording is
on it also appends an `Instant(name, t_ns, n)`, so a reader can count per
call. `spans()`, `instants()` and `counters()` read the records; `clear()`
empties them. There is no exporter: the profiler's xplane is the export.
The span and counter names the program records are listed in
docs/api.md ("Profiling").
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import NamedTuple

import jax
from jax._src import profiler as _jax_profiler

# Enough for hours of benchmark jobs (each records under ten events); the
# oldest records go first.
MAX_EVENTS = 1 << 16


class Span(NamedTuple):
    name: str
    t0_ns: int
    t1_ns: int
    span_id: int
    parent_id: int


class Instant(NamedTuple):
    name: str
    t_ns: int
    n: int


_spans: collections.deque = collections.deque(maxlen=MAX_EVENTS)
_instants: collections.deque = collections.deque(maxlen=MAX_EVENTS)
_counters: collections.Counter = collections.Counter()
_lock = threading.Lock()  # guards the counters and `_forced`
_ids = itertools.count(1)
_open = threading.local()  # .stack: ids of the spans open on this thread
_forced = 0  # depth of open `recording()` blocks
_NOOP = contextlib.nullcontext()


def enabled() -> bool:
    """Whether spans and instants are being recorded. JAX 0.9 keeps its
    profiler session (None when no trace is running) in a private
    attribute; tests/test_obs.py pins it."""
    return _forced > 0 or _jax_profiler._profile_state.profile_session is not None


class _Span:
    __slots__ = ("name", "t0", "id", "parent", "annotation")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        self.parent = stack[-1] if stack else 0
        self.id = next(_ids)
        stack.append(self.id)
        self.annotation = jax.profiler.TraceAnnotation(self.name)
        self.annotation.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self.annotation.__exit__(*exc)
        _open.stack.pop()
        _spans.append(Span(self.name, self.t0, t1, self.id, self.parent))
        return False


def span(name: str):
    """A context manager that records `name` when recording is on."""
    if enabled():
        return _Span(name)
    return _NOOP


def count(name: str, n: int = 1) -> None:
    """Add `n` to the counter `name`; when recording, also log an instant."""
    with _lock:
        _counters[name] += n
    if enabled():
        _instants.append(Instant(name, time.perf_counter_ns(), n))


@contextlib.contextmanager
def recording():
    """Record spans and instants inside the block, profiler or not."""
    global _forced
    with _lock:
        _forced += 1
    try:
        yield
    finally:
        with _lock:
            _forced -= 1


def spans() -> list:
    """The recorded spans, oldest first (at most `MAX_EVENTS`)."""
    return list(_spans)


def instants() -> list:
    """The recorded instants, oldest first (at most `MAX_EVENTS`)."""
    return list(_instants)


def counters() -> collections.Counter:
    """A copy of the process-wide counters; an unseen name reads 0."""
    with _lock:
        return collections.Counter(_counters)


def clear() -> None:
    """Forget every span, instant and counter recorded so far."""
    _spans.clear()
    _instants.clear()
    with _lock:
        _counters.clear()
