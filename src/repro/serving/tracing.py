"""Program spans and the compile listener of the serving hot path.

``span(name, **ids)`` marks one layer boundary of the scheduler or engine
(docs/ARCHITECTURE.md, "Tracing" lists the tree).  While tracing is on, a
span is written twice:

- into the JAX profiler's host plane, as a ``jax.profiler.TraceAnnotation``
  whose arguments are the ``ids``; that plane is on the device trace's
  clock, so a trace reduction can set each span against the device's
  operations with no clock offset to fit;
- into an in-memory list, read with :func:`records`, as
  ``(name, parent, t0_ns, t1_ns, thread, ids)``; ``parent`` is the name of
  the enclosing program span on the same thread (``None`` at the top).

Tracing is a process-wide switch, :func:`enable`.  By default it follows
the JAX profiler: spans are on exactly while a profiler session records
host events (``jax.profiler.start_trace`` … ``stop_trace``).  ``enable(True)``
or ``enable(False)`` overrides that.  Off, :func:`span` returns one shared
no-op context manager: it allocates nothing, formats nothing and touches
no device array.  On, it reads no device array either; ``ids`` are host
values the caller already holds.

Program span names never start with ``bench.``: that prefix belongs to
the benchmark's own spans around the program.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Optional

import jax

__all__ = ["CompileLog", "current", "enable", "is_on", "records", "clear",
           "span"]

# The newest spans kept in memory: about eight minutes of a decode loop
# at 55 ticks per second and ten spans per tick.
MAX_RECORDS = 1 << 18

_profiling = jax.profiler.TraceAnnotation.is_enabled  # a profiler records?
_forced: Optional[bool] = None  # None: follow the profiler
_records: collections.deque = collections.deque(maxlen=MAX_RECORDS)
_local = threading.local()


def enable(on: Optional[bool] = True) -> None:
    """Turn program tracing on (``True``), off (``False``), or back to
    following the JAX profiler (``None``, the default)."""
    global _forced
    _forced = on


def is_on() -> bool:
    """Whether a span opened now would be recorded."""
    return _forced if _forced is not None else _profiling()


def records() -> list:
    """The spans recorded so far, oldest first, each
    ``(name, parent, t0_ns, t1_ns, thread, ids)``; times are
    ``time.perf_counter_ns``."""
    return list(_records)


def clear() -> None:
    """Forget every recorded span."""
    _records.clear()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def current() -> Optional[str]:
    """The innermost program span open on this thread, or ``None``."""
    stack = getattr(_local, "stack", None)
    return stack[-1].name if stack else None


def _arg(v):
    """A span id as a profiler argument: numbers as they are, sequences as
    space-separated integers (the profiler's argument list is
    comma-separated, so a value holds no comma)."""
    if isinstance(v, (int, float, str)):
        return v
    return " ".join(str(int(x)) for x in v)


class _Off:
    """The span while tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Span:
    __slots__ = ("name", "ids", "parent", "t0", "_note")

    def __init__(self, name: str, ids: dict):
        self.name, self.ids = name, ids

    def __enter__(self):
        stack = _stack()
        self.parent = stack[-1].name if stack else None
        stack.append(self)
        self._note = jax.profiler.TraceAnnotation(
            self.name, **{k: _arg(v) for k, v in self.ids.items()})
        self._note.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._note.__exit__(*exc)
        _local.stack.pop()
        _records.append((self.name, self.parent, self.t0, t1,
                         threading.current_thread().name, self.ids))
        return False


def span(name: str, **ids):
    """A context manager marking one program step; ``ids`` name what it
    works on (``rids`` of requests, ``lanes``, a bucket ``shape``) or give
    a counter's reading at the span's start."""
    if is_on():
        return _Span(name, ids)
    return _OFF


class CompileLog:
    """Backend compiles and persistent-cache hits and misses, from JAX's
    monitoring events.  Each backend compile is charged to the innermost
    program span open on the compiling thread (``None`` outside every
    span, or with tracing off), so ``by_span`` says which step compiled."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        self.by_span: collections.Counter = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1
            self.by_span[current()] += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def line(self) -> str:
        where = ", ".join(f"{n or 'outside spans'} {k}"
                          for n, k in self.by_span.most_common())
        return (f"{self.compiles} backend compiles, {self.seconds:.1f}s"
                + (f" ({where})" if where else "")
                + f"; persistent cache {self.hits} hits, {self.misses} misses")
