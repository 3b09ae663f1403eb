"""Program spans and counters, kept while a profiler trace is being taken.

An operator takes a trace with ``jax.profiler.trace`` (or ``start_trace``
... ``stop_trace``). While one runs:

* ``span(name, **ids)`` opens a ``jax.profiler.TraceAnnotation`` named
  ``easter.<name>``, so the span lands in the trace on the device events'
  clock, and keeps a ``Span`` record in this process (``perf_counter``
  nanoseconds; the parent is the innermost span open on the same thread);
* ``count(name, n)`` adds to a counter;
* ``interval(name, start_s, end_s, **ids)`` keeps a record of an interval
  timed on another clock, such as a request's life on a scheduler's clock;
* each program JAX obtains for a call made while a recorded span is open
  (compiled on the backend, or loaded from the persistent compilation
  cache) counts once as ``compiles.<innermost open span>``, which names
  the code that missed JAX's in-memory cache.

``take()`` hands the records and counters over and clears them. With no
trace running, ``span`` returns one shared no-op context and ``count`` and
``interval`` return at once: each costs one check of the profiler's flag.
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

from jax.profiler import TraceAnnotation

class Span(NamedTuple):
    """One recorded span or interval: nanoseconds of ``perf_counter`` for a
    span, of the caller's clock for an interval."""
    name: str
    id: int
    parent_id: Optional[int]
    start_ns: int
    end_ns: int
    ids: dict


_tracing = TraceAnnotation.is_enabled     # true only while a trace runs
_NOOP = contextlib.nullcontext()
_spans: List[Span] = []
_counters: Dict[str, float] = {}
_lock = threading.Lock()
_next_id = itertools.count(1)
_local = threading.local()
_listening = False


def _open() -> list:
    """This thread's stack of open recorded spans."""
    if not hasattr(_local, "stack"):
        _local.stack = []
    return _local.stack


def _parent() -> Optional[int]:
    stack = _open()
    return stack[-1].id if stack else None


def _keep(record: Span):
    with _lock:
        _spans.append(record)


class _Recorded:
    __slots__ = ("name", "ids", "id", "parent_id", "start_ns", "_ann")

    def __init__(self, name: str, ids: dict):
        self.name, self.ids = name, ids

    def __enter__(self):
        _listen()
        self.id, self.parent_id = next(_next_id), _parent()
        self._ann = TraceAnnotation("easter." + self.name, **self.ids)
        self._ann.__enter__()
        _open().append(self)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        _open().pop()
        self._ann.__exit__(*exc)
        _keep(Span(self.name, self.id, self.parent_id, self.start_ns, end,
                   self.ids))
        return False


def span(name: str, **ids):
    """A context manager around one stretch of host work; ``ids`` (lane,
    nonce, sizes) go into its record and onto its event in the trace."""
    if not _tracing():
        return _NOOP
    return _Recorded(name, ids)


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    if _tracing():
        with _lock:
            _counters[name] = _counters.get(name, 0) + n


def interval(name: str, start_s: float, end_s: float, **ids) -> None:
    """Record ``[start_s, end_s]``, seconds on the caller's own clock."""
    if _tracing():
        _keep(Span(name, next(_next_id), _parent(), round(start_s * 1e9),
                   round(end_s * 1e9), ids))


def take() -> Tuple[List[Span], Dict[str, float]]:
    """The records and counters kept so far; both are cleared."""
    global _spans, _counters
    with _lock:
        spans, counters = _spans, _counters
        _spans, _counters = [], {}
    return spans, counters


def _compiled(event: str, *_, **__):
    """JAX times each program it obtains for a call under this event,
    whether the backend compiled it or the persistent cache held it: one
    count a program (a compile request is made only with the cache on,
    and before the cache is read)."""
    stack = getattr(_local, "stack", None)
    if stack and event.endswith("backend_compile_duration"):
        count("compiles." + stack[-1].name)


def _listen():
    """Registers the compile listener, once, with the first record."""
    global _listening
    if _listening:
        return
    import jax.monitoring
    with _lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_compiled)
            _listening = True
