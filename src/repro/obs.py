"""Program spans and counters: an always-on, bounded, in-process record.

Every span is kept twice:

* in this module's ring, the last ``RING_SIZE`` records of
  ``(id, parent, name, start_ns, end_ns, attrs)`` on the clock of
  ``time.perf_counter_ns()`` (the clock of the serve engine's request
  stamps and step times), read back with :func:`spans`;
* as a ``jax.profiler.TraceAnnotation`` named ``"repro." + name``, so that
  under a running profiler the span sits on the host threads of the device
  trace, on the trace's own clock.

A span costs about 2.3 µs of host time on a TPU v5e host, with the
profiler off or on.

A span's parent is the innermost span open on the same thread when it
opened.  :func:`record` writes a span whose start lies in the past (one
that crosses ``await`` points, such as a request's wait in the queue) to
the ring alone, with no parent.

Compiles are recorded too: a listener on JAX's
``/jax/core/compile/backend_compile_duration`` event (which wraps both a
backend compile and a persistent-cache load) writes each one as a
``jax.compile`` span carrying the ``fun_name`` of the compiled program,
parented to the innermost open span of the compiling thread.  A
``jax.compile`` under a steady-state span is a recompile.

The spans and counters the program opens, and what reads each, are listed
in ``PERF.md`` section 3.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Deque, Dict, List, NamedTuple, Optional

import jax

__all__ = ["RING_SIZE", "Span", "span", "record", "count", "counters", "spans",
           "reset"]

RING_SIZE = 65536

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    """One closed span; times in ``perf_counter_ns`` nanoseconds."""

    id: int
    parent: Optional[int]
    name: str
    start_ns: int
    end_ns: int
    attrs: dict

    @property
    def ms(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-6


_ring: Deque[tuple] = collections.deque(maxlen=RING_SIZE)  # Span fields
_ids = itertools.count(1)
_counters: Dict[str, int] = {}
_counters_lock = threading.Lock()
_local = threading.local()


def _open() -> List[int]:
    """The ids of the spans open on this thread, innermost last."""
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """``with span(name, **attrs) as s:`` times the enclosed host code.

    After the block, ``s.start_ns`` and ``s.end_ns`` hold its stamps and
    the ring holds its record."""

    __slots__ = ("name", "attrs", "id", "parent", "start_ns", "end_ns", "_ann",
                 "_stack")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "span":
        stack = self._stack = _open()
        self.parent = stack[-1] if stack else None
        self.id = next(_ids)
        stack.append(self.id)
        self._ann = jax.profiler.TraceAnnotation("repro." + self.name)
        self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        self._stack.pop()
        # a plain tuple: spans() makes the Span records when they are read
        _ring.append((self.id, self.parent, self.name, self.start_ns,
                      self.end_ns, self.attrs))


def record(name: str, start_ns: int, end_ns: int, **attrs) -> Span:
    """Write a span that has already ended to the ring (no annotation, no
    parent)."""
    s = Span(next(_ids), None, name, int(start_ns), int(end_ns), attrs)
    _ring.append(tuple(s))
    return s


def count(name: str, n: int = 1) -> int:
    """Add ``n`` to counter ``name``; returns its new total."""
    with _counters_lock:
        total = _counters[name] = _counters.get(name, 0) + n
    return total


def counters() -> Dict[str, int]:
    with _counters_lock:
        return dict(_counters)


def spans(name: Optional[str] = None) -> List[Span]:
    """The ring's records in the order they closed, or those of one name."""
    return [Span._make(s) for s in list(_ring) if name is None or s[2] == name]


def reset() -> None:
    """Empty the ring and the counters (for tests)."""
    _ring.clear()
    with _counters_lock:
        _counters.clear()


def _on_event_duration(event: str, duration_secs: float, **kwargs) -> None:
    if event != COMPILE_EVENT:
        return
    end = time.perf_counter_ns()
    stack = _open()
    _ring.append((next(_ids), stack[-1] if stack else None, "jax.compile",
                  end - int(duration_secs * 1e9), end,
                  {"fun_name": kwargs.get("fun_name", "")}))


jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
