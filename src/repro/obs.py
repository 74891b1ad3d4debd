"""The program's own spans and counters, recorded in process.

    from repro import obs

    with obs.span("nomad.sweep", seed=7) as attrs:
        ...                       # timed; ``attrs`` may gain entries here
    obs.count("nomad.tokens", 3_393_953)

    obs.spans("nomad.sweep")[-1].seconds      # the newest sweep's span
    obs.counters()["nomad.tokens"]

A span records ``(name, start, end, parent, attrs, error)`` on the host's
``time.perf_counter_ns`` clock when it closes: ``parent`` is the name of
the span open around it on the same thread, ``error`` the name of the
exception it left by (the span is kept).  Spans go to a bounded buffer of
the newest :data:`CAPACITY`, so a long run cannot grow it; counters are
running sums.  Recording is always on and costs a few microseconds a span.

Every span also opens a ``jax.profiler.TraceAnnotation`` of its name, so
inside a profiler trace it is a host event on the trace's own clock,
beside the device operations it dispatched.  The recorder's timestamps
are not on that clock: match the two by name and order, not by time.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from dataclasses import dataclass

from jax.profiler import TraceAnnotation

__all__ = ["CAPACITY", "Span", "span", "count", "spans", "counters"]

CAPACITY = 4096

_spans: collections.deque = collections.deque(maxlen=CAPACITY)
_counters: collections.Counter = collections.Counter()
_lock = threading.Lock()
_open = threading.local()


@dataclass(frozen=True)
class Span:
    name: str
    start_ns: int
    end_ns: int
    parent: str | None
    attrs: dict
    error: str | None

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


@contextlib.contextmanager
def span(name: str, **attrs):
    """Time the body as span ``name``; yields ``attrs`` for the body to
    add to.  Attribute values are kept by reference."""
    stack = _open.__dict__.setdefault("stack", [])
    parent = stack[-1] if stack else None
    stack.append(name)
    error = None
    start = time.perf_counter_ns()
    try:
        with TraceAnnotation(name):
            yield attrs
    except BaseException as e:
        error = type(e).__name__
        raise
    finally:
        end = time.perf_counter_ns()
        stack.pop()
        _spans.append(Span(name, start, end, parent, attrs, error))


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name``."""
    with _lock:
        _counters[name] += n


def spans(name: str | None = None) -> list[Span]:
    """The recorded spans, oldest first (only those named ``name``)."""
    out = list(_spans)
    return out if name is None else [s for s in out if s.name == name]


def counters() -> dict:
    """Every counter's running sum."""
    with _lock:
        return dict(_counters)
