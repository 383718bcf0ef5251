"""Program-owned spans and counters of the dispatch engine.

``span(name, id)`` marks a host phase.  While a profile is being taken it
enters ``jax.profiler.TraceAnnotation(name)``, so the phase lies on the
profiler's clock beside the device's op events, and it appends
``Span(name, start_ns, end_ns, parent, id)`` to an in-memory record.
``count(name, n)`` adds to a counter.  Both act only while a JAX profiler
session is active (``jax.profiler.trace`` / ``start_trace``): with none, a
span or a counter costs one check, and the record holds exactly the
profiled window.

``records()`` and ``counters()`` return what was kept; ``reset()`` clears
both.  The record keeps at most :data:`MAX_SPANS` spans and counts any it
had to drop under ``telemetry.dropped``.

One ``jax.monitoring`` listener, installed on import, counts each jit cache
miss (a jaxpr trace, whether the executable then comes from the persistent
cache or from the compiler) under ``engine.jit_misses`` while recording.
"""
from __future__ import annotations

import threading
import time
from typing import NamedTuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation
from jaxlib import _profiler

__all__ = ["MAX_SPANS", "Span", "count", "counters", "host_bytes",
           "recording", "records", "reset", "span"]

MAX_SPANS = 65_536
JIT_MISS_EVENT = "/jax/core/compile/jaxpr_trace_duration"

# True while a JAX profiler session is active
recording = _profiler.TraceMe.is_enabled


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: "str | None"  # name of the enclosing span, None at the root
    id: "int | None"  # the engine's call sequence number


_lock = threading.Lock()
_spans: "list[Span]" = []
_counters: "dict[str, int]" = {}
_open = threading.local()  # per thread: names of the spans entered


class span:
    """Context manager: one host phase, annotated and, while recording,
    kept."""

    __slots__ = ("name", "id", "_ann", "_parent", "_start")

    def __init__(self, name: str, id: "int | None" = None):
        self.name, self.id = name, id
        self._ann = None

    def __enter__(self):
        if recording():
            stack = getattr(_open, "stack", None)
            if stack is None:
                stack = _open.stack = []
            self._parent = stack[-1] if stack else None
            stack.append(self.name)
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
            self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        if self._ann is None:
            return False
        end = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        self._ann = None
        _open.stack.pop()
        rec = Span(self.name, self._start, end, self._parent, self.id)
        with _lock:
            if len(_spans) < MAX_SPANS:
                _spans.append(rec)
            else:
                _counters["telemetry.dropped"] = (
                    _counters.get("telemetry.dropped", 0) + 1)
        return False


def count(name: str, n: int) -> None:
    """Add ``n`` to counter ``name`` while recording."""
    if recording():
        with _lock:
            _counters[name] = _counters.get(name, 0) + int(n)


def host_bytes(tree) -> int:
    """Bytes of the host (numpy) arrays and scalars among ``tree``'s
    leaves: what handing ``tree`` to a jitted call copies to the device."""
    return sum(a.nbytes for a in jax.tree_util.tree_leaves(tree)
               if isinstance(a, (np.ndarray, np.generic)))


def records() -> "list[Span]":
    """The spans kept so far, in the order they closed."""
    with _lock:
        return list(_spans)


def counters() -> "dict[str, int]":
    with _lock:
        return dict(_counters)


def reset() -> None:
    with _lock:
        _spans.clear()
        _counters.clear()


def _on_duration(event: str, duration: float, **_) -> None:
    if event == JIT_MISS_EVENT:
        count("engine.jit_misses", 1)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
