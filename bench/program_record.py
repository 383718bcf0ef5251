"""The program's own span record and counters, for the readers of the
``program_span`` metrics (``bench/metrics/host_*.py``, ``h2d_bytes.py``,
``jit_misses.py``).

``repro.sched.telemetry`` keeps them in the benchmark's process while the
profiler traces the window.  A program without that module, or a window
that recorded nothing, gives ``None``, and the reader then reports nothing.
"""
from __future__ import annotations


def record():
    """``(spans, counters)`` of the traced window, or ``None``.

    Each span is ``(name, start_ns, end_ns, parent, id)``; ``parent`` is the
    enclosing span's name and ``id`` the engine's call number."""
    try:
        from repro.sched import telemetry
    except ImportError:
        return None
    spans, counts = telemetry.records(), telemetry.counters()
    if not spans and not counts:
        return None
    return spans, counts
