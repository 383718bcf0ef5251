"""Jit cache misses inside the traced window (the engine's
``engine.jit_misses`` counter): each one is a trace of a program, and a
compile or a load from the persistent cache.  Set-up warms every shape, so
the window should hold none."""
from bench.program_record import record


def value(spans, counters, slots):
    return counters.get("engine.jit_misses", 0)


def read(ctx):
    rec = record()
    return None if rec is None else value(*rec, ctx["slots"])
