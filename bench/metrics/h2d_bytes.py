"""Bytes the engine copied from the host to the device per slot: its
``engine.h2d_bytes`` counter (explicit input puts, and host arrays handed
to the stream program)."""
from bench.program_record import record


def value(spans, counters, slots):
    if "engine.h2d_bytes" not in counters or not slots:
        return None
    return counters["engine.h2d_bytes"] / slots


def read(ctx):
    rec = record()
    return None if rec is None else value(*rec, ctx["slots"])
