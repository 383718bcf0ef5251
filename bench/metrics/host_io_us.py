"""Host time per slot of the engine's own runs outside the wait for the
device: each ``engine.run`` span less its ``engine.wait`` child, that is
the input puts, the launch and the read-back with the output's assembly."""
from bench.program_record import record


def value(spans, counters, slots):
    runs = {i: e - s for name, s, e, _, i in spans if name == "engine.run"}
    if not runs or not slots:
        return None
    waits = {i: e - s for name, s, e, parent, i in spans
             if name == "engine.wait" and parent == "engine.run"}
    host_ns = sum(d - waits.get(i, 0) for i, d in runs.items())
    return host_ns / 1e3 / slots


def read(ctx):
    rec = record()
    return None if rec is None else value(*rec, ctx["slots"])
