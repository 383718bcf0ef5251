"""Mean host time of one launch of the engine's stream program: the
program's ``engine.launch`` span, which holds the implicit copy of the
call's host arrays to the device and the dispatch."""
from bench.program_record import record


def value(spans, counters, slots):
    launches = [e - s for name, s, e, _, _ in spans if name == "engine.launch"]
    if not launches:
        return None
    return sum(launches) / len(launches) / 1e3


def read(ctx):
    rec = record()
    return None if rec is None else value(*rec, ctx["slots"])
