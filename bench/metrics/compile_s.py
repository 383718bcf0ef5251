"""Seconds of set-up spent tracing, lowering and compiling (or loading
compiled programs from the persistent cache), from JAX's monitoring events."""


def read(ctx):
    return ctx["compile_s"] or None
