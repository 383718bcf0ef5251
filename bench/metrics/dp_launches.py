"""Pallas launches of the DP forward kernel per slot of the traced window:
the trace's kernel count (``bench/trace_reduce.py``) over the slots.  1 on
the whole plane, one per chunk of ``block_e`` edges on the fused grids, one
per edge on the per-edge scan."""


def read(ctx):
    tr = ctx["trace"]
    if not tr.kernel_count or not ctx["slots"]:
        return None
    return tr.kernel_count / ctx["slots"]
