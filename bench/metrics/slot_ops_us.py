"""Device busy time outside the DP forward kernel per slot: the engine's
admission, statistics, backtrack, packing, accounting and regret oracle.

Every slot launches the kernel, so a trace in which no kernel event was
found is a trace this reader cannot split: it returns nothing rather than
count the kernel's time as the pipeline's."""


def read(ctx):
    tr = ctx["trace"]
    if not ctx["slots"] or tr.busy_s <= 0 or not tr.kernel_count:
        return None
    return (tr.busy_s - tr.kernel_busy_s) * 1e6 / ctx["slots"]
