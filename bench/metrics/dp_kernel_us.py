"""Device time of the DP forward kernel per slot of the traced window."""


def read(ctx):
    tr = ctx["trace"]
    if not tr.kernel_count or not ctx["slots"]:
        return None
    return tr.kernel_s * 1e6 / ctx["slots"]
