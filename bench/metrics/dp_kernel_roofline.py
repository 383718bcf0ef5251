"""The DP forward kernel's share of its roofline, counted from the work.

The least bytes a forward solve must move, whatever its tiling: the (S, C)
int32 value plane read once and written once, the (E, C) feasibility read
once, and one decision bit per (edge, budget, state), all unpadded.  The
kernel does a few integer operations per byte, so HBM bandwidth bounds it;
the published peaks give no vector-unit rate to set against.
"""
import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"


def bytes_min(S, C, E):
    return 4 * (2 * S * C + E * C) + E * S * C / 8


def read(ctx):
    tr = ctx["trace"]
    if not tr.kernel_count or not ctx["slots"]:
        return None
    devices = json.loads(PEAKS.read_text())["devices"]
    kind = ctx["device_kind"]
    if kind not in devices:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"{PEAKS}")
    sz = ctx["sizes"]
    least_s = bytes_min(sz["S"], sz["C"], sz["E"]) / devices[kind][
        "hbm_bytes_per_s"]
    per_solve_s = tr.kernel_s / ctx["slots"]  # one solve per slot
    return 100.0 * least_s / per_solve_s
