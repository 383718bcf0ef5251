"""The comparison that decides ``correct``, and its limits.

Two numbers are compared, each against a limit of its own:

* ``mismatches`` — integer outputs that differ from the reference: every
  slot's ledger counts (arrivals, dead-lettered, blocked, dropped, shed,
  admitted, dispatched, queue length), the variant routing, the per-edge
  dispatches and the bandit counts.  The comparison is exact: limit 0.
* ``value_gap`` — the widest gap of a float output (realized welfare and
  regret per slot, dispatch share per server, bandit sums), as a share of
  the reference's value or of 1, whichever is larger.  Each of these is an
  elementwise float32 operation or a sum with at most one nonzero term
  (one device type has capacity 1, so at most one job starts per slot),
  so its rounding does not depend on where or in which order it is
  computed.  Sound runs read 0 on the chip; the bfloat16 control reads
  0.0017 and more.  The limit lies between, with room above 0 for a
  rounding that fresh seeds might show (PERF.md gives the readings).
"""
from __future__ import annotations

import numpy as np

__all__ = ["LIMITS", "Readings", "verdict"]

LIMITS = {"mismatches": 0, "value_gap": 1e-4}


class Readings:
    """Accumulates the compared numbers over every checked answer."""

    def __init__(self):
        self.mismatches = 0
        self.value_gap = 0.0
        self.compared = 0

    def ints(self, prog, ref):
        a, b = np.asarray(prog), np.asarray(ref)
        if a.shape != b.shape:
            self.mismatches += max(a.size, b.size)
        else:
            self.mismatches += int(np.count_nonzero(a != b))
        self.compared += b.size

    def floats(self, prog, ref):
        a = np.asarray(prog, np.float64)
        b = np.asarray(ref, np.float64)
        if a.shape != b.shape or not np.all(np.isfinite(a)):
            self.value_gap = float("inf")
            return
        if b.size:
            gap = np.abs(a - b) / np.maximum(np.abs(b), 1.0)
            self.value_gap = max(self.value_gap, float(gap.max()))
        self.compared += b.size

    def as_dict(self):
        return {"mismatches": self.mismatches, "value_gap": self.value_gap}


def verdict(readings: Readings) -> "tuple[bool, dict]":
    """(correct, {name: {"value": reading, "limit": limit}})."""
    got = readings.as_dict()
    checks = {k: {"value": got[k], "limit": LIMITS[k]} for k in LIMITS}
    correct = readings.compared > 0 and all(
        got[k] <= LIMITS[k] for k in LIMITS)
    return correct, checks
