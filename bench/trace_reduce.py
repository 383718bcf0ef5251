"""Profiler trace → device busy time, kernel time and host spans, on one clock.

The JAX profiler writes one ``.xplane.pb`` per traced window.  In it, each
TPU is a plane named ``/device:TPU:<n>`` whose ``XLA Ops`` line holds one
event per operation the device ran, and the host's Python threads are lines
of the ``/host:CPU`` plane, where the benchmark's own spans
(``jax.profiler.TraceAnnotation``: ``bench.gen``, ``bench.call``,
``bench.fetch``, ``bench.check``) appear by name.  The profiler puts device
and host events on the host's clock, in nanoseconds from the start of the
trace.

Definitions, per device, within the window:

* window — from the start of the first ``bench.call`` span to the end of
  the last one;
* busy — the union of the intervals of the device's op events;
* kernel — the op events of the DP forward kernel: the Mosaic custom
  calls, found by :data:`KERNEL_MARKS` in the event's name or its
  ``hlo_op``, ``long_name`` or ``tf_op`` stat; their summed durations, their
  count and the union of their intervals.  On a TPU v5e the event's name is
  the op's HLO text, and the custom call is named after the jitted wrapper
  or the enclosing call (``%dp_forward_pallas.10 = ...``,
  ``%closed_call.88 = ...``), so the reduction looks for the call's target.
  An XLA fusion that only reads the kernel's output (``%pallas_call.11``
  among its operands) is not the kernel.  The DP forward solve is the only
  Pallas kernel on the engine's path;
* idle gaps — the longest intervals of the window that no op covers
  (:data:`TOP` of them, over all devices), each named by
  the host span that overlaps it most, the innermost on a tie (``idle``
  when none does).

busy and kernel figures are averaged over the devices that ran ops.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

__all__ = ["KERNEL_MARKS", "Reduced", "find_xplane", "reduce",
           "reduce_profile"]

# what marks a Mosaic kernel launch in an op's HLO text or name stats
KERNEL_MARKS = ('custom_call_target="tpu_custom_call"',)
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
NAME_STATS = ("hlo_op", "long_name", "tf_op")
TOP = 10  # idle gaps named, longest first


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals):
    """Merge (start, end) intervals; returns the sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _length(intervals):
    return sum(e - s for s, e in intervals)


def _is_kernel(name, stats):
    if any(k in name for k in KERNEL_MARKS):
        return True
    return any(k in str(v) for key, v in stats if key in NAME_STATS
               for k in KERNEL_MARKS)


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    kernel_s: float
    kernel_busy_s: float
    kernel_count: int
    devices: int
    top_ops: list  # [(name, seconds)], longest total first
    idle_gaps: list  # the TOP longest [(host span or "idle", seconds)]

    def breakdown(self, n=TOP):
        return {"device_ops": [list(x) for x in self.top_ops[:n]],
                "idle_gaps": [list(x) for x in self.idle_gaps[:n]]}


def _host_spans(profile):
    spans = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
    return spans


def _device_ops(profile, lo, hi):
    """Per device: (op intervals, kernel intervals, {op name: ns}), each
    clipped to the window [lo, hi]."""
    out = []
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            ops, kern, names = [], [], collections.Counter()
            for ev in line.events:
                s, e = ev.start_ns, ev.end_ns
                if e <= lo or s >= hi:
                    continue
                s, e = max(s, lo), min(e, hi)
                ops.append((s, e))
                names[ev.name] += e - s
                if _is_kernel(ev.name, ev.stats):
                    kern.append((s, e))
            if ops:
                out.append((ops, kern, names))
    return out


def reduce_profile(profile) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData``."""
    spans = _host_spans(profile)
    calls = [(s, e) for name, s, e in spans if name == "bench.call"]
    if not calls:
        raise ValueError("the trace holds no bench.call span")
    lo, hi = min(s for s, _ in calls), max(e for _, e in calls)
    devices = _device_ops(profile, lo, hi)
    busy = kernel = kernel_busy = 0.0
    kernel_count = 0
    names = collections.Counter()
    gaps = []
    for ops, kern, n in devices:
        merged = _union(ops)
        busy += _length(merged)
        kernel += _length(kern)
        kernel_busy += _length(_union(kern))
        kernel_count += len(kern)
        names.update(n)
        edge = lo
        for s, e in merged + [(hi, hi)]:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
    nd = max(len(devices), 1)
    labelled = []
    gaps.sort(key=lambda g: g[0] - g[1])
    for gs, ge in gaps[:TOP]:
        best, key = "idle", (0, 0)
        for name, s, e in spans:
            k = (min(e, ge) - max(s, gs), s - e)
            if k[0] > 0 and k > key:
                best, key = name, k
        labelled.append((best, (ge - gs) / 1e9))
    labelled.sort(key=lambda x: -x[1])
    return Reduced(
        window_s=(hi - lo) / 1e9, busy_s=busy / nd / 1e9,
        kernel_s=kernel / nd / 1e9, kernel_busy_s=kernel_busy / nd / 1e9,
        kernel_count=round(kernel_count / nd), devices=len(devices),
        top_ops=[(k, v / nd / 1e9) for k, v in names.most_common()],
        idle_gaps=labelled)


def reduce(path: str) -> Reduced:
    """Reduce the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))
