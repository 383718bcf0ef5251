"""Parametric service rates for the dispatcher.

Mean service rate of (arch × shape) on a slice = tokens/s of a
compute-bound step at 40% MFU on a v5e chip (197 TFLOP/s bf16): 6·N (train)
or 2·N (prefill/decode) FLOPs per token for an arch with N active
parameters, scaled by the slice's relative capability.  Rates depend only
on the committed tables below — never on files a run happens to find on
disk — so the instance a seed builds is reproducible from a checkout.

This closes the loop promised in DESIGN.md §2: the unknown service rates the
paper learns are the measured-systems quantity, fluctuated by multi-tenancy
noise and straggler degradation (sched/dispatcher.py).
"""
from __future__ import annotations

import numpy as np

from ..configs import SHAPES

__all__ = ["roofline_rate", "rate_matrix"]

_ACTIVE_B = {  # active parameters (billions) per arch
    "qwen2.5-32b": 32.8, "gemma3-27b": 27.0, "gemma-7b": 8.5,
    "qwen1.5-32b": 35.2, "zamba2-7b": 5.7, "dbrx-132b": 36.0,
    "deepseek-v3-671b": 37.0, "whisper-medium": 0.79,
    "mamba2-2.7b": 2.8, "qwen2-vl-72b": 72.7,
}


def roofline_rate(arch: str, shape_name: str) -> float:
    """Normalized tokens/s per chip for the single-pod mesh."""
    if arch not in _ACTIVE_B:
        raise ValueError(f"unknown arch {arch!r}; known: {sorted(_ACTIVE_B)}")
    shape = SHAPES[shape_name]
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    n_active = _ACTIVE_B[arch] * 1e9
    factor = 6.0 if shape.kind == "train" else 2.0
    step_s = factor * n_active * tokens / (0.4 * 197e12 * 256)
    return tokens / max(step_s, 1e-9) / 256.0


def rate_matrix(jobs, slices, slice_speed: dict | None = None) -> np.ndarray:
    """mean_rates[l, r] for build_instance; slice_speed scales per slice
    (heterogeneous fleets / chronic stragglers)."""
    out = np.zeros((len(jobs), len(slices)), np.float32)
    for li, job in enumerate(jobs):
        base = roofline_rate(job.arch, job.shape)
        for r, sl in enumerate(slices):
            speed = (slice_speed or {}).get(sl.name, 1.0)
            out[li, r] = base * speed * sl.chips
    return out
