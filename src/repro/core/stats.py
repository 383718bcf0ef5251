"""Evolving statistics of ESDP (paper eqs. 7–15).

All schedules take a (possibly traced) time ``t`` (1-based) and return jnp
scalars, so the whole simulation can live inside one ``lax.scan``.

Integer-domain bounds (why int32 is exact here):
  Υ̂_e = ⌈ξ v̂_e⌉ ≤ ξ                      (v̂ ∈ [0,1])
  Σ̂²_e = ⌈ξ² g/(2n)⌉ ≤ ⌈ξ² g/2⌉          (n ≥ 1)
  With the default schedules at T = 10⁵: ξ ≲ 60·m and g ≲ 200, so
  Σ̂² ≲ 2.1e5·m² and the UNEXPLORED bonus (m+1)·⌈ξ²g/2⌉ with DP sums over
  ‖x‖₁ ≤ Σ_k c_k stays far below 2³¹ for every configuration we run.
  The DP therefore uses exact int32 arithmetic (no float accumulation error),
  which is also the natural datatype for the TPU VPU — see kernels/budgeted_dp.
"""
from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp

__all__ = [
    "delta_default", "delta_fast", "delta_slow",
    "g_default", "g_no_logt", "g_logt_only",
    "xi_of", "s_cap_for_horizon", "u_max_for_horizon",
    "horizon_for_s_cap", "scale_statistics",
    "DELTA_VARIANTS", "G_VARIANTS",
]

# --------------------------------------------------------------------------
# δ(t) — converge-to-zero relaxation sequence (paper eq. 11 & Fig. 7 variants)
# --------------------------------------------------------------------------

def delta_fast(t):
    """(ln(t+1)+1)^-1 — fastest decay."""
    return 1.0 / (jnp.log(t + 1.0) + 1.0)


def delta_default(t):
    """(ln(ln(t+1)+1)+1)^-1 — the paper's default."""
    return 1.0 / (jnp.log(jnp.log(t + 1.0) + 1.0) + 1.0)


def delta_slow(t):
    """(ln(ln(ln(t+1)+1)+1)+1)^-1 — slowest decay."""
    return 1.0 / (jnp.log(jnp.log(jnp.log(t + 1.0) + 1.0) + 1.0) + 1.0)


DELTA_VARIANTS: dict[str, Callable] = {
    "fast": delta_fast, "default": delta_default, "slow": delta_slow,
}


# Host-side float64 mirrors of the registered schedules.  The sizing
# helpers below evaluate δ at STATIC horizons up to t_max = 10¹², far past
# the f32-exact integer range (2²⁴): jnp.float32(T) collapses ≈ 2¹⁷-wide
# plateaus of horizons onto one value there, which made
# ``horizon_for_s_cap`` return a plateau edge instead of the true
# threshold.  Pure ``math`` keeps the host path exact (f64) and jax-free.

def _delta_fast_host(t: float) -> float:
    return 1.0 / (math.log(t + 1.0) + 1.0)


def _delta_default_host(t: float) -> float:
    return 1.0 / (math.log(math.log(t + 1.0) + 1.0) + 1.0)


def _delta_slow_host(t: float) -> float:
    return 1.0 / (math.log(math.log(math.log(t + 1.0) + 1.0) + 1.0) + 1.0)


_DELTA_HOST: dict[Callable, Callable[[float], float]] = {
    delta_fast: _delta_fast_host,
    delta_default: _delta_default_host,
    delta_slow: _delta_slow_host,
}

# --------------------------------------------------------------------------
# g(t) — exploration scale (paper eq. 10 & Fig. 8 variants); m = ⌈α|E|⌉
# --------------------------------------------------------------------------

def g_default(t, m):
    """ln(t+1) + 4 ln(ln(t+1)+1)·m — the paper's default experimental g."""
    return jnp.log(t + 1.0) + 4.0 * jnp.log(jnp.log(t + 1.0) + 1.0) * m


def g_no_logt(t, m):
    """4 ln(ln(t+1)+1)·m."""
    return 4.0 * jnp.log(jnp.log(t + 1.0) + 1.0) * m


def g_logt_only(t, m):
    """ln(t+1) — the variant the paper found 'overwhelmingly' best (Fig. 8)."""
    return jnp.log(t + 1.0)


G_VARIANTS: dict[str, Callable] = {
    "default": g_default, "no_logt": g_no_logt, "logt_only": g_logt_only,
}

# --------------------------------------------------------------------------
# ξ(t) and scaled statistics (paper eqs. 13–15)
# --------------------------------------------------------------------------

def xi_of(t, m, delta_fn=delta_default):
    """ξ(t) = ⌈m / δ(t)⌉ (paper eq. 15)."""
    return jnp.ceil(m / delta_fn(t)).astype(jnp.int32)


def _delta_at_host(T: int, delta_fn=delta_default) -> float:
    """δ(T) evaluated host-side in float64.

    Registered schedules use their pure-``math`` mirrors; custom schedules
    are evaluated under ``jax.enable_x64(True)`` so a python-int
    horizon survives intact (``jnp.float32(T)`` is exact only below 2²⁴ —
    the old f32 path made the T ↦ ξ(T) map constant across ≈ 2¹⁷-wide
    plateaus near t_max and mislocated every threshold inside one)."""
    host = _DELTA_HOST.get(delta_fn)
    if host is not None:
        return host(float(T))
    with jax.enable_x64(True):
        return float(delta_fn(jnp.float64(T)))


def _xi_at_horizon(T: int, m: int, delta_fn=delta_default) -> int:
    """ξ(T) as a host-side static int — the max of ξ(t) over t ≤ T (δ
    decreasing ⇒ ξ increasing ⇒ maximum at t = T).  Evaluated in float64
    (see :func:`_delta_at_host`) so horizons above 2²⁴ stay exact."""
    return int(math.ceil(m / _delta_at_host(T, delta_fn)))


def s_cap_for_horizon(T: int, m: int, delta_fn=delta_default) -> int:
    """Static bound on max_t ξ(t)·m over a horizon."""
    return _xi_at_horizon(T, m, delta_fn) * int(m)


def u_max_for_horizon(T: int, m: int, delta_fn=delta_default) -> int:
    """Static bound on max_{t,e} Υ̂_e(t) + 1 over a horizon.

    Υ̂_e = ⌈ξ(t)·v̂_e⌉ ≤ ξ(t) ≤ ξ(T) because v̂ ∈ [0,1] (env clips z̃).  The +1
    keeps the kernel's shift-padding contract with margin.  This is the
    tight shift-scratch height for the Pallas budgeted-DP kernel: ξ(T)+1
    rows instead of the always-safe s_cap+1 = ξ(T)·m+1 — an m-fold
    reduction of the pad at default horizons.
    """
    return _xi_at_horizon(T, m, delta_fn) + 1


def horizon_for_s_cap(
    s_cap: int, m: int, delta_fn=delta_default, t_max: int = 10 ** 12
) -> "int | None":
    """Smallest horizon T ≤ ``t_max`` whose budget axis reaches ``s_cap``
    (inverse of :func:`s_cap_for_horizon`, which is nondecreasing in T
    because δ decays).  Sizing helper for the S-tiled DP pipeline: it
    answers "what sampling horizon does an S = s_cap + 1 value plane
    correspond to?" — e.g. the S = 4096/8192 benchmark configs.

    Returns ``None`` when even ``t_max`` does not reach ``s_cap``: because
    ξ grows only logarithmically, a given S is reachable at sane horizons
    only for large-enough m (s_cap ≈ ξ(T)·m ≳ m²), and the log-log default
    δ would otherwise push the doubling search past f32 range.  Returns 1
    if T = 1 already reaches ``s_cap``; doubling + bisection, O(log T)
    host calls.
    """
    if s_cap_for_horizon(1, m, delta_fn) >= s_cap:
        return 1
    lo, hi = 1, 2
    while s_cap_for_horizon(hi, m, delta_fn) < s_cap:
        if hi >= t_max:
            return None  # even t_max itself falls short
        lo, hi = hi, min(hi * 2, t_max)
    while lo + 1 < hi:
        mid = (lo + hi) // 2
        if s_cap_for_horizon(mid, m, delta_fn) < s_cap:
            lo = mid
        else:
            hi = mid
    return hi


def scale_statistics(vhat, n, t, m, g_fn=g_default, delta_fn=delta_default):
    """Compute (Υ̂, Σ̂², ξ, s_limit) at time t — eqs. (13)–(15).

    Unexplored channels (n=0) get a finite *dominance* bonus
    ``UNEXP = (m+1)·⌈ξ²g/2⌉`` instead of the paper's +∞: any feasible set
    containing an unexplored channel then strictly beats any set without one
    (the DP objective is a sum of ≤ m terms each ≤ ⌈ξ²g/2⌉), preserving the
    forced-exploration semantics in exact int32 (DESIGN.md §4).
    """
    xi = xi_of(t, m, delta_fn)
    g = g_fn(t, m)
    xif = xi.astype(jnp.float32)
    upsilon = jnp.ceil(xif * vhat).astype(jnp.int32)
    max_explored = jnp.ceil(xif * xif * g / 2.0).astype(jnp.int32)
    sigma2_explored = jnp.ceil(
        xif * xif * g / (2.0 * jnp.maximum(n, 1).astype(jnp.float32))
    ).astype(jnp.int32)
    unexp = (m + 1) * max_explored
    sigma2 = jnp.where(n > 0, sigma2_explored, unexp)
    s_limit = xi * m
    return upsilon, sigma2, xi, s_limit
