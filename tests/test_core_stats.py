"""Property tests for the evolving statistics (paper eqs. 7–15)."""
import jax.numpy as jnp
import numpy as np
import pytest

try:  # optional [test] extra — property tests skip cleanly without it
    from hypothesis import example, given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

from repro.core.dp import build_tables
from repro.core.stats import (DELTA_VARIANTS, G_VARIANTS, horizon_for_s_cap,
                              s_cap_for_horizon, scale_statistics,
                              sigma2_bound, xi_of)
from repro.kernels.budgeted_dp.ops import (VALUE_BOUND, check_value_bound,
                                           max_achievable_value)


if HAS_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(st.integers(1, 100_000), st.integers(1, 64))
    def test_xi_monotone_and_scale(t, m):
        """ξ(t) = ⌈m/δ(t)⌉ is ≥ m and non-decreasing in t (δ decreasing)."""
        x1 = int(xi_of(jnp.float32(t), m))
        x2 = int(xi_of(jnp.float32(t + 50), m))
        assert x1 >= m
        assert x2 >= x1

    @settings(max_examples=25, deadline=None)
    @given(st.integers(2, 10_000), st.integers(1, 40),
           st.integers(0, 2**31 - 1))
    @example(36, 40, 39)  # (m+1)·Σ̂² = 41 · 52,927,925 passes 2^31
    def test_scaled_statistics_int32_bounds(t, m, seed):
        """Υ̂ and Σ̂² stay inside int32, and a DP sum that would reach
        |NEG| is refused by the construction guard (stats.py claim)."""
        rng = np.random.default_rng(seed)
        E = int(rng.integers(1, 64))
        vhat = jnp.asarray(rng.uniform(0, 1, E), jnp.float32)
        n = jnp.asarray(rng.integers(0, 1000, E), jnp.int32)
        ups, sig, xi, s_limit = scale_statistics(vhat, n, jnp.float32(t), m)
        ups, sig = np.asarray(ups), np.asarray(sig, np.int64)
        assert np.all(ups >= 0) and np.all(ups <= int(xi))
        assert np.all(sig > 0)
        # the dominance invariant: one unexplored beats any m explored channels
        explored = sig[np.asarray(n) > 0]
        unexplored = sig[np.asarray(n) == 0]
        if explored.size and unexplored.size:
            assert unexplored.min() > m * explored.max() * 0.99
        # every Σ̂² of a slot t' ≤ t lies within the horizon's bound, which
        # is exact (no int32 wrap) ...
        bound = sigma2_bound(t, m)
        assert int(sig.max()) <= bound < 2**31
        # ... and a DP sum of m+1 such values runs only below |NEG| = 2^29:
        # the construction guard raises for a deployment whose largest
        # selectable set (one device type of capacity m+1, one device per
        # job) reaches it
        tables = build_tables(np.ones((1, m + 1), np.int64),
                              np.array([m + 1]))
        sig_T = np.full(m + 1, bound, np.int64)
        assert max_achievable_value(sig_T, tables) == (m + 1) * bound
        if (m + 1) * bound < VALUE_BOUND:
            check_value_bound(sig_T, tables)
        else:
            with pytest.raises(ValueError, match="2\\^29"):
                check_value_bound(sig_T, tables)
else:
    def test_hypothesis_extra_missing():
        pytest.importorskip(
            "hypothesis",
            reason="property tests need the [test] extra (pip install .[test])")


def test_s_cap_covers_horizon():
    for name, d in DELTA_VARIANTS.items():
        cap = s_cap_for_horizon(2000, 16, d)
        for t in (1, 500, 2000):
            assert int(xi_of(jnp.float32(t), 16, d)) * 16 <= cap, (name, t)


def test_g_variants_ordering():
    """default g dominates ln-t g for m > 1 (the over-exploration source)."""
    t = jnp.float32(1000.0)
    assert float(G_VARIANTS["default"](t, 16)) > float(
        G_VARIANTS["logt_only"](t, 16))


def test_horizon_for_s_cap_inverts_s_cap_for_horizon():
    """The inverse sizing helper: when a horizon within t_max reaches the
    requested budget axis, the returned T does so minimally (T−1 does
    not); unreachable combinations — ξ grows only logarithmically, so
    s_cap ≫ m² needs astronomic horizons under the slow δ schedules —
    yield None instead of overflowing.  This is what ties the long-S
    benchmark configs (S = 4096/8192) back to concrete sampling
    horizons (large-m instances)."""
    for name, d in DELTA_VARIANTS.items():
        for m in (8, 16, 36):
            for s_cap in (64, 1024, 4096):
                T = horizon_for_s_cap(s_cap, m, d)
                if T is None:
                    # genuinely unreachable within t_max
                    assert s_cap_for_horizon(10 ** 12, m, d) < s_cap, \
                        (name, m, s_cap)
                    continue
                assert s_cap_for_horizon(T, m, d) >= s_cap, (name, m, s_cap)
                if T > 1:
                    assert s_cap_for_horizon(T - 1, m, d) < s_cap, \
                        (name, m, s_cap)
    # the S = 4096 benchmark regime is reachable for paper-scale m
    assert horizon_for_s_cap(4096, 36) is not None


def test_horizon_for_s_cap_exact_above_f32_range():
    """Regression (f32 precision): ``_xi_at_horizon`` used to evaluate
    ``delta_fn(jnp.float32(T))`` — exact only for T < 2²⁴.  Above that the
    float32 grid quantizes T (spacing 512 near 3·10⁹, ≈2¹⁷ near 10¹²), so
    ``horizon_for_s_cap`` landed on a float32 grid edge instead of the true
    integer threshold (≈2·10⁴ slots off at the horizon pinned here).  The
    pure-``math`` float64 oracle below reproduces the sizing map
    independently and pins the exact minimal horizon."""
    import math
    m = 16

    def delta_host(t):  # the paper default, float64
        return 1.0 / (math.log(math.log(t + 1.0) + 1.0) + 1.0)

    def cap(T):
        return math.ceil(m / delta_host(float(T))) * m

    s_cap = cap(10 ** 10)
    lo, hi = 1, 10 ** 12
    assert cap(lo) < s_cap <= cap(hi)
    while lo + 1 < hi:  # exact bisection, pure math
        mid = (lo + hi) // 2
        if cap(mid) < s_cap:
            lo = mid
        else:
            hi = mid
    t_star = hi
    assert t_star > 2 ** 24  # the regime f32 mangled
    assert horizon_for_s_cap(s_cap, m) == t_star
    assert s_cap_for_horizon(t_star, m) >= s_cap
    assert s_cap_for_horizon(t_star - 1, m) < s_cap


def test_horizon_for_s_cap_t_max_window():
    """Regression: thresholds between the last power-of-two probe and
    t_max must still be found (the doubling loop clamps its final probe
    to t_max instead of bailing past it)."""
    def delta(t):
        return 1.0 / jnp.sqrt(t)  # s_cap grows fast enough

    m, s_cap = 4, 72
    T = horizon_for_s_cap(s_cap, m, delta)  # unbounded-ish search
    assert T is not None and s_cap_for_horizon(T, m, delta) >= s_cap
    # t_max sits between 2^k and the threshold: must still resolve
    got = horizon_for_s_cap(s_cap, m, delta, t_max=T + 1)
    assert got == T
    # and a t_max just below the threshold is genuinely unreachable
    assert horizon_for_s_cap(s_cap, m, delta, t_max=T - 1) is None
