"""Differential-testing harness for the pluggable Algorithm-2 backends.

The only trustworthy spec for a hand-written kernel against an exact-integer
DP is agreement with an oracle: brute-force enumeration over all 2^E subsets
(the ground truth for P4/eq. 17) and the pure-JAX reference DP.  Property
tests (hypothesis, optional [test] extra) generate random small instances
(E ≤ 12, K ≤ 3) and require *bit-exact* agreement on x, s*, and the value
row across backends, random ``allowed`` masks, ``u_max`` edge cases, and
``s_limit < s_cap`` — plus end-to-end trace invariance through ``simulate``,
``simulate_batch``, and a fig6-style ``SweepSpec``.

The fleet-batched section extends the same contract to B solves per launch:
``solve_budgeted_dp_batched`` and ``jax.vmap`` of the pallas backend (which
dispatches through the custom batching rule) must match a per-instance loop
over the reference backend bit for bit — heterogeneous Υ̂/Σ̂²/allowed/s_limit
across the fleet, ragged batches, random (block_b, block_e, block_s,
block_c) tilings, and the degenerate B=1 fleet against the single-instance
kernel.
"""
import dataclasses
import itertools

import numpy as np
import pytest

try:  # optional [test] extra — property tests skip cleanly without it
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

import jax
import jax.numpy as jnp

from repro.core import (build_tables, generate_instance, make_esdp_policy,
                        simulate, simulate_batch)
from repro.core import stats as stats_mod
from repro.core.baselines import hswf_factory
from repro.core.dp import NEG, oracle_knapsack, solve_budgeted_dp
from repro.core.esdp import esdp_factory
from repro.core.solvers import (SOLVER_ENV_VAR, get_solver, resolve_solver)
from repro.experiments import GridPoint, SweepSpec, get_scenario, run_spec
from repro.kernels.budgeted_dp.kernel import resolve_interpret
from repro.kernels.budgeted_dp.ops import (VALUE_BOUND, max_achievable_value,
                                           prepare_tables,
                                           solve_budgeted_dp_batched,
                                           solve_budgeted_dp_pallas)

REF = get_solver("reference")
PAL = get_solver("pallas_interpret")


# ---------------------------------------------------------------------------
# oracles
# ---------------------------------------------------------------------------

def enumerate_value_row(upsilon, sigma2, A, c, s_cap, allowed=None):
    """Ground-truth {P4(s)}_s: exhaustive max Σ̂²ᵀx over all 2^E subsets with
    Ax ≤ c and Υ̂ᵀx ≥ s, for every s — NEG where no subset reaches budget s."""
    E = len(upsilon)
    bits = ((np.arange(2 ** E)[:, None] >> np.arange(E)[None, :]) & 1
            ).astype(np.int64)
    if allowed is not None:
        bits = bits[(bits <= np.asarray(allowed, np.int64)).all(axis=1)]
    bits = bits[(bits @ np.asarray(A, np.int64).T <=
                 np.asarray(c, np.int64)).all(axis=1)]
    u = bits @ np.asarray(upsilon, np.int64)
    v = bits @ np.asarray(sigma2, np.int64)
    row = np.full(s_cap + 1, int(NEG), np.int64)
    for uu, vv in zip(u, v):  # subset covers every s ≤ Υ̂ᵀx
        hi = min(int(uu), s_cap)
        row[:hi + 1] = np.maximum(row[:hi + 1], vv)
    return row.astype(np.int32)


def eq17_star(row, s_limit):
    """The eq.-17 selection on a value row: argmax_s s + sqrt(P4(s))."""
    s_vals = np.arange(row.shape[0])
    score = s_vals + np.sqrt(np.maximum(row, 0).astype(np.float64))
    score = np.where((row >= 0) & (s_vals <= s_limit), score, -np.inf)
    return int(np.argmax(score))


def _rand_problem(rng, E, K, c_hi=3, u_hi=5, sig_hi=5000):
    A = rng.integers(1, 3, size=(K, E))
    c = rng.integers(1, c_hi + 1, size=K)
    A = np.minimum(A, c[:, None])
    upsilon = rng.integers(0, u_hi + 1, size=E).astype(np.int32)
    sigma2 = rng.integers(1, sig_hi + 1, size=E).astype(np.int32)
    return A, c, upsilon, sigma2


def _solve_with(solver, upsilon, sigma2, tables, s_cap, s_limit, allowed=None):
    x, info = solver(jnp.asarray(upsilon, jnp.int32),
                     jnp.asarray(sigma2, jnp.int32), tables, s_cap,
                     jnp.int32(s_limit),
                     None if allowed is None else jnp.asarray(allowed))
    return (np.asarray(x), int(info["s_star"]),
            np.asarray(info["value_row"]))


# ---------------------------------------------------------------------------
# (a) reference DP vs brute-force enumeration, for every s
# ---------------------------------------------------------------------------

if HAS_HYPOTHESIS:
    @settings(max_examples=60, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_reference_value_row_matches_bruteforce(seed):
        rng = np.random.default_rng(seed)
        E, K = int(rng.integers(4, 13)), int(rng.integers(1, 4))
        A, c, ups, sig = _rand_problem(rng, E, K)
        allowed = (rng.integers(0, 2, E).astype(bool)
                   if rng.integers(0, 2) else None)
        tables = build_tables(A, c)
        s_cap = int(ups.sum())
        x, s_star, row = _solve_with(REF, ups, sig, tables, s_cap, s_cap,
                                     allowed)
        bf_row = enumerate_value_row(ups, sig, A, c, s_cap, allowed)
        np.testing.assert_array_equal(row, bf_row)
        assert s_star == eq17_star(bf_row, s_cap)
        # the returned x realizes the row entry at s*
        assert np.all(A @ x <= c)
        assert int(ups @ x) >= s_star
        assert int(sig @ x) == bf_row[s_star]

    # -----------------------------------------------------------------------
    # (b) reference vs Pallas: bit-exact on x, s*, and the value row.
    # Shapes are drawn from a small pool so the kernel compiles a handful of
    # tiny programs instead of one per example.
    # -----------------------------------------------------------------------

    @settings(max_examples=60, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_reference_vs_pallas_bitexact(seed):
        rng = np.random.default_rng(seed)
        E = int(rng.choice([6, 10]))
        K = int(rng.integers(1, 3))
        A, c, ups, sig = _rand_problem(rng, E, K, c_hi=2, u_hi=4,
                                       sig_hi=10**4)
        allowed = (rng.integers(0, 2, E).astype(bool)
                   if rng.integers(0, 2) else None)
        tables = build_tables(A, c)
        s_cap = 4 * E  # static per E: few jit keys
        s_limit = int(rng.integers(0, s_cap + 1))  # exercises s_limit < s_cap
        got_ref = _solve_with(REF, ups, sig, tables, s_cap, s_limit, allowed)
        got_pal = _solve_with(PAL, ups, sig, tables, s_cap, s_limit, allowed)
        np.testing.assert_array_equal(got_ref[0], got_pal[0])  # x
        assert got_ref[1] == got_pal[1]  # s_star
        np.testing.assert_array_equal(got_ref[2], got_pal[2])  # value_row

    # -----------------------------------------------------------------------
    # (c) oracle_knapsack vs exhaustive search
    # -----------------------------------------------------------------------

    @settings(max_examples=40, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_oracle_knapsack_matches_exhaustive(seed):
        rng = np.random.default_rng(seed)
        E, K = int(rng.integers(4, 11)), int(rng.integers(1, 4))
        A, c, _, _ = _rand_problem(rng, E, K)
        values = rng.uniform(0.0, 1.0, E).astype(np.float32)
        allowed = rng.integers(0, 2, E).astype(bool)
        tables = build_tables(A, c)
        x, v = oracle_knapsack(jnp.asarray(values), tables,
                               jnp.asarray(allowed))
        x = np.asarray(x)
        best = 0.0
        for bits in itertools.product([0, 1], repeat=E):
            xx = np.array(bits)
            if np.any(xx > allowed.astype(int)) or np.any(A @ xx > c):
                continue
            best = max(best, float(values @ xx))
        assert np.all(A @ x <= c) and np.all(x <= allowed.astype(int))
        assert float(v) == pytest.approx(best, rel=1e-5)
else:
    def test_hypothesis_extra_missing():
        pytest.importorskip(
            "hypothesis",
            reason="property tests need the [test] extra (pip install .[test])")


# ---------------------------------------------------------------------------
# u_max edge cases (deterministic — these pin the shift-padding contract)
# ---------------------------------------------------------------------------

def test_pallas_u_max_one_all_zero_upsilon():
    """u_max=1 is legal only when every Υ̂ is 0 (shift never exceeds padding)."""
    rng = np.random.default_rng(5)
    E, K = 8, 2
    A, c, _, sig = _rand_problem(rng, E, K)
    ups = np.zeros(E, np.int32)
    tables = build_tables(A, c)
    s_cap = 6
    x1, i1 = solve_budgeted_dp(jnp.asarray(ups), jnp.asarray(sig), tables,
                               s_cap, jnp.int32(s_cap))
    x2, i2 = solve_budgeted_dp_pallas(ups, sig, tables, s_cap, s_cap,
                                      u_max=1, interpret=True)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    assert int(i1["s_star"]) == int(i2["s_star"]) == 0


@pytest.mark.parametrize("u_max_kind", ["tight", "s_cap_plus_one"])
def test_pallas_u_max_padding_invariance(u_max_kind):
    """The result must not depend on the padding amount (≥ max Υ̂ + 1)."""
    rng = np.random.default_rng(6)
    E, K = 9, 2
    A, c, ups, sig = _rand_problem(rng, E, K, u_hi=4)
    tables = build_tables(A, c)
    s_cap = int(ups.sum())
    u_max = int(ups.max() + 1) if u_max_kind == "tight" else s_cap + 1
    x1, i1 = solve_budgeted_dp(jnp.asarray(ups), jnp.asarray(sig), tables,
                               s_cap, jnp.int32(s_cap))
    x2, i2 = solve_budgeted_dp_pallas(ups, sig, tables, s_cap, s_cap,
                                      u_max=u_max, interpret=True)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    assert int(i1["s_star"]) == int(i2["s_star"])


def test_s_limit_below_cap_matches_bruteforce():
    rng = np.random.default_rng(7)
    A, c, ups, sig = _rand_problem(rng, 8, 2)
    tables = build_tables(A, c)
    s_cap = int(ups.sum())
    s_limit = s_cap // 2
    bf_row = enumerate_value_row(ups, sig, A, c, s_cap)
    for solver in (REF, PAL):
        x, s_star, row = _solve_with(solver, ups, sig, tables, s_cap,
                                     s_limit)
        assert s_star == eq17_star(bf_row, s_limit)
        assert s_star <= s_limit
        np.testing.assert_array_equal(row, bf_row)


# ---------------------------------------------------------------------------
# offset-encoded transitions (the E·C² → E operand contract)
# ---------------------------------------------------------------------------

if HAS_HYPOTHESIS:
    @settings(max_examples=60, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_offset_identity_on_feasible_pairs(seed):
        """DPTables.offsets is the whole transition table: next_state[c, e]
        == c − offsets[e] for EVERY feasible (e, c), and offsets[e] ==
        Σ_k A[k,e]·strides[k]."""
        rng = np.random.default_rng(seed)
        E, K = int(rng.integers(2, 16)), int(rng.integers(1, 5))
        A, c, _, _ = _rand_problem(rng, E, K)
        tables = build_tables(A, c)
        np.testing.assert_array_equal(
            tables.offsets, (A.T * tables.strides[None, :]).sum(axis=1))
        states, edges = np.nonzero(tables.feasible)
        np.testing.assert_array_equal(
            tables.next_state[states, edges],
            states - tables.offsets[edges])


if HAS_HYPOTHESIS:
    @settings(max_examples=40, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_s_tiled_solver_bitexact_random_tilings(seed):
        """The 2-D (S-tile × C-tile) pipeline under RANDOM legal tilings —
        tight (block = halo floor), padded (dividing neither plane
        extent), and everything between, with u_max at or above the exact
        Υ̂ maximum, optional allowed masks, AND a random edge-fusion chunk
        block_e ∈ {None (per-edge scan), 1 … 32} (dividing E or not) —
        yields bit-identical x / s* / value_row vs the reference backend."""
        rng = np.random.default_rng(seed)
        E = int(rng.choice([6, 10]))
        K = int(rng.integers(1, 3))
        A, c, ups, sig = _rand_problem(rng, E, K, c_hi=2, u_hi=4,
                                       sig_hi=10**4)
        allowed = (rng.integers(0, 2, E).astype(bool)
                   if rng.integers(0, 2) else None)
        tables = build_tables(A, c)
        s_cap = 4 * E  # static per E: few jit keys
        S, C = s_cap + 1, tables.n_states
        off_max = int(tables.offsets.max())
        # u_max halo edge cases: the exact bound, +1 margin, or generous
        u_max = int(ups.max()) + int(rng.integers(0, 3))
        u_max = max(u_max, 1)
        block_s = int(rng.integers(max(u_max, 2), S + 3))
        block_c = int(rng.integers(max(off_max, 1), C + 3))
        block_e = (None if rng.integers(0, 4) == 0
                   else int(rng.integers(1, 33)))
        s_limit = int(rng.integers(0, s_cap + 1))
        got_ref = _solve_with(REF, ups, sig, tables, s_cap, s_limit, allowed)
        x, info = solve_budgeted_dp_pallas(
            ups, sig, tables, s_cap, s_limit, u_max=u_max,
            allowed=None if allowed is None else jnp.asarray(allowed),
            interpret=True, block_c=block_c, block_s=block_s,
            block_e=block_e)
        np.testing.assert_array_equal(got_ref[0], np.asarray(x))
        assert got_ref[1] == int(info["s_star"])
        row_ref = got_ref[2].astype(np.int64)
        row = np.asarray(info["value_row"])
        np.testing.assert_array_equal(row_ref >= 0, row >= 0)
        np.testing.assert_array_equal(row_ref[row_ref >= 0],
                                      row[row >= 0].astype(np.int64))


def test_prepare_tables_offsets_track_tables():
    """Kernel operands are pure derivations of DPTables fields — a replaced
    tables object can never serve stale operands (the old side-channel
    cache), and never-feasible edges get offset 0 (keeps the pad tight)."""
    A = np.array([[1, 2, 3]])  # edge 2 needs 3 > c=2: never feasible
    c = np.array([2])
    tables = build_tables(A, c)
    feas, offs = prepare_tables(tables)
    np.testing.assert_array_equal(offs, [1, 2, 0])
    np.testing.assert_array_equal(feas, np.asarray(tables.feasible,
                                                   np.float32).T)
    swapped = dataclasses.replace(
        tables, feasible=np.zeros_like(tables.feasible))
    feas2, _ = prepare_tables(swapped)
    assert not feas2.any()  # derived from the NEW fields


def test_large_c_blocked_grid_bitexact_vs_reference():
    """C = 512 (radices 8·8·8) — a capacity space whose one-hot operand
    (4·E·C² = 16 MB at E=16) could never fit VMEM — through the blocked
    grid path (forced small tiles), bit-exact vs the int32 reference on
    x / s* / value_row, with an allowed mask."""
    rng = np.random.default_rng(21)
    E, K = 16, 3
    A = rng.integers(0, 2, (K, E))  # 0/1 demands keep off_max ≤ 128
    A[:, A.sum(axis=0) == 0] = 1  # no all-zero demand columns
    c = np.array([7, 7, 7])
    ups = rng.integers(0, 4, E).astype(np.int32)
    sig = rng.integers(1, 5000, E).astype(np.int32)
    allowed = rng.integers(0, 2, E).astype(bool)
    allowed[:2] = True
    tables = build_tables(A, c)
    assert tables.n_states == 512
    s_cap = int(ups.sum())
    got_ref = _solve_with(REF, ups, sig, tables, s_cap, s_cap, allowed)
    x, info = solve_budgeted_dp_pallas(
        ups, sig, tables, s_cap, s_cap, allowed=allowed, interpret=True,
        block_c=128)
    assert int(tables.offsets.max()) <= 128  # halo contract holds
    np.testing.assert_array_equal(got_ref[0], np.asarray(x))
    assert got_ref[1] == int(info["s_star"])
    row = np.asarray(info["value_row"])
    ref_row = got_ref[2]
    np.testing.assert_array_equal(ref_row >= 0, row >= 0)
    np.testing.assert_array_equal(ref_row[ref_row >= 0],
                                  row[row >= 0].astype(np.int64))


def test_undersized_u_max_raises_instead_of_clamping():
    """The kernel clamps shifts at u_max for memory safety; the wrapper must
    refuse a concrete contract breach rather than return silently-wrong
    values."""
    rng = np.random.default_rng(22)
    A, c, ups, sig = _rand_problem(rng, 8, 2, u_hi=5)
    ups[0] = 5
    tables = build_tables(A, c)
    with pytest.raises(ValueError, match="u_max"):
        solve_budgeted_dp_pallas(ups, sig, tables, int(ups.sum()),
                                 int(ups.sum()), u_max=3, interpret=True)


def test_u_max_for_horizon_bounds_upsilon():
    """The tight static shift bound: ξ(T)+1 dominates every Υ̂ the schedules
    can emit (v̂ ≤ 1), and is m× smaller than the always-safe s_cap+1."""
    inst = generate_instance(seed=0)
    m = inst.m
    for T in (150, 1500, 10**5):
        u_max = stats_mod.u_max_for_horizon(T, m)
        s_cap = stats_mod.s_cap_for_horizon(T, m)
        assert u_max == s_cap // m + 1
        for t in (1.0, float(T) / 2, float(T)):
            ups, _, _, _ = stats_mod.scale_statistics(
                jnp.ones(inst.n_edges, jnp.float32),
                jnp.ones(inst.n_edges, jnp.int32), jnp.float32(t), m)
            assert int(jnp.max(ups)) < u_max


# ---------------------------------------------------------------------------
# fleet-batched solves: B instances, ONE launch (batched differential
# harness)
# ---------------------------------------------------------------------------

def _ref_loop(ups, sig, tables, s_cap, slim, alw):
    """Per-instance loop over the reference backend — the batched oracle."""
    return [_solve_with(REF, ups[b], sig[b], tables, s_cap, int(slim[b]),
                        None if alw is None else alw[b])
            for b in range(ups.shape[0])]


def _assert_batched_matches(x, info, want):
    for b, (x_r, s_r, row_r) in enumerate(want):
        np.testing.assert_array_equal(np.asarray(x[b]), x_r)
        assert int(info["s_star"][b]) == s_r
        row = np.asarray(info["value_row"][b])
        np.testing.assert_array_equal(row >= 0, row_r >= 0)
        np.testing.assert_array_equal(row[row >= 0].astype(np.int64),
                                      row_r[row_r >= 0].astype(np.int64))


def _rand_fleet(rng, B, E, s_cap, u_hi=4, sig_hi=10**4):
    """Heterogeneous per-instance statistics: every row its own problem."""
    ups = rng.integers(0, u_hi + 1, (B, E)).astype(np.int32)
    sig = rng.integers(1, sig_hi + 1, (B, E)).astype(np.int32)
    alw = rng.integers(0, 2, (B, E)).astype(np.int32)
    slim = rng.integers(0, s_cap + 1, B).astype(np.int32)
    return ups, sig, alw, slim


if HAS_HYPOTHESIS:
    # budget the heaviest sweep in the suite: a hypothesis shrink search
    # over B=32 interpret-mode fleets can otherwise eat the CI job's whole
    # timeout-minutes allowance (enforced only where pytest-timeout is
    # installed — the [test] extra)
    @pytest.mark.timeout(300)
    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_batched_solve_bitexact_vs_instance_loop(seed):
        """Both batched routes — the explicit ``solve_budgeted_dp_batched``
        entry AND ``jax.vmap`` of the pallas backend (the custom batching
        rule) — are bit-exact vs a per-instance loop over the reference
        backend, with heterogeneous Υ̂/Σ̂²/allowed/s_limit across the fleet
        and B spanning 1 (degenerate), non-dividing (7) and wide (32)."""
        rng = np.random.default_rng(seed)
        E = int(rng.choice([6, 10]))
        K = int(rng.integers(1, 3))
        B = int(rng.choice([1, 2, 7, 32]))
        A, c, _, _ = _rand_problem(rng, E, K, c_hi=2)
        tables = build_tables(A, c)
        s_cap = 4 * E  # static per E: few jit keys
        u_max = 5  # static bound over u_hi=4
        ups, sig, alw, slim = _rand_fleet(rng, B, E, s_cap)
        want = _ref_loop(ups, sig, tables, s_cap, slim, alw)

        xb, info = solve_budgeted_dp_batched(
            ups, sig, tables, s_cap, slim, u_max=u_max, allowed=alw,
            interpret=True)
        _assert_batched_matches(xb, info, want)

        vm = jax.vmap(lambda u, s, l, a: PAL(u, s, tables, s_cap, l,
                                             allowed=a, u_max=u_max))
        xv, info_v = vm(jnp.asarray(ups), jnp.asarray(sig),
                        jnp.asarray(slim), jnp.asarray(alw))
        _assert_batched_matches(xv, info_v, want)
        for b, (_, _, row_r) in enumerate(want):
            # the Solver wrapper restores the exact int32 row incl. NEG
            np.testing.assert_array_equal(np.asarray(info_v["value_row"][b]),
                                          row_r)


if HAS_HYPOTHESIS:
    # same 5-minute budget as the fleet sweep above: random tilings multiply
    # the per-example kernel launches
    @pytest.mark.timeout(300)
    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_batched_solver_random_tilings_bitexact(seed):
        """Random legal 4-tuple (block_b, block_e, block_s, block_c)
        tilings: the whole-plane kernel under every ``block_b`` ∈ [1, B]
        (ragged batches pad with inert instances), and the edge-fused
        pipeline, mapped over the instances, under random block_e /
        block_s / block_c — all bit-exact vs the per-instance reference
        loop."""
        rng = np.random.default_rng(seed)
        E = int(rng.choice([6, 10]))
        K = int(rng.integers(1, 3))
        B = int(rng.choice([2, 7]))
        A, c, _, _ = _rand_problem(rng, E, K, c_hi=2)
        tables = build_tables(A, c)
        s_cap = 4 * E
        S, C = s_cap + 1, tables.n_states
        off_max = int(tables.offsets.max())
        ups, sig, alw, slim = _rand_fleet(rng, B, E, s_cap)
        u_max = int(ups.max()) + int(rng.integers(1, 3))
        if rng.integers(0, 2):  # whole-plane, batch-tiled grid
            kw = dict(block_b=int(rng.integers(1, B + 1)), block_c=None)
        else:  # edge-fused, the single solve mapped over the fleet
            kw = dict(block_c=int(rng.integers(max(off_max, 1), C + 3)),
                      block_e=int(rng.integers(1, 33)),
                      block_s=(None if rng.integers(0, 2) else
                               int(rng.integers(max(u_max, 2), S + 3))))
        x, info = solve_budgeted_dp_batched(
            ups, sig, tables, s_cap, slim, u_max=u_max, allowed=alw,
            interpret=True, **kw)
        _assert_batched_matches(
            x, info, _ref_loop(ups, sig, tables, s_cap, slim, alw))


def test_batched_b1_degenerates_to_single_instance():
    """A fleet of one reproduces the single-instance kernel exactly —
    including the raw f32 value row (same sentinel, same bits) — and a
    scalar s_limit broadcasts across the batch."""
    rng = np.random.default_rng(33)
    A, c, ups, sig = _rand_problem(rng, 10, 2, u_hi=4)
    alw = rng.integers(0, 2, 10).astype(np.int32)
    tables = build_tables(A, c)
    s_cap = int(ups.sum())
    x1, i1 = solve_budgeted_dp_pallas(ups, sig, tables, s_cap, s_cap // 2,
                                      u_max=5, allowed=alw, interpret=True)
    xb, ib = solve_budgeted_dp_batched(ups[None], sig[None], tables, s_cap,
                                       np.int32(s_cap // 2), u_max=5,
                                       allowed=alw[None], interpret=True)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(xb[0]))
    assert int(i1["s_star"]) == int(ib["s_star"][0])
    np.testing.assert_array_equal(np.asarray(i1["value_row"]),
                                  np.asarray(ib["value_row"][0]))


def test_cluster_run_batch_reproduces_per_seed_runs(small):
    """``run_batch`` fleet-batches the per-slot solves (ONE launch per
    slot through the batch-aware backend) yet reproduces per-seed
    ``run()`` bit for bit — sw, regret, dispatch_share, asw — for both
    the batch-aware pallas backend and the conventionally-vmapped
    reference, on a DP policy and a greedy one."""
    from repro.sched import ClusterSim
    inst, _ = small
    T, seeds = 40, [4, 9]
    for name, policy in (("pallas_interpret", "esdp"),
                         ("reference", "hswf")):
        outs = ClusterSim(inst, T, seed=0, solver=name).run_batch(
            seeds, policy)
        assert len(outs) == len(seeds)
        for s, ob in zip(seeds, outs):
            o1 = ClusterSim(inst, T, seed=s, solver=name).run(policy)
            np.testing.assert_array_equal(ob.sw, o1.sw)
            np.testing.assert_array_equal(ob.regret, o1.regret)
            np.testing.assert_array_equal(ob.dispatch_share,
                                          o1.dispatch_share)
            assert ob.asw == o1.asw


def test_prepare_tables_cached_per_tables_identity():
    """The host-side operand derivation runs ONCE per DPTables object —
    every per-slot solve of a simulation hits the lru_cache — while a
    ``dataclasses.replace``d tables object is a fresh key (so the cache
    can never serve stale operands; see
    test_prepare_tables_offsets_track_tables)."""
    tables = build_tables(np.array([[1, 1, 2]]), np.array([3]))
    before = prepare_tables.cache_info()
    f1, o1 = prepare_tables(tables)
    mid = prepare_tables.cache_info()
    assert mid.misses == before.misses + 1
    f2, o2 = prepare_tables(tables)
    after = prepare_tables.cache_info()
    assert after.hits == mid.hits + 1 and after.misses == mid.misses
    assert f1 is f2 and o1 is o2  # same host arrays, not copies
    swapped = dataclasses.replace(tables,
                                  feasible=np.zeros_like(tables.feasible))
    prepare_tables(swapped)
    assert prepare_tables.cache_info().misses == after.misses + 1


# ---------------------------------------------------------------------------
# backend resolution logic (the silent-interpret fix)
# ---------------------------------------------------------------------------

def test_backend_resolution_table():
    for platform in ("cpu", "gpu", "tpu"):
        # kernel level: never silently interpreted on TPU
        assert resolve_interpret(None, platform) is (platform != "tpu")
        assert resolve_interpret(True, platform) is True
        assert resolve_interpret(False, platform) is False
        # registry level: auto = compiled pallas on TPU, reference elsewhere
        expect = "pallas" if platform == "tpu" else "reference"
        assert resolve_solver("auto", platform) == expect
        for name in ("reference", "pallas", "pallas_interpret"):
            assert resolve_solver(name, platform) == name
    with pytest.raises(ValueError):
        resolve_solver("bogus")


def test_env_var_overrides_auto_but_not_explicit(monkeypatch):
    monkeypatch.setenv(SOLVER_ENV_VAR, "pallas_interpret")
    assert resolve_solver(None, "tpu") == "pallas_interpret"
    assert resolve_solver("auto", "cpu") == "pallas_interpret"
    assert get_solver(None, "cpu").name == "pallas_interpret"
    assert resolve_solver("reference", "tpu") == "reference"
    monkeypatch.setenv(SOLVER_ENV_VAR, "")
    assert resolve_solver(None, "cpu") == "reference"


def test_invalid_env_var_warns_and_falls_back_to_auto(monkeypatch):
    """A stale/typo'd $REPRO_DP_SOLVER must not hard-crash callers that
    never asked for a concrete backend: env-sourced invalid names WARN and
    fall back to the auto resolution — while an invalid name passed in
    code still raises (the caller asked for something that doesn't
    exist)."""
    monkeypatch.setenv(SOLVER_ENV_VAR, "bogus")
    for requested in (None, "auto"):
        for platform, expect in (("cpu", "reference"), ("gpu", "reference"),
                                 ("tpu", "pallas")):
            with pytest.warns(RuntimeWarning, match="REPRO_DP_SOLVER"):
                assert resolve_solver(requested, platform) == expect
    # explicit names win before the env var is even consulted — no warning
    import warnings as _warnings
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        assert resolve_solver("reference", "tpu") == "reference"
    # names passed IN CODE keep raising, env var irrelevant
    with pytest.raises(ValueError, match="bogus"):
        resolve_solver("bogus", "cpu")


def test_get_solver_caches_identity():
    assert get_solver("reference") is get_solver("reference")
    assert get_solver(PAL) is PAL


# ---------------------------------------------------------------------------
# VALUE_BOUND contract (int32 planes, every reachable sum < |NEG| = 2^29)
# ---------------------------------------------------------------------------

def test_value_bound_overflow_raises():
    rng = np.random.default_rng(8)
    A, c, ups, sig = _rand_problem(rng, 6, 2)
    sig = sig.astype(np.int32)
    tables = build_tables(A, c)
    s_cap = int(ups.sum())
    assert VALUE_BOUND == -int(NEG) == 2 ** 29
    sig[0] = VALUE_BOUND  # a single value at the bound
    with pytest.raises(ValueError, match="2\\^29"):
        solve_budgeted_dp_pallas(ups, sig, tables, s_cap, s_cap,
                                 interpret=True)
    # 2^24, the float32 plane's old limit, now solves exactly
    sig[0] = 2 ** 24 + 1
    x, s_star, row = _solve_with(PAL, ups, sig, tables, s_cap, s_cap)
    x_r, s_r, row_r = _solve_with(REF, ups, sig, tables, s_cap, s_cap)
    np.testing.assert_array_equal(x, x_r)
    assert s_star == s_r and row.max() >= 2 ** 24 + 1
    np.testing.assert_array_equal(row, row_r)


def test_max_achievable_value_topk():
    # K=1, c=2, A=1 per edge: at most 2 edges fit → top-2 sum of Σ̂²
    E = 5
    A = np.ones((1, E), np.int64)
    c = np.array([2], np.int64)
    sig = np.array([10, 50, 20, 40, 30], np.int64)
    tables = build_tables(A, c)
    assert max_achievable_value(sig, tables) == 90


# the engine's deployments at their horizons: (generator arguments, T,
# largest selectable set) — Table 2, Fig. 5's largest graph, and that graph
# at Fig. 6's largest capacities
DEPLOYMENTS = [
    (dict(seed=0), 2_000, 1),
    (dict(seed=0), 56_000, 1),
    (dict(seed=1, n_ports=16, n_servers=160, edge_prob=0.1), 100, 1),
    (dict(seed=1, n_ports=16, n_servers=160, edge_prob=0.1), 200, 1),
    (dict(seed=1, n_ports=16, n_servers=160, edge_prob=0.1, c_lo=6,
          c_hi=6), 50, 6),
    (dict(seed=1, n_ports=16, n_servers=160, edge_prob=0.1, c_lo=6,
          c_hi=6), 100, 6),
    (dict(seed=1, n_ports=16, n_servers=160, edge_prob=0.1, c_lo=6,
          c_hi=6), 200, 6),
]


def test_default_schedules_stay_under_value_bound():
    """Pins the stats.scale_statistics outputs under 2^29 at the default
    horizons (T=1500 benchmarks, T=10^5 stress) of the Table-2 instance
    (m = 17), and the engine's largest selectable sums in its deployments
    (m = 17 and m = 126, whose values pass 2^24 at T = 100) — the sums
    ``DispatchEngine`` checks at construction, since the traced hot path
    cannot see concrete values."""
    inst = generate_instance(seed=0)  # paper Table-2 defaults
    tables = build_tables(inst.A, inst.c)
    m = inst.m
    E = inst.n_edges
    for T in (1500, 10**5):
        # worst explored statistics: n = 1 for every channel at t = T
        _, sig, _, _ = stats_mod.scale_statistics(
            jnp.ones(E, jnp.float32), jnp.ones(E, jnp.int32),
            jnp.float32(T), m)
        assert max_achievable_value(np.asarray(sig), tables) < VALUE_BOUND
    # all channels unexplored (the finite dominance bonus) at t = 1
    _, sig0, _, _ = stats_mod.scale_statistics(
        jnp.zeros(E, jnp.float32), jnp.zeros(E, jnp.int32),
        jnp.float32(1.0), m)
    assert max_achievable_value(np.asarray(sig0), tables) < VALUE_BOUND
    g = stats_mod.g_logt_only  # the engine's schedule
    for kw, T, largest in DEPLOYMENTS:
        inst = generate_instance(**kw)
        tables = build_tables(inst.A, inst.c)
        bonus = stats_mod.sigma2_bound(T, inst.m, g_fn=g)
        _, sig, _, _ = stats_mod.scale_statistics(
            jnp.zeros(inst.n_edges, jnp.float32),
            jnp.zeros(inst.n_edges, jnp.int32), jnp.float32(T), inst.m,
            g_fn=g)
        assert int(np.max(sig)) == bonus  # the bonus of the last slot
        total = max_achievable_value(np.full(inst.n_edges, bonus), tables)
        assert total == largest * bonus < VALUE_BOUND
    # Fig. 5's largest graph: one bonus already passes 2^24 at T = 100
    assert stats_mod.sigma2_bound(100, 126, g_fn=g) > 2 ** 25


def test_engine_refuses_a_horizon_past_the_value_bound():
    """The construction guard: at Fig. 6's capacities six bonuses of
    T = 10^4 reach 2^29, so the engine refuses the horizon before any
    slot is traced; T = 200 runs."""
    from repro.sched import DispatchEngine
    inst = generate_instance(seed=1, n_ports=16, n_servers=160,
                             edge_prob=0.1, c_lo=6, c_hi=6)
    DispatchEngine(inst, 200)
    with pytest.raises(ValueError, match="2\\^29"):
        DispatchEngine(inst, 10_000)


# ---------------------------------------------------------------------------
# end-to-end backend invariance (ESDP through the simulator and sweeps)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small():
    inst = generate_instance(seed=3, n_ports=4, n_servers=10, edge_prob=0.3)
    tables = build_tables(inst.A, inst.c)
    return inst, tables


@pytest.mark.parametrize("scenario", [None, "markov_dvfs"])
def test_esdp_trace_invariance_end_to_end(small, scenario):
    """simulate(instance, esdp, T=200) produces identical SimResult traces
    (decisions, sw, regret) under both backends."""
    inst, tables = small
    T = 200
    scn = None if scenario is None else get_scenario(scenario)
    results = {}
    for name in ("reference", "pallas_interpret"):
        policy = make_esdp_policy(inst, T, tables=tables, solver=name)
        results[name] = simulate(inst, policy, T, seed=1, tables=tables,
                                 scenario=scn)
    ref, pal = results["reference"], results["pallas_interpret"]
    np.testing.assert_array_equal(ref.n_dispatched, pal.n_dispatched)
    np.testing.assert_array_equal(ref.sw, pal.sw)
    np.testing.assert_array_equal(ref.sw_oracle, pal.sw_oracle)
    np.testing.assert_array_equal(ref.regret, pal.regret)


def test_pallas_vmaps_through_simulate_batch(small):
    """The Pallas path is vmap-safe: a seed batch through simulate_batch is
    bit-identical to the reference backend's batch."""
    inst, tables = small
    T, seeds = 80, (0, 1, 2)
    res = {}
    for name in ("reference", "pallas"):  # public name; interpret on CPU
        policy = make_esdp_policy(inst, T, tables=tables, solver=name)
        res[name] = simulate_batch(inst, policy, T, seeds, tables=tables)
    np.testing.assert_array_equal(res["reference"].n_dispatched,
                                  res["pallas"].n_dispatched)
    np.testing.assert_array_equal(res["reference"].sw, res["pallas"].sw)
    np.testing.assert_array_equal(res["reference"].regret,
                                  res["pallas"].regret)


# Mirrors benchmarks.sensitivity.FIG6_SPEC.smoke() (defined locally so the
# test suite never depends on the benchmarks/ namespace package being on
# sys.path).  hswf rides along to cover run_spec's non-solver-aware branch.
FIG6_SMOKE = SweepSpec(
    name="fig6", T=120, seeds=(0,),
    policies={"esdp": esdp_factory(), "hswf": hswf_factory()},
    grid=tuple(GridPoint(f"c_hi{c}",
                         instance_kwargs={"seed": 2, "c_lo": 1, "c_hi": c})
               for c in (1, 2, 4, 6)),
)


def test_cluster_dispatcher_backend_invariance(small):
    """ClusterSim threads solver= into its jitted per-slot DP call."""
    from repro.sched import ClusterSim
    inst, _ = small
    outs = {name: ClusterSim(inst, 60, seed=4, solver=name).run("esdp")
            for name in ("reference", "pallas_interpret")}
    np.testing.assert_array_equal(outs["reference"].sw,
                                  outs["pallas_interpret"].sw)
    np.testing.assert_array_equal(outs["reference"].regret,
                                  outs["pallas_interpret"].regret)
    assert outs["reference"].asw == outs["pallas_interpret"].asw


def test_pallas_through_sweepspec_fig6_smoke():
    """SweepSpec.solver threads the backend through run_spec; the fig6 smoke
    sweep is bit-identical between backends (full per-seed traces, not just
    means)."""
    rows = {}
    for name in ("reference", "pallas"):
        rows[name] = run_spec(dataclasses.replace(FIG6_SMOKE, solver=name))
    assert len(rows["reference"]) == 8  # 4 grid points × 2 policies
    for r_ref, r_pal in zip(rows["reference"], rows["pallas"]):
        assert (r_ref.point, r_ref.policy) == (r_pal.point, r_pal.policy)
        assert r_pal.solver == "pallas"
        np.testing.assert_array_equal(r_ref.result.sw, r_pal.result.sw)
        np.testing.assert_array_equal(r_ref.result.regret,
                                      r_pal.result.regret)
        np.testing.assert_array_equal(r_ref.result.n_dispatched,
                                      r_pal.result.n_dispatched)
        assert r_ref.asw_mean == r_pal.asw_mean


# ---------------------------------------------------------------------------
# (i) incremental legs: the warm-started and cached re-solve layers must be
# bit-exact against cold solves over random DRIFT SEQUENCES — localized
# statistic drifts, eligibility flips, s_limit-only changes, and verbatim
# repeats (core.incremental / kernels.budgeted_dp.ops.WarmPallasSolver)
# ---------------------------------------------------------------------------

def _incremental_legs_body(seed):
    from repro.core.incremental import (solve_budgeted_dp_warm,
                                        warm_carry_init)
    from repro.core.solvers import CachedSolver
    from repro.kernels.budgeted_dp.ops import WarmPallasSolver

    rng = np.random.default_rng(seed)
    E = int(rng.choice([6, 10]))
    K = int(rng.integers(1, 3))
    A, c, ups, sig = _rand_problem(rng, E, K, c_hi=2, u_hi=4, sig_hi=10**4)
    tables = build_tables(A, c)
    s_cap = 4 * E  # static per E: few jit keys
    k = int(rng.choice([2, 4]))

    cached = CachedSolver(REF)
    warm_pal = WarmPallasSolver(tables, s_cap, checkpoint_every=k,
                                interpret=True)
    carry = warm_carry_init(E, s_cap, tables.n_states, k)

    @jax.jit
    def warm_ref(u, s, lim, a, cr):
        return solve_budgeted_dp_warm(u, s, tables, s_cap, lim, cr,
                                      allowed=a, checkpoint_every=k)

    alw = np.ones(E, bool)
    s_limit = s_cap
    for slot in range(6):
        kind = ("cold", "suffix", "slim", "repeat", "alw", "suffix")[slot]
        if kind == "suffix":  # edge 0 folds LAST: long prefix
            e = int(rng.integers(0, max(1, E // 3)))
            ups[e] = rng.integers(0, 5)
            sig[e] = rng.integers(1, 10**4)
        elif kind == "slim":
            s_limit = int(rng.integers(0, s_cap + 1))
        elif kind == "alw":
            e = int(rng.integers(0, E))
            alw[e] = ~alw[e]

        want = _solve_with(REF, ups, sig, tables, s_cap, s_limit, alw)
        got = {}
        got["cached"] = cached(ups, sig, tables, s_cap, s_limit, allowed=alw)
        got["warm_pal"] = warm_pal(ups, sig, tables, s_cap, s_limit,
                                   allowed=alw)
        xw, iw, carry = warm_ref(jnp.asarray(ups, jnp.int32),
                                 jnp.asarray(sig, jnp.int32),
                                 jnp.int32(s_limit), jnp.asarray(alw), carry)
        got["warm_ref"] = (xw, iw)
        for leg, (x, info) in got.items():
            np.testing.assert_array_equal(np.asarray(x), want[0], err_msg=leg)
            assert int(info["s_star"]) == want[1], leg
            np.testing.assert_array_equal(np.asarray(info["value_row"]),
                                          want[2], err_msg=leg)
    # the layers actually skipped work on this trace
    assert cached.stats.hits >= 1  # the "repeat" slot
    assert warm_pal.stats["edges_skipped"] > 0


if HAS_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_incremental_legs_bitexact_over_drift(seed):
        _incremental_legs_body(seed)
else:
    @pytest.mark.parametrize("seed", [0, 42, 20260808])
    def test_incremental_legs_bitexact_over_drift(seed):
        _incremental_legs_body(seed)
