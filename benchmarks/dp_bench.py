"""Timing harness for the Algorithm-2 solver backends.

Times reference vs pallas-interpret vs pallas-compiled across named
(E, C, S) configs of synthetic P4 instances — large capacity spaces
(C = 512 / 1024 / 4096) that the old (E, C, C) one-hot transition operand
could never hold in VMEM, and long budget axes (S = 4096 / 8192) that even
the offset-encoded whole-plane kernel cannot hold (``unblocked_vmem_bytes``
over the budget) and that run through the 2-D S-tiled pipeline — and
writes ``results/BENCH_dp.json``::

    python -m benchmarks.dp_bench            # full grid
    python -m benchmarks.dp_bench --smoke    # CI-sized grid
    python -m benchmarks.dp_bench --smoke --baseline results/BENCH_dp.json
    python -m benchmarks.dp_bench --runs 20 --out results/BENCH_dp.json

``--baseline`` compares the fresh per-config/backend mean timings against a
committed BENCH_dp.json (matched on (E, C, S, backend) so files from before
the config-naming change still compare) and exits non-zero on a
``--max-regression``-fold slowdown — the CI perf-regression guard.  The
baseline records a host fingerprint (CPU model + jax version); when the
fresh run's fingerprint differs, absolute wall-clock is not comparable and
the guard WARNS instead of failing (refresh the committed file from the CI
machine class to re-arm it).

The compiled-pallas leg only runs on a real TPU; elsewhere it is recorded
as skipped (the interpreter leg still exercises the kernel's program).
Configs with a forced ``block`` additionally time the blocked grid paths
as backend ``pallas_interpret_blocked``; every blocked/fused leg is first
checked BIT-EXACT against the reference backend on x / s* / value_row
(the acceptance contract), and its record carries the tiling plus
``unblocked_vmem_bytes`` so "impossible unblocked" is visible in the
artifact.  Per-point records include the one-off table/operand
preparation cost plus a kernel-vs-wrapper split: ``forward_ms`` times the
DP forward kernel alone, so the share spent in the eq.-17 selection +
backtrack wrapper is visible in the numbers.

Every pallas leg also records ``hbm_bytes_streamed`` — the MODELED HBM
traffic of its tiling (``kernel.modeled_hbm_bytes``; wall-clock on
interpret-CPU does not see HBM, so the model is what the nightly perf
trend tracks).  Blocked configs time BOTH the edge-fused pipeline (the
auto tiling since PR 5) and a forced per-edge-scan leg
(``pallas_interpret_scan``, same plane tiling with ``block_e=None``), and
record ``hbm_reduction_vs_scan`` — the modeled traffic ratio the fusion
buys (the PR-5 acceptance bound is ≥ 4× on E16_C512_S4096).

Configs with a ``batch`` tuple additionally time the FLEET-BATCHED legs
at each batch size B (``--smoke`` keeps only B=8): B heterogeneous solves
(per-instance Υ̂/Σ̂²/allowed/s_limit) against
``solve_budgeted_dp_batched`` — ONE launch, tables shared — next to two
single-instance baselines on identical inputs: ``*_vmapped_B{B}``
(conventional ``jax.vmap`` of the per-instance solve: still one launch,
but the feasibility plane replicates to (B, E, C)) and
``*_launch_loop_B{B}`` (``lax.map``: one launch per instance,
sequential).  Every leg is bit-exact-gated against a per-instance
reference loop before it is timed, and the batched record carries
``solves_per_sec``, ``speedup_vs_vmapped`` / ``speedup_vs_launch_loop``
(wall-clock — NOTE that on interpret-CPU all three lower to the same
vectorized XLA loops, so wall-clock parity is expected there; the
launch-grid advantage is the HBM model and launch count, measured on
real TPUs), and ``hbm_reduction_vs_vmapped`` — the modeled shared-vs-
replicated traffic ratio (``kernel.batched_modeled_hbm_bytes``).  A
fleet of tiled planes maps the single solve and shares nothing, so its
``hbm_reduction_vs_vmapped`` is 1.0: the baselines run the same tiling.

Configs with ``incremental: True`` additionally time the CROSS-SLOT
INCREMENTAL legs (``incr_*`` backends) over a recorded post-exploration
drift trace: per-slot statistics come from the real sampling model
(``stats.scale_statistics`` at a large t₀, with (v̂, n) evolving only on
the edges each slot's solve dispatches), so the trace's repeat/drift
structure is the one the scheduler actually sees after exploration — the
⌈·⌉ in Υ̂ = ⌈ξv̂⌉ and Σ̂² = ⌈ξ²g/2n⌉ freezes the integer statistics for
long stretches once n is large.  Legs: a cold per-slot host loop
(``incr_reference`` / ``incr_pallas_interpret``), the exact-key solve
cache (``incr_reference_cached``, bit-exact-gated, cleared at the start
of every timed replay so hits come from WITHIN-trace structure only), a
quantized bounded-staleness cache (``incr_reference_cached_q`` — NOT
exact; records ``utility_gap_mean``/``utility_gap_max``, the relative
eq.-17 score loss of its solutions under the true statistics), the
warm-started reference path (``incr_reference_warm``) and the segmented
carried-plane Pallas driver (``incr_pallas_interpret_warm``).  Each
record carries ``cache_hit_rate`` / ``edge_skip_rate``, ``per_slot_ms``,
and ``speedup_vs_cold`` (the acceptance bound: the exact incremental
legs are ≥ 2× over their cold loop on the full-size trace).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import platform as platform_mod
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import stats as stats_mod
from repro.core.dp import build_tables, solve_budgeted_dp
from repro.core.incremental import solve_budgeted_dp_warm, warm_carry_init
from repro.core.solvers import CachedSolver, get_solver
from repro.kernels.budgeted_dp.ops import WarmPallasSolver
from repro.kernels.budgeted_dp.kernel import (
    NEG, VMEM_BUDGET_BYTES, batched_modeled_hbm_bytes, choose_tiling,
    dp_forward_pallas, modeled_hbm_bytes, unblocked_vmem_bytes)
from repro.kernels.budgeted_dp.ops import (_solve, prepare_tables,
                                           solve_budgeted_dp_batched,
                                           solve_budgeted_dp_pallas)

# Named configs: explicit capacity vector c (C = Π(c_k+1)) and Υ̂ range.
# The first four mirror the legacy (E, K, c_hi, u_hi) random draws so their
# (E, C, S) keys line up with pre-offset baselines; the large-C configs are
# the regime the offset encoding unlocks; the long-S configs (``s_cap``
# overrides the Υ̂-derived budget axis) are the long-horizon regime the
# S-tiled pipeline unlocks — their plane is impossible unblocked
# (``unblocked_vmem_bytes`` > budget, asserted at run time).
CONFIGS = [
    {"name": "E12_C6", "E": 12, "c_rand": (2, 2), "u_hi": 4},
    {"name": "E24_C6", "E": 24, "c_rand": (2, 3), "u_hi": 6},
    {"name": "E40_K3", "E": 40, "c_rand": (3, 2), "u_hi": 6},
    {"name": "E64_K3", "E": 64, "c_rand": (3, 3), "u_hi": 8},
    {"name": "E16_C512", "E": 16, "c": (7, 7, 7), "u_hi": 3,
     "batch": (8, 64), "incremental": True},
    {"name": "E16_C1024", "E": 16, "c": (3, 15, 15), "u_hi": 3},
    {"name": "E16_C4096", "E": 16, "c": (7, 7, 7, 7), "u_hi": 2,
     "block": (8, None, 1024)},  # off_max ≈ 585 (stride of the 4th resource
                                 # is 512), so the halo needs ≥ 1024 tiles;
                                 # fused in chunks of 8 edges
    {"name": "E16_C512_S4096", "E": 16, "c": (7, 7, 7), "u_hi": 3,
     "s_cap": 4095, "verify": True},
    {"name": "E16_C512_S8192", "E": 16, "c": (7, 7, 7), "u_hi": 3,
     "s_cap": 8191, "verify": True},
]
SMOKE_NAMES = ("E12_C6", "E24_C6", "E16_C512", "E16_C512_S4096")


def _make_problem(cfg: dict, seed: int = 0):
    rng = np.random.default_rng(seed)
    E = cfg["E"]
    if "c" in cfg:
        c = np.asarray(cfg["c"], np.int64)
        K = c.shape[0]
        A = rng.integers(0, 2, (K, E))
        A[:, A.sum(axis=0) == 0] = 1  # no all-zero demand columns
    else:
        K, c_hi = cfg["c_rand"]
        A = rng.integers(1, 3, (K, E))
        c = rng.integers(1, c_hi + 1, K)
        A = np.minimum(A, c[:, None])
    ups = rng.integers(0, cfg["u_hi"] + 1, E).astype(np.int32)
    sig = rng.integers(1, 5000, E).astype(np.int32)
    return A, c, ups, sig


def host_fingerprint() -> dict:
    """CPU model + jax version: the facts that make absolute wall-clock
    comparable between a fresh run and a committed baseline."""
    cpu = platform_mod.processor() or platform_mod.machine()
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if line.lower().startswith("model name"):
                    cpu = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    return {"cpu": cpu, "jax": jax.__version__}


def _timed(call, runs: int) -> dict:
    t0 = time.perf_counter()
    call()  # warmup: trace + compile
    warmup_ms = (time.perf_counter() - t0) * 1e3
    samples = []
    for _ in range(runs):
        t0 = time.perf_counter()
        call()
        samples.append((time.perf_counter() - t0) * 1e3)
    return {
        "warmup_ms": warmup_ms,
        "mean_ms": statistics.fmean(samples),
        "min_ms": min(samples),
        "runs": runs,
    }


def _time_solver(solver, ups, sig, tables, s_cap, runs: int, u_max: int):
    # jit the whole contract call so both backends are measured compiled
    # (the reference scan would otherwise run eagerly op-by-op); u_max is
    # the same tight bound _time_forward uses, so the kernel-vs-wrapper
    # split compares kernels with identical scratch sizes
    fn = jax.jit(lambda u, s, lim: solver(u, s, tables, s_cap, lim, None,
                                          u_max=u_max))

    def call():
        x, info = fn(jnp.asarray(ups), jnp.asarray(sig), jnp.int32(s_cap))
        jax.block_until_ready((x, info["s_star"]))
        return x

    return _timed(call, runs)


def _time_forward(
    ups,
    sig,
    tables,
    s_cap,
    runs: int,
    interpret: bool,
    u_max: int,
    block_c: int | None = None,
    block_s: int | None = None,
    block_e: int | None = None,
):
    """The DP forward kernel alone — the kernel side of the
    kernel-vs-wrapper split (mean_ms − forward_ms ≈ s*-rule + backtrack)."""
    feas, offs = prepare_tables(tables)
    S, C = s_cap + 1, tables.n_states
    v0 = jnp.full((S, C), NEG, jnp.int32).at[0, :].set(0)
    fn = jax.jit(lambda u, s: dp_forward_pallas(
        u, s, jnp.asarray(feas), jnp.asarray(offs), v0, n_edges=offs.shape[0],
        u_max=u_max, off_max=int(offs.max()),
        interpret=interpret, block_c=block_c, block_s=block_s,
        block_e=block_e))

    def call():
        jax.block_until_ready(fn(jnp.asarray(ups), jnp.asarray(sig)))

    return _timed(call, runs)


def _hbm_model(
    tables, s_cap: int, E: int, u_max: int, block_e, block_s, block_c
) -> int:
    """Modeled HBM bytes streamed by one forward solve under a tiling."""
    _, offs = prepare_tables(tables)
    return modeled_hbm_bytes(s_cap + 1, tables.n_states, E, u_max,
                             int(offs.max()), block_e, block_s, block_c)


def _verify_blocked_bitexact(
    ups,
    sig,
    tables,
    s_cap,
    u_max: int,
    block_s,
    block_c,
    interpret: bool,
    block_e=None,
    ref=None,
) -> None:
    """Acceptance contract for the blocked/tiled/fused legs: x, s*, and
    the feasibility-normalized value row are bit-exact vs the reference
    backend.  Raises on any mismatch — a wrong kernel must fail the
    benchmark, not record a fast wrong number.  ``ref`` is an optional
    precomputed reference solution — configs gating several legs solve
    the (slow, exact) reference once and share it."""
    x_ref, info_ref = ref if ref is not None else solve_budgeted_dp(
        jnp.asarray(ups, jnp.int32), jnp.asarray(sig, jnp.int32), tables,
        s_cap, jnp.int32(s_cap))
    x_t, info_t = solve_budgeted_dp_pallas(
        ups, sig, tables, s_cap, s_cap, u_max=u_max, interpret=interpret,
        block_c=block_c, block_s=block_s, block_e=block_e)
    np.testing.assert_array_equal(np.asarray(x_ref), np.asarray(x_t))
    assert int(info_ref["s_star"]) == int(info_t["s_star"])
    row_ref = np.asarray(info_ref["value_row"]).astype(np.int64)
    row_t = np.asarray(info_t["value_row"])
    np.testing.assert_array_equal(row_ref >= 0, row_t >= 0)
    np.testing.assert_array_equal(row_ref[row_ref >= 0],
                                  row_t[row_t >= 0].astype(np.int64))


def _bench_batched(
    point: dict,
    cfg: dict,
    tables,
    s_cap: int,
    u_max: int,
    runs: int,
    platform: str,
    B: int,
) -> None:
    """The fleet-batched legs for one batch size B: batched megakernel vs
    conventionally-vmapped vs launch-loop baselines, all on the SAME
    heterogeneous fleet, all bit-exact-gated before timing."""
    rng = np.random.default_rng(100 + B)
    E = cfg["E"]
    S, C = s_cap + 1, tables.n_states
    ups = rng.integers(0, cfg["u_hi"] + 1, (B, E)).astype(np.int32)
    sig = rng.integers(1, 5000, (B, E)).astype(np.int32)
    alw = rng.integers(0, 2, (B, E)).astype(np.int32)
    slim = rng.integers(0, s_cap + 1, B).astype(np.int32)
    interpret = platform != "tpu"
    tag = "pallas_interpret" if interpret else "pallas"
    feas, offs = prepare_tables(tables)
    off_max = int(offs.max())
    bb, be, bs, bc = choose_tiling(S, C, E, u_max, off_max, batch=B)

    def batched_call(u, s, l, a):
        x, info = solve_budgeted_dp_batched(u, s, tables, s_cap, l,
                                            u_max=u_max, allowed=a,
                                            interpret=interpret)
        return x, info["s_star"], info["value_row"]

    fn_batched = jax.jit(batched_call)
    # conventional vmap of the per-instance solve: ONE launch too, but the
    # eligibility fold materializes B copies of the feasibility plane —
    # the replicated-operand lowering the custom batching rule replaces
    single_kw = dict(s_cap=s_cap, u_max=u_max, off_max=off_max,
                     full_state=tables.full_state, interpret=interpret,
                     block_c=bc, block_s=bs, block_e=be)
    feas_j, offs_j = jnp.asarray(feas), jnp.asarray(offs)

    def one(u, s, l, a):
        return _solve(u, s, feas_j * a.astype(jnp.float32)[:, None],
                      offs_j, l, **single_kw)

    fn_vmapped = jax.jit(jax.vmap(one))
    fn_loop = jax.jit(lambda U, Sg, L, Al: jax.lax.map(
        lambda t: one(*t), (U, Sg, L, Al)))

    args = (jnp.asarray(ups), jnp.asarray(sig), jnp.asarray(slim),
            jnp.asarray(alw))
    # bit-exact gate: every leg vs a per-instance reference loop
    got = {"batched": fn_batched(*args), "vmapped": fn_vmapped(*args),
           "launch_loop": fn_loop(*args)}
    for b in range(B):
        x_ref, info_ref = solve_budgeted_dp(
            jnp.asarray(ups[b]), jnp.asarray(sig[b]), tables, s_cap,
            int(slim[b]), allowed=jnp.asarray(alw[b]))
        for leg, (x, s_star, _) in got.items():
            np.testing.assert_array_equal(
                np.asarray(x[b]), np.asarray(x_ref),
                err_msg=f"{leg} B={B} instance {b}")
            assert int(s_star[b]) == int(info_ref["s_star"]), (leg, B, b)

    one_hbm = modeled_hbm_bytes(S, C, E, u_max, off_max, be, bs, bc)
    batched_hbm = batched_modeled_hbm_bytes(S, C, E, u_max, off_max, B,
                                            be, bs, bc)
    recs = {}
    for leg, fn in (("batched", fn_batched), ("vmapped", fn_vmapped),
                    ("launch_loop", fn_loop)):
        rec = _timed(lambda fn=fn: jax.block_until_ready(fn(*args)), runs)
        rec["batch"] = B
        rec["solves_per_sec"] = B / (rec["mean_ms"] / 1e3)
        rec["hbm_bytes_streamed"] = (batched_hbm if leg == "batched"
                                     else B * one_hbm)
        recs[leg] = rec
    recs["batched"]["bitexact_vs_reference"] = True
    recs["batched"]["tiling"] = {"block_b": bb, "block_e": be,
                                 "block_s": bs, "block_c": bc}
    recs["batched"]["speedup_vs_vmapped"] = (
        recs["vmapped"]["mean_ms"] / recs["batched"]["mean_ms"])
    recs["batched"]["speedup_vs_launch_loop"] = (
        recs["launch_loop"]["mean_ms"] / recs["batched"]["mean_ms"])
    recs["batched"]["hbm_reduction_vs_vmapped"] = B * one_hbm / batched_hbm
    for leg, rec in recs.items():
        point["backends"][f"{tag}_{leg}_B{B}"] = rec


def _record_drift_trace(
    E: int, tables, s_cap: int, slots: int, seed: int = 7, t0: int = 200_000
):
    """A recorded post-exploration slot trace with HONEST drift structure.

    Statistics come from the paper's sampling model, not a synthetic
    mutation schedule: at slot i the scaled (Υ̂, Σ̂², s_limit) are
    ``stats.scale_statistics(v̂, n, t₀+i, m)``, and (v̂, n) then evolve
    ONLY on the edges the (exact, reference) solve dispatches — a running
    mean over fresh speed samples and a visit-count increment.  With n in
    the hundreds and t₀ ≫ 1 the ceilings freeze the integer statistics
    for long stretches, which is precisely the repeat structure the
    incremental layers exploit.  Eligibility is near-saturated (a single
    random dropout on ~10% of slots) — the heavy-load regime.

    Returns (trace, cold_out, m, u_max): per-slot concrete inputs, the
    cold reference outputs (the bit-exact gate for every incremental
    leg), the server count m sized so ξ(t_end)·m fits the config's
    budget axis, and the tight Υ̂ bound for the Pallas legs.
    """
    rng = np.random.default_rng(seed)
    t_end = float(t0 + slots)
    m = 0
    while int(stats_mod.xi_of(jnp.float32(t_end), m + 1)) * (m + 1) <= s_cap:
        m += 1
    if m == 0:
        return None, None, 0, 0
    u_max = int(stats_mod.xi_of(jnp.float32(t_end), m)) + 1

    mu = rng.uniform(0.2, 1.0, E)
    vhat = np.clip(mu + rng.normal(0, 0.02, E), 0.0, 1.0)
    n = rng.integers(200, 800, E).astype(np.int64)

    ref = get_solver("reference")
    fn = jax.jit(lambda u, s, lim, a: ref(u, s, tables, s_cap, lim,
                                          allowed=a))
    trace, cold_out = [], []
    for i in range(slots):
        ups, sig, _, s_limit = stats_mod.scale_statistics(
            jnp.asarray(vhat, jnp.float32), jnp.asarray(n, jnp.int32),
            jnp.float32(t0 + i), m)
        ups, sig = np.asarray(ups, np.int32), np.asarray(sig, np.int32)
        lim = min(int(s_limit), s_cap)
        alw = np.ones(E, bool)
        if rng.random() < 0.1:
            alw[rng.integers(0, E)] = False
        x, info = fn(jnp.asarray(ups), jnp.asarray(sig), jnp.int32(lim),
                     jnp.asarray(alw))
        x = np.asarray(x)
        trace.append((ups, sig, alw, lim))
        cold_out.append((x, int(info["s_star"]),
                         np.asarray(info["value_row"])))
        for e in np.flatnonzero(x):  # (v̂, n) drift on dispatch only
            v = float(np.clip(rng.normal(mu[e], 0.05), 0.0, 1.0))
            vhat[e] = (vhat[e] * n[e] + v) / (n[e] + 1)
            n[e] += 1
    return trace, cold_out, m, u_max


def _eq17_score(x, ups, sig, s_limit) -> float:
    """The eq.-17 objective a concrete solution realizes under the TRUE
    statistics — the utility meter for the approximate cache leg."""
    s = min(int(ups @ x), int(s_limit))
    return s + float(np.sqrt(max(int(sig @ x), 0)))


def _bench_incremental(
    point: dict, cfg: dict, tables, s_cap: int, runs: int, platform: str, slots: int
) -> None:
    """The cross-slot incremental legs over one recorded drift trace."""
    E = cfg["E"]
    trace, cold_out, m, u_max = _record_drift_trace(E, tables, s_cap, slots)
    if trace is None:
        point["incremental"] = {"skipped": "budget axis too small for the "
                                           "sampling model (m=0)"}
        return
    point["incremental"] = {"slots": slots, "m": m, "t0": 200_000,
                            "u_max": u_max}
    interpret = platform != "tpu"
    pal_tag = "pallas_interpret" if interpret else "pallas"
    ref, pal = get_solver("reference"), get_solver(
        "pallas_interpret" if interpret else "pallas")

    def gate(outs, leg):
        """Bit-exact acceptance vs the recorded cold reference outputs."""
        for i, ((x, s_star, row), (xc, sc, rowc)) in enumerate(
                zip(outs, cold_out)):
            np.testing.assert_array_equal(np.asarray(x), xc,
                                          err_msg=f"{leg} slot {i}")
            assert int(s_star) == sc, (leg, i)
            np.testing.assert_array_equal(np.asarray(row), rowc,
                                          err_msg=f"{leg} slot {i}")

    def loop_solver(solver):
        fn = jax.jit(lambda u, s, lim, a: solver(u, s, tables, s_cap, lim,
                                                 allowed=a, u_max=u_max))

        def run():
            out = []
            for u, s, a, lim in trace:
                x, info = fn(jnp.asarray(u), jnp.asarray(s), jnp.int32(lim),
                             jnp.asarray(a))
                jax.block_until_ready(x)
                out.append((x, info["s_star"], info["value_row"]))
            return out

        return run

    recs = {}

    # cold per-slot host loops: the speedup denominators
    run_ref_cold = loop_solver(ref)
    recs["incr_reference"] = _timed(run_ref_cold, runs)
    run_pal_cold = loop_solver(pal)
    gate(run_pal_cold(), f"incr_{pal_tag}")
    recs[f"incr_{pal_tag}"] = _timed(run_pal_cold, runs)
    recs[f"incr_{pal_tag}"]["bitexact_vs_cold"] = True

    # exact-key solve cache: cleared per replay — hits are within-trace
    cached = CachedSolver(ref)

    def run_cached():
        cached.cache.clear()
        return [cached(u, s, tables, s_cap, int(lim), allowed=a,
                       u_max=u_max) + (None,)
                for u, s, a, lim in trace]

    gate([(x, info["s_star"], info["value_row"])
          for x, info, _ in run_cached()], "incr_reference_cached")
    hit_rate = cached.stats.hit_rate
    rec = _timed(run_cached, runs)
    rec.update(cache_hit_rate=hit_rate, exact=True, bitexact_vs_cold=True)
    recs["incr_reference_cached"] = rec

    # quantized bounded-staleness cache: NOT exact — measure the utility
    # gap of its solutions under the true per-slot statistics
    cached_q = CachedSolver(ref, q_ups=2, q_sig=64, max_stale=2 * slots)

    def run_cached_q():
        cached_q.cache.clear()
        return [cached_q(u, s, tables, s_cap, int(lim), allowed=a,
                         u_max=u_max)
                for u, s, a, lim in trace]

    gaps = []
    for (x, _), (u, s, a, lim), (xc, _, _) in zip(run_cached_q(), trace,
                                                  cold_out):
        best = _eq17_score(xc, u, s, lim)
        gaps.append((best - _eq17_score(np.asarray(x), u, s, lim))
                    / max(best, 1.0))
    rec = _timed(run_cached_q, runs)
    rec.update(cache_hit_rate=cached_q.stats.hit_rate, exact=False,
               q_ups=2, q_sig=64,
               utility_gap_mean=float(np.mean(gaps)),
               utility_gap_max=float(np.max(gaps)))
    recs["incr_reference_cached_q"] = rec

    # warm-started reference: carry re-initialized per replay
    wfn = jax.jit(lambda u, s, lim, a, cr: solve_budgeted_dp_warm(
        u, s, tables, s_cap, lim, cr, allowed=a))

    def run_warm_ref():
        cr = warm_carry_init(E, s_cap, tables.n_states)
        out, folded = [], 0
        for u, s, a, lim in trace:
            x, info, cr = wfn(jnp.asarray(u), jnp.asarray(s),
                              jnp.int32(lim), jnp.asarray(a), cr)
            jax.block_until_ready(x)
            folded += int(info["edges_folded"])
            out.append((x, info["s_star"], info["value_row"]))
        return out, folded

    out, folded = run_warm_ref()
    gate(out, "incr_reference_warm")
    rec = _timed(lambda: run_warm_ref(), runs)
    rec.update(edge_skip_rate=1.0 - folded / (len(trace) * E), exact=True,
               bitexact_vs_cold=True)
    recs["incr_reference_warm"] = rec

    # segmented carried-plane Pallas driver: reset per replay
    warm_pal = WarmPallasSolver(tables, s_cap, u_max=u_max,
                                interpret=interpret)

    def run_warm_pal():
        warm_pal.reset()
        return [warm_pal(u, s, tables, s_cap, lim, allowed=a)
                for u, s, a, lim in trace]

    gate([(x, info["s_star"], info["value_row"])
          for x, info in run_warm_pal()], f"incr_{pal_tag}_warm")
    rec = _timed(run_warm_pal, runs)
    rec.update(edge_skip_rate=warm_pal.skip_rate, exact=True,
               bitexact_vs_cold=True)
    recs[f"incr_{pal_tag}_warm"] = rec

    for leg, rec in recs.items():
        rec["slots"] = slots
        rec["per_slot_ms"] = rec["mean_ms"] / slots
        cold = ("incr_reference" if leg.startswith("incr_reference")
                else f"incr_{pal_tag}")
        if leg != cold:
            rec["speedup_vs_cold"] = (recs[cold]["mean_ms"]
                                      / rec["mean_ms"])
        point["backends"][leg] = rec


def bench(configs, runs: int, incr_slots: int = 120) -> dict:
    platform = jax.default_backend()
    backends = ["reference", "pallas_interpret", "pallas"]
    records = []
    for cfg in configs:
        A, c, ups, sig = _make_problem(cfg)
        t0 = time.perf_counter()
        tables = build_tables(A, c)
        build_ms = (time.perf_counter() - t0) * 1e3
        t0 = time.perf_counter()
        feas, offs = prepare_tables(tables)  # offsets + feasibility plane
        prepare_ms = (time.perf_counter() - t0) * 1e3
        s_cap = int(cfg.get("s_cap", ups.sum()))
        u_max = int(ups.max() + 1)
        S, C = s_cap + 1, tables.n_states
        off_max = int(offs.max())
        unblocked = unblocked_vmem_bytes(S, C, cfg["E"], u_max, off_max)
        # the tiling the pallas backends auto-resolve for this plane: the
        # solver legs below time exactly that execution path, so the
        # long-S configs get an end-to-end mean_ms AND a kernel-vs-wrapper
        # split through the edge-fused S-tiled pipeline, not just a
        # forward number
        block_e, block_s, block_c = choose_tiling(S, C, cfg["E"], u_max,
                                                  off_max)
        auto_hbm = _hbm_model(tables, s_cap, cfg["E"], u_max,
                              block_e, block_s, block_c)
        point = {"config": cfg["name"], "E": cfg["E"], "K": len(c),
                 "n_states": C, "S": S,
                 "build_tables_ms": build_ms,
                 "prepare_operands_ms": prepare_ms,
                 "unblocked_vmem_bytes": unblocked,
                 "vmem_budget_bytes": VMEM_BUDGET_BYTES,
                 "tiling": {"block_e": block_e, "block_s": block_s,
                            "block_c": block_c},
                 "hbm_bytes_streamed": auto_hbm,
                 "backends": {}}
        # one exact reference solution per config, shared by every
        # bit-exact gate below (it is the slowest solve on the long-S
        # configs — never compute it twice)
        ref = None
        if cfg.get("verify") or cfg.get("block") or (
                block_c is not None and block_e is not None):
            ref = solve_budgeted_dp(
                jnp.asarray(ups, jnp.int32), jnp.asarray(sig, jnp.int32),
                tables, s_cap, jnp.int32(s_cap))
        if cfg.get("verify"):
            _verify_blocked_bitexact(ups, sig, tables, s_cap, u_max,
                                     block_s, block_c, platform != "tpu",
                                     block_e=block_e, ref=ref)
            point["bitexact_vs_reference"] = True
        for name in backends:
            if name == "pallas" and platform != "tpu":
                point["backends"][name] = {
                    "skipped": "compiled pallas needs TPU (platform="
                               f"{platform}); interpret leg covers the "
                               "kernel program"}
                continue
            solver = get_solver(name)
            rec = _time_solver(solver, ups, sig, tables, s_cap, runs, u_max)
            if name != "reference":
                interpret = (name == "pallas_interpret" or platform != "tpu")
                fwd = _time_forward(ups, sig, tables, s_cap, runs, interpret,
                                    u_max, block_c=block_c, block_s=block_s,
                                    block_e=block_e)
                rec["forward_ms"] = fwd["mean_ms"]
                rec["wrapper_ms"] = max(rec["mean_ms"] - fwd["mean_ms"], 0.0)
                rec["hbm_bytes_streamed"] = auto_hbm
                if block_c is not None:
                    rec["block_e"] = block_e
                    rec["block_s"], rec["block_c"] = block_s, block_c
            point["backends"][name] = rec
        if block_c is not None and block_e is not None:
            # the fused-vs-scan comparison: the SAME plane tiling forced
            # through the per-edge-scan pipeline (one pallas_call per
            # edge), bit-exact-gated, so the artifact shows what the
            # fusion buys in wall-clock AND modeled HBM traffic
            interpret = platform != "tpu"
            _verify_blocked_bitexact(ups, sig, tables, s_cap, u_max,
                                     block_s, block_c, interpret,
                                     block_e=None, ref=ref)
            fwd = _time_forward(ups, sig, tables, s_cap, runs, interpret,
                                u_max, block_c=block_c, block_s=block_s,
                                block_e=None)
            scan_hbm = _hbm_model(tables, s_cap, cfg["E"], u_max,
                                  None, block_s, block_c)
            point["backends"]["pallas_interpret_scan" if interpret
                              else "pallas_scan"] = {
                "forward_ms": fwd["mean_ms"], "warmup_ms": fwd["warmup_ms"],
                "runs": runs, "block_c": block_c, "block_s": block_s,
                "block_e": None, "hbm_bytes_streamed": scan_hbm}
            point["hbm_reduction_vs_scan"] = scan_hbm / auto_hbm
        if cfg.get("block"):
            # additionally time a FORCED tiling (e.g. the fused C-blocked
            # grid on a plane that also fits whole-plane, for comparison)
            fbe, fbs, fbc = cfg["block"]
            interpret = platform != "tpu"
            _verify_blocked_bitexact(ups, sig, tables, s_cap, u_max,
                                     fbs, fbc, interpret, block_e=fbe,
                                     ref=ref)
            fwd = _time_forward(ups, sig, tables, s_cap, runs, interpret,
                                u_max, block_c=fbc, block_s=fbs,
                                block_e=fbe)
            point["backends"]["pallas_interpret_blocked" if interpret
                              else "pallas_blocked"] = {
                "forward_ms": fwd["mean_ms"], "warmup_ms": fwd["warmup_ms"],
                "runs": runs, "block_c": fbc, "block_s": fbs,
                "block_e": fbe,
                "hbm_bytes_streamed": _hbm_model(tables, s_cap, cfg["E"],
                                                 u_max, fbe, fbs, fbc)}
        for B in cfg.get("batch", ()):
            _bench_batched(point, cfg, tables, s_cap, u_max, runs,
                           platform, B)
        if cfg.get("incremental"):
            _bench_incremental(point, cfg, tables, s_cap, runs, platform,
                               incr_slots)
        records.append(point)
        print(f"{cfg['name']}: E={cfg['E']} C={C} "
              f"S={S}: " + "  ".join(
                  f"{n}={r['mean_ms']:.2f}ms" if "mean_ms" in r
                  else (f"{n}[fwd]={r['forward_ms']:.2f}ms"
                        if "forward_ms" in r else f"{n}=skip")
                  for n, r in point["backends"].items()), flush=True)
    return {"platform": platform, "jax": jax.__version__,
            "host": host_fingerprint(), "grid": records}


def _guard_ms(rec: dict):
    """The guarded timing of one backend record: the end-to-end mean when
    present, else the forward-only mean (the blocked/tiled legs)."""
    return rec.get("mean_ms", rec.get("forward_ms"))


def check_baseline(result: dict, base: dict, max_regression: float) -> list[str]:
    """Compare per-config/backend timings against a committed baseline.

    Keyed on (E, n_states, S, backend) so baselines written before configs
    had names (including the one-hot-era files) still compare.  Only pairs
    present in both files are checked; returns the list of violations.
    """
    base_ms = {}
    for point in base.get("grid", []):
        for backend, rec in point["backends"].items():
            if _guard_ms(rec) is not None:
                base_ms[(point["E"], point["n_states"], point["S"],
                         backend)] = _guard_ms(rec)
    failures = []
    for point in result["grid"]:
        for backend, rec in point["backends"].items():
            key = (point["E"], point["n_states"], point["S"], backend)
            got = _guard_ms(rec)
            if got is None or key not in base_ms:
                continue
            if got > max_regression * base_ms[key]:
                failures.append(
                    f"{point.get('config', key)}/{backend}: "
                    f"{got:.2f}ms vs baseline "
                    f"{base_ms[key]:.2f}ms (> {max_regression:.1f}x)")
    return failures


def fingerprints_match(result: dict, base: dict) -> bool:
    """Absolute wall-clock only compares within one machine class: same CPU
    model and jax version.  Baselines from before fingerprints were
    recorded never match (they cannot be attributed to a host)."""
    fresh, committed = result.get("host"), base.get("host")
    return bool(fresh and committed and fresh == committed)


def apply_baseline_guard(
    result: dict, base: dict, baseline_path: str, max_regression: float, failures: list
) -> None:
    """Shared guard epilogue (dp_bench and scenarios_bench): fail the run
    on regressions within one machine class, warn when the host
    fingerprint differs (absolute wall-clock is not comparable across
    machines — refresh the committed baseline from the comparison machine
    class to re-arm the strict check)."""
    if failures and not fingerprints_match(result, base):
        print("WARNING: host fingerprint differs from baseline "
              f"({result.get('host')} vs {base.get('host')}); "
              "would-be regressions reported as warnings only — refresh "
              f"{baseline_path} from the comparison machine to re-arm")
        for f in failures:
            print("  WARN " + f)
    elif failures:
        print("PERF REGRESSION vs " + baseline_path)
        for f in failures:
            print("  " + f)
        sys.exit(1)
    else:
        print(f"no >{max_regression:.1f}x regression vs {baseline_path}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="CI-sized grid")
    ap.add_argument("--runs", type=int, default=10)
    ap.add_argument("--out", default="results/BENCH_dp.json")
    ap.add_argument("--baseline", default=None,
                    help="committed BENCH_dp.json to guard against")
    ap.add_argument("--max-regression", type=float, default=2.0,
                    help="fail when mean_ms exceeds baseline by this factor")
    args = ap.parse_args()
    enable_compile_cache()
    configs = ([c for c in CONFIGS if c["name"] in SMOKE_NAMES]
               if args.smoke else CONFIGS)
    if args.smoke:  # CI sizes: keep only the B=8 fleet leg
        configs = [dict(c, batch=tuple(b for b in c["batch"] if b == 8))
                   if "batch" in c else c for c in configs]
    # read the baseline up front: --out may legitimately overwrite it
    base = None
    if args.baseline:
        bpath = pathlib.Path(args.baseline)
        if not bpath.exists():
            sys.exit(f"baseline {bpath} not found — refresh it with: "
                     "PYTHONPATH=src python -m benchmarks.dp_bench "
                     f"--runs 30 --out {bpath}")
        base = json.loads(bpath.read_text())
    out = bench(configs,
                max(1, args.runs if not args.smoke else min(args.runs, 3)),
                incr_slots=32 if args.smoke else 120)
    path = pathlib.Path(args.out)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(out, indent=2))
    print(f"wrote {path}")
    if base is not None:
        apply_baseline_guard(out, base, args.baseline, args.max_regression,
                             check_baseline(out, base, args.max_regression))


if __name__ == "__main__":
    main()
