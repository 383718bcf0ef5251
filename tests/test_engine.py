"""Streaming dispatch engine tests (``sched.engine``).

The load-bearing invariants:

  * adapter faithfulness — ``ClusterSim.run`` (now a thin adapter over
    ``engine.lockstep_run``) stays trace-equivalent to a compact
    reimplementation of the pre-engine loop on all six registered
    fluctuation regimes;
  * stream/lockstep bit-identity — the jitted ``lax.scan`` path and the
    host-driven path compose the same slot functions, so fault-free they
    agree bit for bit;
  * ledger conservation — ``arrivals = rejected + blocked + admitted``
    and ``admitted = dispatched + dropped + shed + final_queue``, under
    every backpressure policy;
  * dead-letter isolation — rejected arrivals never consume capacity and
    never enter the bandit statistics;
  * deterministic A/B routing — same seed ⇒ same variant assignment,
    split ≈ weights, different salt ⇒ different assignment;
  * one-launch scaling — the stream jaxpr contains a single scan and its
    equation count does not grow with the horizon.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import stats as stats_mod
from repro.core.baselines import greedy_pack
from repro.core.dp import oracle_knapsack
from repro.core.graph import generate_instance
from repro.experiments import get_scenario, scenario_names
from repro.sched import (BACKPRESSURE_POLICIES, ClusterSim, DispatchEngine,
                         EngineConfig, FailureModel, JobType, Slice,
                         VariantSpec, feasible_ports, validate_jobs)

REGIMES = ("iid", "markov_dvfs", "mmpp_arrivals", "chronic_straggler",
           "transient_brownout", "elastic_outage", "power_coupled")

AB = EngineConfig(variants=(VariantSpec("esdp", weight=0.9),
                            VariantSpec("challenger", kind="hswf",
                                        weight=0.1)))

ENGINE_FIELDS = ("sw", "regret", "dispatch_share", "sw_variant",
                 "regret_variant", "dispatched_variant", "routed_variant",
                 "n", "sumz", "queue_len")


@pytest.fixture(scope="module")
def inst():
    return generate_instance(seed=0)


def assert_conserves(out):
    led = out.ledger
    assert led["total_arrivals"] == (led["total_rejected"]
                                     + led["total_blocked"]
                                     + led["total_admitted"])
    assert led["total_admitted"] == (led["total_dispatched"]
                                     + led["total_dropped"]
                                     + led["total_shed"]
                                     + led["final_queue"])


# ---------------------------------------------------------------------------
# adapter faithfulness: ClusterSim.run == the pre-engine loop, bit for bit
# ---------------------------------------------------------------------------

def _reference_run(sim, policy="esdp", tiebreak=1e-4):
    """Compact reimplementation of the pre-engine ``ClusterSim.run`` loop
    (plain backend, no failure runtime) — the trace ``lockstep_run`` must
    keep reproducing exactly."""
    inst, tables = sim.inst, sim.tables
    E = inst.n_edges
    port = inst.port_of_edge
    server = inst.edges[:, 1]
    arrivals, noise = sim._streams()
    rng = np.random.default_rng(sim.seed + 1)
    n = np.zeros(E, np.int64)
    sumz = np.zeros(E, np.float64)
    waiting = np.zeros(inst.n_ports, np.int64)
    sw = np.zeros(sim.T, np.float32)
    regret = np.zeros(sim.T, np.float32)
    share = np.zeros((sim.T, inst.n_servers), np.float32)
    jit_dp = jax.jit(lambda u, s, lim, al: sim.solver(
        u, s, tables, sim.s_cap, lim, allowed=al, u_max=sim.u_max)[0])
    jit_oracle = jax.jit(lambda v, al: oracle_knapsack(v, tables, al)[0])
    jit_greedy = jax.jit(lambda sc, el: greedy_pack(
        sc, el, jnp.asarray(inst.A), jnp.asarray(inst.c)))
    for t0 in range(sim.T):
        alive_srv = np.asarray(sim.alive_fn(t0), bool)
        allowed = arrivals[t0][port] & alive_srv[server]
        vhat = np.where(n > 0, sumz / np.maximum(n, 1), 0.0).astype(
            np.float32)
        if policy == "esdp":
            ups, sig, _, s_lim = stats_mod.scale_statistics(
                jnp.asarray(vhat), jnp.asarray(n.astype(np.int32)),
                jnp.float32(t0 + 1), sim.m, g_fn=sim.g_fn)
            x = np.asarray(jit_dp(ups, sig, s_lim, jnp.asarray(allowed)))
        else:
            tb = rng.random(E).astype(np.float32) * tiebreak
            score = {"hswf": vhat + tb, "lcf": -inst.cost + tb,
                     "lwtf": waiting[port] * 1e3 + vhat + tb}[policy]
            x = np.asarray(jit_greedy(jnp.asarray(score),
                                      jnp.asarray(allowed)))
        x = x * allowed
        z = sim._z(t0, noise[t0])
        sw[t0] = float((x * z).sum())
        v_true = sim._v_true(t0)
        x_star = np.asarray(jit_oracle(jnp.asarray(v_true),
                                       jnp.asarray(allowed)))
        regret[t0] = float((v_true * x_star).sum() - (v_true * x).sum())
        n += x
        sumz += x * z
        served = np.zeros(inst.n_ports, bool)
        np.maximum.at(served, port, x > 0)
        waiting = np.where(served, 0, waiting + arrivals[t0])
        if x.sum() > 0:
            np.add.at(share[t0], server, x / x.sum())
    return sw, regret, share


@pytest.mark.parametrize("scenario", REGIMES)
def test_adapter_trace_equivalent_on_regimes(inst, scenario):
    assert scenario in scenario_names()
    sim = ClusterSim(inst, 48, scenario=get_scenario(scenario), seed=11)
    out = sim.run("esdp")
    sw, regret, share = _reference_run(sim, "esdp")
    np.testing.assert_array_equal(out.sw, sw)
    np.testing.assert_array_equal(out.regret, regret)
    np.testing.assert_array_equal(out.dispatch_share, share)


@pytest.mark.parametrize("policy", ["hswf", "lcf", "lwtf"])
def test_adapter_trace_equivalent_greedy_policies(inst, policy):
    sim = ClusterSim(inst, 48, scenario=get_scenario("markov_dvfs"), seed=11)
    out = sim.run(policy)
    sw, regret, share = _reference_run(sim, policy)
    np.testing.assert_array_equal(out.sw, sw)
    np.testing.assert_array_equal(out.regret, regret)
    np.testing.assert_array_equal(out.dispatch_share, share)


# ---------------------------------------------------------------------------
# stream/lockstep bit-identity + ledger conservation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("config", [None, AB], ids=["single", "ab"])
def test_stream_matches_lockstep_bitwise(inst, config):
    eng = DispatchEngine(inst, 80, config, seed=3)
    o_s, o_l = eng.run(mode="stream"), eng.run(mode="lockstep")
    assert o_s.mode == "stream" and o_l.mode == "lockstep"
    for f in ENGINE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(o_s, f)), np.asarray(getattr(o_l, f)),
            err_msg=f)
    assert_conserves(o_s)
    assert_conserves(o_l)
    assert o_s.ledger["total_dispatched"] > 0


def test_stream_replay_deterministic(inst):
    a = DispatchEngine(inst, 60, AB, seed=5).run(mode="stream")
    b = DispatchEngine(inst, 60, AB, seed=5).run(mode="stream")
    for f in ENGINE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)), err_msg=f)


# ---------------------------------------------------------------------------
# backpressure
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", BACKPRESSURE_POLICIES)
def test_backpressure_policy_table(inst, policy):
    """Under pressure, exactly the configured overflow channel fires —
    and the ledger still balances."""
    cfg = EngineConfig(queue_capacity=1, backpressure=policy)
    out = DispatchEngine(inst, 80, cfg, arr_scale=3.0,
                         seed=5).run(mode="stream")
    led = out.ledger
    active = {"drop_oldest": "dropped", "block": "blocked",
              "shed_by_utility": "shed"}[policy]
    assert led[f"total_{active}"] > 0
    for ch in ("dropped", "blocked", "shed"):
        if ch != active:
            assert led[f"total_{ch}"] == 0
    assert_conserves(out)


def test_engine_config_validates(inst):
    with pytest.raises(ValueError, match="backpressure"):
        EngineConfig(backpressure="bogus")
    with pytest.raises(ValueError, match="unique"):
        EngineConfig(variants=(VariantSpec("a"), VariantSpec("a")))
    with pytest.raises(ValueError, match="kind"):
        VariantSpec("x", kind="bogus")
    with pytest.raises(ValueError):
        DispatchEngine(inst, 10).run(mode="bogus")


# ---------------------------------------------------------------------------
# admission: dead-letter isolation
# ---------------------------------------------------------------------------

def test_dead_letter_never_consumes(inst):
    """Arrivals on a never-feasible port are rejected at admission: no
    capacity use, no bandit observations, and the feasible ports dispatch
    exactly as if the dead port's traffic never existed."""
    A2 = inst.A.copy()
    A2[:, inst.port_of_edge == 0] = int(inst.c.max()) + 5
    bad = dataclasses.replace(inst, A=A2)
    ok = feasible_ports(bad)
    assert not ok[0] and ok[1:].all()

    out = DispatchEngine(bad, 80, seed=3).run(mode="stream")
    assert out.ledger["total_rejected"] > 0
    bad_edges = ~ok[bad.port_of_edge]
    assert np.asarray(out.n)[:, bad_edges].sum() == 0
    assert np.asarray(out.sumz)[:, bad_edges].sum() == 0
    assert_conserves(out)


def test_validate_jobs_preflight():
    slices = [Slice("pod-a", "v5e", 256, 32, 4)]
    jobs = [JobType("ok", "m", "s", ("v5e",), 256, 32, 4, value_rate=1.0),
            JobType("wrong-accel", "m", "s", ("trn2",), 8, 1, 1,
                    value_rate=1.0),
            JobType("too-big", "m", "s", ("v5e",), 512, 64, 8,
                    value_rate=1.0)]
    reasons = validate_jobs(slices, jobs)
    assert set(reasons) == {"wrong-accel", "too-big"}
    assert "accelerator" in reasons["wrong-accel"]
    assert "exceeds" in reasons["too-big"]


# ---------------------------------------------------------------------------
# A/B routing
# ---------------------------------------------------------------------------

def test_ab_split_deterministic_and_weighted(inst):
    a = DispatchEngine(inst, 400, AB, seed=7).run(mode="stream")
    b = DispatchEngine(inst, 400, AB, seed=7).run(mode="stream")
    np.testing.assert_array_equal(a.routed_variant, b.routed_variant)
    assert a.variants == ("esdp", "challenger")
    tot = np.asarray(a.routed_variant).sum(axis=0).astype(float)
    assert tot.sum() > 0
    frac = tot / tot.sum()
    assert abs(frac[0] - 0.9) < 0.05, frac
    # per-variant accounting decomposes the totals
    np.testing.assert_allclose(
        np.asarray(a.sw_variant).sum(axis=1), np.asarray(a.sw),
        rtol=1e-5, atol=1e-5)
    assert np.asarray(a.dispatched_variant).sum() \
        == a.ledger["total_dispatched"]


def test_route_salt_changes_assignment(inst):
    base = DispatchEngine(inst, 400, AB, seed=7).run(mode="stream")
    salted_cfg = EngineConfig(variants=AB.variants, route_salt=0xBEEF)
    salted = DispatchEngine(inst, 400, salted_cfg, seed=7).run(mode="stream")
    assert not np.array_equal(base.routed_variant, salted.routed_variant)


def test_single_variant_routes_everything(inst):
    out = DispatchEngine(inst, 60, seed=1).run(mode="stream")
    routed = np.asarray(out.routed_variant)
    assert routed.shape[1] == 1
    assert routed.sum() == out.ledger["total_arrivals"] \
        - out.ledger["total_rejected"]


# ---------------------------------------------------------------------------
# scaling: one jitted call per trace, batch == per-seed
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scenario", [None, "power_coupled"])
def test_jaxpr_single_scan_horizon_independent(inst, scenario):
    """The stream path stays ONE jitted lax.scan with a horizon-independent
    jaxpr — including under the coupled-speed regime, whose schedule enters
    as precomputed scan inputs rather than extra equations."""
    scn = get_scenario(scenario) if scenario else None
    eng = DispatchEngine(inst, 1000, scenario=scn)
    j1 = eng.make_stream_jaxpr(1_000)
    j2 = eng.make_stream_jaxpr(1_000_000)
    scans = [e for e in j1.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    assert len(j1.jaxpr.eqns) == len(j2.jaxpr.eqns)


def test_run_batch_matches_per_seed(inst):
    outs = DispatchEngine(inst, 60, AB, seed=0).run_batch([11, 12, 13])
    for s, ob in zip([11, 12, 13], outs):
        one = DispatchEngine(inst, 60, AB, seed=s).run(mode="stream")
        for f in ENGINE_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(one, f)), np.asarray(getattr(ob, f)),
                err_msg=f"seed {s}: {f}")


# ---------------------------------------------------------------------------
# failure runtime integration (lockstep)
# ---------------------------------------------------------------------------

def test_failure_lockstep_per_variant_ledgers(inst):
    fm = FailureModel(p_crash=0.1, redundancy=2)
    out = DispatchEngine(inst, 60, AB, seed=3, failures=fm).run(mode="auto")
    assert out.mode == "lockstep"  # auto routes failure runs host-side
    fv = out.failures["per_variant"]
    assert set(fv) == set(out.variants)
    for name in out.variants:
        led = fv[name]
        np.testing.assert_allclose(
            np.asarray(led["dispatched"]),
            np.asarray(led["completed"]) + np.asarray(led["lost"])
            + np.asarray(led["salvaged"]), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(out.failures["dispatched"]),
        sum(np.asarray(fv[n]["dispatched"]) for n in out.variants),
        rtol=1e-6, atol=1e-6)
    assert_conserves(out)


@pytest.mark.parametrize("solver", ["reference", "pallas_interpret"])
def test_stream_matches_plain_reference_with_several_starts(solver):
    """At capacity 6 per device type (Fig. 6's largest) the capacity check
    packs several gang jobs into a slot; the stream path still equals the
    benchmark's plain reference in every integer output (ledger, routing,
    bandit counts), and its float sums of several terms agree to rounding."""
    from bench.reference import Reference
    from bench.traffic.gen import streams

    inst = generate_instance(seed=1, n_ports=8, n_servers=24, edge_prob=0.2,
                             c_lo=6, c_hi=6)
    T, seed = 40, 5
    eng = DispatchEngine(inst, T, EngineConfig(
        variants=(VariantSpec("esdp", solver=solver),)))
    trace = streams(inst.rho, inst.n_edges, T, seed)
    out = eng.run(mode="stream", seed=seed, streams=trace)
    ref = Reference.for_instance(
        inst, T, {"queue_capacity": 4, "backpressure": "drop_oldest"})
    assert ref.C == 343
    want = ref.replay(trace[0], trace[1])
    assert out.ledger["dispatched"].max() >= 2
    assert out.ledger["dispatched"].mean() > 1
    for key in ("arrivals", "rejected", "blocked", "dropped", "shed",
                "admitted", "dispatched", "queue_len"):
        np.testing.assert_array_equal(out.ledger[key], want[key], key)
    np.testing.assert_array_equal(out.routed_variant, want["routed"])
    np.testing.assert_array_equal(out.n, want["n"])
    for got, key in ((out.sw, "sw"), (out.regret, "regret"),
                     (out.dispatch_share, "share"), (out.sumz, "sumz")):
        np.testing.assert_allclose(got, want[key], rtol=0, atol=1e-6,
                                   err_msg=key)
