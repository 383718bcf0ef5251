"""Robustness-layer tests: the failure-aware cluster runtime (crash/repair
scenarios, redundancy, opportunistic checkpointing, detection-driven
eligibility) and the graceful-degradation solver chain (FallbackSolver with
DP-invariant output validation and deterministic fault injection).

The load-bearing invariants:

  * ledger conservation — ``completed + lost + salvaged = dispatched``
    exactly, per slot, under every mitigation combination;
  * replay determinism — same seed, same crash stream, same ledger
    (counter-based injector, no hidden generator state);
  * zero-cost wrappers — a no-op FailureModel and a fault-free
    FallbackSolver are bit-invisible (identical sw/regret; identical
    jaxpr under trace);
  * exact degradation — with faults injected, results stay bit-identical
    to the fault-free run because every chain link is bit-exact.
"""
import copy

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import build_tables, simulate, simulate_batch
from repro.core.baselines import hswf_factory
from repro.core.dp import NEG
from repro.core.env import crash_events
from repro.core.solvers import FallbackSolver, get_solver
from repro.experiments import get_scenario, scenario_names, unroll_scenario
from repro.kernels.budgeted_dp.ops import VALUE_BOUND, validate_value_row
from repro.runtime.fault import (FAULT_RATE_ENV, InjectedFault,
                                 fault_rate_from_env, planned_fault)
from repro.sched import (ClusterSim, FailureModel, JobType, Slice,
                         build_instance, rate_matrix)

REF = get_solver("reference")


@pytest.fixture(scope="module")
def cluster():
    slices = [Slice("pod-a", "v5e", 256, 32, 4),
              Slice("pod-b", "v5e", 256, 32, 4),
              Slice("pod-c", "v5p", 256, 32, 4)]
    jobs = [JobType("train", "qwen2.5-32b", "train_4k", ("v5e", "v5p"),
                    256, 32, 4, value_rate=1.0),
            JobType("decode", "deepseek-v3-671b", "decode_32k", ("v5e",),
                    256, 32, 4, value_rate=1.2)]
    rates = rate_matrix(jobs, slices)
    inst, _ = build_instance(slices, jobs, rates, seed=0)
    return inst


def _lemon_scenario(**over):
    """The failure regime the recovery tests share: crashy cluster with a
    lemon subset and spare capacity for replicas."""
    kw = dict(p_crash=0.12, p_repair=0.6, lemon_frac=0.34, lemon_mult=3.0,
              arr_scale=0.6)
    kw.update(over)
    return get_scenario("server_failures", **kw)


# ---------------------------------------------------------------------------
# crash-event coupling
# ---------------------------------------------------------------------------

def test_crash_events_helper():
    alive = np.array([[1, 1], [0, 1], [1, 1], [1, 0]], bool)
    ev = crash_events(alive)
    # up at t, down at t+1 => crashed during slot t; last slot never flags
    np.testing.assert_array_equal(
        ev, np.array([[1, 0], [0, 0], [0, 1], [0, 0]], bool))


def test_server_failures_scenario_registered():
    assert "server_failures" in scenario_names()
    scn = _lemon_scenario()
    arr, speed, alive = unroll_scenario(scn, 120, 6, seed=4, n_ports=2)
    assert not alive.all() and alive.any()  # crashes AND repairs both fire
    assert crash_events(alive).any()
    np.testing.assert_allclose(arr, 0.6)  # arr_scale reaches the ports
    np.testing.assert_allclose(speed, 1.0)  # failures, not stragglers


def test_scenario_trace_invariance_server_failures(cluster):
    """server_failures runs identically through the jitted env (simulate /
    simulate_batch, decision bit-exact) and drives ClusterSim's aliveness:
    a down server gets zero dispatch share that slot."""
    inst = cluster
    tables = build_tables(inst.A, inst.c)
    T, seeds = 80, (0, 1)
    scn = _lemon_scenario()
    policy = hswf_factory()(inst, T, tables)
    batch = simulate_batch(inst, policy, T, seeds, tables=tables,
                           scenario=scn)
    for i, s in enumerate(seeds):
        one = simulate(inst, policy, T, seed=s, tables=tables, scenario=scn)
        np.testing.assert_array_equal(batch.n_dispatched[i], one.n_dispatched)
        np.testing.assert_array_equal(batch.regret[i], one.regret)
        np.testing.assert_allclose(batch.sw[i], one.sw, rtol=1e-6, atol=1e-6)

    _, _, alive = unroll_scenario(scn, T, inst.n_servers, seed=2)
    assert not alive.all()
    out = ClusterSim(inst, T, scenario=scn, seed=2).run("esdp")
    assert out.dispatch_share[~alive].sum() == 0.0


# ---------------------------------------------------------------------------
# failure-aware runtime: ledger conservation + determinism
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model", [
    FailureModel(p_crash=0.15),
    FailureModel(p_crash=0.15, redundancy=2),
    FailureModel(p_crash=0.15, checkpoints=2, checkpoint_cost=0.003),
    FailureModel(p_crash=0.1, n_racks=2, p_rack=0.1, detect=True),
    FailureModel(p_crash=0.2, redundancy=3, checkpoints=3,
                 checkpoint_cost=0.005, detect=True),
], ids=["bare", "redundant", "checkpoint", "racks+detect", "all"])
@pytest.mark.parametrize("seed", [0, 1])
def test_failure_ledger_conservation(cluster, model, seed):
    """dispatched = completed + lost + salvaged, exactly, per slot — and
    sw = completed + salvaged − checkpoint costs."""
    out = ClusterSim(cluster, 60, seed=seed, failures=model).run("esdp")
    led = out.failures
    np.testing.assert_allclose(
        led["dispatched"], led["completed"] + led["lost"] + led["salvaged"],
        rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        out.sw, led["completed"] + led["salvaged"] - led["ckpt_cost"],
        rtol=1e-5, atol=1e-5)
    assert led["total_dispatched"] > 0
    assert led["restarts"] >= int(led["lost"].sum() > 0)
    assert led["model"] == {
        "p_crash": model.p_crash, "n_racks": model.n_racks,
        "p_rack": model.p_rack, "redundancy": model.redundancy,
        "checkpoints": model.checkpoints,
        "checkpoint_cost": model.checkpoint_cost, "detect": model.detect}


def test_failure_runtime_replay_deterministic(cluster):
    model = FailureModel(p_crash=0.15, redundancy=2, checkpoints=2,
                         checkpoint_cost=0.003)
    a = ClusterSim(cluster, 60, seed=3, failures=model).run("esdp")
    b = ClusterSim(cluster, 60, seed=3, failures=model).run("esdp")
    np.testing.assert_array_equal(a.sw, b.sw)
    np.testing.assert_array_equal(a.regret, b.regret)
    assert a.failures["restarts"] == b.failures["restarts"]
    for k in ("dispatched", "completed", "lost", "salvaged", "crashes"):
        np.testing.assert_array_equal(a.failures[k], b.failures[k])


@pytest.mark.parametrize("model", [
    FailureModel(p_crash=0.15),
    FailureModel(p_crash=0.2, redundancy=2, checkpoints=2,
                 checkpoint_cost=0.003, detect=True),
], ids=["bare", "all"])
def test_engine_per_variant_ledger_conservation(cluster, model):
    """The streaming engine's A/B rollout keeps the PR 8 conservation law
    *per variant* — ``dispatched = completed + lost + salvaged`` for each
    arm — while overflow shedding is ledgered separately and neither shed
    nor rejected jobs ever enter the bandit statistics."""
    from repro.sched import DispatchEngine, EngineConfig, VariantSpec

    # global bound 1 with both ports arriving every slot: the second
    # arrival of a slot always overflows, so shedding provably fires
    cfg = EngineConfig(
        queue_capacity=1, total_capacity=1,
        backpressure="shed_by_utility",
        variants=(VariantSpec("esdp", weight=0.9),
                  VariantSpec("challenger", kind="hswf", weight=0.1)))
    out = DispatchEngine(cluster, 60, cfg, arr_scale=2.0, seed=1,
                         failures=model).run(mode="lockstep")
    fv = out.failures["per_variant"]
    assert set(fv) == set(out.variants)
    for name in out.variants:
        led = fv[name]
        np.testing.assert_allclose(
            np.asarray(led["dispatched"]),
            np.asarray(led["completed"]) + np.asarray(led["lost"])
            + np.asarray(led["salvaged"]), rtol=1e-6, atol=1e-6)
    # the combined ledger is exactly the sum of the per-variant ledgers
    np.testing.assert_allclose(
        np.asarray(out.failures["dispatched"]),
        sum(np.asarray(fv[n]["dispatched"]) for n in out.variants),
        rtol=1e-6, atol=1e-6)
    # shed jobs are ledgered, not silently lost — and every bandit
    # observation corresponds to a dispatched unit (shed/rejected jobs
    # never feed the estimator)
    led = out.ledger
    assert led["total_shed"] > 0
    assert led["total_arrivals"] == (led["total_rejected"]
                                     + led["total_blocked"]
                                     + led["total_admitted"])
    assert led["total_admitted"] == (led["total_dispatched"]
                                     + led["total_dropped"]
                                     + led["total_shed"]
                                     + led["final_queue"])
    assert int(np.asarray(out.n).sum()) == led["total_dispatched"]


def test_zero_failure_model_is_invisible(cluster):
    """A no-op FailureModel (no crash channels, all servers up) changes
    nothing: bit-identical sw/regret, and the ledger shows every dispatched
    unit completing."""
    plain = ClusterSim(cluster, 60, seed=5).run("esdp")
    fm = ClusterSim(cluster, 60, seed=5, failures=FailureModel()).run("esdp")
    np.testing.assert_array_equal(plain.sw, fm.sw)
    np.testing.assert_array_equal(plain.regret, fm.regret)
    led = fm.failures
    assert led["total_lost"] == 0.0 and led["total_salvaged"] == 0.0
    np.testing.assert_array_equal(led["dispatched"], led["completed"])
    assert led["restarts"] == 0


def test_run_batch_rejects_failures(cluster):
    sim = ClusterSim(cluster, 10, failures=FailureModel(p_crash=0.1))
    with pytest.raises(NotImplementedError):
        sim.run_batch((0, 1))


def test_failure_model_validates():
    with pytest.raises(ValueError):
        FailureModel(redundancy=0)
    with pytest.raises(ValueError):
        FailureModel(checkpoint_cost=-0.1)


# ---------------------------------------------------------------------------
# mitigations actually mitigate (the arXiv:1707.01655 axis)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def crashy_runs(cluster):
    """naive / redundant / checkpointing runs of the same crashy regime."""
    T, seed = 200, 4
    scn = _lemon_scenario()

    def run(model):
        return ClusterSim(cluster, T, scenario=scn, seed=seed,
                          failures=model).run("esdp")

    return {
        "naive": run(FailureModel()),
        "redundant": run(FailureModel(redundancy=2)),
        "checkpoint": run(FailureModel(checkpoints=3,
                                       checkpoint_cost=0.003)),
    }


def test_redundancy_recovers_lost_utility(crashy_runs):
    naive, red = crashy_runs["naive"], crashy_runs["redundant"]
    assert red.failures["replicas"].sum() > 0  # spare capacity was used
    assert red.failures["total_lost"] < naive.failures["total_lost"]
    assert red.asw > naive.asw


def test_checkpointing_recovers_lost_utility(crashy_runs):
    naive, ck = crashy_runs["naive"], crashy_runs["checkpoint"]
    assert ck.failures["total_salvaged"] > 0
    assert ck.failures["total_ckpt_cost"] > 0  # salvage is not free
    assert ck.failures["total_lost"] < naive.failures["total_lost"]
    assert ck.asw > naive.asw


def test_detection_routes_around_lemons(cluster):
    """With persistent lemon hosts, CrashRateTracker-driven eligibility
    cuts the number of crashed dispatches."""
    T, seed = 200, 4
    scn = _lemon_scenario()
    naive = ClusterSim(cluster, T, scenario=scn, seed=seed,
                       failures=FailureModel()).run("esdp")
    det = ClusterSim(cluster, T, scenario=scn, seed=seed,
                     failures=FailureModel(detect=True)).run("esdp")
    assert det.failures["restarts"] < naive.failures["restarts"]


# ---------------------------------------------------------------------------
# value-plane validation (the invariant checks behind the fallback chain)
# ---------------------------------------------------------------------------

def _solved_row():
    rng = np.random.default_rng(0)
    A = rng.integers(1, 3, size=(2, 6))
    c = rng.integers(2, 4, size=2)
    A = np.minimum(A, c[:, None])
    ups = rng.integers(1, 5, size=6).astype(np.int32)
    sig = rng.integers(1, 5000, size=6).astype(np.int32)
    tables = build_tables(A, c)
    s_cap = int(ups.sum())
    _, info = REF(jnp.asarray(ups), jnp.asarray(sig), tables, s_cap,
                  jnp.int32(s_cap))
    return np.asarray(info["value_row"])


def test_validate_value_row_accepts_real_planes():
    row = _solved_row()
    assert validate_value_row(row) is None
    assert validate_value_row(np.stack([row, row])) is None  # batched


def test_validate_value_row_rejects_corruption():
    row = _solved_row()
    n_feas = int((row != NEG).sum())
    assert n_feas >= 3  # the checks below need an interior feasible entry

    def poisoned(idx, val):
        bad = row.copy()
        bad[idx] = val
        return bad

    assert "source" in validate_value_row(poisoned(0, NEG))
    assert "source" in validate_value_row(poisoned(0, -5))
    assert "neg-contract" in validate_value_row(poisoned(n_feas - 1, -5))
    assert "value-bound" in validate_value_row(poisoned(0, VALUE_BOUND))
    assert VALUE_BOUND == 2 ** 29  # |NEG| of the int32 planes
    # past 2^24 (float32's exact integers) a value is still a valid one
    assert validate_value_row(poisoned(0, 2 ** 24 + 1)) is None
    assert validate_value_row(poisoned(0, VALUE_BOUND - 1)) is None
    # NEG hole inside the feasible prefix
    assert "feasible-prefix" in validate_value_row(poisoned(n_feas // 2, NEG))
    # a value row must be non-increasing in the budget s
    rising = row.copy()
    rising[n_feas - 1] = rising[0] + 1
    assert "monotone" in validate_value_row(rising)
    # batched: the failing row is named
    assert "row 1" in validate_value_row(np.stack([row, rising]))


# ---------------------------------------------------------------------------
# FallbackSolver: chain construction, exactness, degradation accounting
# ---------------------------------------------------------------------------

def test_fallback_chain_construction():
    fb = FallbackSolver("pallas")
    assert fb.name == "fallback:pallas->pallas_interpret->reference"
    assert FallbackSolver("reference").chain == (REF,)
    assert FallbackSolver(
        "pallas_interpret").name == "fallback:pallas_interpret->reference"
    # solver-shaped wrappers pass through get_solver unchanged, so every
    # consumer taking solver= accepts a preassembled chain
    assert get_solver(fb) is fb
    with pytest.raises(ValueError):
        FallbackSolver(chain=())


def _fallback_problem():
    rng = np.random.default_rng(1)
    A = rng.integers(1, 3, size=(2, 6))
    c = rng.integers(2, 4, size=2)
    A = np.minimum(A, c[:, None])
    ups = rng.integers(1, 5, size=6).astype(np.int32)
    sig = rng.integers(1, 5000, size=6).astype(np.int32)
    return build_tables(A, c), ups, sig, int(ups.sum())


def test_fallback_matches_plain_backend():
    tables, ups, sig, s_cap = _fallback_problem()
    fb = FallbackSolver("reference", fault_rate=0.0)
    x, info = fb(ups, sig, tables, s_cap, s_cap)
    xr, infor = REF(jnp.asarray(ups), jnp.asarray(sig), tables, s_cap,
                    jnp.int32(s_cap))
    np.testing.assert_array_equal(x, np.asarray(xr))
    np.testing.assert_array_equal(info["value_row"],
                                  np.asarray(infor["value_row"]))
    assert int(info["s_star"]) == int(infor["s_star"])
    st = fb.stats
    assert st["calls"] == 1 and st["served_by"]["reference"] == 1
    assert st["degraded_calls"] == 0 and st["events"] == []


def test_fallback_every_attempt_faulted_still_exact():
    """fault_rate=1.0 kills every non-final attempt (launch or corrupt —
    both kinds must occur and be caught); the final link always serves and
    the answers never change."""
    tables, ups, sig, s_cap = _fallback_problem()
    fb = FallbackSolver(chain=("pallas_interpret", "reference"),
                        fault_rate=1.0, fault_seed=0)
    for call in range(8):
        x, info = fb(ups, sig, tables, s_cap, s_cap)
        xr, _ = REF(jnp.asarray(ups), jnp.asarray(sig), tables, s_cap,
                    jnp.int32(s_cap))
        np.testing.assert_array_equal(x, np.asarray(xr))
        assert validate_value_row(info["value_row"]) is None
    st = fb.stats
    assert st["calls"] == 8 == st["degraded_calls"] == st["faults_injected"]
    assert st["served_by"] == {"pallas_interpret": 0, "reference": 8}
    assert st["launch_failures"] + st["validation_failures"] == 8
    assert st["launch_failures"] > 0 and st["validation_failures"] > 0
    kinds = {e["kind"] for e in st["events"]}
    assert kinds == {"launch", "validate"}
    assert all(e["injected"] for e in st["events"])


def test_fallback_final_link_failure_propagates():
    """A chain that cannot serve at all is an outage, not a degradation."""
    tables, ups, sig, s_cap = _fallback_problem()

    class Dead:
        name = "dead"
        accepts_batch = False
        interpret = None

        def __call__(self, *a, **k):
            raise InjectedFault("backend gone")

    fb = FallbackSolver(chain=(Dead(),))
    with pytest.raises(InjectedFault):
        fb(ups, sig, tables, s_cap, s_cap)


def test_fallback_traced_bypass_adds_zero_launches():
    """Under jit the wrapper is invisible: the jaxpr of a traced call
    through the chain equals the plain backend's, so fault-free production
    runs pay no extra launches."""
    tables, ups, sig, s_cap = _fallback_problem()
    fb = FallbackSolver("reference", fault_rate=0.0)

    def jaxpr_of(solver):
        def f(u, s, lim):
            return solver(u, s, tables, s_cap, lim)[0]
        return jax.make_jaxpr(f)(jnp.asarray(ups), jnp.asarray(sig),
                                 jnp.int32(s_cap))

    assert str(jaxpr_of(fb)) == str(jaxpr_of(REF))
    assert fb.stats["bypasses"] == 1 and fb.stats["calls"] == 0


def test_cluster_sim_fallback_bit_identical_under_faults(cluster):
    """The acceptance bar: a full ESDP ClusterSim run with faults injected
    at 5%+ completes with sw/regret BIT-IDENTICAL to the fault-free run,
    every degradation accounted in solve_stats."""
    T = 60
    plain = ClusterSim(cluster, T, seed=7).run("esdp")
    fb = FallbackSolver(chain=("pallas_interpret", "reference"),
                        fault_rate=0.2, fault_seed=1)
    out = ClusterSim(cluster, T, seed=7, solver=fb).run("esdp")
    np.testing.assert_array_equal(plain.sw, out.sw)
    np.testing.assert_array_equal(plain.regret, out.regret)
    st = out.solve_stats
    assert st["calls"] == T and st["faults_injected"] > 0
    assert st["degraded_calls"] == len(st["events"]) > 0
    assert sum(st["served_by"].values()) == T
    # fault-free wrapper: same answers, zero degradation events
    quiet = ClusterSim(cluster, T, seed=7, fallback=True).run("esdp")
    np.testing.assert_array_equal(plain.sw, quiet.sw)
    assert quiet.solve_stats["degraded_calls"] == 0
    assert quiet.solve_stats["events"] == []


def test_cluster_sim_fallback_excludes_incremental(cluster):
    with pytest.raises(ValueError):
        ClusterSim(cluster, 10, fallback=True, incremental="cache")


# ---------------------------------------------------------------------------
# deterministic fault hook + env plumbing
# ---------------------------------------------------------------------------

def test_planned_fault_deterministic():
    plan = [planned_fault(i, 0.5, seed=3) for i in range(64)]
    assert plan == [planned_fault(i, 0.5, seed=3) for i in range(64)]
    assert {"launch", "corrupt"} <= set(plan) and None in plan
    assert all(planned_fault(i, 0.0) is None for i in range(16))
    # attempts draw independently: a faulted first attempt does not force
    # the second to fault too
    a0 = [planned_fault(i, 0.5, seed=3, attempt=0) for i in range(64)]
    a1 = [planned_fault(i, 0.5, seed=3, attempt=1) for i in range(64)]
    assert a0 != a1


def test_fault_rate_env_parsing(monkeypatch):
    monkeypatch.delenv(FAULT_RATE_ENV, raising=False)
    assert fault_rate_from_env() == 0.0
    monkeypatch.setenv(FAULT_RATE_ENV, "0.25")
    assert fault_rate_from_env() == 0.25
    monkeypatch.setenv(FAULT_RATE_ENV, "lots")
    with pytest.warns(RuntimeWarning):
        assert fault_rate_from_env() == 0.0
    monkeypatch.setenv(FAULT_RATE_ENV, "1.5")
    with pytest.warns(RuntimeWarning):
        assert fault_rate_from_env() == 0.0


# ---------------------------------------------------------------------------
# solve_stats plumbing (run_batch per-seed copies)
# ---------------------------------------------------------------------------

def test_run_batch_stats_are_per_output_copies(cluster):
    """Every SimOutput owns its OWN solve_stats dict (fleet-labelled):
    mutating one seed's record must not leak into another's."""
    sim = ClusterSim(cluster, 30, incremental="cache")
    outs = sim.run_batch((0, 1, 2))
    stats = [o.solve_stats for o in outs]
    assert all(s["scope"] == "fleet" for s in stats)
    assert stats[0] == stats[1] == stats[2]
    assert stats[0] is not stats[1] and stats[1] is not stats[2]
    original = copy.deepcopy(stats[1])
    stats[0]["solves"] = -1
    stats[0]["scope"] = "tampered"
    assert stats[1] == original


def test_run_batch_fallback_stats_copied(cluster):
    """The deep-copy guard also covers wrapper-style nested stats
    (FallbackSolver's served_by/events live in nested containers)."""
    fb = FallbackSolver(fault_rate=0.0)
    outs = ClusterSim(cluster, 20, solver=fb).run_batch((0, 1))
    a, b = outs[0].solve_stats, outs[1].solve_stats
    assert a is not b and a["served_by"] is not b["served_by"]
    assert a == b
    a["served_by"]["reference"] = 10 ** 6
    assert b["served_by"] != a["served_by"]
