"""Beyond the paper: ESDP vs the baseline field under every registered
fluctuation regime (DVFS, MMPP bursts, stragglers, brownouts, power-coupled
speeds, outages, server crash/repair) — the field now includes the two
Markovian-service-rate baselines (``msr_greedy`` / ``msr_index``,
arXiv:2412.08915) alongside HSWF, plus a malleable-jobs leg (rigid vs
shrink vs shrink+preempt — ``docs/scenarios.md``).

One declarative spec per scenario — the scenario registry makes "does ESDP
still win under regime X?" a 5-line question (see docs/scenarios.md).

Run as a module for the timed benchmark (the nightly perf-trend artifact)::

    python -m benchmarks.scenarios_bench                 # full regimes
    python -m benchmarks.scenarios_bench --smoke
    python -m benchmarks.scenarios_bench --baseline results/BENCH_scenarios.json
    python -m benchmarks.scenarios_bench --fault-smoke   # CI degradation leg
    python -m benchmarks.scenarios_bench --engine-smoke \
        --baseline results/BENCH_engine.json             # CI throughput gate
    python -m benchmarks.scenarios_bench --engine-full \
        --out results/BENCH_engine.json                  # refresh baseline

Writes ``results/BENCH_scenarios.json``: per-scenario end-to-end sweep
wall-clock (trace + compile recorded separately from the steady-state
re-run) plus the ASW/regret summary, the failure-aware mitigation legs
(utility recovered by redundancy / opportunistic checkpointing vs naive on
the crashy ``server_failures`` regime — docs/robustness.md), and the
fault-injection bit-exactness record.  ``--baseline`` applies the same
guard as ``dp_bench``: exits non-zero on a ``--max-regression``-fold
slowdown, warn-not-fail when the host fingerprint (CPU model + jax
version) differs from the committed file.  ``--fault-smoke`` runs ONLY
the degradation-chain check (honouring ``$REPRO_DP_FAULT_RATE``) and
exits non-zero unless the faulted run is bit-identical to the fault-free
one with at least one fault actually injected — the CI leg.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

from repro.compile_cache import enable_compile_cache
from repro.core.baselines import (hswf_factory, msr_greedy_factory,
                                  msr_index_factory)
from repro.core.esdp import esdp_factory
from repro.core.stats import g_logt_only
from repro.experiments import SweepSpec, run_spec, scenario_names

from .dp_bench import host_fingerprint

T = 800
SEEDS = (21, 22)

# the crashy regime the mitigation legs share: frequent crashes, a lemon
# subset crashing 3x as often, and spare capacity so replicas fit
FAILURE_REGIME = dict(p_crash=0.12, p_repair=0.6, lemon_frac=0.34,
                      lemon_mult=3.0, arr_scale=0.6)


def _spec(scenario: str) -> SweepSpec:
    return SweepSpec(
        name=f"scenarios/{scenario}", T=T, seeds=SEEDS,
        policies={"esdp": esdp_factory(g_fn=g_logt_only),
                  "hswf": hswf_factory(),
                  "msr_greedy": msr_greedy_factory(),
                  "msr_index": msr_index_factory()},
        scenario=scenario,
        instance_kwargs={"seed": 0},
    )


def scenario_table(rows, smoke=False):
    names = scenario_names() if not smoke else ("iid", "markov_dvfs")
    for scen in names:
        spec = _spec(scen)
        if smoke:
            spec = spec.smoke()
        res = {r.policy: r for r in run_spec(spec)}
        e = res["esdp"]
        rows.append((f"scenarios/{scen}",
                     f"esdp={e.asw_mean:.1f}",
                     f"hswf={res['hswf'].asw_mean:.1f};"
                     f"msr_greedy={res['msr_greedy'].asw_mean:.1f};"
                     f"msr_index={res['msr_index'].asw_mean:.1f};"
                     f"oracle={e.oracle_asw_mean:.1f};"
                     f"esdp_regret={e.regret_mean:.1f}"))


def bench(smoke: bool) -> dict:
    """Time every registered regime's full sweep (both policies, all
    seeds).  The first run of a spec pays trace + compile; the second run
    hits jit caches — recording both separates compile drift from
    steady-state throughput drift in the nightly trend."""
    import jax

    names = ("iid", "markov_dvfs") if smoke else scenario_names()
    records = []
    for scen in names:
        spec = _spec(scen)
        if smoke:
            spec = spec.smoke()
        t0 = time.perf_counter()
        res = {r.policy: r for r in run_spec(spec)}
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        res = {r.policy: r for r in run_spec(spec)}
        warm_s = time.perf_counter() - t0
        e, h = res["esdp"], res["hswf"]
        records.append({
            "scenario": scen, "T": spec.T, "seeds": len(spec.seeds),
            "cold_s": cold_s, "warm_s": warm_s,
            "esdp_asw": e.asw_mean, "hswf_asw": h.asw_mean,
            "msr_greedy_asw": res["msr_greedy"].asw_mean,
            "msr_index_asw": res["msr_index"].asw_mean,
            "esdp_regret": e.regret_mean,
        })
        print(f"scenarios/{scen}: cold={cold_s:.2f}s warm={warm_s:.2f}s "
              f"esdp={e.asw_mean:.1f} hswf={h.asw_mean:.1f} "
              f"msr_greedy={res['msr_greedy'].asw_mean:.1f} "
              f"msr_index={res['msr_index'].asw_mean:.1f}", flush=True)
    return {"platform": jax.default_backend(), "jax": jax.__version__,
            "host": host_fingerprint(), "smoke": smoke, "grid": records}


def _failure_cluster():
    """The tiny roofline-grounded cluster the failure legs run on."""
    from repro.sched import JobType, Slice, build_instance, rate_matrix

    slices = [Slice("pod-a", "v5e", 256, 32, 4),
              Slice("pod-b", "v5e", 256, 32, 4),
              Slice("pod-c", "v5p", 256, 32, 4)]
    jobs = [JobType("train", "qwen2.5-32b", "train_4k", ("v5e", "v5p"),
                    256, 32, 4, value_rate=1.0),
            JobType("decode", "deepseek-v3-671b", "decode_32k", ("v5e",),
                    256, 32, 4, value_rate=1.2)]
    inst, _ = build_instance(slices, jobs, rate_matrix(jobs, slices), seed=0)
    return inst


def failure_bench(smoke: bool) -> list[dict]:
    """Mitigation legs on the crashy regime: how much of the utility lost
    to in-slot crashes does each failure-aware mode recover vs dispatching
    naively?  (ClusterSim host loop — the failure runtime settles crashes
    per slot, so these legs time the failure-aware path itself.)"""
    from repro.experiments import get_scenario
    from repro.sched import ClusterSim, FailureModel

    T = 200 if smoke else 600
    scn = get_scenario("server_failures", **FAILURE_REGIME)
    inst = _failure_cluster()
    legs = {
        "naive": FailureModel(),
        "redundant": FailureModel(redundancy=2),
        "checkpoint": FailureModel(checkpoints=3, checkpoint_cost=0.003),
        "detect": FailureModel(detect=True),
    }
    records = []
    for leg, model in legs.items():
        t0 = time.perf_counter()
        out = ClusterSim(inst, T, scenario=scn, seed=4,
                         failures=model).run("esdp")
        led = out.failures
        records.append({
            "leg": leg, "T": T, "wall_s": time.perf_counter() - t0,
            "asw": out.asw, "lost": led["total_lost"],
            "salvaged": led["total_salvaged"],
            "ckpt_cost": led["total_ckpt_cost"],
            "restarts": led["restarts"],
            "replicas": int(led["replicas"].sum()),
        })
        print(f"failures/{leg}: asw={out.asw:.1f} "
              f"lost={led['total_lost']:.1f} "
              f"salvaged={led['total_salvaged']:.1f} "
              f"restarts={led['restarts']}", flush=True)
    return records


def malleable_bench(smoke: bool) -> list[dict]:
    """Malleable-jobs legs: the same cluster with shrinkable gangs, rigid
    vs shrink(+grow) vs shrink+preempt (docs/scenarios.md).  Records ASW,
    the conserving work-units ledger totals, and transition counts — the
    headline is how much utility mid-flight reconfiguration buys once its
    explicit costs are ledgered."""
    from repro.sched import (ClusterSim, JobType, MalleableModel, Slice,
                             build_instance, rate_matrix)

    slices = [Slice("pod-a", "v5e", 256, 32, 4),
              Slice("pod-b", "v5e", 256, 32, 4),
              Slice("pod-c", "v5p", 256, 32, 4)]

    def _jobs(malleable):
        return [JobType("train", "qwen2.5-32b", "train_4k", ("v5e", "v5p"),
                        256, 32, 4, value_rate=1.0, malleable=malleable,
                        min_chips=128, min_hosts=16, min_ici_domains=2),
                JobType("decode", "deepseek-v3-671b", "decode_32k", ("v5e",),
                        256, 32, 4, value_rate=1.2, malleable=malleable,
                        min_chips=64, min_hosts=8, min_ici_domains=1)]

    def _inst(malleable):
        jobs = _jobs(malleable)
        return build_instance(slices, jobs, rate_matrix(jobs, slices),
                              seed=0)[0]

    T = 150 if smoke else 500
    # rigid runs the same multi-slot jobs on an instance WITHOUT shrunk
    # config edges (nothing to shrink to) — what reconfiguration buys
    legs = {
        "rigid": (_inst(False), MalleableModel(duration=4)),
        "shrink": (_inst(True), MalleableModel(duration=4)),
        "shrink_preempt": (_inst(True), MalleableModel(duration=4,
                                                       preempt=True)),
    }
    records = []
    for leg, (inst, model) in legs.items():
        t0 = time.perf_counter()
        out = ClusterSim(inst, T, seed=4, malleable=model).run("esdp")
        mal = out.malleable
        records.append({
            "leg": leg, "T": T, "wall_s": time.perf_counter() - t0,
            "asw": out.asw,
            "dispatched_units": mal["total_dispatched"],
            "done_units": mal["total_done"],
            "lost_units": mal["total_lost"],
            "reconfig_cost": mal["total_reconfig_cost"],
            "shutdown_cost": mal["total_shutdown_cost"],
            "transitions": mal["transitions"],
            "shutdowns": int(mal["shutdowns"].sum()),
            "blocked": int(mal["blocked"].sum()),
        })
        print(f"malleable/{leg}: asw={out.asw:.1f} "
              f"transitions={mal['transitions']} "
              f"blocked={int(mal['blocked'].sum())} "
              f"lost={mal['total_lost']:.1f}", flush=True)
    return records


def engine_bench(smoke: bool) -> dict:
    """Arrivals/sec of the streaming dispatch engine vs the lockstep host
    loop it replaced, on the roofline cluster.

    Legs (all seed-pinned):
      * ``lockstep``   — ``ClusterSim.run("esdp")``, the pre-engine per-slot
        host loop, at a modest horizon (it is ~100x slower per arrival);
      * ``engine``     — single-variant stream mode: the whole trace is ONE
        jitted ``lax.scan`` call;
      * ``engine_ab``  — stream mode with a 90/10 ESDP/HSWF A/B split.

    Before any timing, stream mode must be bit-identical to lockstep mode
    at a small horizon — a throughput number for a wrong engine is
    meaningless.  Full (non-smoke) mode adds the ~100k-arrival horizon the
    acceptance bar targets (engine >= 5x lockstep arrivals/sec) and stamps
    ``speedup`` / ``speedup_ok`` from that leg.
    """
    import jax
    import numpy as np

    from repro.sched import (ClusterSim, DispatchEngine, EngineConfig,
                             VariantSpec)

    inst = _failure_cluster()
    seed = 9

    # -- equivalence gate: stream == lockstep, bit for bit, or no timing --
    eng = DispatchEngine(inst, 200, seed=seed)
    o_s, o_l = eng.run(mode="stream"), eng.run(mode="lockstep")
    for f in ("sw", "regret", "n", "sumz", "queue_len"):
        if not np.array_equal(np.asarray(getattr(o_s, f)),
                              np.asarray(getattr(o_l, f))):
            raise AssertionError(
                f"engine stream/lockstep diverged on {f!r} — refusing to "
                "record a throughput number for a wrong engine")

    ab = EngineConfig(variants=(VariantSpec("esdp", weight=0.9),
                                VariantSpec("challenger", kind="hswf",
                                            weight=0.1)))
    T_lock = 200 if smoke else 400
    horizons = (3_000,) if smoke else (3_000, 56_000)
    records = []

    def record(leg, T, arrivals, wall_s, mode):
        records.append({
            "leg": leg, "T": T, "arrivals": int(arrivals),
            "wall_s": wall_s, "arrivals_per_s": arrivals / wall_s,
            "mode": mode,
        })
        print(f"engine/{leg}: T={T} arrivals={arrivals} "
              f"wall={wall_s:.2f}s -> {arrivals / wall_s:,.0f} arr/s",
              flush=True)

    # lockstep leg: second run so jit caches are warm and only the host
    # loop itself is on the clock
    arr_lock = int(DispatchEngine(inst, T_lock, seed=seed)
                   ._streams(seed)[0].sum())
    ClusterSim(inst, T_lock, seed=seed).run("esdp")
    t0 = time.perf_counter()
    ClusterSim(inst, T_lock, seed=seed).run("esdp")
    record("lockstep", T_lock, arr_lock, time.perf_counter() - t0,
           "host-loop")

    for T in horizons:
        for leg, cfg in (("engine", None), ("engine_ab", ab)):
            eng = DispatchEngine(inst, T, cfg, seed=seed)
            out = eng.run(mode="stream")  # pays trace + compile
            t0 = time.perf_counter()
            out = eng.run(mode="stream")
            wall = time.perf_counter() - t0
            record(leg, T, out.ledger["total_arrivals"], wall, "stream")

    res = {"platform": jax.default_backend(), "jax": jax.__version__,
           "host": host_fingerprint(), "smoke": smoke, "grid": records}
    lock_rate = records[0]["arrivals_per_s"]
    big = max((r for r in records if r["leg"] == "engine"),
              key=lambda r: r["T"])
    res["speedup"] = big["arrivals_per_s"] / lock_rate
    res["speedup_ok"] = bool(res["speedup"] >= 5.0)
    print(f"engine speedup vs lockstep ({big['arrivals']} arrivals): "
          f"{res['speedup']:.0f}x (>=5x: {res['speedup_ok']})", flush=True)
    return res


def check_engine_baseline(result: dict, base: dict, max_regression: float) -> list[str]:
    """Arrivals/sec per (leg, T) vs the committed file — a leg that got
    ``max_regression``-fold slower (or a speedup that fell below the 5x
    acceptance bar) fails the gate."""
    base_r = {(r["leg"], r["T"]): r["arrivals_per_s"]
              for r in base.get("grid", [])}
    failures = []
    for r in result["grid"]:
        key = (r["leg"], r["T"])
        if key not in base_r or r["leg"] == "lockstep":
            continue  # lockstep is the denominator, not the gated path
        if r["arrivals_per_s"] * max_regression < base_r[key]:
            failures.append(
                f"engine/{r['leg']} T={r['T']}: "
                f"{r['arrivals_per_s']:,.0f} arr/s vs baseline "
                f"{base_r[key]:,.0f} (> {max_regression:.1f}x slower)")
    if not result.get("speedup_ok", True):
        failures.append(
            f"engine speedup {result['speedup']:.1f}x fell below the 5x "
            "acceptance bar vs the lockstep host loop")
    return failures


def fault_injection_check(rate: "float | None" = None) -> dict:
    """The graceful-degradation acceptance bar: a full ESDP ClusterSim run
    with solver faults injected (``rate``, else ``$REPRO_DP_FAULT_RATE``)
    completes BIT-IDENTICAL to the fault-free run — every fallback link is
    exact, so degradation costs speed, never answers."""
    import numpy as np

    from repro.core.solvers import FallbackSolver
    from repro.sched import ClusterSim

    inst = _failure_cluster()
    T = 120
    plain = ClusterSim(inst, T, seed=7).run("esdp")
    fb = FallbackSolver(chain=("pallas_interpret", "reference"),
                        fault_rate=rate)
    out = ClusterSim(inst, T, seed=7, solver=fb).run("esdp")
    identical = bool(np.array_equal(plain.sw, out.sw)
                     and np.array_equal(plain.regret, out.regret))
    rec = {"T": T, "rate": fb.fault_rate, "identical": identical,
           "served_by": dict(fb.stats["served_by"]),
           **{k: v for k, v in fb.stats.items() if isinstance(v, int)}}
    print(f"fault-injection: rate={rec['rate']} "
          f"faults={rec['faults_injected']} "
          f"degraded={rec['degraded_calls']} identical={identical}",
          flush=True)

    # streaming-engine leg: lockstep mode driving the faulted degradation
    # chain must stay bit-identical to plain stream mode (every fallback
    # link is exact), with at least one degradation event actually fired
    from repro.sched import DispatchEngine, EngineConfig, VariantSpec

    eng_plain = DispatchEngine(inst, T, seed=7).run(mode="stream")
    fb_eng = FallbackSolver(chain=("pallas_interpret", "reference"),
                            fault_rate=rate)
    cfg = EngineConfig(variants=(VariantSpec("esdp", solver=fb_eng),))
    eng_fault = DispatchEngine(inst, T, cfg, seed=7).run(mode="lockstep")
    eng_identical = bool(
        np.array_equal(np.asarray(eng_plain.sw), np.asarray(eng_fault.sw))
        and np.array_equal(np.asarray(eng_plain.regret),
                           np.asarray(eng_fault.regret)))
    rec["engine"] = {
        "identical": eng_identical,
        "served_by": dict(fb_eng.stats["served_by"]),
        **{k: v for k, v in fb_eng.stats.items() if isinstance(v, int)}}
    print(f"fault-injection/engine: "
          f"faults={rec['engine']['faults_injected']} "
          f"degraded={rec['engine']['degraded_calls']} "
          f"identical={eng_identical}", flush=True)
    return rec


def check_baseline(result: dict, base: dict, max_regression: float) -> list[str]:
    """Warm (steady-state) per-scenario wall-clock vs the committed file;
    only (scenario, T, seeds)-matched rows compare."""
    base_s = {(r["scenario"], r["T"], r["seeds"]): r["warm_s"]
              for r in base.get("grid", [])}
    failures = []
    for r in result["grid"]:
        key = (r["scenario"], r["T"], r["seeds"])
        if key not in base_s:
            continue
        if r["warm_s"] > max_regression * base_s[key]:
            failures.append(
                f"scenarios/{r['scenario']}: warm {r['warm_s']:.2f}s vs "
                f"baseline {base_s[key]:.2f}s (> {max_regression:.1f}x)")
    return failures


def main() -> None:
    from .dp_bench import apply_baseline_guard

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="CI-sized regimes")
    ap.add_argument("--out", default="results/BENCH_scenarios.json")
    ap.add_argument("--baseline", default=None,
                    help="committed BENCH_scenarios.json to guard against")
    ap.add_argument("--max-regression", type=float, default=2.0)
    ap.add_argument("--fault-smoke", action="store_true",
                    help="run ONLY the degradation-chain bit-exactness "
                         "check (rate from $REPRO_DP_FAULT_RATE); non-zero "
                         "exit on mismatch or zero injected faults")
    ap.add_argument("--engine-smoke", action="store_true",
                    help="run ONLY the streaming-engine arrivals/sec legs "
                         "at CI size (the engine-throughput gate)")
    ap.add_argument("--engine-full", action="store_true",
                    help="run ONLY the engine legs at full size, including "
                         "the ~100k-arrival horizon the 5x acceptance bar "
                         "targets — refreshes results/BENCH_engine.json")
    args = ap.parse_args()
    enable_compile_cache()
    if args.fault_smoke:
        rec = fault_injection_check()
        if rec["rate"] <= 0.0:
            sys.exit("fault-smoke needs a positive rate — set "
                     "REPRO_DP_FAULT_RATE (e.g. 0.05)")
        if not rec["identical"]:
            sys.exit("FAULT SMOKE FAILED: faulted run diverged from the "
                     "fault-free run — a fallback link is not exact")
        if rec["faults_injected"] == 0:
            sys.exit("FAULT SMOKE FAILED: no faults injected at rate "
                     f"{rec['rate']} over {rec['T']} solves — the hook "
                     "is not firing")
        if not rec["engine"]["identical"]:
            sys.exit("FAULT SMOKE FAILED: the streaming engine's faulted "
                     "lockstep run diverged from plain stream mode")
        if rec["engine"]["degraded_calls"] == 0:
            sys.exit("FAULT SMOKE FAILED: the engine leg fired no "
                     "degradation events — the chain never acted")
        return
    base = None
    if args.baseline:
        bpath = pathlib.Path(args.baseline)
        if not bpath.exists():
            sys.exit(f"baseline {bpath} not found — refresh it with: "
                     "PYTHONPATH=src python -m benchmarks.scenarios_bench "
                     f"--out {bpath}")
        base = json.loads(bpath.read_text())
    if args.engine_smoke or args.engine_full:
        out = engine_bench(smoke=not args.engine_full)
        path = pathlib.Path(args.out)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(out, indent=2))
        print(f"wrote {path}")
        if not out["speedup_ok"]:
            sys.exit(f"ENGINE BENCH FAILED: speedup {out['speedup']:.1f}x "
                     "< 5x vs the lockstep host loop")
        if base is not None:
            apply_baseline_guard(
                out, base, args.baseline, args.max_regression,
                check_engine_baseline(out, base, args.max_regression))
        return
    out = bench(args.smoke)
    out["failures"] = failure_bench(args.smoke)
    out["malleable"] = malleable_bench(args.smoke)
    out["fault_injection"] = fault_injection_check(rate=0.05)
    path = pathlib.Path(args.out)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(out, indent=2))
    print(f"wrote {path}")
    if base is not None:
        apply_baseline_guard(out, base, args.baseline, args.max_regression,
                             check_baseline(out, base, args.max_regression))


if __name__ == "__main__":
    main()
