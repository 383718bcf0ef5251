"""Scenario sweep in a few lines: ESDP vs HSWF across fluctuation regimes.

Demonstrates the two levels of batching in ``repro.experiments``:

  1. a declarative SweepSpec — every (policy × scenario) cell is ONE jitted
     ``jax.vmap`` over the seed batch (no per-seed Python loop), and
  2. a scenario-parameter grid — severity values folded into a single
     compilation via ``lax.map`` on top of the vmapped seeds.

    PYTHONPATH=src python examples/scenario_sweep.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import build_tables, generate_instance
from repro.core.baselines import hswf_factory
from repro.core.esdp import esdp_factory
from repro.core.stats import g_logt_only
from repro.experiments import (SweepSpec, run_spec, scenario_names,
                               sweep_scenario_param, write_csv)

T = 1000
SEEDS = (0, 1, 2)


def main():
    enable_compile_cache()
    # -- 1. registry sweep: every named regime, one spec each ---------------
    # paper-literal HSWF (tiebreak=0), as in the paper's Fig.-2 comparison
    policies = {"esdp": esdp_factory(g_fn=g_logt_only),
                "hswf": hswf_factory(tiebreak=0.0)}
    print(f"{'scenario':20s} {'esdp ASW':>12s} {'hswf ASW':>12s} {'winner':>8s}")
    rows = []
    for scen in scenario_names():
        spec = SweepSpec(name=f"sweep/{scen}", T=T, seeds=SEEDS,
                         policies=policies, scenario=scen,
                         instance_kwargs={"seed": 0})
        res = {r.policy: r for r in run_spec(spec)}
        rows += list(res.values())
        e, h = res["esdp"], res["hswf"]
        print(f"{scen:20s} {e.asw_mean:8.1f}±{e.asw_ci95:3.0f} "
              f"{h.asw_mean:8.1f}±{h.asw_ci95:3.0f} "
              f"{'esdp' if e.asw_mean > h.asw_mean else 'hswf':>8s}")
    path = write_csv(rows, "results/scenario_sweep.csv")
    print(f"\nwrote {path}")

    # -- 2. severity grid: one compiled lax.map × vmap call -----------------
    inst = generate_instance(seed=0)
    tables = build_tables(inst.A, inst.c)
    speeds = (0.2, 0.4, 0.6, 0.8, 1.0)
    grid = sweep_scenario_param(
        inst, esdp_factory(g_fn=g_logt_only), T, SEEDS,
        "chronic_straggler", "straggler_speed", speeds, tables=tables)
    print("\nstraggler severity sweep (single jitted lax.map × vmap call):")
    asw = grid.asw[..., -1]                  # (G, S)
    for v, mean, sd in zip(speeds, asw.mean(axis=1), asw.std(axis=1)):
        print(f"  straggler_speed={v:.1f}  ASW={mean:7.1f} ± {sd:4.1f}")


if __name__ == "__main__":
    main()
