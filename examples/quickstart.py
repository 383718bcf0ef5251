"""Quickstart: the paper in 60 seconds.

Generates the paper's default bipartite instance (Table 2), runs ESDP
against the three baselines for 2000 slots, and prints the accumulative
social welfare + regret — the headline numbers of Fig. 2.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (build_tables, generate_instance, make_esdp_policy,
                        make_hswf_policy, make_lcf_policy, make_lwtf_policy,
                        simulate)
from repro.core.stats import g_logt_only


def main():
    enable_compile_cache()
    inst = generate_instance(seed=0)          # |L|=8, |R|=40, Table-2 defaults
    tables = build_tables(inst.A, inst.c)
    T = 2000
    print(f"instance: |L|={inst.n_ports} |R|={inst.n_servers} "
          f"|E|={inst.n_edges} K={inst.n_device_types} c={inst.c.tolist()}")

    policies = {
        "ESDP (paper default g)": make_esdp_policy(inst, T, tables=tables),
        "ESDP (g=ln t, Fig-8 winner)": make_esdp_policy(
            inst, T, g_fn=g_logt_only, tables=tables),
        "HSWF": make_hswf_policy(inst, tiebreak=0.0),
        "LCF": make_lcf_policy(inst, tiebreak=0.0),
        "LWTF": make_lwtf_policy(inst, tiebreak=0.0),
    }
    results = {}
    for name, pol in policies.items():
        r = simulate(inst, pol, T, seed=42, tables=tables)
        results[name] = r
        print(f"{name:30s} ASW={r.asw[-1]:8.1f}  "
              f"cumRegret={r.cum_regret[-1]:8.1f}  "
              f"avg‖x‖={r.n_dispatched.mean():.2f}")

    best = results["ESDP (g=ln t, Fig-8 winner)"].asw[-1]
    for b in ("HSWF", "LCF", "LWTF"):
        print(f"ESDP improvement vs {b}: "
              f"{(best / results[b].asw[-1] - 1) * 100:+.0f}%")


if __name__ == "__main__":
    main()
