"""Benchmark harness — one function per paper table/figure.

Prints ``name,value,derived`` CSV rows. Select subsets:
    python -m benchmarks.run                 # everything
    python -m benchmarks.run fig2 fig8       # substring filter
    python -m benchmarks.run fig2 --smoke    # CI-sized horizons/seeds

Every figure is a declarative sweep spec over ``repro.experiments`` — see
the per-module ``*_SPEC`` constants.
"""
from __future__ import annotations

import sys

from repro.compile_cache import enable_compile_cache

from . import asw, overhead, roofline_bench, scenarios_bench, sensitivity

ALL = [
    asw.fig2_asw_vs_time,
    asw.fig3_asw_ratio,
    asw.fig4_avg_asw,
    overhead.fig5_overhead,
    sensitivity.fig6_solution_space,
    sensitivity.fig7_delta,
    sensitivity.fig8_g,
    sensitivity.fig9_rho,
    sensitivity.fig10_edges,
    scenarios_bench.scenario_table,
    roofline_bench.roofline_table,
]


def main() -> None:
    enable_compile_cache()
    smoke = "--smoke" in sys.argv
    filters = [a for a in sys.argv[1:] if not a.startswith("-")]
    rows: list[tuple] = []
    print("name,value,derived")
    for fn in ALL:
        if filters and not any(f in fn.__name__ for f in filters):
            continue
        start = len(rows)
        fn(rows, smoke=smoke)
        for r in rows[start:]:
            print(",".join(str(x) for x in r), flush=True)


if __name__ == "__main__":
    main()
