#!/usr/bin/env python3
"""Read the control: the plain reference with its float32 statistics in
bfloat16, put in the program's place at a cell's own size, compared by the
same check that decides ``correct``.  It has to come out not correct.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 [--units N]

``--units`` is the episodes (replay) or slots (online) the control runs.
For a replay cell it defaults to the episodes a run's check compares.  An
online run's check draws its solved slots from every slot of the window,
so an online control has to run as many: give ``--units`` the slots one
window holds (a run's ``attempted``).  Run on the chip; one line of JSON
per seed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--units", type=int)
    args = p.parse_args(argv)

    from bench import check, run
    from bench.loops import LOOPS
    from bench.traffic.gen import load_traffic
    from repro.core.graph import generate_instance

    cell, config, _, _ = run.load_cell(args.workload)
    run.require_accelerator(cell["chips"])
    traffic = load_traffic(cell["traffic"])
    inst = generate_instance(**config["instance"])
    T = traffic.horizon or config["horizon"]
    if traffic.loop == "online" and not args.units:
        p.error("an online control needs --units: the slots one window "
                "holds")
    units = args.units or traffic.check
    for seed in args.seeds:
        loop = LOOPS[traffic.loop](None, inst, T, traffic, config, seed)
        correct, checks = check.verdict(loop.control_readings(units))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "units": units, "correct": correct,
                          "checks": checks}), flush=True)


if __name__ == "__main__":
    main()
