#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with a TPU.  The cell is an
entry of ``BENCHMARK.json``'s ``workloads``; it names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``), whose ``loop`` picks the timed loop in
``bench/loops.py``.  With ``--trace 0`` the window runs for ``--seconds``
and the line carries the cell's end-to-end metrics; with ``--trace 1`` a
short window (the mix's ``trace_units``) runs under the profiler and the
line carries the cell's per-layer metrics, each read by
``bench/metrics/<metric>.py`` (or ``<part before the first dot>.py``).
After the window its output is compared with the plain reference
(``bench/reference``); the numbers compared are printed beside their limits
as the last lines on standard error and under ``checks`` in the line.

Exits non-zero, with no result line, without a TPU or with fewer chips than
the cell asks for.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


class NoAccelerator(SystemExit):
    """No TPU, or fewer chips than the cell asks for."""


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if args.seed < 0:
        p.error("--seed must be a whole number >= 0")
    return args


def load_cell(name: str):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    config = json.loads((BENCH / "configs" / f"{cell['config']}.json")
                        .read_text())

    def applies(metric):
        return name in metric.get("workloads", [name])

    return (cell, config, [m for m in spec["end_to_end"] if applies(m)],
            [m for m in spec["per_layer"] if applies(m)])


def require_accelerator(chips: int):
    """The devices the cell runs on; exits without a TPU or enough chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX found {devices[0].platform!r} "
                            "devices; this benchmark measures the chip")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX found "
                            f"{len(devices)}")
    return devices[:chips]


def load_reader(metric: str):
    """``bench/metrics/<metric>.py``, else the file of its first part."""
    for stem in (metric, metric.split(".")[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.is_file():
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise SystemExit(f"no reader for per-layer metric {metric!r} under "
                     f"{BENCH / 'metrics'}")


INSTANCE_ARRAYS = ("edges", "A", "c", "cost", "mu", "sigma", "rho")


def instance_digest(inst) -> str:
    """SHA-256 of the deployment's arrays, in fixed types and order."""
    h = hashlib.sha256()
    for name in INSTANCE_ARRAYS:
        a = np.asarray(getattr(inst, name))
        a = a.astype(np.float32 if a.dtype.kind == "f" else np.int64)
        h.update(name.encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


def check_instance(inst, config):
    """Fail unless the generated deployment is the one the configuration
    records, so that a change to the generator cannot move the cell."""
    from bench.reference import Reference

    ref = Reference.for_instance(inst, config["horizon"], config["engine"])
    got = {"P": inst.n_ports, "R": inst.n_servers, "E": inst.n_edges,
           "m": ref.m, "C": ref.C, "S": ref.S}
    want = {k: config["sizes"][k] for k in got}
    if got != want or instance_digest(inst) != config["digest"]:
        raise SystemExit(
            f"the generator built another deployment than "
            f"{config['name']!r} records: sizes {got} (recorded {want}), "
            f"digest {instance_digest(inst)} (recorded {config['digest']})")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading from
    the persistent cache), from its ``jax.monitoring`` duration events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.active = True

    def __call__(self, event, duration, **_):
        if self.active and event in self.EVENTS:
            self.seconds += duration


def run(args):
    cell, config, end_to_end, per_layer = load_cell(args.workload)
    import jax

    from bench import adapter, check, trace_reduce
    from bench.loops import LOOPS
    from bench.reference import Reference
    from bench.traffic.gen import load_traffic
    from repro.compile_cache import enable_compile_cache
    from repro.core.graph import generate_instance

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    devices = require_accelerator(cell["chips"])
    enable_compile_cache()

    traffic = load_traffic(cell["traffic"])
    inst = generate_instance(**config["instance"])
    check_instance(inst, config)
    T = traffic.horizon or config["horizon"]
    engine = adapter.build_engine(inst, T, config["engine"])
    loop = LOOPS[traffic.loop](engine, inst, T, traffic, config,
                                     args.seed)
    loop.setup()
    setup_s = time.perf_counter() - PROCESS_START
    clock.active = False

    metrics = {}
    breakdown = None
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            jax.profiler.start_trace(trace_dir)
            try:
                _, slots = loop.window(units=traffic.trace_units)
            finally:
                jax.profiler.stop_trace()
            reduced = trace_reduce.reduce(trace_reduce.find_xplane(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        breakdown = reduced.breakdown()
        ref = Reference.for_instance(inst, T, config["engine"])
        ctx = {"trace": reduced, "slots": slots, "T": T,
               "compile_s": clock.seconds, "device_kind": device["kind"],
               "sizes": {"S": ref.S, "C": ref.C, "E": ref.E}}
        for m in per_layer:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        measured, _ = loop.window(seconds=args.seconds)
        measured["setup_s"] = setup_s
        for m in end_to_end:
            metrics[m["name"]] = {"value": measured[m["name"]],
                                  "unit": m["unit"]}
    device["memory_peak_bytes"] = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in devices)

    readings = check.Readings()
    loop.check(readings)
    correct, checks = check.verdict(readings)
    if loop.failed:
        correct = False
    line = {"correct": correct, "attempted": loop.attempted,
            "failed": loop.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return line


def main(argv=None):
    run(parse_args(argv))


if __name__ == "__main__":
    main()
