#!/usr/bin/env python3
"""Chip smoke test: the ESDP dispatch path on one TPU chip, compiled kernels.

    python chip_smoke.py

Run on a TPU host from the root of a checkout.  One process drives the
chip; nothing here starts another.  Phases, in order:

1. device and solver checks — the platform must be ``tpu``, no solver or
   fault-injection override may be set in the environment, and the Pallas
   backend must resolve to the compiled kernel (never the interpreter);
2. every kernel regime the ``pallas`` solver reaches — whole-plane,
   fused C-blocked, fused S-tiled, fleet-batched (``jax.vmap`` at B=64) —
   each bit-exact against the ``reference`` solver on the same chip
   (``x``, ``s_star``, ``value_row``);
3. ``DispatchEngine`` in stream mode on the paper's Table-2 instance over
   T = 56,000 slots (about 400k arrivals), with the default single-variant
   config and the 90/10 ESDP/HSWF A/B config — each identical to the same
   engine on the ``reference`` solver, with a conserving admission ledger
   and a compiled Pallas kernel in its stream program;
4. the fleet path — ``DispatchEngine.run_batch`` over 8 seeds equals
   looping ``run(seed)``.

Compile and run times and arrival rates are printed as information, not as
benchmark results.  Any failed check exits non-zero without the result
line; on success the last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

ENGINE_T = 56_000  # the horizon of scenarios_bench's --engine-full leg
ENGINE_SEED = 9
FLEET_T = 2_000
FLEET_SEEDS = tuple(range(8))
BATCH = 64


class Failed(Exception):
    """A phase check did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Failed(what)


def log(msg: str) -> None:
    print(msg, flush=True)


class CacheCounter:
    """Counts persistent-compilation-cache hits and misses of this run."""

    def __init__(self):
        self.hits = self.misses = 0

    def __call__(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


# -- phase 1 -------------------------------------------------------------

def phase_device(jax):
    from repro.core.solvers import SOLVER_ENV_VAR, get_solver
    from repro.kernels.budgeted_dp.kernel import resolve_interpret
    from repro.runtime.fault import FAULT_RATE_ENV

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']} jax={jax.__version__}")
    check(dev["platform"] == "tpu",
          f"no TPU: JAX runs on {dev['platform']!r}")
    for var in (SOLVER_ENV_VAR, FAULT_RATE_ENV):
        check(not os.environ.get(var),
              f"${var} is set; the smoke runs the solvers it names itself")
    check(resolve_interpret(None) is False,
          "the Pallas kernel would run in interpret mode on this chip")
    pallas = get_solver("pallas")
    check(pallas.name == "pallas" and pallas.interpret is None,
          f"get_solver('pallas') resolved to {pallas!r}")
    return dev


# -- phase 2 -------------------------------------------------------------

def _problem(name: str, s_cap=None):
    from benchmarks.dp_bench import CONFIGS, _make_problem
    from repro.core.dp import build_tables
    from repro.kernels.budgeted_dp.ops import prepare_tables

    cfg = next(c for c in CONFIGS if c["name"] == name)
    A, c, ups, sig = _make_problem(cfg)
    tables = build_tables(A, c)
    _, offs = prepare_tables(tables)
    s_cap = int(cfg.get("s_cap", ups.sum()) if s_cap is None else s_cap)
    return tables, ups, sig, s_cap, int(ups.max() + 1), int(offs.max())


def _assert_same(got, want, what: str) -> None:
    import numpy as np

    for key, a, b in (("x", got[0], want[0]),
                      ("s_star", got[1]["s_star"], want[1]["s_star"]),
                      ("value_row", got[1]["value_row"],
                       want[1]["value_row"])):
        a, b = np.asarray(a), np.asarray(b)
        check(a.shape == b.shape and np.array_equal(a, b),
              f"{what}: pallas {key} differs from the reference")


def _solve_pair(jax, regime, tables, s_cap, u_max, args, batched):
    """Compile and run the pallas and reference solves on ``args``."""
    from repro.core.solvers import get_solver

    outs = {}
    for name in ("pallas", "reference"):
        solver = get_solver(name)

        def one(u, s, lim, al, solver=solver):
            return solver(u, s, tables, s_cap, lim, allowed=al, u_max=u_max)

        fn = jax.jit(jax.vmap(one) if batched else one)
        lowered, t_lower = timed(lambda fn=fn: fn.lower(*args))
        if name == "pallas":
            n_kernels = lowered.as_text().count("tpu_custom_call")
            check(n_kernels == 1,
                  f"{regime}: {n_kernels} Pallas kernels in the lowered "
                  "solve, want 1")
        compiled, t_comp = timed(lowered.compile)
        out, t_run = timed(lambda c=compiled: jax.block_until_ready(c(*args)))
        outs[name] = out
        if name == "pallas":
            _, t_warm = timed(
                lambda c=compiled: jax.block_until_ready(c(*args)))
            log(f"  {regime}: pallas compile {t_lower + t_comp:.2f} s, "
                f"first run {t_run * 1e3:.2f} ms, warm run "
                f"{t_warm * 1e3:.3f} ms")
    _assert_same(outs["pallas"], outs["reference"], regime)


def phase_kernels(jax, jnp, kind: str):
    import numpy as np

    from repro.kernels.budgeted_dp.kernel import choose_tiling

    log(f"kernels ({kind}; times are information, not a benchmark):")
    # (regime, dp_bench config, budget-axis override, expected tiling class)
    cases = (
        ("whole-plane", "E16_C512", None, "whole"),
        # E16_C4096 at a budget axis past the whole-plane VMEM budget, so
        # the auto tiling takes the fused C-blocked (full-height) grid
        ("fused C-blocked", "E16_C4096", 127, "c_blocked"),
        ("fused S-tiled", "E16_C512_S4096", None, "s_tiled"),
    )
    for regime, name, s_cap, want in cases:
        tables, ups, sig, s_cap, u_max, off_max = _problem(name, s_cap)
        S, C, E = s_cap + 1, tables.n_states, len(ups)
        til = choose_tiling(S, C, E, u_max, off_max)
        got = ("whole" if til[2] is None else
               "c_blocked" if til[1] is None else "s_tiled")
        log(f"  {regime}: {name} E={E} S={S} C={C} u_max={u_max} "
            f"off_max={off_max} choose_tiling(block_e, block_s, block_c)="
            f"{til}")
        check(got == want and (want == "whole" or til[0] is not None),
              f"{regime}: choose_tiling picked {til}, not the {want} path")
        args = (jnp.asarray(ups), jnp.asarray(sig), jnp.int32(s_cap),
                jnp.ones(E, jnp.int32))
        _solve_pair(jax, regime, tables, s_cap, u_max, args, False)

    # fleet batch: B heterogeneous solves under jax.vmap → one launch
    tables, ups1, _, s_cap, u_max, off_max = _problem("E16_C512")
    S, C, E = s_cap + 1, tables.n_states, len(ups1)
    til = choose_tiling(S, C, E, u_max, off_max, batch=BATCH)
    log(f"  fleet-batched: E16_C512 B={BATCH} choose_tiling(block_b, "
        f"block_e, block_s, block_c)={til}")
    check(til[1:] == (None, None, None),
          f"fleet-batched: choose_tiling picked {til}, not the "
          "whole-plane batched kernel")
    rng = np.random.default_rng(100 + BATCH)
    args = (jnp.asarray(rng.integers(0, u_max, (BATCH, E)), jnp.int32),
            jnp.asarray(rng.integers(1, 5000, (BATCH, E)), jnp.int32),
            jnp.asarray(rng.integers(0, s_cap + 1, BATCH), jnp.int32),
            jnp.asarray(rng.integers(0, 2, (BATCH, E)), jnp.int32))
    _solve_pair(jax, "fleet-batched", tables, s_cap, u_max, args, True)


# -- phase 3 -------------------------------------------------------------

def _engine_configs(solver: str):
    from repro.sched import EngineConfig, VariantSpec

    return {
        "default": EngineConfig(variants=(VariantSpec("esdp",
                                                      solver=solver),)),
        "ab_90_10": EngineConfig(variants=(
            VariantSpec("esdp", weight=0.9, solver=solver),
            VariantSpec("challenger", kind="hswf", weight=0.1))),
    }


def _assert_same_trace(got, want, what: str) -> None:
    import numpy as np

    for field in ("sw", "regret", "n", "sumz", "queue_len"):
        a = np.asarray(getattr(got, field))
        b = np.asarray(getattr(want, field))
        check(a.shape == b.shape and np.array_equal(a, b),
              f"{what}: {field!r} differs")


def _assert_ledger(out, what: str) -> None:
    led = out.ledger
    check(led["total_arrivals"] == led["total_rejected"]
          + led["total_blocked"] + led["total_admitted"],
          f"{what}: admission ledger does not conserve")
    check(led["total_admitted"] == led["total_dispatched"]
          + led["total_dropped"] + led["total_shed"] + led["final_queue"],
          f"{what}: queue ledger does not conserve")


def phase_engine(jax, kind: str):
    from repro.core.graph import generate_instance
    from repro.sched import DispatchEngine

    inst = generate_instance()  # Table 2: 8 job types × 40 servers
    log(f"engine ({kind}; times are information, not a benchmark): "
        f"Table-2 instance P={inst.n_ports} R={inst.n_servers} "
        f"E={inst.n_edges}, T={ENGINE_T}")
    pal, ref = _engine_configs("pallas"), _engine_configs("reference")
    for name in pal:
        eng = DispatchEngine(inst, ENGINE_T, pal[name], seed=ENGINE_SEED)
        text = eng._stream_fn().lower(*eng.stream_arg_shapes()).as_text()
        check("tpu_custom_call" in text,
              f"engine/{name}: no compiled Pallas kernel in the stream "
              "program")
        first, t_first = timed(lambda eng=eng: eng.run(mode="stream"))
        out, t_run = timed(lambda eng=eng: eng.run(mode="stream"))
        arrivals = out.ledger["total_arrivals"]
        log(f"  {name}: solver=pallas S={eng.s_cap + 1} "
            f"C={eng.tables.n_states} arrivals={arrivals} first call "
            f"(trace + compile + run) {t_first:.2f} s, warm run "
            f"{t_run:.2f} s = {arrivals / t_run:,.0f} arrivals/s")
        _assert_same_trace(out, first, f"engine/{name} rerun")
        _assert_ledger(out, f"engine/{name}")
        want, t_ref = timed(lambda name=name: DispatchEngine(
            inst, ENGINE_T, ref[name], seed=ENGINE_SEED).run(mode="stream"))
        log(f"  {name}: solver=reference first call {t_ref:.2f} s; "
            f"asw pallas={out.asw!r} reference={want.asw!r}")
        _assert_same_trace(out, want, f"engine/{name} vs reference")
    return inst


# -- phase 4 -------------------------------------------------------------

def phase_fleet(inst, kind: str):
    from repro.sched import DispatchEngine

    eng = DispatchEngine(inst, FLEET_T, _engine_configs("pallas")["default"],
                         seed=ENGINE_SEED)
    outs, t_batch = timed(lambda: eng.run_batch(FLEET_SEEDS))
    log(f"fleet ({kind}; information, not a benchmark): run_batch over "
        f"{len(FLEET_SEEDS)} seeds, T={FLEET_T}: first call {t_batch:.2f} s")
    for seed, got in zip(FLEET_SEEDS, outs):
        want = eng.run(mode="stream", seed=seed)
        _assert_same_trace(got, want, f"fleet seed {seed} vs run(seed)")
        _assert_ledger(got, f"fleet seed {seed}")


def main() -> int:
    try:
        import jax
        import jax.numpy as jnp

        from repro.compile_cache import enable_compile_cache
    except ImportError as err:
        print(f"chip_smoke: cannot import the program ({err}); run it "
              "from the root of a checkout", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    counter = CacheCounter()
    jax.monitoring.register_event_listener(counter)
    phase = "device"
    try:
        dev = phase_device(jax)
        phase = "kernels"
        phase_kernels(jax, jnp, dev["kind"])
        phase = "engine"
        inst = phase_engine(jax, dev["kind"])
        phase = "fleet"
        phase_fleet(inst, dev["kind"])
    except Failed as err:
        print(f"chip_smoke: FAILED in phase {phase}: {err}", file=sys.stderr)
        return 1
    log(f"compile cache: {cache_dir} hits={counter.hits} "
        f"misses={counter.misses}")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
