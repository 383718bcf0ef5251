"""Compile the main path for a TPU v5e without one attached.

The TPU compiler is installed with jaxlib and compiles for a described
topology: these tests AOT-compile each Pallas kernel regime of the
budgeted-DP solve and the dispatch engine's stream program at the sizes
``chip_smoke.py`` runs them, from ShapeDtypeStructs placed on one v5e chip.
A kernel the chip's compiler would refuse (an unaligned dynamic slice, a
vector load from SMEM, scratch over the VMEM limit) fails here, at no chip
time.  Nothing runs, so these say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every pytest
worker imports this file.  The compiled-mode solver is built in the test
(``interpret=False``), since the program's own auto-resolution sees the CPU
here and would pick the interpreter.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dp import build_tables
from repro.kernels.budgeted_dp.kernel import choose_tiling, dp_forward_pallas
from repro.kernels.budgeted_dp.ops import prepare_tables


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    # a compile written to the persistent cache cannot be read back
    # without a chip; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.values["jax_enable_compilation_cache"]
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def compiled_pallas():
    """The ``pallas`` backend in compiled mode, as it resolves on a chip."""
    from repro.core.solvers import Solver, _make_pallas_solve
    return Solver(name="pallas", interpret=False,
                  _fn=_make_pallas_solve(False), accepts_batch=True)


def _dp_bench_problem(name, s_cap=None):
    from benchmarks.dp_bench import CONFIGS, _make_problem
    cfg = next(c for c in CONFIGS if c["name"] == name)
    A, c, ups, sig = _make_problem(cfg)
    tables = build_tables(A, c)
    _, offs = prepare_tables(tables)
    s_cap = int(cfg.get("s_cap", ups.sum()) if s_cap is None else s_cap)
    return tables, s_cap, int(ups.max() + 1), int(offs.max())


def _fig5_problem(c, T):
    """Fig. 5's largest graph with ``c`` units of every device type, at
    horizon ``T``: (tables, s_cap, u_max, off_max) as the engine sizes
    them."""
    from repro.core import stats
    from repro.core.graph import generate_instance
    inst = generate_instance(seed=1, n_ports=16, n_servers=160,
                             edge_prob=0.1, c_lo=c, c_hi=c)
    tables = build_tables(inst.A, inst.c)
    _, offs = prepare_tables(tables)
    return (tables, stats.s_cap_for_horizon(T, inst.m),
            stats.u_max_for_horizon(T, inst.m), int(offs.max()))


# (regime, problem, expected tiling class, kernel name) — the shapes of
# chip_smoke.py's kernel phase, and Fig. 5's largest graph at c = 12 per
# type and T = 100 (S = 43,345, C = 2,197, u_max = 345), where even a
# one-edge chunk overflows the VMEM budget and the plane takes the per-edge
# scan
REGIMES = [
    ("whole_plane", lambda: _dp_bench_problem("E16_C512"), "whole",
     "dp_forward"),
    ("fused_c_blocked", lambda: _dp_bench_problem("E16_C4096", 127),
     "c_blocked", "dp_forward_fused"),
    ("fused_s_tiled", lambda: _dp_bench_problem("E16_C512_S4096"),
     "s_tiled", "dp_forward_fused"),
    ("edge_s_tiled", lambda: _fig5_problem(12, 100), "s_tiled_edge",
     "dp_forward_edge_tiled"),
]


@pytest.mark.parametrize("regime,problem,want,kernel", REGIMES,
                         ids=[r[0] for r in REGIMES])
def test_kernel_regime_compiles_for_v5e(one_chip, regime, problem, want, kernel):
    tables, s_cap, u_max, off_max = problem()
    S, C = s_cap + 1, tables.n_states
    E = int(np.asarray(tables.offsets).shape[0])
    be, bs, bc = choose_tiling(S, C, E, u_max, off_max)
    got = ("whole" if bc is None else "c_blocked" if bs is None
           else "s_tiled") + ("_edge" if bc is not None and be is None else "")
    assert got == want
    if regime == "edge_s_tiled":
        assert (S, C, E, u_max) == (43_345, 2_197, 252, 345)
        assert (be, bs, bc) == (None, 512, 512)

    def fwd(ups, sig, feas, offs, v0):
        return dp_forward_pallas(ups, sig, feas, offs, v0, n_edges=E,
                                 u_max=u_max, off_max=off_max,
                                 interpret=False, block_c=bc, block_s=bs,
                                 block_e=be)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(fwd).lower(
        spec((E,), jnp.int32), spec((E,), jnp.int32),
        spec((E, C), jnp.float32), spec((E,), jnp.int32),
        spec((S, C), jnp.int32)).compile()
    kernels = [ln for ln in compiled.as_text().splitlines()
               if "tpu_custom_call" in ln]
    assert kernels and all(f"/{kernel}/" in ln for ln in kernels)


def _fig6_capacity_deployment():
    """Fig. 5's largest graph at Fig. 6's largest capacity (6 per device
    type): the ``fig5_l16r160_c6`` benchmark deployment."""
    from repro.core.graph import generate_instance
    return generate_instance(seed=1, n_ports=16, n_servers=160,
                             edge_prob=0.1, c_lo=6, c_hi=6)


@pytest.mark.parametrize("T,S,Sp", [(50, 41_329, 41_472),
                                    (100, 43_345, 43_520)])
def test_fused_two_c_tiles_compile_for_v5e(one_chip, T, S, Sp):
    """At C = 343 (T = 50, and the benchmark cell's T = 100) the fused grid
    runs 4-edge chunks over (512, 256) tiles, two C tiles with a lane halo
    between them, on int32 value planes: the kernel compiles for a v5e and
    reads and writes no float32 plane."""
    from repro.core import stats
    inst = _fig6_capacity_deployment()
    tables = build_tables(inst.A, inst.c)
    _, offs = prepare_tables(tables)
    E, C, off_max = inst.n_edges, tables.n_states, int(offs.max())
    assert stats.s_cap_for_horizon(T, inst.m) + 1 == S
    u_max = stats.u_max_for_horizon(T, inst.m)
    be, bs, bc = choose_tiling(S, C, E, u_max, off_max)
    assert (C, be, bs, bc) == (343, 4, 512, 256)

    def fwd(ups, sig, feas, offs, v0):
        return dp_forward_pallas(ups, sig, feas, offs, v0, n_edges=E,
                                 u_max=u_max, off_max=off_max,
                                 interpret=False, block_c=bc, block_s=bs,
                                 block_e=be)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(fwd).lower(
        spec((E,), jnp.int32), spec((E,), jnp.int32),
        spec((E, C), jnp.float32), spec((E,), jnp.int32),
        spec((S, C), jnp.int32)).compile().as_text()
    kernels = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert kernels
    outs = [ln.split("custom-call(")[0] for ln in kernels]
    assert all(f"s32[{Sp},512]" in o and "f32" not in o for o in outs)


def test_fused_fig5_tiling_compiles_for_v5e(one_chip):
    """Fig. 5's largest graph at T = 100 (S = 43,345, C = 18) resolves the
    fused grid (8, 1024, 128) under the VMEM charge of a value tile and a
    word tile in and out, and that kernel compiles for a v5e: a tiling
    whose tiles overflow the chip's scoped VMEM fails here."""
    from repro.core import stats
    from repro.core.graph import generate_instance
    inst = generate_instance(seed=1, n_ports=16, n_servers=160,
                             edge_prob=0.1)
    tables = build_tables(inst.A, inst.c)
    _, offs = prepare_tables(tables)
    E, C, off_max = inst.n_edges, tables.n_states, int(offs.max())
    S = stats.s_cap_for_horizon(100, inst.m) + 1
    u_max = stats.u_max_for_horizon(100, inst.m)
    be, bs, bc = choose_tiling(S, C, E, u_max, off_max)
    assert (S, C, be, bs, bc) == (43_345, 18, 8, 1024, 128)

    def fwd(ups, sig, feas, offs, v0):
        return dp_forward_pallas(ups, sig, feas, offs, v0, n_edges=E,
                                 u_max=u_max, off_max=off_max,
                                 interpret=False, block_c=bc, block_s=bs,
                                 block_e=be)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(fwd).lower(
        spec((E,), jnp.int32), spec((E,), jnp.int32),
        spec((E, C), jnp.float32), spec((E,), jnp.int32),
        spec((S, C), jnp.int32)).compile().as_text()
    kernels = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(kernels) == 1
    assert "(s32[44032,128]" in kernels[0]
    assert "s32[352256,128]" in kernels[0]


def test_batched_vmap_solve_compiles_to_one_kernel(one_chip, compiled_pallas):
    """jax.vmap of the compiled solve at B=64 (the fleet batch) is ONE
    kernel launch in the compiled program."""
    tables, s_cap, u_max, _ = _dp_bench_problem("E16_C512")
    E, B = int(np.asarray(tables.offsets).shape[0]), 64

    def one(u, s, lim, al):
        return compiled_pallas(u, s, tables, s_cap, lim, allowed=al,
                               u_max=u_max)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(jax.vmap(one)).lower(
        spec((B, E), jnp.int32), spec((B, E), jnp.int32),
        spec((B,), jnp.int32), spec((B, E), jnp.int32)).compile()
    assert compiled.as_text().count("custom_call_target=\"tpu_custom_call\"") \
        == 1


def test_engine_stream_program_compiles_for_v5e(one_chip, compiled_pallas):
    """The dispatch engine's whole stream scan — admission, the compiled
    budgeted-DP kernel, packing, the bandit update — compiles for one v5e
    chip at the Table-2 instance and the 56,000-slot horizon."""
    from repro.core.graph import generate_instance
    from repro.sched import DispatchEngine, EngineConfig, VariantSpec

    inst = generate_instance()
    for variants in (
            (VariantSpec("esdp", solver=compiled_pallas),),
            (VariantSpec("esdp", weight=0.9, solver=compiled_pallas),
             VariantSpec("challenger", kind="hswf", weight=0.1))):
        eng = DispatchEngine(inst, 56_000, EngineConfig(variants=variants))
        compiled = eng._stream_fn().lower(
            *eng.stream_arg_shapes(sharding=one_chip)).compile()
        assert "tpu_custom_call" in compiled.as_text()


def test_engine_stream_program_names_its_stages_for_v5e(one_chip, compiled_pallas):
    """At Fig. 5's largest graph (the fused S-tiled kernel grid, T = 100)
    the compiled stream program names the kernel ``dp_forward_fused`` and
    carries every stage's ``esdp.*`` scope in its op_name metadata, so a
    profile of the chip splits a slot by stage."""
    from repro.core.graph import generate_instance
    from repro.sched import DispatchEngine, EngineConfig, VariantSpec

    inst = generate_instance(seed=1, n_ports=16, n_servers=160,
                             edge_prob=0.1)
    eng = DispatchEngine(inst, 100, EngineConfig(
        variants=(VariantSpec("esdp", solver=compiled_pallas),)))
    text = eng._stream_fn().lower(
        *eng.stream_arg_shapes(sharding=one_chip)).compile().as_text()
    kernels = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert kernels and all("/dp_forward_fused/" in ln for ln in kernels)
    stages = ("admission", "statistics", "forward", "select", "backtrack",
              "packing", "account", "oracle")
    assert not [s for s in stages if f"/esdp.{s}/" not in text]


_HLO_DEF = re.compile(r"^\s*(?:ROOT\s+)?%(\S+) = (\w+)\[([0-9,]*)\]\S* "
                      r"([\w-]+)\(([^)]*)\)", re.M)


@pytest.mark.parametrize("block_e", [8, 5], ids=["one_word", "two_words"])
def test_fused_merge_writes_owned_words_for_v5e(one_chip, block_e):
    """Compiled for a v5e, each fused chunk ORs its decision bits into the
    one word plane it owns inside the kernel (E=72, so W=3 word planes;
    block_e=5, ``two_words``, pads each word's edges to whole chunks, where
    its chunks would otherwise straddle two words): the
    ``tpu_custom_call`` aliases the value plane and the flat (W·Sp, Cp)
    decision carry to its two outputs, every int32 op the size of the
    carry is a parameter, tuple element, the zero broadcast or that custom
    call, and no (Sp, Cp) plane is copied at all.  The forward is taken
    as the solve consumes it: the value column of one state and the
    packed words."""
    E, S, C, Sp, Cp = 72, 256, 18, 256, 128
    W = (E + 31) // 32

    def fwd(ups, sig, feas, offs, v0):
        V, dec = dp_forward_pallas(ups, sig, feas, offs, v0, n_edges=E,
                                   u_max=8, off_max=17, interpret=False,
                                   block_c=Cp, block_s=64, block_e=block_e)
        return V[:, C - 1], dec

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(fwd).lower(
        spec((E,), jnp.int32), spec((E,), jnp.int32),
        spec((E, C), jnp.float32), spec((E,), jnp.int32),
        spec((S, C), jnp.int32)).compile().as_text()
    kernels = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(kernels) == 1
    out, operands = kernels[0].split(" custom-call(", 1)
    assert f"(s32[{Sp},{Cp}]" in out and f"s32[{W * Sp},{Cp}]" in out
    n_in = operands.split(")")[0].count("%")
    assert (f"output_to_operand_aliasing={{{{0}}: ({n_in - 2}, {{}}), "
            f"{{1}}: ({n_in - 1}, {{}})}}") in kernels[0]
    defs = _HLO_DEF.findall(text)
    sizes = {name: math.prod(int(d) for d in dims.split(",") if d)
             for name, _, dims, _, _ in defs}
    carry = {op for name, dtype, _, op, _ in defs
             if dtype == "s32" and sizes[name] == W * Sp * Cp}
    assert carry <= {"parameter", "get-tuple-element", "broadcast",
                     "custom-call"}
    assert not [name for name, dtype, _, op, _ in defs
                if dtype == "s32" and op == "copy" and sizes[name] == Sp * Cp]
