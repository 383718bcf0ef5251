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
import os

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


# (regime, dp_bench config, budget-axis override, expected tiling class) —
# the shapes of chip_smoke.py's kernel phase
REGIMES = [
    ("whole_plane", "E16_C512", None, "whole"),
    ("fused_c_blocked", "E16_C4096", 127, "c_blocked"),
    ("fused_s_tiled", "E16_C512_S4096", None, "s_tiled"),
]


@pytest.mark.parametrize("regime,name,s_cap,want", REGIMES,
                         ids=[r[0] for r in REGIMES])
def test_kernel_regime_compiles_for_v5e(one_chip, regime, name, s_cap, want):
    tables, s_cap, u_max, off_max = _dp_bench_problem(name, s_cap)
    S, C = s_cap + 1, tables.n_states
    E = int(np.asarray(tables.offsets).shape[0])
    be, bs, bc = choose_tiling(S, C, E, u_max, off_max)
    got = "whole" if bc is None else "c_blocked" if bs is None else "s_tiled"
    assert got == want and (want == "whole" or be is not None)

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
        spec((S, C), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


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
