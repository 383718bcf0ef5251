"""The engine's own spans and counters (``repro.sched.telemetry``) and the
stage names its compiled stream program carries."""
import jax
import numpy as np
import pytest

from repro.core.graph import generate_instance
from repro.sched import DispatchEngine, EngineConfig, VariantSpec, telemetry

STAGES = ("esdp.admission", "esdp.statistics", "esdp.forward",
          "esdp.select", "esdp.backtrack", "esdp.packing", "esdp.account",
          "esdp.oracle")
T = 16


@pytest.fixture(scope="module")
def engine():
    eng = DispatchEngine(generate_instance(seed=0, n_ports=4, n_servers=8),
                         T, EngineConfig())
    eng.run(mode="stream", seed=1)  # compiles the T-slot program
    return eng


@pytest.fixture
def session(tmp_path):
    """A profiler session around the test body, with a cleared record."""
    telemetry.reset()
    with jax.profiler.trace(str(tmp_path)):
        yield
    telemetry.reset()


def one_slot(eng, t=0, seed=3):
    arrived, noise, tb = eng._streams(seed)
    return {"arrived": arrived[t:t + 1], "noise": noise[t:t + 1],
            "tb": tb[t:t + 1], "speed": eng.speed[t:t + 1],
            "alive": eng.alive[t:t + 1], "t": np.array([t], np.int32)}


def test_nothing_recorded_without_a_profiler_session(engine):
    telemetry.reset()
    assert not telemetry.recording()
    engine.run(mode="stream", seed=2)
    telemetry.count("engine.h2d_bytes", 10)
    assert telemetry.records() == []
    assert telemetry.counters() == {}


def test_stream_run_records_its_four_phases(engine, session):
    out = engine.run(mode="stream", seed=2)
    spans = telemetry.records()
    root = [s for s in spans if s.name == "engine.run"]
    assert len(root) == 1 and root[0].parent is None
    kids = sorted((s for s in spans if s.parent == "engine.run"),
                  key=lambda s: s.start_ns)
    assert [s.name for s in kids] == ["engine.inputs", "engine.launch",
                                      "engine.wait", "engine.fetch"]
    assert {s.id for s in kids} == {root[0].id}
    for a, b in zip(kids, kids[1:]):
        assert a.end_ns <= b.start_ns
    assert root[0].start_ns <= kids[0].start_ns
    assert kids[-1].end_ns <= root[0].end_ns
    inst = engine.inst
    # arrivals (bool), noise and tie-break (f32), the schedule's speed
    # (f32) and alive (bool), and the u32 salt; the slot index is made on
    # the device
    want = (T * inst.n_ports + 2 * 4 * T * inst.n_edges
            + 5 * T * inst.n_servers + 4)
    counts = telemetry.counters()
    assert counts["engine.h2d_bytes"] == want
    # read back: every per-slot output, and the final bandit statistics
    carry, ys = jax.eval_shape(engine._stream_scan, *engine.stream_arg_shapes())
    assert counts["engine.d2h_bytes"] == sum(
        a.size * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves((ys, carry["n"], carry["sumz"])))
    assert out.n.shape == carry["n"].shape
    assert counts.get("engine.jit_misses", 0) == 0


def test_one_slot_call_records_one_launch_and_its_host_bytes(engine, tmp_path):
    fn = engine._stream_fn()
    carry, xs, salt = engine._carry0(), one_slot(engine), np.uint32(7)
    fn(carry, xs, salt)  # compiles the one-slot program outside the window
    telemetry.reset()
    with jax.profiler.trace(str(tmp_path)):
        carry2, ys = fn(carry, xs, salt)
    spans = telemetry.records()
    counts = telemetry.counters()
    telemetry.reset()
    assert [(s.name, s.parent) for s in spans] == [("engine.launch", None)]
    assert counts["engine.h2d_bytes"] == sum(a.nbytes for a in xs.values()) + 4
    assert counts.get("engine.jit_misses", 0) == 0
    assert int(np.asarray(ys["arrivals"])[0]) == int(xs["arrived"].sum())
    # the seam keeps the jitted scan's ahead-of-time path
    assert fn.lower(*engine.stream_arg_shapes(1)).compile() is not None


def test_jit_misses_count_new_shapes_only(engine, session):
    fn = engine._stream_fn()
    xs = one_slot(engine)
    fn(engine._carry0(), xs, np.uint32(1))
    fn(engine._carry0(), xs, np.uint32(1))
    warm = telemetry.counters().get("engine.jit_misses", 0)
    fn(engine._carry0(), xs, np.uint32(2))
    assert telemetry.counters().get("engine.jit_misses", 0) == warm
    two = {k: np.concatenate([v, v]) for k, v in xs.items()}
    fn(engine._carry0(), two, np.uint32(2))  # a new shape: a miss
    assert telemetry.counters()["engine.jit_misses"] > warm


def test_record_is_bounded(session, monkeypatch):
    monkeypatch.setattr(telemetry, "MAX_SPANS", 3)
    for i in range(5):
        with telemetry.span("x", i):
            pass
    assert [s.id for s in telemetry.records()] == [0, 1, 2]
    assert telemetry.counters()["telemetry.dropped"] == 2


def test_nested_spans_name_their_parent(session):
    with telemetry.span("outer", 9):
        with telemetry.span("inner", 9):
            pass
    inner, outer = telemetry.records()
    assert (inner.name, inner.parent, outer.parent) == ("inner", "outer",
                                                        None)
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns


@pytest.mark.parametrize("solver", ["reference", "pallas_interpret"])
def test_stream_program_carries_the_stage_scopes(solver):
    """Each stage's ops carry its ``esdp.*`` scope in the compiled
    program's op_name metadata, for either solver backend."""
    eng = DispatchEngine(generate_instance(seed=0, n_ports=4, n_servers=8),
                         T, EngineConfig(variants=(
                             VariantSpec("esdp", solver=solver),)))
    text = eng._stream_fn().lower(*eng.stream_arg_shapes()).compile() \
        .as_text()
    missing = [s for s in STAGES if f"/{s}/" not in text]
    assert not missing

