"""The plain reference agrees with ``DispatchEngine`` slot for slot (CPU,
small sizes), through both timed loops the benchmark uses."""
import numpy as np
import pytest

from bench.adapter import LEDGER_KEYS, OnlineStep, build_engine
from bench.reference import Reference
from bench.traffic.gen import streams
from repro.core.graph import generate_instance

ENGINE = {"queue_capacity": 4, "backpressure": "drop_oldest",
          "variants": ["esdp"]}
# the two configurations' generators, cut to CPU size
GENERATORS = {
    "table2": dict(seed=0, n_ports=8, n_servers=40, edge_prob=0.1),
    "fig5_l16r160": dict(seed=1, n_ports=16, n_servers=40, edge_prob=0.1),
}


@pytest.mark.parametrize("config", sorted(GENERATORS))
@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_replay_matches_engine(config, seed):
    inst = generate_instance(**GENERATORS[config])
    T = 200
    engine = build_engine(inst, T, ENGINE)
    trace = streams(inst.rho, inst.n_edges, T, seed)
    _, noise, tb = engine._streams(seed)  # the generator is the engine's,
    np.testing.assert_array_equal(trace[1], noise)
    np.testing.assert_array_equal(trace[2], tb)
    # with each port's arrival count fixed at round(ρ·T)
    np.testing.assert_array_equal(trace[0].sum(axis=0),
                                  np.rint(inst.rho * T).astype(np.int64))
    out = engine.run(mode="stream", seed=seed, streams=trace)
    want = Reference.for_instance(inst, T, ENGINE).replay(trace[0], trace[1])
    assert want["dispatched"].sum() > T // 2
    for key in ("arrivals", "rejected", "blocked", "dropped", "shed",
                "admitted", "dispatched", "queue_len"):
        np.testing.assert_array_equal(out.ledger[key], want[key], key)
    np.testing.assert_array_equal(out.routed_variant, want["routed"])
    np.testing.assert_array_equal(out.n, want["n"])
    for got, key in ((out.sw, "sw"), (out.regret, "regret"),
                     (out.dispatch_share, "share"), (out.sumz, "sumz")):
        np.testing.assert_array_equal(got, want[key], key)


@pytest.mark.parametrize("config", sorted(GENERATORS))
def test_online_step_matches_teacher_forced_reference(config):
    inst = generate_instance(**GENERATORS[config])
    T, N, seed = 500, 150, 7
    engine = build_engine(inst, T, ENGINE)
    arrived, noise, tb = streams(inst.rho, inst.n_edges, T, seed)
    step = OnlineStep(engine)
    carry, salt = step.start(seed)
    prev = np.zeros(inst.n_edges, np.int64)
    xs, ledgers = [], []
    for t in range(N):
        carry, n, ledger = step.step(carry, salt, t, arrived[t:t + 1],
                                     noise[t:t + 1], tb[t:t + 1])
        xs.append(n[0] - prev)
        prev = n[0]
        ledgers.append(ledger)
    xs = np.asarray(xs)
    ref = Reference.for_instance(inst, T, ENGINE)
    solve_at = range(0, N, 3)
    want, solved, n_ref, sumz_ref = ref.follow(arrived[:N], noise[:N], xs,
                                               solve_at)
    for key in LEDGER_KEYS:
        got = np.array([int(np.asarray(l[key])[0]) for l in ledgers])
        np.testing.assert_array_equal(
            got, want["queue_len" if key == "qlen" else key], key)
    assert len(solved) == len(solve_at)
    for i, x in solved.items():
        np.testing.assert_array_equal(xs[i], x, f"slot {i}")
    np.testing.assert_array_equal(np.asarray(carry["n"])[0], n_ref)
    np.testing.assert_array_equal(np.asarray(carry["sumz"])[0], sumz_ref)
    # the same trace run whole gives the same dispatches
    out = engine.run(mode="stream", seed=seed, streams=(arrived, noise, tb))
    np.testing.assert_array_equal(out.ledger["admitted"][:N],
                                  want["admitted"])


def test_changed_deployment_is_refused():
    """The harness refuses to run a configuration whose generator now
    builds another deployment than the one the file records."""
    import json
    import pathlib

    from bench import run

    path = pathlib.Path(run.BENCH) / "configs" / "table2.json"
    config = json.loads(path.read_text())
    inst = generate_instance(**config["instance"])
    run.check_instance(inst, config)  # the recorded deployment passes
    other = generate_instance(**dict(config["instance"], seed=7))
    with pytest.raises(SystemExit, match="another deployment"):
        run.check_instance(other, config)
    with pytest.raises(SystemExit, match="another deployment"):
        run.check_instance(inst, dict(config, digest="0" * 16))
