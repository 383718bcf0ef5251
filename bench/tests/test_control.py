"""The comparison that decides ``correct`` fails its control and the faults
a run of this scheduler can have (CPU, small sizes)."""
import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, run
from bench.loops import LOOPS
from bench.traffic import gen
from repro.core.graph import generate_instance
from repro.sched.engine import DispatchEngine

CONFIG = {"name": "tiny", "instance": {"seed": 0, "n_ports": 8,
                                       "n_servers": 40, "edge_prob": 0.1},
          "horizon": 120,
          "engine": {"queue_capacity": 4, "backpressure": "drop_oldest",
                     "variants": ["esdp"]},
          "sizes": {"P": 8, "R": 40, "E": 33, "m": 17, "C": 12, "S": 800},
          "digest": "dfdcd9c7963ad04e"}
TRAFFIC = {
    "replay": gen.Traffic(name="replay", loop="replay", pool=2, check=2,
                          trace_units=1),
    "online": gen.Traffic(name="online", loop="online", horizon=400,
                          check=40, trace_units=60),
}


def _stand_in(monkeypatch, traffic):
    """A tiny cell, and no look for a chip."""
    cell = {"name": f"tiny.{traffic}", "config": "tiny", "traffic": traffic,
            "chips": 1}
    e2e = [{"name": "arrivals_per_s", "unit": "arrivals/s"},
           {"name": "decision_p50_ms", "unit": "ms"},
           {"name": "setup_s", "unit": "s"}]
    e2e = [m for m in e2e if m["name"] == "setup_s"
           or (m["name"] == "arrivals_per_s") == (traffic == "replay")]
    monkeypatch.setattr(run, "load_cell", lambda name: (cell, CONFIG, e2e, []))
    monkeypatch.setattr(run, "require_accelerator",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(gen, "load_traffic", lambda name: TRAFFIC[name])


def _run(capsys, traffic, seed=5):
    args = argparse.Namespace(workload=f"tiny.{traffic}", seed=seed,
                              seconds=0.3, trace=0)
    line = run.run(args)
    out = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(out) == json.loads(json.dumps(line))
    return line


@pytest.mark.parametrize("traffic", ["replay", "online"])
def test_sound_run_is_correct(monkeypatch, capsys, traffic):
    _stand_in(monkeypatch, traffic)
    line = _run(capsys, traffic)
    assert line["correct"], line["checks"]
    assert line["checks"]["mismatches"]["value"] == 0
    assert list(line)[-1] == "checks"
    assert line["attempted"] > 0 and line["failed"] == 0


def _state_unchanged(orig):
    def body(self, carry, xs_t, salt):
        _, ys = orig(self, carry, xs_t, salt)
        return carry, ys
    return "_scan_body", body


def _half_left_out(orig):
    def body(self, carry, xs_t, salt):
        P = xs_t["arrived"].shape[-1]
        keep = jnp.arange(P) < P // 2
        return orig(self, carry, dict(xs_t, arrived=xs_t["arrived"] & keep),
                    salt)
    return "_scan_body", body


def _answer_altered(orig):
    def dispatch(self, queue2, load, x_raw, elig, vhat, age):
        xv, x, served, queue3, load2, qlen = orig(self, queue2, load, x_raw,
                                                  elig, vhat, age)
        # every dispatch from the fourth job of the trace on; the online
        # check solves a sample of slots afresh, so an answer altered in a
        # single slot is caught only where that slot is drawn
        later = jnp.sum(load) >= 3
        xv = jnp.where(later, jnp.roll(xv, 1, axis=1), xv)
        return xv, x, served, queue3, load2, qlen
    return "_slot_dispatch", dispatch


FAULTS = {"state_unchanged": _state_unchanged,
          "half_left_out": _half_left_out,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("traffic", ["replay", "online"])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(monkeypatch, capsys, traffic, fault):
    _stand_in(monkeypatch, traffic)
    name = {"state_unchanged": "_scan_body", "half_left_out": "_scan_body",
            "answer_altered": "_slot_dispatch"}[fault]
    attr, broken = FAULTS[fault](getattr(DispatchEngine, name))
    monkeypatch.setattr(DispatchEngine, attr, broken)
    line = _run(capsys, traffic)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("traffic", ["replay", "online"])
def test_bfloat16_control_is_not_correct(traffic):
    """The reference in bfloat16 statistics, in the program's place."""
    inst = generate_instance(**CONFIG["instance"])
    tr = TRAFFIC[traffic]
    T = tr.horizon or CONFIG["horizon"]
    low = LOOPS[traffic](None, inst, T, tr, CONFIG, seed=9)
    readings = low.control_readings(units=2 if traffic == "replay" else 300)
    correct, checks = check.verdict(readings)
    assert not correct, checks
    assert checks["mismatches"]["value"] > 0
