"""The benchmark's only contact with the program: building the engine and,
for the online cell, one slot per call.

The replay cells use the public ``DispatchEngine.run(mode="stream",
streams=...)``.  The engine has no public per-slot entry, so the online
loop steps the engine's own jitted stream scan one slot at a time, with
the carry kept on the device between calls.  That uses four members of
``repro.sched.engine.DispatchEngine`` — the seam this file depends on:

* ``_stream_fn()`` — the jitted ``(carry, xs, salt) -> (carry, ys)`` scan
  that ``run(mode="stream")`` calls;
* ``_carry0()`` — the fresh carry (queue, bandit counts ``n`` and sums,
  server load);
* ``_route_salt(seed)`` — the per-trace routing salt ``run`` passes;
* ``stream_arg_shapes(T)`` — the shapes of the scan's arguments, which
  this file checks against the inputs it hands over.

If the seam moves, :class:`OnlineStep` raises :class:`SeamMoved` naming
what changed, before anything is timed.  Once the engine has a public
per-slot step (and ``run_batch`` takes given streams), point this file at
those and drop the private names.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.sched.engine import DispatchEngine, EngineConfig, VariantSpec

__all__ = ["SeamMoved", "build_engine", "OnlineStep", "LEDGER_KEYS"]

SEAM = ("_stream_fn", "_carry0", "_route_salt", "stream_arg_shapes")
XS_KEYS = ("arrived", "noise", "tb", "speed", "alive", "t")
# per-slot ledger outputs of the scan, as the engine names them
LEDGER_KEYS = ("arrivals", "rejected", "blocked", "dropped", "shed",
               "admitted", "dispatched", "qlen")


class SeamMoved(RuntimeError):
    """The engine internals the online loop relies on have changed."""


def build_engine(inst, T: int, engine: dict) -> DispatchEngine:
    """The engine as the configuration states it: one ESDP variant."""
    if engine.get("variants", ["esdp"]) != ["esdp"]:
        raise ValueError("the benchmark's configurations run the single "
                         "ESDP variant")
    cfg = EngineConfig(queue_capacity=engine["queue_capacity"],
                       backpressure=engine["backpressure"],
                       variants=(VariantSpec("esdp"),))
    return DispatchEngine(inst, T, cfg)


class OnlineStep:
    """One slot per call of the engine's stream scan, carry on the device."""

    def __init__(self, engine: DispatchEngine):
        missing = [a for a in SEAM if not callable(getattr(engine, a, None))]
        if missing:
            raise SeamMoved(
                f"DispatchEngine no longer has {missing}; the online loop "
                "in bench/adapter.py needs a per-slot entry to call")
        carry, xs, _ = engine.stream_arg_shapes(1)
        if set(xs) != set(XS_KEYS) or "n" not in carry:
            raise SeamMoved(
                f"the stream scan now takes xs keys {sorted(xs)} and carry "
                f"keys {sorted(carry)}; bench/adapter.py hands over "
                f"{sorted(XS_KEYS)} and reads carry['n']")
        self.engine = engine
        self._fn = engine._stream_fn()
        self._speed = np.asarray(engine.speed, np.float32)
        self._alive = np.asarray(engine.alive, bool)

    def start(self, seed: int):
        """(fresh carry on the device, routing salt) for a trace."""
        return (self.engine._carry0(),
                np.uint32(self.engine._route_salt(int(seed))))

    def step(self, carry, salt, t: int, arrived, noise, tb):
        """Run slot ``t``; returns (carry, n on the host, ledger on the host).

        ``arrived``, ``noise`` and ``tb`` are the slot's rows, shaped (1, ·).
        The call returns once the slot's bandit counts ``n`` (whose change
        is the slot's per-edge dispatch) and its ledger counts are on the
        host."""
        xs = {"arrived": arrived, "noise": noise, "tb": tb,
              "speed": self._speed[t:t + 1], "alive": self._alive[t:t + 1],
              "t": np.array([t], np.int32)}
        carry, ys = self._fn(carry, xs, salt)
        n, ledger = jax.device_get(
            (carry["n"], {k: ys[k] for k in LEDGER_KEYS}))
        return carry, n, ledger
