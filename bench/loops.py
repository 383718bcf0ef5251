"""The two timed loops a traffic mix can name: ``replay`` and ``online``.

A loop object owns one cell's timed loop.  ``setup`` makes the traffic and warms
the one program shape the cell uses; ``window`` runs the timed loop for a
number of seconds (or, for the traced run, a number of units) and returns
the end-to-end measurements; ``check`` compares what the window produced
with the reference once the window has closed.
"""
from __future__ import annotations

import time
import types

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from bench.adapter import LEDGER_KEYS, OnlineStep
from bench.check import Readings
from bench.reference import Reference
from bench.traffic.gen import streams

__all__ = ["LOOPS"]

# EngineOutput.ledger keys, as the reference names them
REPLAY_LEDGER = ("arrivals", "rejected", "blocked", "dropped", "shed",
                 "admitted", "dispatched", "queue_len")


class Replay:
    """Whole episodes back to back, one ``DispatchEngine.run`` each.

    Episode k of a run with seed s replays the trace of seed s + k."""

    def __init__(self, engine, inst, T, traffic, config, seed):
        self.engine, self.inst, self.T = engine, inst, T
        self.traffic, self.config, self.seed = traffic, config, seed
        self.outputs = []
        self.attempted = self.failed = 0

    def _streams(self, k):
        if k < len(self.pool):
            return self.pool[k]
        with TraceAnnotation("bench.gen"):
            return streams(self.inst.rho, self.inst.n_edges, self.T,
                           self.seed + k)

    def setup(self):
        self.pool = []
        for k in range(self.traffic.pool):
            self.pool.append(self._streams(k))
        # warm-up: compiles the stream program, or loads it from the cache
        self.engine.run(mode="stream", seed=self.seed, streams=self.pool[0])

    def window(self, seconds=None, units=None):
        arrivals = 0
        k = 0
        start = time.perf_counter()
        while True:
            trace = self._streams(k)
            self.attempted += self.T
            with TraceAnnotation("bench.call"):
                try:
                    out = self.engine.run(mode="stream", seed=self.seed + k,
                                          streams=trace)
                except Exception as exc:  # counted, and the run goes on
                    print(f"episode {k} raised: {exc!r}", flush=True)
                    out = None
            if out is None:
                self.failed += self.T
            else:
                arrivals += int(trace[0].sum())
            self.outputs.append(out)
            k += 1
            if units is not None:
                if k >= units:
                    break
            elif time.perf_counter() - start >= seconds:
                break
        elapsed = time.perf_counter() - start
        return {"arrivals_per_s": arrivals / elapsed}, k * self.T

    def check(self, readings: Readings, precision="float32"):
        ref = Reference.for_instance(self.inst, self.T,
                                     self.config["engine"], precision)
        rng = np.random.default_rng(self.seed)
        n = len(self.outputs)
        for k in sorted(rng.choice(n, size=min(self.traffic.check, n),
                                   replace=False)):
            out = self.outputs[k]
            if out is None:
                continue
            arrivals, noise, _ = self._streams(int(k))
            with TraceAnnotation("bench.check"):
                want = ref.replay(arrivals, noise)
            for key in REPLAY_LEDGER:
                readings.ints(out.ledger[key], want[key])
            readings.ints(out.routed_variant, want["routed"])
            readings.ints(out.n, want["n"])
            readings.floats(out.sw, want["sw"])
            readings.floats(out.regret, want["regret"])
            readings.floats(out.dispatch_share, want["share"])
            readings.floats(out.sumz, want["sumz"])

    def control_readings(self, units, precision="bfloat16"):
        """The check, with the reference at ``precision`` in the program's
        place over ``units`` episodes."""
        low = Reference.for_instance(self.inst, self.T,
                                     self.config["engine"], precision)
        self.pool, self.outputs = [], []
        for k in range(units):
            arrivals, noise, _ = self._streams(k)
            o = low.replay(arrivals, noise)
            self.outputs.append(types.SimpleNamespace(
                ledger=o, routed_variant=o["routed"], n=o["n"], sw=o["sw"],
                regret=o["regret"], dispatch_share=o["share"],
                sumz=o["sumz"]))
        readings = Readings()
        self.check(readings)
        return readings


class Online:
    """One slot per call in a closed loop; a new trace (seed + 1, fresh
    carry) starts when one reaches the engine's horizon."""

    def __init__(self, engine, inst, T, traffic, config, seed):
        self.engine, self.inst, self.T = engine, inst, T
        self.traffic, self.config, self.seed = traffic, config, seed
        self.attempted = self.failed = 0
        self.segments = []  # per trace: (seed, decisions, ledgers, sumz)

    def _trace(self, seed):
        return streams(self.inst.rho, self.inst.n_edges, self.T, seed)

    def setup(self):
        self.step = OnlineStep(self.engine)
        self.traces = {self.seed: self._trace(self.seed)}
        # warm-up on a carry of its own: compiles the one-slot program
        arrived, noise, tb = self.traces[self.seed]
        carry, salt = self.step.start(self.seed)
        for t in range(3):
            carry, _, _ = self.step.step(carry, salt, t, arrived[t:t + 1],
                                         noise[t:t + 1], tb[t:t + 1])

    def window(self, seconds=None, units=None):
        lat = []
        seed = self.seed
        start = time.perf_counter()
        done = False
        while not done:
            if seed not in self.traces:
                with TraceAnnotation("bench.gen"):
                    self.traces[seed] = self._trace(seed)
            arrived, noise, tb = self.traces[seed]
            carry, salt = self.step.start(seed)
            n_prev = np.zeros((1, self.inst.n_edges), np.int32)
            xs, ledgers = [], []
            for t in range(self.T):
                t_call = time.perf_counter()
                self.attempted += 1
                with TraceAnnotation("bench.call"):
                    try:
                        carry, n, ledger = self.step.step(
                            carry, salt, t, arrived[t:t + 1],
                            noise[t:t + 1], tb[t:t + 1])
                    except Exception as exc:  # counted, the trace ends
                        print(f"slot {t} raised: {exc!r}", flush=True)
                        self.failed += 1
                        break
                now = time.perf_counter()
                lat.append(now - t_call)
                xs.append(n[0] - n_prev[0])
                ledgers.append(ledger)
                n_prev = n
                if units is not None:
                    done = len(lat) >= units
                else:
                    done = now - start >= seconds
                if done:
                    break
            with TraceAnnotation("bench.fetch"):
                sumz = np.asarray(jax.device_get(carry["sumz"]))
            self.segments.append((seed, np.asarray(xs), ledgers, sumz))
            seed += 1
        ms = np.asarray(lat) * 1e3
        return ({"decision_p50_ms": float(np.percentile(ms, 50)),
                 "decision_p99_ms": float(np.percentile(ms, 99))}, len(lat))

    def check(self, readings: Readings, precision="float32"):
        ref = Reference.for_instance(self.inst, self.T,
                                     self.config["engine"], precision)
        rng = np.random.default_rng(self.seed)
        total = sum(len(x) for _, x, _, _ in self.segments)
        picks = set(rng.choice(total, size=min(self.traffic.check, total),
                               replace=False).tolist()) if total else set()
        base = 0
        for seed, xs, ledgers, sumz in self.segments:
            N = len(xs)
            if N == 0:
                continue
            arrived, noise, _ = self.traces[seed]
            solve_at = [i - base for i in picks if base <= i < base + N]
            with TraceAnnotation("bench.check"):
                want, solved, _, ref_sumz = ref.follow(
                    arrived[:N], noise[:N], xs, solve_at)
            for key in LEDGER_KEYS:
                got = np.array([int(np.asarray(l[key]).reshape(-1)[0])
                                for l in ledgers])
                readings.ints(got, want["queue_len" if key == "qlen"
                                        else key])
            for i, x_ref in solved.items():
                readings.ints(xs[i], x_ref)
            readings.floats(sumz[0], ref_sumz)
            base += N

    def control_readings(self, units, precision="bfloat16"):
        """The check, with the reference at ``precision`` in the program's
        place over the first ``units`` slots of the run's first trace."""
        low = Reference.for_instance(self.inst, self.T,
                                     self.config["engine"], precision)
        trace = self._trace(self.seed)
        self.traces = {self.seed: trace}
        o = low.replay(trace[0][:units], trace[1][:units])
        names = {k: "queue_len" if k == "qlen" else k for k in LEDGER_KEYS}
        ledgers = [{k: np.array([o[v][t]]) for k, v in names.items()}
                   for t in range(units)]
        self.segments = [(self.seed, o["x"], ledgers, o["sumz"])]
        readings = Readings()
        self.check(readings)
        return readings


LOOPS = {"replay": Replay, "online": Online}
