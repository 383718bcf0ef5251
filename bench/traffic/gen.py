"""The one traffic generator: a traffic mix's parameters → per-slot inputs.

The arrays follow the engine's own trace rules (``DispatchEngine._streams``),
copied here so that a later change to the engine cannot move the yardstick,
with one change that keeps the work of a trace the same from seed to seed:

* arrivals: Bernoulli(ρ_l) per port and slot, conditioned on their count —
  each port receives exactly round(ρ_l · T) jobs in a trace, at slots drawn
  uniformly without replacement (the ranks of a (T, P) uniform draw, made
  first from ``default_rng(seed)``).  Free Bernoulli counts vary by about
  1% per 100-slot trace, which moved a rate in arrivals per second with the
  seed while the solver's work stayed the same;
* valuation noise: N(0, 1) per slot and edge, float32, drawn next from the
  same generator;
* tie-break: U(0, 1) per slot and edge, float32, from
  ``default_rng(seed + 1)``.

Every server runs at speed 1 and stays alive: the paper's Table-2 setting
has no speed fluctuation schedule, and the engine's default is the same.
The program only ever receives the arrays made here.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Traffic:
    """One traffic mix, read from ``traffic/<name>.json``."""

    name: str
    loop: str  # "replay": one engine run per episode; "online": one slot per call
    horizon: "int | None" = None  # overrides the configuration's horizon
    pool: int = 64  # episodes generated in set-up (replay)
    check: int = 2  # episodes (replay) or solved slots (online) the check samples
    trace_units: int = 1  # episodes (replay) or slots (online) in the traced window


def load_traffic(name: str) -> Traffic:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r}: {path} is missing")
    raw = json.loads(path.read_text())
    raw.pop("why", None)
    return Traffic(name=name, **raw)


def streams(rho: np.ndarray, n_edges: int, T: int, seed: int):
    """(arrivals (T, P) bool, noise (T, E) f32, tiebreak (T, E) f32)."""
    rng = np.random.default_rng(seed)
    count = np.rint(np.asarray(rho, np.float64) * T).astype(np.int64)
    rank = rng.random((T, count.shape[0])).argsort(axis=0).argsort(axis=0)
    arrivals = rank < count[None, :]
    noise = rng.normal(0.0, 1.0, (T, n_edges)).astype(np.float32)
    tb = np.random.default_rng(seed + 1).random((T, n_edges)).astype(
        np.float32)
    return arrivals, noise, tb
