"""Simulation environment for the EASW maximization problem (paper Sec. 2).

One jitted ``lax.scan`` over the horizon: draw arrivals ~ Bernoulli(ρ_l) and
net valuations z̃_e(t) = clip(N(μ_e·speed_r(t) − cost_e, σ_e), 0, 1), ask the
policy for x(t), enforce constraint (2), realize SW(x(t)) = Σ_e x_e·z̃_e
(eq. 4), update the shared observation statistics, and account the per-slot
regret against the omniscient oracle x*(t) (eq. 5–6).

The generative regime — how arrival intensities, processing speeds, and
server aliveness evolve over time — is pluggable through the ``Scenario``
protocol below.  The default scenario (constant unit speeds, constant ρ, all
servers alive) reproduces the paper's iid-Gaussian setting bit-for-bit; the
named fluctuation regimes (Markov-modulated DVFS, bursty MMPP arrivals,
chronic stragglers, transient brownouts, elastic outages) live in
``repro.experiments.scenarios`` and are consumed both here and by
``repro.sched.dispatcher`` — one scenario interface for both simulators.

Batched evaluation: ``simulate_batch`` vmaps the whole scan over a seed
batch (one jitted call per (policy × scenario × grid-point)), and
``repro.experiments.sweep`` adds a ``lax.map`` over scenario-parameter
grids on top.  This is what replaces the per-seed Python loops the
benchmarks used to run.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from .dp import DPTables, build_tables, oracle_knapsack
from .esdp import Policy
from .graph import Instance

__all__ = [
    "Scenario", "default_scenario", "SimResult",
    "simulate", "simulate_batch", "simulate_grid", "crash_events",
]

# Salt folded into the simulation key to derive the scenario's private PRNG
# chain.  Keeping the chains separate means *adding* a stochastic scenario
# never perturbs the arrival/valuation/policy streams of the base seed —
# paired comparisons across scenarios stay paired.
_SCENARIO_SALT = 0x5CE


@dataclasses.dataclass(frozen=True, eq=False)  # identity hash — jit-static-safe
class Scenario:
    """A named generative regime for arrivals and processing speeds.

    ``init(params, key, n_servers) -> state`` builds the scenario's carry
    (e.g. Markov regime indicators plus a private PRNG key); ``step(params,
    state, t, n_servers) -> (state, arr_scale, speed, alive)`` advances it one
    slot and emits:

      arr_scale: scalar or (L,) f32 — multiplies the instance's ρ (clipped to
        [0, 1]); models bursty / modulated arrival processes.
      speed:     (R,) f32 — per-server processing-speed multiplier; the mean
        net valuation of channel e = (l, r) becomes μ_e·speed_r − cost_e
        (the paper's "fluctuated processing speeds").
      alive:     (R,) bool — dead servers make their channels infeasible
        (elastic scale-down/up; the dispatcher's ``allowed`` mask).

    ``params`` is a pytree of scalars/arrays and is passed *traced*, so sweeps
    can ``lax.map`` over stacked parameter grids without recompiling.
    ``fluctuates`` must be True iff ``speed`` can differ from 1: it switches
    the regret oracle from the precomputed true means to per-slot clipped
    means (a static branch — each scenario compiles its own jaxpr).

    ``speed_bounds`` is the regime's declared (lo, hi) envelope for every
    emitted per-server speed — a *contract*, not a hint: the scenario
    contract suite (``tests/test_scenario_contracts.py``) asserts each
    registered regime's realized speeds stay inside its declared bounds.
    Builders derive it from their resolved parameters (e.g. ``markov_dvfs``
    declares ``(slow_speed, 1.0)``); the default ``(1.0, 1.0)`` is the
    non-fluctuating contract.
    """

    name: str
    init: Callable[..., Any]
    step: Callable[..., tuple]
    params: dict = dataclasses.field(default_factory=dict)
    fluctuates: bool = False
    description: str = ""
    speed_bounds: tuple = (1.0, 1.0)


def _default_init(params, key, n_servers):
    return ()


def _default_step(params, state, t, n_servers):
    return (state, jnp.float32(1.0), jnp.ones(n_servers, jnp.float32),
            jnp.ones(n_servers, dtype=bool))


def default_scenario() -> Scenario:
    """The paper's baseline regime: iid-Gaussian valuations, constant ρ,
    unit speeds, every server alive.  Multiplying by the emitted unit scales
    is IEEE-exact, so this reproduces the pre-Scenario simulator bit-for-bit.
    """
    return Scenario(
        name="iid",
        init=_default_init,
        step=_default_step,
        fluctuates=False,
        description="iid clipped-Gaussian valuations at constant unit speed "
                    "(paper Sec. 5 baseline setting)",
    )


def crash_events(alive):
    """(T, R) bool: server r crashed DURING slot t.

    The aliveness trace encodes crashes as up→down transitions: a server
    that was alive when slot t dispatched but is dead at slot t+1 died
    mid-slot, so work dispatched onto it in slot t is at risk (the
    failure-aware runtime in ``sched.dispatcher`` uses exactly this
    coupling — the ``server_failures`` scenario emits ``alive`` BEFORE
    applying the slot's crash draws so the transition is observable).
    The final slot has no successor to compare against and reports no
    crashes.  Host-side numpy helper; pure in the trace.
    """
    alive = np.asarray(alive, dtype=bool)
    out = np.zeros_like(alive)
    out[:-1] = alive[:-1] & ~alive[1:]
    return out


_DEFAULT_SCENARIO = default_scenario()

_SQRT2 = math.sqrt(2.0)
_INV_SQRT_2PI = 1.0 / math.sqrt(2.0 * math.pi)


def _clipped_normal_mean_jnp(m, s, lo=0.0, hi=1.0):
    """E[clip(N(m, s), lo, hi)] — traced counterpart of
    ``graph.clipped_normal_mean`` for per-slot fluctuated oracle means."""
    s = jnp.maximum(s, 1e-6)
    a = (lo - m) / s
    b = (hi - m) / s
    phi_a = _INV_SQRT_2PI * jnp.exp(-0.5 * a * a)
    phi_b = _INV_SQRT_2PI * jnp.exp(-0.5 * b * b)
    Phi_a = 0.5 * (1.0 + jax.scipy.special.erf(a / _SQRT2))
    Phi_b = 0.5 * (1.0 + jax.scipy.special.erf(b / _SQRT2))
    inner = m * (Phi_b - Phi_a) - s * (phi_b - phi_a)
    return lo * Phi_a + hi * (1.0 - Phi_b) + inner


@dataclasses.dataclass(frozen=True)
class SimResult:
    """Per-slot traces.  Arrays are (T,) for ``simulate`` and gain leading
    batch axes — (S, T) for ``simulate_batch``, (G, S, T) for parameter
    grids — with all derived quantities accumulating along the last axis."""

    sw: np.ndarray  # (..., T) realized social welfare per slot
    sw_oracle: np.ndarray  # (..., T) oracle expected welfare ṽᵀx*(t)
    regret: np.ndarray  # (..., T) ṽᵀx*(t) − ṽᵀx(t)  (expected per-slot gap)
    n_dispatched: np.ndarray  # (..., T) ‖x(t)‖₁
    # final policy state (numpy pytree, batch axes as above) — e.g. the
    # incremental-solve counters that Policy.finalize turns into stats
    policy_final: Any = None

    @property
    def asw(self) -> np.ndarray:
        return np.cumsum(self.sw, axis=-1)

    @property
    def cum_regret(self) -> np.ndarray:
        return np.cumsum(self.regret, axis=-1)


def _run_impl(
    policy: Policy,
    T: int,
    tables: DPTables,
    scenario: Scenario,
    n_servers: int,
    arrays,
    key,
    scn_params,
):
    v_true, mu, sigma, cost, rho, port, server = arrays
    E = v_true.shape[0]
    L = rho.shape[0]

    scn_state0 = scenario.init(scn_params, jax.random.fold_in(
        key, _SCENARIO_SALT), n_servers)

    def slot(carry, t):
        n, sumz, pstate, sstate, key = carry
        key, k_arr, k_val, k_pol = jax.random.split(key, 4)

        sstate, arr_scale, speed, alive = scenario.step(
            scn_params, sstate, t, n_servers)
        rho_t = jnp.clip(rho * arr_scale, 0.0, 1.0)
        arrived = jax.random.uniform(k_arr, (L,)) < rho_t
        mean_e = mu * speed[server] - cost
        z = jnp.clip(mean_e + sigma * jax.random.normal(k_val, (E,)), 0.0, 1.0)
        eligible = arrived[port] & alive[server]

        vhat = jnp.where(n > 0, sumz / jnp.maximum(n, 1).astype(jnp.float32), 0.0)
        x, pstate = policy.step(pstate, t.astype(jnp.float32), eligible,
                                arrived, vhat, n, k_pol)
        x = x * eligible.astype(jnp.int32)  # constraint (2)

        xf = x.astype(jnp.float32)
        sw = jnp.sum(xf * z)  # realized SW (eq. 4)
        if scenario.fluctuates:  # static branch
            v_t = _clipped_normal_mean_jnp(mean_e, sigma)
        else:
            v_t = v_true
        x_star, sw_star = oracle_knapsack(v_t, tables, eligible)
        regret = sw_star - jnp.sum(xf * v_t)  # expected gap (eq. 5)

        n = n + x
        sumz = sumz + xf * z
        return (n, sumz, pstate, sstate, key), (sw, sw_star, regret, jnp.sum(x))

    carry0 = (jnp.zeros(E, jnp.int32), jnp.zeros(E, jnp.float32),
              policy.init(), scn_state0, key)
    ts = jnp.arange(1, T + 1)
    carry, (sw, sw_star, regret, nd) = jax.lax.scan(slot, carry0, ts)
    return (sw, sw_star, regret, nd), carry[2]  # traces + final policy state


_STATIC = ("policy", "T", "tables", "scenario", "n_servers")

_run = functools.partial(jax.jit, static_argnames=_STATIC)(_run_impl)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _run_batch(policy, T, tables, scenario, n_servers, arrays, keys, scn_params):
    """One jitted call: vmap the whole horizon scan over a seed batch."""
    return jax.vmap(
        lambda k: _run_impl(policy, T, tables, scenario, n_servers, arrays, k,
                            scn_params))(keys)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _run_param_grid(
    policy, T, tables, scenario, n_servers, arrays, keys, stacked_params
):
    """lax.map over a stacked scenario-parameter grid of vmapped seed
    batches — one compilation covers the whole (grid × seeds) sweep."""
    def one(params):
        return jax.vmap(
            lambda k: _run_impl(policy, T, tables, scenario, n_servers,
                                arrays, k, params))(keys)
    return jax.lax.map(one, stacked_params)


def _instance_arrays(instance: Instance):
    return (
        jnp.asarray(instance.v), jnp.asarray(instance.mu),
        jnp.asarray(instance.sigma), jnp.asarray(instance.cost),
        jnp.asarray(instance.rho), jnp.asarray(instance.port_of_edge),
        jnp.asarray(instance.edges[:, 1].astype(np.int32)),
    )


def _scenario_args(instance, tables, scenario):
    if tables is None:
        tables = build_tables(instance.A, instance.c)
    if scenario is None:
        scenario = _DEFAULT_SCENARIO
    params = jax.tree.map(jnp.asarray, scenario.params)
    return tables, scenario, params


def simulate(
    instance: Instance,
    policy: Policy,
    T: int,
    seed: int = 0,
    tables: DPTables | None = None,
    scenario: Scenario | None = None,
) -> SimResult:
    """Run one policy for T slots; identical seeds ⇒ identical arrival and
    valuation streams across policies (paired comparison, as in the paper).
    ``scenario=None`` uses the paper's iid baseline regime."""
    tables, scenario, params = _scenario_args(instance, tables, scenario)
    key = jax.random.PRNGKey(seed)
    (sw, sw_star, regret, nd), pfinal = _run(
        policy, T, tables, scenario, instance.n_servers,
        _instance_arrays(instance), key, params)
    return SimResult(
        sw=np.asarray(sw), sw_oracle=np.asarray(sw_star),
        regret=np.asarray(regret), n_dispatched=np.asarray(nd),
        policy_final=jax.tree.map(np.asarray, pfinal))


def simulate_grid(
    instance: Instance,
    policy: Policy,
    T: int,
    seeds,
    scenario: Scenario,
    stacked_params,
    tables: DPTables | None = None,
) -> SimResult:
    """Sweep a scenario-parameter grid in one jitted call: ``lax.map`` over
    the stacked parameter axis wrapping the vmapped seed batch.

    ``stacked_params`` must match ``scenario.params`` in structure with every
    leaf gaining a leading grid axis of the same length G; the scenario's
    state/output shapes must not depend on parameter *values* (true for all
    registered scenarios).  Returns a SimResult of shape (G, len(seeds), T).
    """
    if tables is None:
        tables = build_tables(instance.A, instance.c)
    stacked = jax.tree.map(jnp.asarray, stacked_params)
    keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
    (sw, sw_star, regret, nd), pfinal = _run_param_grid(
        policy, T, tables, scenario, instance.n_servers,
        _instance_arrays(instance), keys, stacked)
    return SimResult(
        sw=np.asarray(sw), sw_oracle=np.asarray(sw_star),
        regret=np.asarray(regret), n_dispatched=np.asarray(nd),
        policy_final=jax.tree.map(np.asarray, pfinal))


def simulate_batch(
    instance: Instance,
    policy: Policy,
    T: int,
    seeds,
    tables: DPTables | None = None,
    scenario: Scenario | None = None,
) -> SimResult:
    """Vectorized ``simulate`` over a seed batch: one jitted vmapped call.

    Returns a SimResult whose arrays have shape (len(seeds), T).  Row i is
    decision-identical to ``simulate(..., seed=seeds[i])``: the dispatch
    vectors, oracle values, and regret match bit-for-bit (identical PRNG
    streams per key).  The realized-welfare slot sums Σ_e x_e·z̃_e may differ
    in the last float32 ulp only, because XLA reorders the E-way reduction
    when it vectorizes over the batch axis.

    With a batch-aware DP backend (``Solver.accepts_batch`` — the Pallas
    backends), the vmap over seeds triggers the solve core's custom
    batching rule: on a whole plane each slot issues ONE fleet-batched
    kernel launch for the whole seed batch, with the DP-table operands
    shared across seeds rather than replicated per instance."""
    tables, scenario, params = _scenario_args(instance, tables, scenario)
    keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
    (sw, sw_star, regret, nd), pfinal = _run_batch(
        policy, T, tables, scenario, instance.n_servers,
        _instance_arrays(instance), keys, params)
    return SimResult(
        sw=np.asarray(sw), sw_oracle=np.asarray(sw_star),
        regret=np.asarray(regret), n_dispatched=np.asarray(nd),
        policy_final=jax.tree.map(np.asarray, pfinal))
