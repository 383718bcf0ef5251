"""Streaming dispatch engine: admission → bounded queue → dispatch.

``ClusterSim.run`` (the paper-faithful lockstep research loop, preserved
bit-for-bit as :func:`lockstep_run` below) assumes every arrival is
dispatchable the slot it lands and silently forgets the ones that are not.
Production model-serving schedulers do neither: arrivals are *validated*
(fail-fast rejection of jobs that can never run — wrong accelerator
family, gang larger than the fleet), *queued* under an explicit bound with
a backpressure policy, and *dispatched* against capacity checks, while a
new learned policy rolls out to a weighted fraction of traffic next to the
incumbent.  :class:`DispatchEngine` is that loop for this repo's
bipartite multi-server-job model (modeled on osml-model-runner's
validate-then-queue + throttling design; see ``docs/engine.md``):

* **Admission** — arrivals whose port has no feasible edge (no
  capacity-respecting (job, server) pair) are rejected into a dead-letter
  ledger *before* touching the queue: rejected jobs never consume
  capacity and never enter the bandit statistics.
* **Bounded queue** — per-port FIFO of depth ``queue_capacity`` plus a
  global bound ``total_capacity``; on overflow the configured
  backpressure policy fires: ``drop_oldest`` (evict the oldest queued
  job), ``block`` (refuse the newcomer), or ``shed_by_utility`` (evict
  the lowest-estimated-value job, newest first on ties).
* **Dispatch** — each port serves at most its *head* (oldest) job per
  slot, on one edge; contention is broken by estimated utility, then
  oldest job first, then least-loaded server, then edge index, and every
  start is capacity-checked against the residual ``c − A·x`` in that
  order (challenger variants pack into what the primary left).
* **A/B routing** — jobs hash (job-id × seed, splitmix-style) onto
  weighted policy variants (e.g. ESDP 90 / greedy challenger 10);
  utility, regret, and bandit state are tracked *per variant*, so a
  challenger's regret is read directly off the output.

Two execution modes share one set of slot functions:

* ``stream`` — the whole horizon is ONE jitted ``lax.scan``: a
  million-arrival trace is a single device call (the jaxpr is
  horizon-independent — ``tests/test_engine.py`` asserts it), and
  ``run_batch`` vmaps it so fleet solves hit the PR 6 batched-kernel
  dispatch.
* ``lockstep`` — the same slot functions driven from the host, one slot
  at a time, so host-side solver wrappers (``CachedSolver``,
  ``FallbackSolver`` — PR 7/8) see concrete inputs and can cache, skip,
  or degrade, and the PR 8 failure runtime can settle crashes per slot.
  Fault-free, ``lockstep`` is bit-identical to ``stream``.
"""
from __future__ import annotations

import copy
import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import build_tables, stats as stats_mod
from ..core.baselines import greedy_pack
from ..core.dp import oracle_knapsack
from ..core.env import Scenario
from ..core.graph import Instance
from ..core.solvers import Solver, get_solver

__all__ = ["BACKPRESSURE_POLICIES", "LOCKSTEP_POLICIES", "VariantSpec",
           "EngineConfig", "EngineOutput", "DispatchEngine",
           "feasible_ports", "lockstep_run"]

BACKPRESSURE_POLICIES = ("drop_oldest", "block", "shed_by_utility")
VARIANT_KINDS = ("esdp", "hswf", "lcf", "lwtf")
# named policies the host lockstep loop implements (ClusterSim.run /
# run_batch validate against this — an unknown name used to silently fall
# through to lwtf)
LOCKSTEP_POLICIES = ("esdp", "hswf", "lcf", "lwtf")

_EMPTY = -1  # queue sentinel: no job in this slot of the FIFO


def feasible_ports(instance: Instance) -> np.ndarray:
    """(P,) bool: ports with at least one capacity-respecting edge.

    A port fails when it has no edges at all (service locality or
    solely-servable filters dropped every server — ``build_instance``)
    or when every edge's requirement column exceeds cluster capacity.
    Arrivals on such ports can NEVER run; the engine dead-letters them
    at admission instead of letting them camp in the queue.
    """
    ok = np.zeros(instance.n_ports, bool)
    fits = np.all(np.asarray(instance.A) <= np.asarray(instance.c)[:, None],
                  axis=0)
    np.logical_or.at(ok, instance.port_of_edge, fits)
    return ok


@dataclasses.dataclass(frozen=True)
class VariantSpec:
    """One policy variant in the weighted A/B rollout.

    ``kind`` picks the dispatch rule (``esdp`` — the paper's
    Algorithm 1/2 bandit; ``hswf``/``lcf``/``lwtf`` — the greedy
    baselines); ``weight`` is the traffic fraction (normalized over the
    config); ``solver`` optionally pins the Algorithm-2 backend for an
    ``esdp`` variant (name or solver object — host-side wrappers such as
    ``CachedSolver``/``FallbackSolver`` need ``mode="lockstep"`` to act).
    """
    name: str
    kind: str = "esdp"
    weight: float = 1.0
    solver: "str | object | None" = None

    def __post_init__(self):
        if self.kind not in VARIANT_KINDS:
            raise ValueError(f"unknown variant kind {self.kind!r}; "
                             f"choose from {VARIANT_KINDS}")
        if not self.weight > 0:
            raise ValueError("variant weight must be > 0")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Queueing + rollout knobs of the streaming engine.

    ``queue_capacity`` bounds each port's FIFO; ``total_capacity`` bounds
    the whole queue (default: ``P × queue_capacity``, i.e. only the
    per-port bound binds).  ``backpressure`` picks the overflow policy
    (:data:`BACKPRESSURE_POLICIES`).  ``route_salt`` perturbs the
    deterministic job-id → variant hash (same seed + salt ⇒ same split).
    """
    queue_capacity: int = 4
    total_capacity: "int | None" = None
    backpressure: str = "drop_oldest"
    variants: "tuple[VariantSpec, ...]" = (VariantSpec("esdp"),)
    route_salt: int = 0x5A17

    def __post_init__(self):
        if self.backpressure not in BACKPRESSURE_POLICIES:
            raise ValueError(
                f"unknown backpressure policy {self.backpressure!r}; "
                f"choose from {BACKPRESSURE_POLICIES}")
        if self.queue_capacity < 1:
            raise ValueError("queue_capacity must be >= 1")
        if not self.variants:
            raise ValueError("need at least one variant")
        names = [v.name for v in self.variants]
        if len(set(names)) != len(names):
            raise ValueError(f"variant names must be unique: {names}")


@dataclasses.dataclass(frozen=True)
class EngineOutput:
    """Per-slot traces + per-variant accounting + the conservation ledger.

    ``ledger`` is exactly conserving (asserted by ``tests/test_engine.py``):

        arrivals  = rejected + blocked + admitted          (admission)
        admitted  = dispatched + dropped + shed + final_queue   (queue)

    with ``rejected`` the dead-letter count (never-feasible ports) and
    ``dispatched`` counting jobs started.  ``n``/``sumz`` are the final
    per-variant bandit statistics — rejected/shed jobs never appear in
    them (they are never dispatched, and only dispatch updates the
    bandit).
    """
    sw: np.ndarray  # (T,)
    regret: np.ndarray  # (T,)
    dispatch_share: np.ndarray  # (T, R)
    asw: float
    variants: "tuple[str, ...]"
    sw_variant: np.ndarray  # (T, V)
    regret_variant: np.ndarray  # (T, V)
    dispatched_variant: np.ndarray  # (T, V) jobs started per variant
    routed_variant: np.ndarray  # (T, V) admitted arrivals routed per variant
    n: np.ndarray  # (V, E) final bandit pull counts
    sumz: np.ndarray  # (V, E) final bandit reward sums
    ledger: dict  # per-slot int32 arrays + totals (see class docstring)
    queue_len: np.ndarray  # (T,) jobs queued after each slot
    mode: str
    solve_stats: "dict | None" = None  # {variant: counters} for wrappers
    failures: "dict | None" = None  # combined + per-variant crash ledgers

    @property
    def cum_regret(self):
        return np.cumsum(self.regret)


def _route_u01(job_id, salt):
    """Deterministic job-id → [0, 1) hash (splitmix-style avalanche)."""
    h = job_id.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
    h = h ^ jnp.asarray(salt).astype(jnp.uint32)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h.astype(jnp.float32) * jnp.float32(2.0**-32)


class DispatchEngine:
    """The streaming admission/queue/dispatch loop over one instance.

    Construction mirrors :class:`ClusterSim` (scenario= or raw
    ``speed_fn``/``alive_fn`` schedules; the schedule is shared by every
    seed), plus an :class:`EngineConfig`.  ``ClusterSim.engine()`` builds
    one that shares the sim's instance, horizon, schedule, and seed.
    """

    def __init__(
        self,
        instance: Instance,
        T: int,
        config: "EngineConfig | None" = None,
        *,
        scenario: Optional[Scenario] = None,
        speed_fn: Optional[Callable[[int], np.ndarray]] = None,
        alive_fn: Optional[Callable[[int], np.ndarray]] = None,
        arr_scale: "np.ndarray | None" = None,
        g_fn=stats_mod.g_logt_only,
        seed: int = 0,
        failures=None,
    ):
        self.inst = instance
        self.T = int(T)
        self.config = config or EngineConfig()
        self.g_fn = g_fn
        self.seed = int(seed)
        self.failures = failures
        self.tables = build_tables(instance.A, instance.c)
        self.m = instance.m
        self.s_cap = stats_mod.s_cap_for_horizon(T, self.m)
        self.u_max = stats_mod.u_max_for_horizon(T, self.m)
        P, R = instance.n_ports, instance.n_servers

        if scenario is not None:
            if speed_fn is not None or alive_fn is not None:
                raise ValueError("pass either scenario= or "
                                 "speed_fn/alive_fn, not both")
            from ..experiments.scenarios import unroll_scenario
            arr_scale, speeds, alive = unroll_scenario(
                scenario, T, R, seed, n_ports=P)
            self.speed = np.asarray(speeds, np.float32)
            self.alive = np.asarray(alive, bool)
        else:
            self.speed = (np.ones((T, R), np.float32) if speed_fn is None
                          else np.stack([np.asarray(speed_fn(t), np.float32)
                                         for t in range(T)]))
            self.alive = (np.ones((T, R), bool) if alive_fn is None
                          else np.stack([np.asarray(alive_fn(t), bool)
                                         for t in range(T)]))
        self.arr_scale = (np.ones((T, P), np.float32) if arr_scale is None
                          else np.asarray(arr_scale, np.float32))
        self.port_ok = feasible_ports(instance)

        cfg = self.config
        self.Q = int(cfg.queue_capacity)
        self.Ktot = int(cfg.total_capacity if cfg.total_capacity is not None
                        else P * self.Q)
        w = np.asarray([v.weight for v in cfg.variants], np.float64)
        # routing thresholds: variant v wins u01 ∈ [cum[v-1], cum[v])
        self._cum_w = np.cumsum(w / w.sum())[:-1].astype(np.float32)
        self._solvers = []
        for v in cfg.variants:
            if v.kind != "esdp":
                self._solvers.append(None)
            elif v.solver is None or isinstance(v.solver, str):
                self._solvers.append(get_solver(v.solver))
            else:
                if getattr(v.solver, "scope", "") is None:
                    v.solver.scope = v.name  # per-variant stats scoping
                self._solvers.append(v.solver)
        self._jit_cache: dict = {}

    # -- host-side randomness ------------------------------------------
    def _streams(self, seed: "int | None" = None):
        """(arrivals (T,P) bool, noise (T,E) f32, tiebreak (T,E) f32).

        Same generator layout as ``ClusterSim._streams`` (arrivals +
        valuation noise off ``seed``) with the greedy tie-break stream
        off ``seed + 1`` — one seed fully determines a trace, and
        ``run_batch([s])`` replays ``run(seed=s)``.
        """
        seed = self.seed if seed is None else int(seed)
        rng = np.random.default_rng(seed)
        inst = self.inst
        rho_t = np.clip(inst.rho[None, :] * self.arr_scale, 0.0, 1.0)
        arrivals = rng.random((self.T, inst.n_ports)) < rho_t
        noise = rng.normal(0.0, 1.0, (self.T, inst.n_edges)).astype(np.float32)
        tb = np.random.default_rng(seed + 1).random(
            (self.T, inst.n_edges)).astype(np.float32)
        return arrivals, noise, tb

    def _xs(self, streams):
        arrivals, noise, tb = streams
        return {
            "arrived": jnp.asarray(arrivals),
            "noise": jnp.asarray(noise),
            "tb": jnp.asarray(tb),
            "speed": jnp.asarray(self.speed),
            "alive": jnp.asarray(self.alive),
            "t": jnp.arange(self.T, dtype=jnp.int32),
        }

    def _carry0(self):
        inst, V = self.inst, len(self.config.variants)
        return {
            "queue": jnp.full((inst.n_ports, self.Q), _EMPTY, jnp.int32),
            "n": jnp.zeros((V, inst.n_edges), jnp.int32),
            "sumz": jnp.zeros((V, inst.n_edges), jnp.float32),
            "load": jnp.zeros(inst.n_servers, jnp.int32),
        }

    # -- slot functions (shared by stream scan and lockstep host loop) --
    def _consts(self):
        inst = self.inst
        return (jnp.asarray(inst.A), jnp.asarray(inst.c),
                jnp.asarray(inst.port_of_edge),
                jnp.asarray(inst.edges[:, 1]),
                jnp.asarray(inst.cost), jnp.asarray(inst.mu),
                jnp.asarray(inst.sigma), jnp.asarray(self.port_ok),
                jnp.asarray(self._cum_w))

    def _slot_pre(self, queue, n, sumz, arrived_raw, alive_t, suspicious, t0, salt):
        """Admission + enqueue + head/variant/eligibility computation.

        ``salt`` is the per-trace routing salt (u32 scalar, a pure
        function of config.route_salt and the TRACE seed — an argument,
        not a baked constant, so ``run_batch`` routes each seed exactly
        as its single-seed run would)."""
        A, c, port, server, cost, mu, sigma, port_ok, cum_w = self._consts()
        P, Q, Ktot = self.inst.n_ports, self.Q, self.Ktot
        V = len(self.config.variants)
        bp = self.config.backpressure
        i32 = jnp.int32

        arrived = arrived_raw & port_ok
        rejected = jnp.sum((arrived_raw & ~port_ok).astype(i32))

        # pooled value estimate → per-port utility (the shedding signal)
        n_all = jnp.sum(n, axis=0)
        vpool = jnp.where(n_all > 0,
                          jnp.sum(sumz, axis=0) / jnp.maximum(n_all, 1), 0.0)
        u_port = jnp.zeros(P, jnp.float32).at[port].max(
            vpool.astype(jnp.float32))

        def row_count(row):
            return jnp.sum((row >= 0).astype(i32))

        def append(qs, l):
            return qs.at[l, row_count(qs[l])].set(t0.astype(i32))

        def evict_head(qs, p):
            shifted = jnp.concatenate(
                [qs[p, 1:], jnp.full((1,), _EMPTY, i32)])
            return qs.at[p].set(shifted)

        def evict_newest(qs, p):
            k = jnp.maximum(row_count(qs[p]) - 1, 0)
            return qs.at[p, k].set(_EMPTY)

        def enq_body(l, st):
            qs, blocked, dropped, shed, admitted = st
            arr = arrived[l]
            port_full = row_count(qs[l]) >= Q
            glob_full = jnp.sum((qs >= 0).astype(i32)) >= Ktot
            overflow = arr & (port_full | glob_full)
            room = arr & ~(port_full | glob_full)
            qs_app = jnp.where(room, append(qs, l), qs)
            if bp == "block":
                return (qs_app, blocked + overflow.astype(i32), dropped,
                        shed, admitted + room.astype(i32))
            if bp == "drop_oldest":
                heads = qs[:, 0]
                oldest = jnp.argmin(jnp.where(heads >= 0, heads,
                                              jnp.iinfo(i32).max))
                tgt = jnp.where(port_full, l, oldest)
                qs_ev = append(evict_head(qs, tgt), l)
                qs2 = jnp.where(overflow, qs_ev, qs_app)
                return (qs2, blocked, dropped + overflow.astype(i32),
                        shed, admitted + (room | overflow).astype(i32))
            # shed_by_utility: evict the lowest-utility job, newest first
            # on ties — a structurally-full port ties with the newcomer,
            # so the newcomer itself is shed
            cnts = jnp.sum((qs >= 0).astype(i32), axis=1)
            uq = jnp.where(cnts > 0, u_port, jnp.inf)
            pmin = jnp.argmin(uq)
            shed_new = port_full | (u_port[l] <= uq[pmin])
            qs_ev = append(evict_newest(qs, pmin), l)
            qs2 = jnp.where(overflow & ~shed_new, qs_ev, qs_app)
            return (qs2, blocked, dropped, shed + overflow.astype(i32),
                    admitted + (room | overflow).astype(i32))

        zero = jnp.zeros((), i32)
        queue2, blocked, dropped, shed, admitted = jax.lax.fori_loop(
            0, P, enq_body, (queue, zero, zero, zero, zero))

        head = queue2[:, 0]
        has = head >= 0
        age = jnp.where(has, t0.astype(i32) - head, 0)
        ports = jnp.arange(P, dtype=i32)
        u01 = _route_u01(head * P + ports, salt)
        hvar = jnp.sum((u01[None, :] >= cum_w[:, None]).astype(i32), axis=0)
        # admission-time routing split: the job id of THIS slot's arrival
        # on port l is t0·P + l, the same id its queue head carries later
        u01_arr = _route_u01(t0.astype(i32) * P + ports, salt)
        avar = jnp.sum((u01_arr[None, :] >= cum_w[:, None]).astype(i32),
                       axis=0)
        routed = jnp.stack([jnp.sum((arrived & (avar == v)).astype(i32))
                            for v in range(V)])

        elig_base = has[port] & alive_t[server] & ~suspicious[server]
        elig = jnp.stack([elig_base & (hvar[port] == v) for v in range(V)])
        vhat = jnp.where(n > 0, sumz / jnp.maximum(n, 1), 0.0).astype(
            jnp.float32)
        counts = {"arrivals": jnp.sum(arrived_raw.astype(i32)),
                  "rejected": rejected, "blocked": blocked,
                  "dropped": dropped, "shed": shed, "admitted": admitted,
                  "routed_v": routed}
        return queue2, counts, age, elig, vhat

    def _route_salt(self, seed: int) -> int:
        return (self.config.route_salt ^ (seed * 0x85EBCA6B)) & 0xFFFFFFFF

    def _variant_x(self, v, elig_v, vhat_v, n_v, age, tb_t, t0):
        """Raw per-variant dispatch proposal (possibly >1 edge per port)."""
        A, c, port, server, cost, mu, sigma, port_ok, cum_w = self._consts()
        spec = self.config.variants[v]
        if spec.kind == "esdp":
            ups, sig, _, s_lim = stats_mod.scale_statistics(
                vhat_v, n_v, (t0 + 1).astype(jnp.float32), self.m,
                g_fn=self.g_fn)
            x, _ = self._solvers[v](ups, sig, self.tables, self.s_cap,
                                    s_lim, allowed=elig_v, u_max=self.u_max)
            return x
        if spec.kind == "hswf":
            score = vhat_v + tb_t * 1e-4
        elif spec.kind == "lcf":
            score = -cost + tb_t * 1e-4
        else:  # lwtf: oldest head job first (queue age replaces the
            # lockstep loop's waiting counters)
            score = age[port].astype(jnp.float32) * 1e3 + vhat_v + tb_t * 1e-4
        return greedy_pack(score, elig_v, A, c)

    def _slot_dispatch(self, queue2, load, x_raw, elig, vhat, age):
        """Trim to one head job per port, capacity-check in priority
        order (utility desc, oldest job, least-loaded server), pop
        served heads."""
        A, c, port, server, cost, mu, sigma, port_ok, cum_w = self._consts()
        P, E = self.inst.n_ports, self.inst.n_edges
        V = len(self.config.variants)
        i32 = jnp.int32

        residual = c
        xs = []
        for v in range(V):
            cand = (x_raw[v] > 0) & elig[v]
            # priority rank: utility desc → oldest head job → least-loaded
            # server → edge index (jnp.lexsort: last key is primary)
            order = jnp.lexsort((jnp.arange(E), load[server],
                                 -age[port].astype(jnp.float32), -vhat[v]))
            rank = jnp.zeros(E, i32).at[order].set(jnp.arange(E, dtype=i32))
            best = jnp.full(P, E, i32).at[port].min(
                jnp.where(cand, rank, E))
            x1 = (cand & (rank == best[port])).astype(i32)

            def cap_body(j, st):
                res, xo = st
                e = order[j]
                take = (x1[e] > 0) & jnp.all(res >= A[:, e])
                xo = xo.at[e].set(take.astype(i32))
                res = res - jnp.where(take, A[:, e], 0)
                return res, xo

            residual, x_v = jax.lax.fori_loop(
                0, E, cap_body, (residual, jnp.zeros(E, i32)))
            xs.append(x_v)

        xv = jnp.stack(xs)  # (V, E), one unit per served port overall
        x = jnp.sum(xv, axis=0)
        served = jnp.zeros(P, i32).at[port].add(x) > 0
        popped = jnp.concatenate(
            [queue2[:, 1:], jnp.full((P, 1), _EMPTY, i32)], axis=1)
        queue3 = jnp.where(served[:, None], popped, queue2)
        load2 = load + jnp.zeros_like(load).at[server].add(x)
        qlen = jnp.sum((queue3 >= 0).astype(i32))
        return xv, x, served, queue3, load2, qlen

    def _slot_account(self, n, sumz, xv, elig, noise_t, speed_t):
        """Realized welfare, per-variant regret, bandit update, share."""
        A, c, port, server, cost, mu, sigma, port_ok, cum_w = self._consts()
        V = len(self.config.variants)
        mean = mu * speed_t[server] - cost
        z = jnp.clip(mean + sigma * noise_t, 0.0, 1.0)
        v_true = jnp.clip(mean, 0.0, 1.0).astype(jnp.float32)
        x = jnp.sum(xv, axis=0)

        sw_v = jnp.sum(xv * z, axis=1).astype(jnp.float32)
        reg = []
        for v in range(V):
            x_star, _ = oracle_knapsack(v_true, self.tables, elig[v])
            reg.append(jnp.sum(v_true * x_star) - jnp.sum(v_true * xv[v]))
        regret_v = jnp.stack(reg).astype(jnp.float32)
        if V == 1:
            regret = regret_v[0]
        else:
            x_all, _ = oracle_knapsack(v_true, self.tables,
                                       jnp.any(elig, axis=0))
            regret = (jnp.sum(v_true * x_all)
                      - jnp.sum(v_true * x)).astype(jnp.float32)

        n2 = n + xv
        sumz2 = sumz + (xv * z).astype(jnp.float32)
        tot = jnp.sum(x)
        share = jnp.zeros(self.inst.n_servers, jnp.float32).at[server].add(
            x / jnp.maximum(tot, 1))
        return n2, sumz2, jnp.sum(sw_v), sw_v, regret, regret_v, share

    # -- stream mode ----------------------------------------------------
    def _scan_body(self, carry, xs_t, salt):
        V = len(self.config.variants)
        suspicious = jnp.zeros(self.inst.n_servers, bool)
        queue2, counts, age, elig, vhat = self._slot_pre(
            carry["queue"], carry["n"], carry["sumz"], xs_t["arrived"],
            xs_t["alive"], suspicious, xs_t["t"], salt)
        x_raw = jnp.stack([
            self._variant_x(v, elig[v], vhat[v], carry["n"][v], age,
                            xs_t["tb"], xs_t["t"])
            for v in range(V)])
        xv, x, served, queue3, load2, qlen = self._slot_dispatch(
            queue2, carry["load"], x_raw, elig, vhat, age)
        n2, sumz2, sw, sw_v, regret, regret_v, share = self._slot_account(
            carry["n"], carry["sumz"], xv, elig, xs_t["noise"],
            xs_t["speed"])
        carry2 = {"queue": queue3, "n": n2, "sumz": sumz2, "load": load2}
        ys = dict(counts, sw=sw, sw_v=sw_v, regret=regret,
                  regret_v=regret_v, share=share, qlen=qlen,
                  dispatched=jnp.sum(served.astype(jnp.int32)),
                  dispatched_v=jnp.sum(xv, axis=1))
        return carry2, ys

    def _stream_fn(self):
        fn = self._jit_cache.get("stream")
        if fn is None:
            def run_scan(carry0, xs, salt):
                return jax.lax.scan(
                    lambda c, x: self._scan_body(c, x, salt), carry0, xs)
            fn = jax.jit(run_scan)
            self._jit_cache["stream"] = fn
        return fn

    def stream_arg_shapes(self, T: "int | None" = None, sharding=None):
        """``(carry0, xs, salt)`` of the stream scan as ShapeDtypeStructs
        at horizon ``T`` (default: the engine's), each placed on
        ``sharding`` when given — what ``jax.jit(...).lower`` takes to
        trace or compile the stream program without any data."""
        T = self.T if T is None else int(T)
        inst = self.inst

        def spec(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        carry0 = jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype),
                                        self._carry0())
        xs = {"arrived": spec((T, inst.n_ports), jnp.bool_),
              "noise": spec((T, inst.n_edges), jnp.float32),
              "tb": spec((T, inst.n_edges), jnp.float32),
              "speed": spec((T, inst.n_servers), jnp.float32),
              "alive": spec((T, inst.n_servers), jnp.bool_),
              "t": spec((T,), jnp.int32)}
        return carry0, xs, spec((), jnp.uint32)

    def make_stream_jaxpr(self, T: int):
        """The traced (unjitted) stream jaxpr at horizon ``T`` — the
        launch-count test inspects it: one ``scan`` eqn regardless of T."""
        def run_scan(carry0, xs, salt):
            return jax.lax.scan(
                lambda c, x: self._scan_body(c, x, salt), carry0, xs)

        return jax.make_jaxpr(run_scan)(*self.stream_arg_shapes(T))

    def _outputs(self, ys, carry, mode, solve_stats=None, failures=None):
        ys = {k: np.asarray(v) for k, v in ys.items()}
        led = {k: ys[k] for k in ("arrivals", "rejected", "blocked",
                                  "dropped", "shed", "admitted",
                                  "dispatched")}
        led["queue_len"] = ys["qlen"]
        led["final_queue"] = int(ys["qlen"][-1])
        for k in ("arrivals", "rejected", "blocked", "dropped", "shed",
                  "admitted", "dispatched"):
            led[f"total_{k}"] = int(led[k].sum())
        return EngineOutput(
            sw=ys["sw"], regret=ys["regret"], dispatch_share=ys["share"],
            asw=float(ys["sw"].sum()),
            variants=tuple(v.name for v in self.config.variants),
            sw_variant=ys["sw_v"], regret_variant=ys["regret_v"],
            dispatched_variant=ys["dispatched_v"],
            routed_variant=ys["routed_v"],
            n=np.asarray(carry["n"]), sumz=np.asarray(carry["sumz"]),
            ledger=led, queue_len=ys["qlen"], mode=mode,
            solve_stats=solve_stats, failures=failures)

    def _wrapper_stats(self) -> "dict | None":
        out = {}
        for spec, solver in zip(self.config.variants, self._solvers):
            if solver is None or isinstance(solver, Solver):
                continue
            if hasattr(solver, "stats_dict"):
                out[spec.name] = solver.stats_dict()
            elif isinstance(getattr(solver, "stats", None), dict):
                out[spec.name] = copy.deepcopy(solver.stats)
        return out or None

    def run(
        self, mode: str = "auto", seed: "int | None" = None, streams=None
    ) -> EngineOutput:
        """One trace.  ``mode="stream"`` is the single jitted scan;
        ``"lockstep"`` drives the same slot functions host-side (solver
        wrappers act, the failure runtime settles); ``"auto"`` picks
        lockstep iff a failure model is attached."""
        if mode == "auto":
            mode = "lockstep" if self.failures is not None else "stream"
        if mode not in ("stream", "lockstep"):
            raise ValueError(f"unknown mode {mode!r}")
        seed = self.seed if seed is None else int(seed)
        if streams is None:
            streams = self._streams(seed)
        salt = self._route_salt(seed)
        if mode == "stream":
            if self.failures is not None:
                raise ValueError("failure settlement is host-side: use "
                                 'mode="lockstep" (or "auto")')
            carry, ys = self._stream_fn()(self._carry0(), self._xs(streams),
                                          jnp.uint32(salt))
            return self._outputs(ys, carry, "stream",
                                 solve_stats=self._wrapper_stats())
        return self._run_lockstep(streams, salt)

    def run_batch(self, seeds, mode: str = "stream") -> "list[EngineOutput]":
        """One trace per seed, fleet-batched: ONE vmapped jitted scan, so
        batch-aware solver backends collapse each slot's fleet of solves
        into a single batched kernel launch (the PR 6 dispatch path).
        Stream-only; every seed shares the schedule, as in
        ``ClusterSim.run_batch``."""
        if mode != "stream":
            raise NotImplementedError("run_batch is the vmapped stream "
                                      "path; loop run() for lockstep")
        if self.failures is not None:
            raise NotImplementedError("failure settlement is host-side "
                                      "and single-seed; loop run()")
        seeds = [int(s) for s in seeds]
        streams = [self._streams(s) for s in seeds]
        xs = {
            "arrived": jnp.asarray(np.stack([s[0] for s in streams])),
            "noise": jnp.asarray(np.stack([s[1] for s in streams])),
            "tb": jnp.asarray(np.stack([s[2] for s in streams])),
            "speed": jnp.asarray(self.speed),
            "alive": jnp.asarray(self.alive),
            "t": jnp.arange(self.T, dtype=jnp.int32),
        }
        fn = self._jit_cache.get("stream_batch")
        if fn is None:
            def run_scan(carry0, xs, salt):
                return jax.lax.scan(
                    lambda c, x: self._scan_body(c, x, salt), carry0, xs)
            fn = jax.jit(jax.vmap(
                run_scan,
                in_axes=(0, {"arrived": 0, "noise": 0, "tb": 0,
                             "speed": None, "alive": None, "t": None}, 0)))
            self._jit_cache["stream_batch"] = fn
        B = len(seeds)
        carry0 = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (B,) + a.shape), self._carry0())
        salts = jnp.asarray([self._route_salt(s) for s in seeds], jnp.uint32)
        carry, ys = fn(carry0, xs, salts)
        return [self._outputs(
                    jax.tree_util.tree_map(lambda a: a[b], ys),
                    jax.tree_util.tree_map(lambda a: a[b], carry),
                    "stream")
                for b in range(B)]

    # -- lockstep mode --------------------------------------------------
    def _lockstep_jits(self):
        jits = self._jit_cache.get("lockstep")
        if jits is None:
            jits = {
                "pre": jax.jit(self._slot_pre),
                "dispatch": jax.jit(self._slot_dispatch),
                "account": jax.jit(self._slot_account),
                "stats": jax.jit(lambda vh, nn, tt: stats_mod.scale_statistics(
                    vh, nn, tt, self.m, g_fn=self.g_fn)),
                "oracle": jax.jit(lambda v, al: oracle_knapsack(
                    v, self.tables, al)[0]),
                "greedy": {},
                "solve": {},
            }
            self._jit_cache["lockstep"] = jits
        return jits

    def _lockstep_solve(self, jits, v, elig_v, vhat_v, n_v, age, tb_t, t0):
        spec, solver = self.config.variants[v], self._solvers[v]
        if spec.kind != "esdp":
            fn = jits["greedy"].get(v)
            if fn is None:
                fn = jax.jit(lambda e, vh, a, tb, t: self._variant_x(
                    v, e, vh, None, a, tb, t))
                jits["greedy"][v] = fn
            return fn(elig_v, vhat_v, age, tb_t, t0)
        ups, sig, _, s_lim = jits["stats"](
            vhat_v, n_v, jnp.float32(int(t0) + 1))
        if isinstance(solver, Solver):
            fn = jits["solve"].get(v)
            if fn is None:
                fn = jax.jit(lambda u, s, lim, al: solver(
                    u, s, self.tables, self.s_cap, lim, allowed=al,
                    u_max=self.u_max)[0])
                jits["solve"][v] = fn
            return fn(ups, sig, s_lim, elig_v)
        # host-side wrapper (CachedSolver / FallbackSolver / warm): hand
        # it concrete arrays so it can cache, skip, or walk its chain
        x, _ = solver(np.asarray(ups), np.asarray(sig), self.tables,
                      self.s_cap, int(s_lim), allowed=np.asarray(elig_v),
                      u_max=self.u_max)
        return jnp.asarray(x)

    def _run_lockstep(self, streams, salt: int) -> EngineOutput:
        inst, V, T = self.inst, len(self.config.variants), self.T
        arrivals, noise, tb = streams
        jits = self._lockstep_jits()
        carry = self._carry0()
        fr = None
        vled = None
        if self.failures is not None:
            from .dispatcher import FailureRuntime
            alive = self.alive
            fr = FailureRuntime(self.failures, inst, T,
                                lambda t: alive[t], self.seed)
            vled = [{k: np.zeros(T, np.float64) for k in
                     ("dispatched", "completed", "lost", "salvaged",
                      "ckpt_cost")} for _ in range(V)]
        server = inst.edges[:, 1]
        ys = {k: [] for k in ("arrivals", "rejected", "blocked", "dropped",
                              "shed", "admitted", "dispatched", "qlen",
                              "sw", "sw_v", "regret", "regret_v", "share",
                              "dispatched_v", "routed_v")}
        suspicious = np.zeros(inst.n_servers, bool)
        for t0 in range(T):
            queue2, counts, age, elig, vhat = jits["pre"](
                carry["queue"], carry["n"], carry["sumz"],
                jnp.asarray(arrivals[t0]), jnp.asarray(self.alive[t0]),
                jnp.asarray(suspicious), jnp.int32(t0), jnp.uint32(salt))
            x_raw = jnp.stack([
                self._lockstep_solve(jits, v, elig[v], vhat[v],
                                     carry["n"][v], age,
                                     jnp.asarray(tb[t0]), t0)
                for v in range(V)])
            xv, x, served, queue3, load2, qlen = jits["dispatch"](
                queue2, carry["load"], x_raw, elig, vhat, age)
            if fr is None:
                n2, sumz2, sw, sw_v, regret, regret_v, share = (
                    jits["account"](carry["n"], carry["sumz"], xv, elig,
                                    jnp.asarray(noise[t0]),
                                    jnp.asarray(self.speed[t0])))
                carry = {"queue": queue3, "n": n2, "sumz": sumz2,
                         "load": load2}
            else:
                (sw, sw_v, regret, regret_v, share, carry, suspicious) = (
                    self._settle_failures(fr, vled, t0, carry, queue3,
                                          load2, xv, elig, noise[t0], jits))
            ys["sw"].append(float(sw))
            ys["sw_v"].append(np.asarray(sw_v))
            ys["regret"].append(float(regret))
            ys["regret_v"].append(np.asarray(regret_v))
            ys["share"].append(np.asarray(share))
            ys["qlen"].append(int(qlen))
            ys["dispatched"].append(int(np.asarray(served).sum()))
            ys["dispatched_v"].append(np.asarray(xv).sum(axis=1))
            for k, cnt in counts.items():
                ys[k].append(np.asarray(cnt) if k == "routed_v"
                             else int(cnt))
        ys = {k: (np.asarray(v, np.float32)
                  if k in ("sw", "regret") else np.asarray(v))
              for k, v in ys.items()}
        for k in ("arrivals", "rejected", "blocked", "dropped", "shed",
                  "admitted", "dispatched", "qlen"):
            ys[k] = ys[k].astype(np.int32)
        failures = None
        if fr is not None:
            failures = fr.summary()
            failures["per_variant"] = {
                self.config.variants[v].name: {
                    **{k: a.astype(np.float32) for k, a in vled[v].items()},
                    **{f"total_{k}": float(a.sum())
                       for k, a in vled[v].items()},
                } for v in range(V)}
        return self._outputs(ys, carry, "lockstep",
                             solve_stats=self._wrapper_stats(),
                             failures=failures)

    def _settle_failures(
        self, fr, vled, t0, carry, queue3, load2, xv, elig, noise_t, jits
    ):
        """Host-side crash settlement (PR 8 runtime), per variant: each
        variant's dispatched units settle into its OWN conserving ledger
        (dispatched = completed + lost + salvaged per slot per variant),
        and its bandit sees the realized (crash-discounted) signal."""
        inst, V = self.inst, len(self.config.variants)
        server = inst.edges[:, 1]
        xv_np = np.asarray(xv)
        x_np = xv_np.sum(axis=0)
        elig_np = np.asarray(elig)
        alive_row = self.alive[t0]
        speed_t = self.speed[t0]
        mean = inst.mu * speed_t[server] - inst.cost
        z = np.clip(mean + inst.sigma * np.asarray(noise_t), 0.0, 1.0)
        v_true = np.clip(mean, 0.0, 1.0).astype(np.float32)

        crashed = fr.crashed_servers(t0, np.asarray(alive_row, bool))
        reps = fr.place_replicas(t0, x_np, elig_np.any(axis=0))
        sw_v, regret_v = np.zeros(V, np.float32), np.zeros(V, np.float32)
        n2 = np.asarray(carry["n"]).copy()
        sumz2 = np.asarray(carry["sumz"]).copy()
        for v in range(V):
            sw_t, realized = fr.settle(t0, xv_np[v], z, crashed, reps,
                                       ledger=vled[v])
            sw_v[v] = sw_t
            n2[v] += xv_np[v]
            sumz2[v] += realized.astype(np.float32)
            x_star = np.asarray(jits["oracle"](jnp.asarray(v_true),
                                               jnp.asarray(elig_np[v])))
            regret_v[v] = ((v_true * x_star).sum()
                           - (v_true * xv_np[v]).sum())
        for k in fr.ledger:
            fr.ledger[k][t0] = sum(vled[v][k][t0] for v in range(V))
        fr.observe(t0, crashed)
        x_star = np.asarray(jits["oracle"](jnp.asarray(v_true),
                                           jnp.asarray(elig_np.any(axis=0))))
        regret = (v_true * x_star).sum() - (v_true * x_np).sum()
        tot = x_np.sum()
        share = np.zeros(inst.n_servers, np.float32)
        np.add.at(share, server, x_np / max(tot, 1))
        carry2 = {"queue": queue3, "n": jnp.asarray(n2),
                  "sumz": jnp.asarray(sumz2), "load": load2}
        return (float(sw_v.sum()), sw_v, float(regret), regret_v, share,
                carry2, fr.suspicious.copy())


# ----------------------------------------------------------------------
def lockstep_run(sim, policy: str = "esdp", tiebreak: float = 1e-4):
    """The pre-engine ``ClusterSim.run`` loop, preserved bit-for-bit.

    ``ClusterSim.run`` delegates here: the paper-faithful lockstep
    semantics (every arrival dispatchable the slot it lands, f64 bandit
    accumulators, host RNG tie-breaks, failure settlement) are frozen as
    the reference the streaming engine is benchmarked against —
    ``tests/test_engine.py`` pins its outputs across the registered
    regimes.  With ``sim.malleable`` set, the slot flow gains the
    malleable phases (grow → solve → admit/shrink/preempt → advance) and
    the bandit is fed realized per-job gains at completion; with it None
    the original rigid path runs unchanged.
    """
    from .dispatcher import FailureRuntime, MalleableRuntime, SimOutput

    if policy not in LOCKSTEP_POLICIES:
        raise ValueError(
            f"unknown policy {policy!r}; valid lockstep policies: "
            f"{', '.join(LOCKSTEP_POLICIES)}")

    inst, tables = sim.inst, sim.tables
    E, R = inst.n_edges, inst.n_servers
    port = inst.port_of_edge
    server = inst.edges[:, 1]
    arrivals, noise = sim._streams()
    rng = np.random.default_rng(sim.seed + 1)

    n = np.zeros(E, np.int64)
    sumz = np.zeros(E, np.float64)
    waiting = np.zeros(inst.n_ports, np.int64)

    sw = np.zeros(sim.T, np.float32)
    regret = np.zeros(sim.T, np.float32)
    share = np.zeros((sim.T, R), np.float32)

    if sim.incremental is None and isinstance(sim.solver, Solver):
        jit_dp = jax.jit(
            lambda u, s, lim, al: sim.solver(
                u, s, tables, sim.s_cap, lim, allowed=al,
                u_max=sim.u_max)[0])

        def solve_x(u, s, lim, al):
            return np.asarray(jit_dp(u, s, lim, jnp.asarray(al)))
    else:
        # host-side wrapper paths need concrete inputs — the
        # CachedSolver/WarmPallasSolver/FallbackSolver jit their own
        # launch internals and skip/degrade them per call
        inc = sim._warm if sim.incremental == "warm" else sim.solver

        def solve_x(u, s, lim, al):
            return np.asarray(inc(u, s, tables, sim.s_cap, int(lim),
                                  allowed=al, u_max=sim.u_max)[0])

    jit_oracle = jax.jit(
        lambda v, al: oracle_knapsack(v, tables, al)[0])
    jit_greedy = jax.jit(
        lambda sc, el: greedy_pack(sc, el, jnp.asarray(inst.A),
                                   jnp.asarray(inst.c)))

    fr = (FailureRuntime(sim.failures, inst, sim.T, sim.alive_fn, sim.seed)
          if sim.failures is not None else None)
    mr = (MalleableRuntime(sim.malleable, inst, sim.T)
          if getattr(sim, "malleable", None) is not None else None)

    for t0 in range(sim.T):
        t = t0 + 1  # 1-based for the bandit schedules
        alive_srv = np.asarray(sim.alive_fn(t0), bool)  # 0-based
        alive = alive_srv[server]
        arrived = arrivals[t0][port]
        allowed = arrived & alive
        if fr is not None:
            allowed = fr.eligibility(allowed, server)
        if mr is not None:
            mr.grow(t0)
        vhat = np.where(n > 0, sumz / np.maximum(n, 1), 0.0).astype(
            np.float32)

        if policy == "esdp":
            ups, sig, _, s_lim = stats_mod.scale_statistics(
                jnp.asarray(vhat), jnp.asarray(n.astype(np.int32)),
                jnp.float32(t), sim.m, g_fn=sim.g_fn)
            x = solve_x(ups, sig, s_lim, allowed)
        else:
            tb = rng.random(E).astype(np.float32) * tiebreak
            if policy == "hswf":
                score = vhat + tb
            elif policy == "lcf":
                score = -inst.cost + tb
            else:  # lwtf
                score = waiting[port] * 1e3 + vhat + tb
            x = np.asarray(jit_greedy(jnp.asarray(score),
                                      jnp.asarray(allowed)))

        x = x * allowed
        z = sim._z(t0, noise[t0])
        settled = None
        if mr is not None:
            x = mr.admit(t0, x, vhat)
            sw[t0], settled = mr.advance(t0, z)
        elif fr is None:
            sw[t0] = float((x * z).sum())
            bandit_z = x * z
        else:
            crashed = fr.crashed_servers(t0, alive_srv)
            reps = fr.place_replicas(t0, x, allowed)
            sw[t0], bandit_z = fr.settle(t0, x, z, crashed, reps)
            fr.observe(t0, crashed)
        v_true = sim._v_true(t0)
        x_star = np.asarray(jit_oracle(jnp.asarray(v_true),
                                       jnp.asarray(allowed)))
        regret[t0] = float((v_true * x_star).sum() - (v_true * x).sum())

        if mr is not None:
            # the bandit learns realized per-job totals at settlement
            # (completion or shutdown) — mid-flight jobs are not yet signal
            for e0, gain in settled:
                n[e0] += 1
                sumz[e0] += max(gain, 0.0)
        else:
            n += x
            sumz += bandit_z
        served = np.zeros(inst.n_ports, bool)
        np.maximum.at(served, port, x > 0)
        waiting = np.where(served, 0, waiting + arrivals[t0])
        if x.sum() > 0:
            np.add.at(share[t0], server, x / x.sum())

    return SimOutput(sw=sw, regret=regret, dispatch_share=share,
                     asw=float(sw.sum()),
                     solve_stats=(sim._solve_stats()
                                  if policy == "esdp" else None),
                     failures=fr.summary() if fr is not None else None,
                     malleable=mr.summary() if mr is not None else None)
