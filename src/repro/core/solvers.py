"""Pluggable backends for the per-slot Algorithm-2 solve (paper P4/P5).

Every backend implements one contract::

    solver(upsilon, sigma2, tables, s_cap, s_limit,
           allowed=None, u_max=None) -> (x, info)

with ``x`` the (E,) int32 dispatch vector of Alg.-1 Step 8 and ``info`` a
dict holding ``s_star`` (int32 scalar) and ``value_row`` — the (s_cap+1,)
int32 DP value row with exactly ``dp.NEG`` at budget-infeasible entries.
``u_max`` is an optional static bound on max Υ̂ (``stats.u_max_for_horizon``)
that kernel backends may use to size scratch buffers; it must never change
results, and the reference backend ignores it.
Backends are *bit-exact interchangeable*: identical inputs yield identical
``x``, ``s_star``, and ``value_row`` (the differential-testing harness in
``tests/test_solver_equiv.py`` enforces this against brute force).

Registry:
  reference        — pure-JAX lax.scan over edges, exact int32 values
                     (``core.dp.solve_budgeted_dp``).
  pallas           — the VMEM-resident Pallas kernel
                     (``kernels.budgeted_dp``); compiled on TPU, Pallas
                     interpreter elsewhere (never silently interpreted on
                     real TPU hardware).  Plane tiling (whole-plane vs
                     C-blocked vs the 2-D S×C grid for long horizons, with
                     edge-fused chunks keeping tiles VMEM-resident across
                     ``block_e`` consecutive edges on the blocked paths) is
                     resolved inside the backend from the VMEM budget
                     (``kernels.budgeted_dp.kernel.choose_tiling``) — it is
                     an execution detail invisible at this contract, and
                     never changes results.  Batch-aware
                     (``accepts_batch``): under ``jax.vmap`` the solve
                     core's custom batching rule runs every mapped
                     instance in ONE fleet-batched kernel launch with the
                     DP-table operands shared across the batch.  See
                     ``docs/kernel_pipeline.md`` for the kernel internals.
  pallas_interpret — the same kernel forced through the interpreter on any
                     backend; what differential tests run on CPU CI.
  auto             — TPU → pallas (compiled), CPU/GPU → reference.

Selection: ``get_solver(None)`` consults the ``REPRO_DP_SOLVER`` env var and
falls back to ``auto``; an explicit name in code always wins over the env
var, except that explicit ``"auto"`` lets the env var refine it (so a sweep
declared with the default can be redirected from the shell).  An INVALID
env var value warns and falls back to the ``auto`` resolution (a stale
shell var must not hard-crash policy builds that never asked for a
concrete backend); an invalid name passed in code still raises.

Incremental layer: :class:`CachedSolver` wraps any backend with the
quantized-statistics solve cache (``core.incremental.SolveCache``) —
same call contract, ``accepts_batch`` passthrough, kernel launches
skipped on concrete-input cache hits.  See ``docs/solvers.md``.

Degradation layer: :class:`FallbackSolver` wraps the registry with a
bounded retry chain (pallas → pallas_interpret → reference by default),
catching backend launch failures and rejecting corrupted value planes
(``kernels.budgeted_dp.ops.validate_value_row`` invariants) before
falling through — bit-identical results whichever link serves, because
backends are bit-exact interchangeable.  A deterministic fault-injection
hook (``runtime.fault.planned_fault``, env-togglable via
``$REPRO_DP_FAULT_RATE``) exercises the chain in CI without real
hardware faults.  See ``docs/robustness.md``.
"""
from __future__ import annotations

import dataclasses
import os
import warnings
from typing import Callable

import jax
import jax.numpy as jnp

from .dp import NEG, DPTables, solve_budgeted_dp

__all__ = ["SOLVER_ENV_VAR", "SOLVER_NAMES", "Solver", "resolve_solver",
           "get_solver", "CachedSolver", "FallbackSolver"]

SOLVER_ENV_VAR = "REPRO_DP_SOLVER"
SOLVER_NAMES = ("auto", "reference", "pallas", "pallas_interpret")


def _auto_backend(platform: str | None) -> str:
    platform = platform or jax.default_backend()
    return "pallas" if platform == "tpu" else "reference"


def resolve_solver(name: str | None = None, platform: str | None = None) -> str:
    """Resolve a requested backend to a concrete one.

    Returns ``"reference"``, ``"pallas"``, or ``"pallas_interpret"``.
    ``name=None``/``"auto"`` consults ``$REPRO_DP_SOLVER`` first, then picks
    by platform: TPU → compiled pallas, anything else → reference.
    ``platform`` overrides ``jax.default_backend()`` (unit-testable).

    Error handling distinguishes where a bad name came from: an invalid
    name passed IN CODE raises (the caller asked for something that does
    not exist), while an invalid ``$REPRO_DP_SOLVER`` only warns and falls
    back to the ``auto`` resolution — a stale shell var must never crash a
    policy build that requested ``None``/``"auto"``.
    """
    from_env = False
    if name is None or name == "auto":
        env_name = os.environ.get(SOLVER_ENV_VAR) or None
        if env_name is not None:
            name, from_env = env_name, True
        else:
            name = "auto"
    if name == "auto":
        name = _auto_backend(platform)
    if name not in ("reference", "pallas", "pallas_interpret"):
        if from_env:
            warnings.warn(
                f"ignoring invalid {SOLVER_ENV_VAR}={name!r} (choose from "
                f"{SOLVER_NAMES}); falling back to 'auto'",
                RuntimeWarning, stacklevel=2)
            return _auto_backend(platform)
        raise ValueError(
            f"unknown DP solver backend {name!r}; choose from {SOLVER_NAMES}")
    return name


@dataclasses.dataclass(frozen=True, eq=False)  # identity hash — jit-static-safe
class Solver:
    """A resolved Algorithm-2 backend (callable with the shared contract)."""

    name: str  # concrete backend name
    interpret: bool | None  # kernel mode (None = auto); reference: None
    _fn: Callable = dataclasses.field(repr=False)
    accepts_batch: bool = False  # vmap → ONE fleet-batched kernel launch

    def __call__(
        self,
        upsilon,
        sigma2,
        tables: DPTables,
        s_cap: int,
        s_limit,
        allowed=None,
        u_max: int | None = None,
    ):
        """``u_max`` is an optional static bound on max Υ̂ (e.g. from
        ``stats.u_max_for_horizon``); the Pallas backends clamp shifts to
        it and size their tiled halos from it, the reference backend
        ignores it.

        Backends with ``accepts_batch`` carry a custom batching rule on
        the solve core: ``jax.vmap`` of this call dispatches all mapped
        instances through ONE batched kernel launch with the DP-table
        operands shared (never replicated per instance) — results stay
        bit-exact with a per-instance loop.  Other backends vmap
        conventionally (per-instance computation, replicated operands)."""
        return self._fn(upsilon, sigma2, tables, s_cap, s_limit, allowed,
                        u_max)


def _reference_solve(upsilon, sigma2, tables, s_cap, s_limit, allowed, u_max=None):
    del u_max  # exact scan needs no shift padding
    x, info = solve_budgeted_dp(upsilon, sigma2, tables, s_cap, s_limit,
                                allowed=allowed)
    row = info["value_row"]
    return x, {"s_star": info["s_star"],
               "value_row": jnp.where(row >= 0, row, NEG)}


def _make_pallas_solve(interpret: bool | None):
    from ..kernels.budgeted_dp.ops import solve_budgeted_dp_pallas

    def solve(upsilon, sigma2, tables, s_cap, s_limit, allowed, u_max=None):
        x, info = solve_budgeted_dp_pallas(
            upsilon, sigma2, tables, s_cap, s_limit, u_max=u_max,
            allowed=allowed, interpret=interpret)
        row = info["value_row"]  # f32, kernel NEG sentinel
        row = jnp.where(row >= 0, row, float(NEG)).astype(jnp.int32)
        return x, {"s_star": info["s_star"], "value_row": row}

    return solve


class CachedSolver:
    """A backend wrapped with the quantized-statistics solve cache.

    Same call contract as :class:`Solver` (and ``accepts_batch`` follows
    the wrapped backend), so it drops into every consumer that takes a
    solver.  The cache is HOST-side: it can only act when the solve inputs
    are concrete arrays.  Calls with traced inputs (inside a caller's
    ``jit``/``scan``/``vmap``) bypass it entirely — correctness is never
    at risk, only the hit opportunity — and are counted in
    ``stats.bypasses``.  Host-loop drivers (``sched.dispatcher``, the
    bench) call it with concrete per-slot statistics and skip the whole
    backend launch on a hit; for in-scan carried memoization use the
    ``cache=`"memo"`` policy mode in ``core.esdp`` instead.

    Batched concrete inputs (``(B, E)`` statistics) are keyed PER ROW —
    instance i's key never aliases instance j's — and the (single)
    batched launch is skipped only when every row hits; any miss solves
    the whole batch and refreshes all rows.

    With the default quanta the cache is EXACT: hits are bit-identical to
    cold solves.  Coarser ``q_ups``/``q_sig`` give bounded-staleness
    approximate reuse (see :class:`repro.core.incremental.SolveCache`);
    ``exact`` exposes which mode this wrapper is in.
    """

    def __init__(
        self,
        base: Solver,
        cache: "SolveCache | None" = None,
        scope: "str | None" = None,
        **cache_kwargs,
    ):
        from .incremental import SolveCache
        self.base = base
        self.cache = cache if cache is not None else SolveCache(**cache_kwargs)
        # consumers owning several wrappers (e.g. one per A/B variant in
        # sched.engine) label each one so its counters can't be confused
        self.scope = scope
        self._jitted: dict = {}

    def stats_dict(self) -> dict:
        """``stats.as_dict()`` plus the ``scope`` label when set."""
        d = self.cache.stats.as_dict()
        if self.scope is not None:
            d["scope"] = self.scope
        return d

    @property
    def name(self) -> str:
        return f"cached:{self.base.name}"

    @property
    def interpret(self):
        return self.base.interpret

    @property
    def accepts_batch(self) -> bool:
        return self.base.accepts_batch

    @property
    def exact(self) -> bool:
        return self.cache.exact

    @property
    def stats(self):
        return self.cache.stats

    def _base_jit(self, tables, s_cap, u_max, batched: bool):
        key = (id(tables), s_cap, u_max, batched)
        fn = self._jitted.get(key)
        if fn is None:
            def single(upsilon, sigma2, s_limit, allowed):
                return self.base(upsilon, sigma2, tables, s_cap, s_limit,
                                 allowed=allowed, u_max=u_max)
            fn = jax.jit(jax.vmap(single) if batched else single)
            self._jitted[key] = fn
        return fn

    def __call__(
        self,
        upsilon,
        sigma2,
        tables: DPTables,
        s_cap: int,
        s_limit,
        allowed=None,
        u_max: int | None = None,
    ):
        if any(isinstance(a, jax.core.Tracer)
               for a in (upsilon, sigma2, s_limit, allowed) if a is not None):
            self.cache.stats.bypasses += 1
            return self.base(upsilon, sigma2, tables, s_cap, s_limit,
                             allowed=allowed, u_max=u_max)

        import numpy as np
        ups = np.asarray(upsilon)
        self.cache.tick()
        if ups.ndim == 1:
            key = self.cache.key(ups, sigma2, allowed, int(s_limit))
            hit = self.cache.get(key)
            if hit is not None:
                self.cache.stats.launches_saved += 1
                return hit
            fn = self._base_jit(tables, s_cap, u_max, batched=False)
            alw = (jnp.ones(ups.shape[0], bool) if allowed is None
                   else jnp.asarray(allowed, bool))
            x, info = fn(jnp.asarray(upsilon), jnp.asarray(sigma2),
                         jnp.asarray(s_limit), alw)
            out = (np.asarray(x),
                   {"s_star": np.asarray(info["s_star"]),
                    "value_row": np.asarray(info["value_row"])})
            self.cache.put(key, out)
            return out

        # batched (B, E): per-row keys; skip the launch only on a full hit
        sig = np.asarray(sigma2)
        slim = np.broadcast_to(np.asarray(s_limit), (ups.shape[0],))
        alw = (np.ones(ups.shape, bool) if allowed is None
               else np.broadcast_to(np.asarray(allowed, bool), ups.shape))
        keys = [self.cache.key(ups[b], sig[b], alw[b], int(slim[b]))
                for b in range(ups.shape[0])]
        hits = [self.cache.get(k) for k in keys]
        if all(h is not None for h in hits):
            self.cache.stats.launches_saved += 1
            x = np.stack([h[0] for h in hits])
            info = {"s_star": np.stack([h[1]["s_star"] for h in hits]),
                    "value_row": np.stack([h[1]["value_row"] for h in hits])}
            return x, info
        fn = self._base_jit(tables, s_cap, u_max, batched=True)
        x, info = fn(jnp.asarray(ups), jnp.asarray(sig),
                     jnp.asarray(slim), jnp.asarray(alw))
        x = np.asarray(x)
        stars, rows = np.asarray(info["s_star"]), np.asarray(info["value_row"])
        for b, k in enumerate(keys):
            self.cache.put(k, (x[b], {"s_star": stars[b],
                                      "value_row": rows[b]}))
        return x, {"s_star": stars, "value_row": rows}


class FallbackSolver:
    """Graceful degradation of the solve path: a bounded backend retry chain.

    The production failure mode this guards is a kernel backend dying or
    corrupting its output at dispatch time — a failed ``pallas_call``
    launch, an OOM, a bad lowering after a toolchain bump, a clamped
    scratch silently poisoning a plane.  Because the registry backends are
    *bit-exact interchangeable* (``tests/test_solver_equiv.py``), any link
    of the chain can serve any solve with identical results, so degrading
    never changes ``x``/``s_star``/``value_row`` — it only costs speed.

    Per concrete-input call the wrapper walks ``chain`` (default: the
    primary backend, then ``pallas_interpret`` if the primary was compiled
    pallas, then ``reference``).  An attempt degrades when

      * the backend RAISES (launch failure — caught and recorded), or
      * the returned value row violates the DP-invariant checks of
        :func:`repro.kernels.budgeted_dp.ops.validate_value_row`
        (NEG-source contract, ``VALUE_BOUND``, feasible-prefix and
        monotone-in-budget checks — theorems of the recurrence, so a
        violation always means corruption, never a legitimate input).

    The LAST link is exempt from fault injection and its exceptions
    propagate: a chain that cannot serve at all is a real outage, not a
    degradation.  Every degradation is recorded as a structured event in
    ``stats["events"]`` and counted in ``stats``; consumers
    (``sched.dispatcher.ClusterSim``, the sweep engine) surface those via
    ``solve_stats``.

    Deterministic fault injection: with ``fault_rate > 0`` (explicit arg,
    else ``$REPRO_DP_FAULT_RATE``), each non-final attempt consults
    :func:`repro.runtime.fault.planned_fault` — a pure function of
    ``(fault_seed, call_index, attempt)`` — and either raises a synthetic
    :class:`repro.runtime.fault.InjectedFault` before launching or poisons
    the returned value row so validation must catch it.  Injection is a
    plan computed per call index, so a run is bit-reproducible and, since
    fallbacks are exact, bit-identical to the fault-free run.

    Host-side like :class:`CachedSolver`: calls with traced inputs bypass
    the chain entirely and run the primary backend (counted in
    ``stats["bypasses"]``) — under ``jit``/``vmap`` the wrapper is
    invisible and adds zero launches (guarded by a jaxpr test).
    ``accepts_batch`` follows the primary; batched (B, E) concrete inputs
    walk the same chain with per-row plane validation.
    """

    def __init__(
        self,
        base: "Solver | str | None" = None,
        chain: "tuple | None" = None,
        fault_rate: "float | None" = None,
        fault_seed: "int | None" = None,
        scope: "str | None" = None,
    ):
        from ..runtime.fault import FAULT_SEED_ENV, fault_rate_from_env
        if chain is not None:
            links = [get_solver(s) for s in chain]
            if not links:
                raise ValueError("FallbackSolver chain must be non-empty")
        else:
            primary = get_solver(base)
            links = [primary]
            if primary.name == "pallas":
                links.append(get_solver("pallas_interpret"))
            if primary.name != "reference":
                links.append(get_solver("reference"))
        self.chain = tuple(links)
        self.base = self.chain[0]
        self.fault_rate = (fault_rate_from_env() if fault_rate is None
                           else float(fault_rate))
        self.fault_seed = (int(os.environ.get(FAULT_SEED_ENV, "0") or 0)
                           if fault_seed is None else int(fault_seed))
        self._jitted: dict = {}
        # scope labels this wrapper's counters when a consumer owns several
        # (e.g. one chain per A/B variant in sched.engine)
        self.scope = scope
        self.stats: dict = {
            "calls": 0, "bypasses": 0, "degraded_calls": 0,
            "launch_failures": 0, "validation_failures": 0,
            "faults_injected": 0, "served_by": {s.name: 0 for s in links},
            "events": [],
        }
        if scope is not None:
            self.stats["scope"] = scope

    def stats_dict(self) -> dict:
        """A detached copy of the counters (scope label included)."""
        import copy as _copy

        d = _copy.deepcopy(self.stats)
        if self.scope is not None:
            d["scope"] = self.scope
        return d

    _MAX_EVENTS = 256  # structured events kept; counters never truncate

    @property
    def name(self) -> str:
        return "fallback:" + "->".join(s.name for s in self.chain)

    @property
    def interpret(self):
        return self.base.interpret

    @property
    def accepts_batch(self) -> bool:
        return self.base.accepts_batch

    def _record(self, **event) -> None:
        ev = self.stats["events"]
        if len(ev) < self._MAX_EVENTS:
            ev.append(event)

    def _link_jit(self, link: Solver, tables, s_cap, u_max, batched: bool):
        key = (link.name, id(tables), s_cap, u_max, batched)
        fn = self._jitted.get(key)
        if fn is None:
            def solve(upsilon, sigma2, s_limit, allowed):
                return link(upsilon, sigma2, tables, s_cap, s_limit,
                            allowed=allowed, u_max=u_max)
            fn = jax.jit(jax.vmap(solve) if batched else solve)
            self._jitted[key] = fn
        return fn

    def __call__(
        self,
        upsilon,
        sigma2,
        tables: DPTables,
        s_cap: int,
        s_limit,
        allowed=None,
        u_max: int | None = None,
    ):
        if any(isinstance(a, jax.core.Tracer)
               for a in (upsilon, sigma2, s_limit, allowed) if a is not None):
            self.stats["bypasses"] += 1
            return self.base(upsilon, sigma2, tables, s_cap, s_limit,
                             allowed=allowed, u_max=u_max)

        import numpy as np

        from ..kernels.budgeted_dp.ops import validate_value_row
        from ..runtime.fault import InjectedFault, planned_fault

        call = self.stats["calls"]
        self.stats["calls"] += 1
        shape = np.shape(upsilon)
        batched = len(shape) == 2
        ups = jnp.asarray(upsilon)
        alw = (np.ones(shape, bool) if allowed is None
               else np.broadcast_to(np.asarray(allowed, bool), shape))
        slim = (np.broadcast_to(np.asarray(s_limit), shape[:1]) if batched
                else np.asarray(s_limit))
        last = len(self.chain) - 1
        for attempt, link in enumerate(self.chain):
            fault = (None if attempt == last else planned_fault(
                call, self.fault_rate, seed=self.fault_seed,
                attempt=attempt))
            try:
                if fault == "launch":
                    self.stats["faults_injected"] += 1
                    raise InjectedFault(
                        f"injected launch failure (call {call}, "
                        f"attempt {attempt}, backend {link.name})")
                fn = self._link_jit(link, tables, s_cap, u_max, batched)
                x, info = fn(ups, jnp.asarray(sigma2),
                             jnp.asarray(slim), jnp.asarray(alw))
                row = np.asarray(info["value_row"])
                if fault == "corrupt":
                    # poison out of the f32-exact domain: validation MUST
                    # reject this row, proving the checks are live
                    self.stats["faults_injected"] += 1
                    row = row.copy()
                    row[..., 0] = 2 ** 24
            except Exception as err:  # noqa: BLE001 — any launch failure degrades
                if attempt == last:
                    raise
                self.stats["launch_failures"] += 1
                self._record(call=call, attempt=attempt, backend=link.name,
                             kind="launch",
                             injected=isinstance(err, InjectedFault),
                             error=f"{type(err).__name__}: {err}")
                continue
            reason = validate_value_row(row)
            if reason is not None:
                if attempt == last:
                    raise RuntimeError(
                        f"DP value plane failed validation on the final "
                        f"chain link {link.name!r}: {reason}")
                self.stats["validation_failures"] += 1
                self._record(call=call, attempt=attempt, backend=link.name,
                             kind="validate", injected=fault == "corrupt",
                             error=reason)
                continue
            if attempt > 0:
                self.stats["degraded_calls"] += 1
            self.stats["served_by"][link.name] += 1
            return (np.asarray(x),
                    {"s_star": np.asarray(info["s_star"]), "value_row": row})
        raise AssertionError("unreachable: final chain link never skips")


_CACHE: dict[str, Solver] = {}


def get_solver(
    name: "str | Solver | None" = None, platform: str | None = None
) -> Solver:
    """Resolve ``name`` (see :func:`resolve_solver`) and return the Solver.

    Instances are cached per concrete backend, so repeated policy builds
    share one identity (jit-static-friendly).  Solver-shaped wrapper
    objects (:class:`CachedSolver`, :class:`FallbackSolver`, or anything
    callable exposing ``name``/``accepts_batch``) pass through unchanged,
    so every consumer that takes ``solver=`` accepts a wrapped chain."""
    if isinstance(name, Solver) or (
            callable(name) and hasattr(name, "accepts_batch")
            and hasattr(name, "name")):
        return name
    concrete = resolve_solver(name, platform)
    solver = _CACHE.get(concrete)
    if solver is None:
        if concrete == "reference":
            solver = Solver(name=concrete, interpret=None,
                            _fn=_reference_solve)
        else:
            interpret = True if concrete == "pallas_interpret" else None
            solver = Solver(name=concrete, interpret=interpret,
                            _fn=_make_pallas_solve(interpret),
                            accepts_batch=True)
        _CACHE[concrete] = solver
    return solver
