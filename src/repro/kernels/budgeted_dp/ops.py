"""jit'd wrapper: ESDP Algorithm 2 on the Pallas budgeted-DP kernel.

Drop-in equivalent of core.dp.solve_budgeted_dp (tested for exact
agreement): derives the offset-encoded kernel operands, runs the
VMEM-resident kernel (or its blocked pipelines — C-blocked for large
capacity spaces, (S-tile × C-tile) for long horizons, both edge-FUSED by
default so every tile stays VMEM-resident across ``block_e`` consecutive
edges instead of re-streaming the plane per edge; ``choose_tiling``
resolves the whole (block_e, block_s, block_c) split), then applies the
eq.-17 s* rule and backtracks in plain jnp from the bit-packed decision
words.  The backtrack is
tiling-oblivious: the forward pass returns the full packed-decision plane
(device memory, not VMEM), and the walk reads ONE 1-element slice per
edge, so the same scan serves every tiling.

Operand contract (what makes this usable from the hot path):
  * the kernel operands are the (E, C) feasibility plane and the (E,) int32
    transition-offset vector — O(E·C) and O(E) memory.  ``offsets`` is a
    field of ``DPTables`` itself, built and VALIDATED in
    ``core.dp.build_tables`` (the old per-instance one-hot cache bolted on
    via ``object.__setattr__`` is gone: a frozen or ``dataclasses.replace``d
    tables object can never carry a stale operand again);
  * operands are prepared with HOST numpy so repeated traces never leak a
    tracer; ``prepare_tables`` is a cheap pure function of the tables;
  * the whole wrapper is vmap-safe AND batch-aware: a ``custom_vmap``
    rule on the solve core dispatches every mapped instance of a whole
    plane through ONE
    :func:`repro.kernels.budgeted_dp.kernel.dp_forward_pallas_batched`
    launch — ``simulate_batch``/``simulate_grid`` mapping it over seed
    batches get one fleet-batched kernel per slot instead of B replicated
    launches, the shared (E, C) feasibility plane stays an unbatched
    constant (per-instance eligibility multiplies into the mask inside
    the kernel, never folded into B feasibility copies), and
    ``prepare_tables`` derives the host operands exactly once per tables
    object (identity-cached).  A fleet of tiled planes runs its instances
    one after another through the single solve.
    :func:`solve_budgeted_dp_batched` is the
    explicit batched entry point for callers that already hold stacked
    (B, E) statistics;
  * decisions come back packed (⌈E/32⌉, S, C) int32 — 32× less memory than
    the old (E, S, C) f32 tensor — and the backtrack walks them with pure
    offset arithmetic (cs − offsets[e]), per-edge constants streamed as
    lax.scan inputs instead of per-element table gathers.

VALUE_BOUND contract: the kernel's value planes are int32 with the
reference DP's sentinel ``core.dp.NEG = −2²⁹``, so every value is exact as
long as every reachable sum stays below ``VALUE_BOUND = |NEG| = 2²⁹``: a
NEG-seeded chain then stays negative (the s* rule reads it as infeasible)
and no sum overflows.  The bound is the largest Σ̂²ᵀx over capacity-feasible
x (:func:`max_achievable_value`); :func:`check_value_bound` raises when it
is reached.  This wrapper checks it whenever it is called with CONCRETE
statistics.  A traced call (inside jit/scan) cannot see its values, so the
caller that traces it checks the bound once for its whole run:
``DispatchEngine`` does so at construction, with every edge at the largest
Σ̂² its horizon can produce (``stats.sigma2_bound``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...core import dp as core_dp
from ...core.dp import DPTables
from .kernel import (choose_tiling, dp_forward_pallas,
                     dp_forward_pallas_batched, resolve_interpret)

__all__ = ["VALUE_BOUND", "prepare_tables", "max_achievable_value",
           "check_value_bound", "validate_value_row",
           "solve_budgeted_dp_pallas", "solve_budgeted_dp_batched",
           "WarmPallasSolver", "resolve_interpret"]

VALUE_BOUND = -int(core_dp.NEG)  # 2²⁹: every reachable DP sum stays below


def validate_value_row(value_row) -> "str | None":
    """Cheap host-side invariant check of a returned DP value row.

    The checked properties are THEOREMS of the P4/P5 recurrence — true for
    any correct backend and tiling, so a violation means the plane is
    corrupted (bad launch, clamped shift, bit flip), never a legitimate
    input.  On the contract row (int32, ``core.dp.NEG`` at
    budget-infeasible entries; see ``core.solvers``):

      * source: ``value_row[0] >= 0`` — the empty selection achieves s=0;
      * NEG contract: every entry is ``>= 0`` or exactly the sentinel;
      * VALUE_BOUND: feasible values stay ``< 2**29`` (|NEG|: the domain
        in which the int32 planes are exact);
      * prefix feasibility: feasible s form a prefix — any x with
        ``Υ̂ᵀx >= s`` also witnesses every ``s' < s``;
      * monotone: values are non-increasing in s over the feasible prefix
        (raising the budget floor only shrinks the feasible set).

    Accepts an (S,) row or a batched (B, S) stack; returns ``None`` when
    every invariant holds, else a short reason string (first violation).
    """
    row = np.asarray(value_row)
    if row.ndim == 2:
        for b in range(row.shape[0]):
            reason = validate_value_row(row[b])
            if reason is not None:
                return f"row {b}: {reason}"
        return None
    neg = int(core_dp.NEG)
    feas = row != neg
    if not feas[0] or row[0] < 0:
        return f"source: value_row[0] = {row[0]} (must be >= 0)"
    bad = feas & (row < 0)
    if bad.any():
        s = int(np.flatnonzero(bad)[0])
        return (f"neg-contract: value_row[{s}] = {row[s]} is negative but "
                f"not the NEG sentinel ({neg})")
    over = feas & (row >= VALUE_BOUND)
    if over.any():
        s = int(np.flatnonzero(over)[0])
        return (f"value-bound: value_row[{s}] = {row[s]} >= 2^29 "
                "(outside the exact int32 domain)")
    n_feas = int(feas.sum())
    if not feas[:n_feas].all():
        s = int(np.flatnonzero(~feas)[0])
        return (f"feasible-prefix: value_row[{s}] is infeasible but a "
                "larger budget is feasible")
    pre = row[:n_feas]
    rising = np.flatnonzero(np.diff(pre.astype(np.int64)) > 0)
    if rising.size:
        s = int(rising[0])
        return (f"monotone: value_row[{s + 1}] = {pre[s + 1]} > "
                f"value_row[{s}] = {pre[s]} (must be non-increasing in s)")
    return None


@functools.lru_cache(maxsize=32)
def prepare_tables(tables: DPTables):
    """(feasible (E, C) f32, offsets (E,) i32) kernel operands.

    Pure host-numpy derivations of ``DPTables`` fields — nothing is cached
    on the tables object, so there is no stale-cache hazard.  Offsets of
    never-feasible edges (infeasible even at full capacity) are zeroed:
    they are masked everywhere, and zeroing keeps ``max(offsets)`` — the
    kernel's pad width — tight.

    Memoized by tables IDENTITY (``DPTables`` is frozen with ``eq=False``,
    so the object itself is the hashable key and the cache holds it
    alive): every solver call against the same tables — in particular all
    B instances of a vmapped or batched dispatch — derives the operands
    exactly ONCE.  A ``dataclasses.replace``d or rebuilt tables object is
    a different key, so the cache can never serve stale operands; the
    returned arrays are shared and must be treated as read-only.
    """
    feas = np.asarray(tables.feasible).T.astype(np.float32)  # (E, C)
    usable = np.asarray(tables.feasible)[tables.full_state]  # (E,)
    offsets = np.where(usable, np.asarray(tables.offsets), 0)
    return feas, offsets.astype(np.int32)


def max_achievable_value(sigma2, tables: DPTables) -> int:
    """The largest DP sum: max Σ̂²ᵀx over capacity-feasible x (Ax ≤ c).

    An exact 0/1 knapsack over the capacity states in int64 on the host,
    O(E·C).  Every value the forward solve materializes is at most this —
    a state of remaining capacity c' ≤ c only admits subsets of the same
    selections, and a NEG-seeded chain adds a subset of the same gains to
    NEG.  With every Σ̂² equal to one value g it is g times the largest
    selectable set.
    """
    sig = np.asarray(sigma2, dtype=np.int64)
    feas = np.asarray(tables.feasible)  # (C, E)
    offs = np.asarray(tables.offsets, dtype=np.int64)
    V = np.zeros(tables.n_states, np.int64)
    for e in range(sig.shape[0]):
        states = np.flatnonzero(feas[:, e])
        if states.size:
            V[states] = np.maximum(V[states],
                                   V[states - offs[e]] + sig[e])
    return int(V[tables.full_state])


def check_value_bound(sigma2, tables: DPTables) -> None:
    """Raise unless every DP sum under ``sigma2`` stays below VALUE_BOUND.

    Traced statistics cannot be checked: their caller checks a bound for
    its whole run (``DispatchEngine`` at construction)."""
    if isinstance(sigma2, jax.core.Tracer):
        return
    bound = max_achievable_value(sigma2, tables)
    if bound >= VALUE_BOUND:
        raise ValueError(
            f"budgeted-DP values can reach {bound} ≥ 2^29 = |NEG|: the "
            "int32 planes would no longer tell reachable sums from the "
            "sentinel. Rescale Σ̂² or shorten the horizon.")


def _check_u_max(upsilon, u_max: int) -> None:
    """The kernel clamps shifts at u_max (its tiled halos hold u_max
    rows), which would SILENTLY corrupt values if any Υ̂ exceeded it — turn
    a contract breach into an error whenever the statistics are concrete
    (traced calls are covered by the u_max_for_horizon bound test)."""
    if isinstance(upsilon, jax.core.Tracer):
        return
    top = int(np.max(np.asarray(upsilon))) if np.size(upsilon) else 0
    if top > u_max:
        raise ValueError(
            f"max Υ̂ = {top} exceeds u_max = {u_max}: the kernel would "
            "clamp the shift (wrong values). Pass "
            "u_max ≥ max Υ̂ (stats.u_max_for_horizon bounds the default "
            "schedules) or leave u_max=None.")


def _select_backtrack(V, decisions, upsilon, offsets, s_limit, full_state, words=None):
    """The eq.-17 s* rule on the forward plane, then the backtrack.

    ``V`` (S, C) and ``decisions`` (words, S, C) are one solve's forward
    outputs; returns ``(x (E,), s*, value row (S,))``.  The backtrack runs
    on offset arithmetic from (s*, ``full_state``): the per-edge constants
    (Υ̂, offset, word row, bit) stream in as scan inputs, so the loop body
    is scalar arithmetic plus ONE 1-element dynamic slice of the packed
    words — no per-element gathers from (E, C) transition tables.
    ``words`` is each edge's (word row, bit) in ``decisions``; ``None``
    is the kernel's packing, word e // 32 and bit e % 32."""
    with jax.named_scope("esdp.select"):
        v_row = V[:, full_state]
        s_vals = jnp.arange(V.shape[0], dtype=jnp.int32)
        # feasible ⇔ value ≥ 0: Σ̂² ≥ 0 so reachable values are
        # non-negative, while NEG-seeded chains stay < 0 for any partial
        # sum < 2²⁹ (the VALUE_BOUND contract) — the rule of core.dp
        ok = (v_row >= 0) & (s_vals <= s_limit)
        score = s_vals.astype(jnp.float32) + jnp.sqrt(
            jnp.maximum(v_row, 0).astype(jnp.float32))
        s_star = jnp.argmax(jnp.where(ok, score, -jnp.inf)).astype(
            jnp.int32)

    if words is None:
        e_ids = jnp.arange(upsilon.shape[0], dtype=jnp.int32)
        words = (e_ids // 32, e_ids % 32)

    def back(carry, x):
        s, cs = carry
        u, off, w, b = x
        word = jax.lax.dynamic_slice(decisions, (w, s, cs), (1, 1, 1))
        d = (word[0, 0, 0] >> b) & 1
        taken = d > 0
        s = jnp.where(taken, jnp.maximum(s - u, 0), s)
        cs = jnp.where(taken, cs - off, cs)
        return (s, cs), d

    with jax.named_scope("esdp.backtrack"):
        (_, _), x = jax.lax.scan(
            back, (s_star, jnp.int32(full_state)), (upsilon, offsets, *words))
    return x, s_star, v_row


@functools.partial(jax.jit,
                   static_argnames=("s_cap", "u_max", "off_max", "full_state",
                                    "interpret", "block_c", "block_s",
                                    "block_e"))
def _solve(
    upsilon,
    sigma2,
    feasible,
    offsets,
    s_limit,
    *,
    s_cap: int,
    u_max: int,
    off_max: int,
    full_state: int,
    interpret: bool,
    block_c: int | None,
    block_s: int | None,
    block_e: int | None,
):
    E = upsilon.shape[0]
    v0 = core_dp.initial_plane(s_cap, feasible.shape[1])

    with jax.named_scope("esdp.forward"):
        V, decisions = dp_forward_pallas(
            upsilon, sigma2, feasible, offsets, v0,
            n_edges=E, u_max=u_max, off_max=off_max, interpret=interpret,
            block_c=block_c, block_s=block_s, block_e=block_e)

    return _select_backtrack(V, decisions, upsilon, offsets, s_limit,
                             full_state)


@functools.partial(jax.jit,
                   static_argnames=("s_cap", "u_max", "off_max", "full_state",
                                    "interpret", "block_b", "block_c",
                                    "block_s", "block_e"))
def _solve_batched(
    upsilon,
    sigma2,
    allowed,
    feasible,
    offsets,
    s_limit,
    *,
    s_cap: int,
    u_max: int,
    off_max: int,
    full_state: int,
    interpret: bool,
    block_b: int | None,
    block_c: int | None,
    block_s: int | None,
    block_e: int | None,
):
    """Batched :func:`_solve`: B solves through one batched forward.

    upsilon/sigma2/allowed are (B, E), ``s_limit`` is (B,); the tables
    operands stay SHARED (unbatched).  The eq.-17 selection and the
    backtrack are :func:`_select_backtrack` under ``jax.vmap``: the walks
    run in lockstep, each step reading one 1-element slice of each
    instance's packed-decision words."""
    E = upsilon.shape[1]
    v0 = core_dp.initial_plane(s_cap, feasible.shape[1])

    with jax.named_scope("esdp.forward"):
        V, decisions = dp_forward_pallas_batched(
            upsilon, sigma2, allowed, feasible, offsets, v0,
            n_edges=E, u_max=u_max, off_max=off_max, interpret=interpret,
            block_b=block_b, block_c=block_c, block_s=block_s,
            block_e=block_e)

    return jax.vmap(
        lambda V_, d, u, sl: _select_backtrack(V_, d, u, offsets, sl,
                                               full_state))(
        V, decisions, upsilon, s_limit)


@functools.lru_cache(maxsize=None)
def _vmappable_core(
    s_cap: int,
    u_max: int,
    off_max: int,
    full_state: int,
    interpret: bool,
    block_c,
    block_s,
    block_e,
    n_edges: int,
    n_states: int,
):
    """The solve core for one static kernel config, with a custom vmap rule.

    The single-instance path folds ``allowed`` into the feasibility plane
    and runs :func:`_solve`.  Under ``jax.vmap`` the rule fires instead.
    A whole plane routes ALL mapped instances through ONE
    :func:`dp_forward_pallas_batched` launch (``block_b`` from
    ``choose_tiling(batch=B)``): the shared (E, C) feasibility plane
    stays an unbatched constant (vmapping the fold would materialize B
    per-instance copies of it) and per-instance eligibility rides the
    (B, E) ``allowed`` rows.  A tiled plane, fused or per-edge, runs the
    instances one after another through the single solve.  Cached per
    static config so repeated solver calls reuse one ``custom_vmap``
    object and its jit traces."""

    def plain(upsilon, sigma2, s_limit, allowed, feasible, offsets):
        feas = feasible * allowed.astype(jnp.float32)[:, None]
        return _solve(upsilon, sigma2, feas, offsets, s_limit,
                      s_cap=s_cap, u_max=u_max, off_max=off_max,
                      full_state=full_state, interpret=interpret,
                      block_c=block_c, block_s=block_s, block_e=block_e)

    core = jax.custom_batching.custom_vmap(plain)

    @core.def_vmap
    def _batched_rule(
        axis_size, in_batched, upsilon, sigma2, s_limit, allowed, feasible, offsets
    ):
        up_b, sg_b, sl_b, al_b, fe_b, of_b = in_batched
        if fe_b or of_b:
            raise NotImplementedError(
                "the DP tables are shared across a batch: vmap over "
                "per-instance feasibility/offset operands is not "
                "supported — rebuild per-instance tables and solve them "
                "separately instead")
        B = axis_size

        def bcast(x, batched):
            return x if batched else jnp.broadcast_to(x, (B,) + jnp.shape(x))

        ups = bcast(upsilon, up_b)
        sig = bcast(sigma2, sg_b)
        sl = bcast(s_limit, sl_b)
        alw = bcast(allowed, al_b)

        if block_c is not None:
            outs = jax.lax.map(
                lambda t: plain(*t, feasible, offsets), (ups, sig, sl, alw))
        else:
            bb = choose_tiling(s_cap + 1, n_states, n_edges, u_max, off_max,
                               batch=B)[0]
            outs = _solve_batched(
                ups, sig, alw, feasible, offsets, sl,
                s_cap=s_cap, u_max=u_max, off_max=off_max,
                full_state=full_state, interpret=interpret, block_b=bb,
                block_c=None, block_s=block_s, block_e=block_e)
        return outs, (True, True, True)

    return core


def solve_budgeted_dp_pallas(
    upsilon,
    sigma2,
    tables: DPTables,
    s_cap: int,
    s_limit,
    u_max: int | None = None,
    allowed=None,
    interpret: bool | None = None,
    block_c: "int | str | None" = "auto",
    block_s: int | None = None,
    block_e: int | None = None,
):
    """Same contract as :func:`repro.core.dp.solve_budgeted_dp`, executed on
    the Pallas kernel (+ kernel knobs).

    Args:
      upsilon, sigma2: (E,) int32 scaled statistics Υ̂(t), Σ̂²(t).
      tables: :class:`repro.core.dp.DPTables` from ``build_tables``.
      s_cap: static bound on s (value-row height − 1).
      s_limit: dynamic ξ(t)·m budget mask (s values beyond it are ignored
        by the eq.-17 selection).
      u_max: static bound on max Υ̂: the kernel clamps shifts to it and
        sizes its tiled halos from it.  ``None`` uses the always-safe
        ``s_cap + 1``; callers that know the schedule bound
        (``stats.u_max_for_horizon``) should pass it — the halos shrink
        m-fold.  An undersized concrete bound raises instead of clamping.
      allowed: optional (E,) bool eligibility mask (arrival ∧ aliveness).
      interpret: ``None`` auto-resolves (compiled on TPU, Pallas
        interpreter elsewhere); an explicit bool forces the mode.
      block_c, block_s, block_e: the plane tiling.  ``block_c="auto"``
        (default) picks all three from the VMEM budget via
        ``choose_tiling``: whole-plane when it fits, C-blocked for large
        capacity spaces, the 2-D (S-tile × C-tile) grid for long
        horizons — and on every blocked pipeline the largest edge-fused
        chunk ``block_e`` that fits, so tiles stay VMEM-resident across
        ``block_e`` consecutive edges instead of re-streaming per edge.
        Explicit ints force a tiling (``block_c=None`` forces whole-plane;
        ``block_s``/``block_e`` require a concrete ``block_c``).

    Returns:
      ``(x, info)`` — the (E,) int32 dispatch vector and ``{"s_star",
      "value_row"}``, bit-exact vs the reference backend for every tiling.

    Under ``jax.vmap`` the solve core's custom batching rule dispatches
    every mapped instance of a whole plane through ONE batched kernel
    launch, and maps a tiled plane's instances through the single solve
    (see :func:`_vmappable_core`) — callers never need to opt in.
    """
    check_value_bound(sigma2, tables)
    feas, offs = prepare_tables(tables)
    if u_max is None:
        u_max = s_cap + 1
    _check_u_max(upsilon, int(u_max))
    E = offs.shape[0]
    off_max = int(offs.max()) if E else 0
    if block_c == "auto":
        if block_s is not None or block_e is not None:
            forced = "block_s" if block_s is not None else "block_e"
            raise ValueError(
                f'{forced} was forced but block_c is "auto": the auto '
                "tiling would overwrite it — pass a concrete block_c "
                "(e.g. the number of capacity states for a single "
                "full-width tile)")
        block_e, block_s, block_c = choose_tiling(
            s_cap + 1, tables.n_states, E, int(u_max), off_max)
    core = _vmappable_core(
        s_cap, int(u_max), off_max, tables.full_state,
        resolve_interpret(interpret), block_c, block_s, block_e, E,
        tables.n_states)
    alw = (jnp.ones((E,), jnp.int32) if allowed is None
           else jnp.asarray(allowed, jnp.int32))
    x, s_star, v_row = core(
        jnp.asarray(upsilon, jnp.int32), jnp.asarray(sigma2, jnp.int32),
        jnp.asarray(s_limit, jnp.int32), alw, jnp.asarray(feas),
        jnp.asarray(offs))
    return x, {"s_star": s_star, "value_row": v_row}


def solve_budgeted_dp_batched(
    upsilon,
    sigma2,
    tables: DPTables,
    s_cap: int,
    s_limit,
    u_max: int | None = None,
    allowed=None,
    interpret: bool | None = None,
    block_b: "int | str" = "auto",
    block_c: "int | str | None" = "auto",
    block_s: int | None = None,
    block_e: int | None = None,
):
    """B solves against SHARED tables: ONE kernel launch on a whole
    plane, the single solve mapped over the instances on a tiled plane.

    The explicit batched entry point for callers that already hold
    stacked statistics (``jax.vmap`` of :func:`solve_budgeted_dp_pallas`
    reaches the same forward through the custom batching rule).

    Args:
      upsilon, sigma2: (B, E) int32 per-instance statistics.
      s_limit: scalar or (B,) per-instance budget mask.
      allowed: optional (B, E) per-instance eligibility; the (E, C)
        feasibility plane itself stays shared — eligibility multiplies
        into the mask inside the kernel.
      block_b: instances advanced per grid step.  ``"auto"`` (default)
        resolves with the tiling; an explicit int outside [1, B] raises,
        and forcing it while ``block_c="auto"`` raises (the auto tiling
        would overwrite it).  B need not be a multiple of block_b: ragged
        batches pad with inert ``allowed ≡ 0`` instances.
      Everything else matches :func:`solve_budgeted_dp_pallas`.

    Returns:
      ``(x, info)`` — (B, E) int32 dispatch vectors and ``{"s_star":
      (B,), "value_row": (B, S)}``, bit-exact vs a per-instance loop
      over the reference backend.
    """
    if not isinstance(sigma2, jax.core.Tracer):
        # worst case per edge across the batch bounds every instance
        check_value_bound(np.max(np.asarray(sigma2), axis=0), tables)
    feas, offs = prepare_tables(tables)
    if u_max is None:
        u_max = s_cap + 1
    _check_u_max(upsilon, int(u_max))
    E = offs.shape[0]
    B = int(np.shape(upsilon)[0])
    off_max = int(offs.max()) if E else 0
    if block_c == "auto":
        forced = next((name for name, val in (("block_b", block_b),
                                              ("block_s", block_s),
                                              ("block_e", block_e))
                       if val is not None and val != "auto"), None)
        if forced is not None:
            raise ValueError(
                f'{forced} was forced but block_c is "auto": the auto '
                "tiling would overwrite it — pass a concrete block_c "
                "(e.g. the number of capacity states for a single "
                "full-width tile)")
        block_b, block_e, block_s, block_c = choose_tiling(
            s_cap + 1, tables.n_states, E, int(u_max), off_max, batch=B)
    elif block_b == "auto":
        block_b = (1 if block_c is not None else choose_tiling(
            s_cap + 1, tables.n_states, E, int(u_max), off_max,
            batch=B)[0])
    alw = (jnp.ones((B, E), jnp.int32) if allowed is None
           else jnp.asarray(allowed, jnp.int32))
    sl = jnp.broadcast_to(jnp.asarray(s_limit, jnp.int32), (B,))
    x, s_star, v_row = _solve_batched(
        jnp.asarray(upsilon, jnp.int32), jnp.asarray(sigma2, jnp.int32),
        alw, jnp.asarray(feas), jnp.asarray(offs), sl,
        s_cap=s_cap, u_max=int(u_max), off_max=off_max,
        full_state=tables.full_state,
        interpret=resolve_interpret(interpret), block_b=block_b,
        block_c=block_c, block_s=block_s, block_e=block_e)
    return x, {"s_star": s_star, "value_row": v_row}


class WarmPallasSolver:
    """Warm-started Pallas path: carried value planes + per-segment launches.

    The kernel entry :func:`dp_forward_pallas` already takes a seed plane
    ``v0`` (the carried-plane hook), so warm-starting needs NO kernel
    changes — only a host driver that splits the edge fold into fixed
    SEGMENTS of ``checkpoint_every`` fold steps and launches them chained
    (each segment's output plane seeds the next).  A chain of segment
    launches executes the identical int32 op sequence as one launch, so the
    split itself is bit-invisible.  Across slots the driver keeps every
    inter-segment plane plus each segment's packed decision words: when a
    new solve's delta mask (vs the previous inputs, in FOLD order — edge
    ``E-1-j`` at fold step ``j``) leaves a prefix of fold steps unchanged,
    all fully-unchanged segments are SKIPPED — their planes and decisions
    are reused verbatim — and the fold resumes from the stored plane
    before the first touched segment.  Resuming from a pre-segment plane
    (not the final plane) is what keeps the result bit-identical to a cold
    solve: re-folding an edge into a plane that already absorbed it would
    double-take it (see ``core.incremental`` for the worked example).

    The eq.-17 selection and the backtrack are recomputed every call (so a
    changed ``s_limit`` alone costs zero launches).  Decision words are
    packed per segment in LOCAL edge numbering and concatenated along the
    word axis; the backtrack streams host-precomputed (word-row, bit)
    constants per global edge, so it never shifts between packings.

    This is a HOST-side driver: inputs must be concrete (calls with traced
    arrays raise — put it behind ``sched.dispatcher``'s host loop, not
    inside a ``lax.scan``).  Call contract and returned ``info`` match the
    ``pallas`` Solver backend (``value_row`` sanitized to int32/NEG), plus
    ``edges_folded``.  One instance is bound to one (tables, s_cap, u_max)
    problem; ``accepts_batch`` is False — batched fleets should use the
    solve cache instead (``core.solvers.CachedSolver``).
    """

    accepts_batch = False
    interpret = None

    def __init__(
        self,
        tables: DPTables,
        s_cap: int,
        u_max: int | None = None,
        checkpoint_every: int = 8,
        interpret: bool | None = None,
    ):
        feas, offs = prepare_tables(tables)
        self.tables = tables
        self.s_cap = int(s_cap)
        self.u_max = int(u_max) if u_max is not None else self.s_cap + 1
        self.k = int(checkpoint_every)
        if self.k < 1:
            raise ValueError("checkpoint_every must be >= 1")
        self.interpret = resolve_interpret(interpret)
        self._feas, self._offs = feas, offs
        E = offs.shape[0]
        self._E = E
        self._off_max = int(offs.max()) if E else 0

        # fixed fold-order segmentation: segment si covers fold steps
        # [si·k, (si+1)·k) = edges [max(E-(si+1)k, 0), E-si·k)
        k = self.k
        self._n_seg = max(1, -(-E // k))
        self._bounds = [(max(E - (si + 1) * k, 0), E - si * k)
                        for si in range(self._n_seg)]
        word_off, off = [], 0
        for lo, hi in self._bounds:
            word_off.append(off)
            off += -(-(hi - lo) // 32)
        # global edge e → its word row / bit in the concatenated packing
        e_ids = np.arange(E)
        si_of = np.minimum((E - 1 - e_ids) // k, self._n_seg - 1)
        lo_of = np.array([self._bounds[si][0] for si in si_of])
        local = e_ids - lo_of
        self._w_rows = (np.array([word_off[si] for si in si_of])
                        + local // 32).astype(np.int32)
        self._bits = (local % 32).astype(np.int32)

        self._launch = [self._make_launch(lo, hi) for lo, hi in self._bounds]
        self._select_back = self._make_select_back()

        # carried fold artifacts (host side)
        self._v0 = core_dp.initial_plane(self.s_cap, tables.n_states)
        self._planes = [self._v0] + [None] * self._n_seg
        self._dec = [None] * self._n_seg
        self._dec_cat = None
        self._prev = None  # (ups, sig, alw) of the carried solve
        self.stats = {"solves": 0, "segments_launched": 0,
                      "segments_skipped": 0, "edges_folded": 0,
                      "edges_skipped": 0, "full_hits": 0}

    @property
    def name(self) -> str:
        return "warm:pallas" + ("_interpret" if self.interpret else "")

    @property
    def skip_rate(self) -> float:
        n = self.stats["edges_folded"] + self.stats["edges_skipped"]
        return self.stats["edges_skipped"] / n if n else 0.0

    def _make_launch(self, lo: int, hi: int):
        feas_seg = jnp.asarray(self._feas[lo:hi])
        offs_seg = jnp.asarray(self._offs[lo:hi])
        be, bs, bc = choose_tiling(self.s_cap + 1, self.tables.n_states,
                                   hi - lo, self.u_max, self._off_max)

        @jax.jit
        def launch(ups, sig, alw, v0):
            f = feas_seg * alw.astype(jnp.float32)[:, None]
            return dp_forward_pallas(
                ups, sig, f, offs_seg, v0, n_edges=hi - lo,
                u_max=self.u_max, off_max=self._off_max,
                interpret=self.interpret, block_c=bc, block_s=bs,
                block_e=be)

        return launch

    def _make_select_back(self):
        offs = jnp.asarray(self._offs)
        words = (jnp.asarray(self._w_rows), jnp.asarray(self._bits))
        full_state = self.tables.full_state

        @jax.jit
        def select_back(V, decisions, upsilon, s_limit):
            x, s_star, v_row = _select_backtrack(
                V, decisions, upsilon, offs, s_limit, full_state, words)
            # contract sanitization: every budget-infeasible entry reads
            # exactly the sentinel
            return x, s_star, jnp.where(v_row >= 0, v_row, core_dp.NEG)

        return select_back

    def reset(self) -> None:
        """Drop the carried solve (the next call folds everything)."""
        self._planes = [self._v0] + [None] * self._n_seg
        self._dec = [None] * self._n_seg
        self._dec_cat = None
        self._prev = None

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
        if tables is not self.tables or int(s_cap) != self.s_cap:
            raise ValueError(
                "WarmPallasSolver is bound to one (tables, s_cap) problem; "
                "build a new instance for a different one")
        if any(isinstance(a, jax.core.Tracer)
               for a in (upsilon, sigma2, s_limit, allowed)
               if a is not None):
            raise TypeError(
                "WarmPallasSolver carries host state and needs concrete "
                "inputs; inside jit/scan use the reference warm path "
                "(core.incremental.solve_budgeted_dp_warm) or the solve "
                "cache instead")
        check_value_bound(np.asarray(sigma2), self.tables)
        _check_u_max(np.asarray(upsilon), self.u_max)

        E = self._E
        ups = np.asarray(upsilon, np.int32)
        sig = np.asarray(sigma2, np.int32)
        alw = (np.ones(E, bool) if allowed is None
               else np.asarray(allowed, bool))

        # delta mask in fold order → longest unchanged fold prefix
        if self._prev is None:
            p = 0
        else:
            pu, ps, pa = self._prev
            changed = ((ups[::-1] != pu[::-1]) | (sig[::-1] != ps[::-1])
                       | (alw[::-1] != pa[::-1]))
            nz = np.flatnonzero(changed)
            p = int(nz[0]) if nz.size else E
        si_r = self._n_seg if p >= E else p // self.k

        self.stats["solves"] += 1
        self.stats["segments_skipped"] += si_r
        self.stats["segments_launched"] += self._n_seg - si_r
        folded = 0
        if si_r == self._n_seg:
            self.stats["full_hits"] += 1
        else:
            V = self._planes[si_r]
            for si in range(si_r, self._n_seg):
                lo, hi = self._bounds[si]
                V, dec = self._launch[si](
                    jnp.asarray(ups[lo:hi]), jnp.asarray(sig[lo:hi]),
                    jnp.asarray(alw[lo:hi]), V)
                self._planes[si + 1] = V
                self._dec[si] = dec
                folded += hi - lo
            self._dec_cat = jnp.concatenate(self._dec, axis=0)
            # defensive copies: np.asarray above is a no-copy view, and a
            # host loop that mutates its statistics buffers in place would
            # otherwise mutate the carried inputs too — blinding the delta
            # mask and silently serving stale planes
            self._prev = (ups.copy(), sig.copy(), alw.copy())
        self.stats["edges_folded"] += folded
        self.stats["edges_skipped"] += E - folded

        x, s_star, row = self._select_back(
            self._planes[self._n_seg], self._dec_cat, jnp.asarray(ups),
            jnp.asarray(np.int32(s_limit)))
        return x, {"s_star": s_star, "value_row": row,
                   "edges_folded": folded}
