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
    rule on the solve core dispatches every mapped instance through ONE
    :func:`repro.kernels.budgeted_dp.kernel.dp_forward_pallas_batched`
    launch — ``simulate_batch``/``simulate_grid`` mapping it over seed
    batches get one fleet-batched kernel per slot instead of B replicated
    launches, the shared (E, C) feasibility plane stays an unbatched
    constant (per-instance eligibility multiplies into the mask inside
    the kernel, never folded into B feasibility copies), and
    ``prepare_tables`` derives the host operands exactly once per tables
    object (identity-cached).  :func:`solve_budgeted_dp_batched` is the
    explicit batched entry point for callers that already hold stacked
    (B, E) statistics;
  * decisions come back packed (⌈E/32⌉, S, C) int32 — 32× less memory than
    the old (E, S, C) f32 tensor — and the backtrack walks them with pure
    offset arithmetic (cs − offsets[e]), per-edge constants streamed as
    lax.scan inputs instead of per-element table gathers.

VALUE_BOUND contract: kernel arithmetic is f32, exact for integers < 2²⁴.
Whenever this wrapper is called with CONCRETE statistics it verifies that no
capacity-feasible subset can accumulate a value ≥ 2²⁴ and raises otherwise;
traced calls (inside jit/scan) skip the check, which is why
``tests/test_solver_equiv.py`` pins the default schedules under the bound.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...core import dp as core_dp
from ...core.dp import DPTables
from .kernel import (NEG, choose_tiling, dp_forward_pallas,
                     dp_forward_pallas_batched, resolve_interpret)

__all__ = ["VALUE_BOUND", "prepare_tables", "max_achievable_value",
           "validate_value_row", "solve_budgeted_dp_pallas",
           "solve_budgeted_dp_batched", "WarmPallasSolver",
           "resolve_interpret"]

VALUE_BOUND = 2 ** 24  # f32-exact integer domain (kernel contract)


def validate_value_row(value_row) -> "str | None":
    """Cheap host-side invariant check of a returned DP value row.

    The checked properties are THEOREMS of the P4/P5 recurrence — true for
    any correct backend and tiling, so a violation means the plane is
    corrupted (bad launch, clamped shift, bit flip), never a legitimate
    input.  On the contract row (int32, ``core.dp.NEG`` at
    budget-infeasible entries; see ``core.solvers``):

      * source: ``value_row[0] >= 0`` — the empty selection achieves s=0;
      * NEG contract: every entry is ``>= 0`` or exactly the sentinel;
      * VALUE_BOUND: feasible values stay ``< 2**24`` (the f32-exact
        domain the kernel is allowed to produce);
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
        return (f"value-bound: value_row[{s}] = {row[s]} >= 2^24 "
                "(outside the f32-exact domain)")
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
    """Upper bound on any DP partial sum: max Σ̂²ᵀx over capacity-feasible x.

    Per-edge requirements are recovered from the transition out of the
    full-capacity state; if every usable edge consumes ≥ 1 device the
    selection size is capped by Σ_k c_k, else by E.  The top-k sum of Σ̂²
    then bounds every value the kernel can ever materialize (feasible or
    not — infeasible states only accumulate subsets of the same sums).
    """
    sig = np.asarray(sigma2, dtype=np.int64)
    E = sig.shape[0]
    usable = np.asarray(tables.feasible)[tables.full_state]  # (E,)
    if not usable.any():
        return 0
    cap = np.asarray(tables.cap_of_state, dtype=np.int64)
    c = np.asarray(tables.radices, dtype=np.int64) - 1
    nxt = np.asarray(tables.next_state)[tables.full_state]  # (E,)
    req_total = (c[None, :] - cap[nxt]).sum(axis=1)  # (E,)
    if np.all(req_total[usable] >= 1):
        k = min(E, int(c.sum()))
    else:
        k = E
    top = np.sort(sig[usable])[::-1][:k]
    return int(top.sum())


def _check_value_bound(sigma2, tables: DPTables) -> None:
    if isinstance(sigma2, jax.core.Tracer):
        return  # traced call — bound pinned by tests
    bound = max_achievable_value(sigma2, tables)
    if bound >= VALUE_BOUND:
        raise ValueError(
            f"budgeted-DP values can reach {bound} ≥ 2^24: the Pallas "
            "kernel's f32 arithmetic is no longer exact. Rescale Σ̂² or "
            "use the 'reference' (int32) solver backend.")


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
    S = s_cap + 1
    v0 = jnp.full((S, feasible.shape[1]), NEG, jnp.float32).at[0, :].set(0.0)

    V, decisions = dp_forward_pallas(
        upsilon, sigma2, feasible, offsets, v0,
        n_edges=E, u_max=u_max, off_max=off_max, interpret=interpret,
        block_c=block_c, block_s=block_s, block_e=block_e)

    v_row = V[:, full_state]
    s_vals = jnp.arange(S, dtype=jnp.int32)
    # feasible ⇔ value ≥ 0: Σ̂² ≥ 0 so reachable values are non-negative,
    # while NEG-seeded chains stay < 0 for any partial sum < 2²⁴ (the
    # VALUE_BOUND contract) — sharper than thresholding at NEG/2.
    ok = (v_row >= 0) & (s_vals <= s_limit)
    score = s_vals.astype(jnp.float32) + jnp.sqrt(jnp.maximum(v_row, 0.0))
    s_star = jnp.argmax(jnp.where(ok, score, -jnp.inf)).astype(jnp.int32)

    # backtrack on offset arithmetic: the per-edge constants (Υ̂, offset,
    # word id, bit id) stream in as scan inputs, so the loop body is scalar
    # arithmetic plus ONE 1-element dynamic slice of the packed words — no
    # per-element gathers from (E, C) transition tables
    e_ids = jnp.arange(E, dtype=jnp.int32)

    def back(carry, x):
        s, cs = carry
        u, off, w, b = x
        word = jax.lax.dynamic_slice(decisions, (w, s, cs), (1, 1, 1))
        d = (word[0, 0, 0] >> b) & 1
        taken = d > 0
        s = jnp.where(taken, jnp.maximum(s - u, 0), s)
        cs = jnp.where(taken, cs - off, cs)
        return (s, cs), d

    (_, _), x = jax.lax.scan(
        back, (s_star, jnp.int32(full_state)),
        (upsilon, offsets, e_ids // 32, e_ids % 32))
    return x, s_star, v_row


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
    """Batched :func:`_solve`: B solves through ONE kernel launch.

    upsilon/sigma2/allowed are (B, E), ``s_limit`` is (B,); the tables
    operands stay SHARED (unbatched).  The eq.-17 selection runs across
    the batch axis, and the backtrack scans all B walks in lockstep —
    per-edge constants stream once, each step reads one 1-element slice
    of each instance's packed-decision words."""
    B, E = upsilon.shape
    S = s_cap + 1
    v0 = jnp.full((S, feasible.shape[1]), NEG, jnp.float32).at[0, :].set(0.0)

    V, decisions = dp_forward_pallas_batched(
        upsilon, sigma2, allowed, feasible, offsets, v0,
        n_edges=E, u_max=u_max, off_max=off_max, interpret=interpret,
        block_b=block_b, block_c=block_c, block_s=block_s, block_e=block_e)

    v_row = V[:, :, full_state]  # (B, S)
    s_vals = jnp.arange(S, dtype=jnp.int32)
    ok = (v_row >= 0) & (s_vals[None, :] <= s_limit[:, None])
    score = (s_vals[None, :].astype(jnp.float32)
             + jnp.sqrt(jnp.maximum(v_row, 0.0)))
    s_star = jnp.argmax(jnp.where(ok, score, -jnp.inf),
                        axis=1).astype(jnp.int32)

    e_ids = jnp.arange(E, dtype=jnp.int32)

    def back(carry, x):
        s, cs = carry  # (B,) each
        u, off, w, b = x  # u (B,); rest scalar
        word = jax.vmap(
            lambda d, s_, c_: jax.lax.dynamic_slice(
                d, (w, s_, c_), (1, 1, 1))[0, 0, 0])(decisions, s, cs)
        d = (word >> b) & 1
        taken = d > 0
        s = jnp.where(taken, jnp.maximum(s - u, 0), s)
        cs = jnp.where(taken, cs - off, cs)
        return (s, cs), d

    (_, _), x = jax.lax.scan(
        back, (s_star, jnp.full((B,), full_state, jnp.int32)),
        (upsilon.T, offsets, e_ids // 32, e_ids % 32))
    return x.T, s_star, v_row


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
    auto_tiling: bool,
    n_edges: int,
    n_states: int,
):
    """The solve core for one static kernel config, with a custom vmap rule.

    The single-instance path folds ``allowed`` into the feasibility plane
    and runs :func:`_solve` exactly as before.  Under ``jax.vmap`` the
    rule fires instead and routes ALL mapped instances through ONE
    :func:`dp_forward_pallas_batched` launch: the shared (E, C)
    feasibility plane stays an unbatched constant (vmapping the fold
    would materialize B per-instance copies of it), per-instance
    eligibility rides the (B, E) ``allowed`` rows, and when the tiling is
    auto it re-resolves for the batch via ``choose_tiling(batch=B)``.
    Cached per static config so repeated solver calls reuse one
    ``custom_vmap`` object and its jit traces."""

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

        if auto_tiling:
            bb, be, bs, bc = choose_tiling(
                s_cap + 1, n_states, n_edges, u_max, off_max, batch=B)
        else:
            be, bs, bc = block_e, block_s, block_c
            if bc is not None and be is None:
                # a forced per-edge-scan tiling has no batched pipeline
                # (re-streaming the plane per edge gains nothing from a
                # shared launch) — run the instances sequentially, one
                # trace, bit-exact by construction
                outs = jax.lax.map(
                    lambda t: plain(t[0], t[1], t[2], t[3], feasible,
                                    offsets), (ups, sig, sl, alw))
                return outs, (True, True, True)
            bb = 1 if bc is not None else choose_tiling(
                s_cap + 1, n_states, n_edges, u_max, off_max, batch=B)[0]
        outs = _solve_batched(
            ups, sig, alw, feasible, offsets, sl,
            s_cap=s_cap, u_max=u_max, off_max=off_max,
            full_state=full_state, interpret=interpret, block_b=bb,
            block_c=bc, block_s=bs, block_e=be)
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
    every mapped instance through ONE batched kernel launch (see
    :func:`_vmappable_core`) — callers never need to opt in.
    """
    _check_value_bound(sigma2, tables)
    feas, offs = prepare_tables(tables)
    if u_max is None:
        u_max = s_cap + 1
    _check_u_max(upsilon, int(u_max))
    E = offs.shape[0]
    off_max = int(offs.max()) if E else 0
    auto = block_c == "auto"
    if auto:
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
        resolve_interpret(interpret), block_c, block_s, block_e, auto,
        E, tables.n_states)
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
    """B solves against SHARED tables in ONE kernel launch.

    The explicit batched entry point for callers that already hold
    stacked statistics (``jax.vmap`` of :func:`solve_budgeted_dp_pallas`
    reaches the same kernel through the custom batching rule).

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
        _check_value_bound(np.max(np.asarray(sigma2), axis=0), tables)
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
    launches executes the identical f32 op sequence as one launch, so the
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
        S = self.s_cap + 1
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
        self._v0 = jnp.full((S, tables.n_states), NEG,
                            jnp.float32).at[0, :].set(0.0)
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
        w_rows, bits = jnp.asarray(self._w_rows), jnp.asarray(self._bits)
        full_state = self.tables.full_state
        S = self.s_cap + 1

        @jax.jit
        def select_back(V, decisions, upsilon, s_limit):
            v_row = V[:, full_state]
            s_vals = jnp.arange(S, dtype=jnp.int32)
            ok = (v_row >= 0) & (s_vals <= s_limit)
            score = s_vals.astype(jnp.float32) + jnp.sqrt(
                jnp.maximum(v_row, 0.0))
            s_star = jnp.argmax(jnp.where(ok, score,
                                          -jnp.inf)).astype(jnp.int32)

            def back(carry, x):
                s, cs = carry
                u, off, w, b = x
                word = jax.lax.dynamic_slice(decisions, (w, s, cs),
                                             (1, 1, 1))
                d = (word[0, 0, 0] >> b) & 1
                taken = d > 0
                s = jnp.where(taken, jnp.maximum(s - u, 0), s)
                cs = jnp.where(taken, cs - off, cs)
                return (s, cs), d

            (_, _), x = jax.lax.scan(
                back, (s_star, jnp.int32(full_state)),
                (upsilon, offs, w_rows, bits))
            # contract sanitization: budget-infeasible entries become the
            # CORE int32 sentinel (−2²⁹), not the kernel's f32 one
            row = jnp.where(v_row >= 0, v_row,
                            float(core_dp.NEG)).astype(jnp.int32)
            return x, s_star, row

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
        _check_value_bound(np.asarray(sigma2), self.tables)
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
