"""Polynomial-time dynamic programming (paper Algorithm 2) + oracle knapsack.

The budgeted integer program P4(s,t):  max Σ̂²ᵀx  s.t.  A x ≤ c,  Υ̂ᵀx ≥ s
is solved for *all* s ∈ S(t) at once by one DP over states
(s, remaining-capacity, edge index i) — paper problem P5(s,t,c,i):

    V(s, c', i) = max( V(s, c', i+1),
                       [A_{:,i} ≤ c']·( V(max(s−Υ̂_i,0), c'−A_{:,i}, i+1) + Σ̂²_i ) )

Capacity vectors are encoded as mixed-radix state ids (Π_k (c_k+1) states),
so the per-edge update is a (S × C) plane refresh: a *uniform shift* along s
(Υ̂_i is a per-edge scalar) and — because taking edge e from a feasible state
c always lands on c − offsets[e] — a *uniform shift* along the capacity axis
too. That structure is exactly what `kernels/budgeted_dp` exploits on TPU
(whole plane in VMEM, both shifts = padded dynamic slices, transitions = an
(E,) offset vector instead of an (E, C, C) one-hot; planes too big for
VMEM stream through C-blocked or 2-D S×C-tiled grids — both shifts read
only towards smaller indices, so one halo tile per axis covers them — and
the edge loop fuses into those grids in chunks of `block_e`, so each tile
streams HBM once per chunk instead of once per edge; see
docs/kernel_pipeline.md).
This module is the pure-JAX *reference* backend of the pluggable solver
registry (`core/solvers.py`); the Pallas kernel backend is validated against
`solve_budgeted_dp` by the differential harness in tests/test_solver_equiv.py.

Values are exact int32 (see stats.py for the bounds argument).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["DPTables", "build_tables", "solve_budgeted_dp", "oracle_knapsack",
           "dp_edge_fold", "initial_plane"]

NEG = jnp.int32(-(2**29))  # -inf sentinel; NEG + max Σ̂² never overflows
FNEG = jnp.float32(-1e30)


# eq=False ⇒ identity hash (jit-static-safe)
@dataclasses.dataclass(frozen=True, eq=False)
class DPTables:
    """Static per-instance tables for capacity-state transitions.

    ``offsets`` is the structural fact the TPU kernel is built on: in the
    mixed-radix encoding, serving edge e from any *feasible* state c lands on
    ``next_state[c, e] == c - offsets[e]`` with ``offsets[e] = Σ_k
    A[k,e]·strides[k]`` a per-edge constant (no borrows can occur because
    feasibility means every digit satisfies cap_k ≥ A[k,e]).  That turns the
    per-edge capacity gather into a uniform shift along the state axis, so
    the kernel needs an (E,) int32 vector instead of an (E, C, C) one-hot
    tensor.  ``build_tables`` validates the identity on every feasible pair.
    """

    feasible: np.ndarray  # (n_states, E) bool — A_{:,e} ≤ capacity(state)
    next_state: np.ndarray  # (n_states, E) int32 — state after taking edge e
    n_states: int
    full_state: int  # encoding of the full capacity vector c
    radices: np.ndarray  # (K,) int32 — c_k + 1
    cap_of_state: np.ndarray  # (n_states, K) int32 — decoded capacity vectors
    strides: np.ndarray  # (K,) int64 — mixed-radix strides of the encoding
    offsets: np.ndarray  # (E,) int32 — Σ_k A[k,e]·strides[k] (see above)


def build_tables(A: np.ndarray, c: np.ndarray) -> DPTables:
    """Build the static capacity-state transition tables for one instance.

    Args:
      A: (K, E) int demand matrix — column e is edge e's device
        requirement vector a^e over the K resource types.
      c: (K,) int cluster capacities.

    Returns:
      :class:`DPTables` over the Π_k (c_k + 1) mixed-radix capacity
      states, with the per-edge transition offsets derived AND validated
      (``next_state[c, e] == c - offsets[e]`` is asserted on every
      feasible pair — the structural identity the TPU kernel's uniform
      capacity shift rests on).  Host numpy; build once per instance and
      share across slots/backends (every solver takes ``tables``).
    """
    A = np.asarray(A, dtype=np.int64)
    c = np.asarray(c, dtype=np.int64)
    K, E = A.shape
    radices = (c + 1).astype(np.int64)
    n_states = int(np.prod(radices))

    ids = np.arange(n_states, dtype=np.int64)
    cap = np.zeros((n_states, K), dtype=np.int64)
    rem = ids.copy()
    strides = np.zeros(K, dtype=np.int64)
    stride = 1
    for k in range(K):
        strides[k] = stride
        cap[:, k] = (rem // stride) % radices[k]
        stride *= radices[k]

    feasible = np.all(cap[:, None, :] >= A.T[None, :, :], axis=2)  # (n_states, E)
    nxt_cap = np.maximum(cap[:, None, :] - A.T[None, :, :], 0)  # (n_states, E, K)
    next_state = (nxt_cap * strides[None, None, :]).sum(axis=2)
    next_state = np.where(feasible, next_state, 0).astype(np.int32)

    # per-edge transition offsets: next(c) = c - offset_e on feasible states
    offsets = (A.T * strides[None, :]).sum(axis=1)  # (E,)
    expect = ids[:, None] - offsets[None, :]  # (n_states, E)
    if not np.array_equal(next_state[feasible],
                          expect.astype(np.int32)[feasible]):
        raise AssertionError(
            "mixed-radix offset identity violated: next_state[c, e] != "
            "c - offsets[e] on a feasible pair")

    full_state = int((c * strides).sum())
    assert full_state == n_states - 1
    return DPTables(
        feasible=feasible.astype(bool),
        next_state=next_state,
        n_states=n_states,
        full_state=full_state,
        radices=radices.astype(np.int32),
        cap_of_state=cap.astype(np.int32),
        strides=strides,
        offsets=offsets.astype(np.int32),
    )


def dp_edge_fold(V, ups, sig, feas_col, next_col, rows):
    """ONE fold step of the layered DP (plane refresh for a single edge).

    The body shared — verbatim — by the reference scan below and the
    warm-resume path (``core.incremental``): identical ops on identical
    int32 inputs is what makes a checkpointed resume bitwise-identical to
    a cold solve.  ``rows`` is ``arange(S)`` (hoisted by callers).
    """
    shifted = V[jnp.maximum(rows - ups, 0), :]  # s' = max(s-Υ̂_e, 0)
    take = jnp.take(shifted, next_col, axis=1) + sig  # capacity gather
    take = jnp.where(feas_col[None, :], take, NEG)
    decision = take > V  # strict ⇒ ties keep x_e=0
    return jnp.maximum(V, take), decision


def initial_plane(s_cap: int, n_states: int):
    """The cold-start DP plane: 0 at s = 0, NEG elsewhere."""
    return jnp.full((s_cap + 1, n_states), NEG, dtype=jnp.int32).at[0, :].set(0)


def _dp_forward(upsilon, sigma2, feasible, next_state, s_cap: int, v0=None):
    """Run the layered DP; returns (V at i=0, decision bits per edge).

    decisions[j] corresponds to edge e = E-1-j (the scan walks i downward).
    ``v0`` optionally seeds the value plane (the carried-plane hook the
    incremental layer resumes from); ``None`` is the cold start.
    """
    S = s_cap + 1
    rows = jnp.arange(S, dtype=jnp.int32)
    if v0 is None:
        v0 = initial_plane(s_cap, feasible.shape[0])

    def body(V, inputs):
        ups, sig, feas_e, next_e = inputs
        return dp_edge_fold(V, ups, sig, feas_e, next_e, rows)

    xs = (upsilon[::-1], sigma2[::-1], feasible[:, ::-1].T, next_state[:, ::-1].T)
    V_final, decisions = jax.lax.scan(body, v0, xs)
    return V_final, decisions


def solve_budgeted_dp(
    upsilon, sigma2, tables: DPTables, s_cap: int, s_limit, allowed=None
):
    """Solve {P4(s,t)}_{s∈S(t)} and apply the s*-selection rule (eq. 17).

    Args:
      upsilon: (E,) int32 scaled means Υ̂(t).
      sigma2:  (E,) int32 scaled variances Σ̂²(t).
      tables:  capacity-state transition tables.
      s_cap:   static bound on s (table height − 1).
      s_limit: dynamic ξ(t)·m — s values beyond it are masked out.
      allowed: optional (E,) bool — edges eligible this slot. P3(t) maximizes
        over Ω(t), which includes arrival constraint (2); masking here is the
        Ω(t)-faithful reading (Alg.-1 Steps 9–16 stay as a safety harness).

    Returns:
      x: (E,) int32 — the Alg.-1 Step-8 solution (before arrival zeroing).
      info: dict with s_star and the DP value row for diagnostics.
    """
    feasible = jnp.asarray(tables.feasible)
    if allowed is not None:
        feasible = feasible & allowed[None, :]
    next_state = jnp.asarray(tables.next_state)
    E = upsilon.shape[0]

    # the esdp.* scopes name the stages as the Pallas backend's do
    with jax.named_scope("esdp.forward"):
        V, decisions = _dp_forward(upsilon, sigma2, feasible, next_state,
                                   s_cap)

    with jax.named_scope("esdp.select"):
        v_row = V[:, tables.full_state]  # (S,)
        s_vals = jnp.arange(s_cap + 1, dtype=jnp.int32)
        # feasible ⇔ value ≥ 0: Σ̂² ≥ 0 so reachable values are
        # non-negative, while NEG-seeded chains stay < 0 for any partial
        # sum < 2²⁹ (same classification the Pallas backend uses — keeps
        # s* bit-identical).
        ok = (v_row >= 0) & (s_vals <= s_limit)
        score = s_vals.astype(jnp.float32) + jnp.sqrt(
            jnp.maximum(v_row, 0).astype(jnp.float32))
        score = jnp.where(ok, score, FNEG)
        s_star = jnp.argmax(score).astype(jnp.int32)

    def back_body(e, carry):
        s, cs, x = carry
        d = decisions[E - 1 - e, s, cs]
        x = x.at[e].set(d.astype(jnp.int32))
        s_new = jnp.maximum(s - upsilon[e], 0)
        cs_new = next_state[cs, e]
        return (jnp.where(d, s_new, s), jnp.where(d, cs_new, cs), x)

    x0 = jnp.zeros(E, dtype=jnp.int32)
    with jax.named_scope("esdp.backtrack"):
        _, _, x = jax.lax.fori_loop(
            0, E, back_body, (s_star, jnp.int32(tables.full_state), x0))
    return x, {"s_star": s_star, "value_row": v_row}


def oracle_knapsack(values, tables: DPTables, take_allowed):
    """Omniscient per-slot optimum: max valuesᵀx s.t. Ax ≤ c, x∈{0,1}^E.

    ``take_allowed`` masks edges of ports with no arrival (constraint (2)).
    Exact DP over capacity states × edges; float32 objective.
    """
    with jax.named_scope("esdp.oracle"):
        return _oracle_knapsack(values, tables, take_allowed)


def _oracle_knapsack(values, tables: DPTables, take_allowed):
    feasible = jnp.asarray(tables.feasible)
    next_state = jnp.asarray(tables.next_state)
    E = values.shape[0]

    V0 = jnp.zeros(tables.n_states, dtype=jnp.float32)

    def body(V, inputs):
        val, allowed, feas_e, next_e = inputs
        take = jnp.take(V, next_e) + val
        take = jnp.where(feas_e & allowed, take, FNEG)
        decision = take > V
        return jnp.maximum(V, take), decision

    xs = (values[::-1], take_allowed[::-1], feasible[:, ::-1].T,
          next_state[:, ::-1].T)
    V, decisions = jax.lax.scan(body, V0, xs)

    def back_body(e, carry):
        cs, x = carry
        d = decisions[E - 1 - e, cs]
        x = x.at[e].set(d.astype(jnp.int32))
        return (jnp.where(d, next_state[cs, e], cs), x)

    _, x = jax.lax.fori_loop(
        0, E, back_body,
        (jnp.int32(tables.full_state), jnp.zeros(E, dtype=jnp.int32)))
    return x, V[tables.full_state]
