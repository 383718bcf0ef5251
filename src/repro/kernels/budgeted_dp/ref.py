"""Pure-jnp oracle for the budgeted-DP kernel (mirrors core/dp._dp_forward
in the kernel's int32 value domain, including the bit-packed decision words
and the offset-encoded capacity transition next(c) = c − offsets[e]).

This oracle is the CONTRACT every kernel tiling must reproduce bit for
bit: whole-plane, C-blocked, and the 2-D (S-tile × C-tile) grid all
compare against the same ``dp_forward_ref`` output — the tiling is an
execution detail, never a numeric one (enforced in tests/test_kernels.py
and the hypothesis sweep in tests/test_solver_equiv.py)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import NEG, packed_words


def dp_forward_ref(upsilon, sigma2, feasible, offsets, v0):
    """Same contract as kernel.dp_forward_pallas, computed with jnp gathers:
    returns (V (S, C) i32, decisions (⌈E/32⌉, S, C) i32 bit-packed).

    The capacity gather clamps c − offsets[e] at 0; clamped reads are
    exactly the states with c < offsets[e], which are infeasible and masked
    to NEG — the same inertness argument the kernel's pad columns rely on.
    """
    E = upsilon.shape[0]
    S, C = v0.shape
    rows = jnp.arange(S)
    cols = jnp.arange(C)

    def body(V, e_rev):
        e = E - 1 - e_rev
        u = upsilon[e]
        off = offsets[e]
        shifted = V[jnp.maximum(rows - u, 0), :]
        take = shifted[:, jnp.maximum(cols - off, 0)] + sigma2[e].astype(
            jnp.int32)
        take = jnp.where(feasible[e][None, :] > 0, take, NEG)
        dec = (take > V).astype(jnp.int32)
        return jnp.maximum(V, take), dec

    V, decs = jax.lax.scan(body, v0.astype(jnp.int32), jnp.arange(E))
    decs = decs[::-1]  # index by edge id
    # pack edge bits into int32 words: bit (e % 32) of word (e // 32)
    W = packed_words(E)
    pad = W * 32 - E
    decs = jnp.concatenate(
        [decs, jnp.zeros((pad, S, C), jnp.int32)], axis=0)
    shifts = jnp.arange(32, dtype=jnp.int32)[None, :, None, None]
    packed = (decs.reshape(W, 32, S, C) << shifts).sum(
        axis=1).astype(jnp.int32)
    return V, packed
