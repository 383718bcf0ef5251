"""Per-kernel allclose sweeps against the pure-jnp oracles."""
import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd.ops import ssd_op
from repro.kernels.ssd.ref import ssd_ref
from repro.core.dp import build_tables, solve_budgeted_dp
from repro.kernels.budgeted_dp.kernel import (
    MAX_BLOCK_E, NEG, VMEM_BUDGET_BYTES, batched_fused_tile_vmem_bytes,
    batched_modeled_hbm_bytes, batched_vmem_bytes,
    c_blocked_tile_vmem_bytes, choose_tiling, dp_forward_pallas,
    dp_forward_pallas_batched, fused_tile_vmem_bytes, modeled_hbm_bytes,
    tiled_vmem_bytes, unblocked_vmem_bytes)
from repro.kernels.budgeted_dp.ops import (prepare_tables,
                                           solve_budgeted_dp_batched,
                                           solve_budgeted_dp_pallas)
from repro.kernels.budgeted_dp.ref import dp_forward_ref


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,KH,hd,causal,window", [
    (2, 256, 4, 4, 64, True, 0),
    (1, 256, 8, 2, 64, True, 0),  # GQA g=4
    (2, 128, 4, 1, 32, True, 0),  # MQA
    (1, 512, 2, 2, 128, True, 128),  # sliding window
    (2, 256, 4, 4, 64, False, 0),  # bidirectional (whisper encoder)
])
def test_flash_attention_matches_ref(B, S, H, KH, hd, causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KH, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KH, hd), dtype)
    scale = 1.0 / np.sqrt(hd)
    got = flash_attention_op(q, k, v, scale=scale, causal=causal,
                             window=window, blk_q=64, blk_k=128)
    want = attention_ref(q, k, v, scale=scale, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_cross_lengths():
    """Sq < Sk (query block at the end of a longer KV) — prefill tail."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64))
    k = jax.random.normal(ks[1], (1, 512, 4, 64))
    v = jax.random.normal(ks[2], (1, 512, 4, 64))
    got = flash_attention_op(q, k, v, scale=0.125, blk_q=64, blk_k=128)
    want = attention_ref(q, k, v, scale=0.125)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# ssd
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,P,N,Q", [
    (2, 128, 2, 32, 16, 32),
    (1, 96, 4, 64, 32, 32),  # S not multiple of Q after pad? 96%32=0
    (2, 80, 2, 32, 16, 32),  # padding path (80 % 32 != 0)
    (1, 256, 2, 64, 64, 64),
])
def test_ssd_matches_ref(B, S, H, P, N, Q, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N), dtype)
    Cm = jax.random.normal(ks[0], (B, S, N), dtype)
    y_got, st_got = ssd_op(x, dt, A, Bm, Cm, chunk=Q)
    y_want, st_want = ssd_ref(x, dt, A, Bm, Cm, chunk=Q)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(y_got), np.asarray(y_want),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(st_got), np.asarray(st_want),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# budgeted_dp
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(6))
def test_budgeted_dp_matches_core(seed):
    rng = np.random.default_rng(seed)
    E, K = int(rng.integers(4, 14)), int(rng.integers(1, 4))
    A = rng.integers(1, 3, (K, E))
    c = rng.integers(1, 4, K)
    A = np.minimum(A, c[:, None])
    ups = rng.integers(0, 9, E)
    sig = rng.integers(1, 5000, E)
    tables = build_tables(A, c)
    s_cap = int(ups.sum())
    x1, i1 = solve_budgeted_dp(jnp.asarray(ups, jnp.int32),
                               jnp.asarray(sig, jnp.int32), tables, s_cap,
                               jnp.int32(s_cap))
    x2, i2 = solve_budgeted_dp_pallas(ups, sig, tables, s_cap, s_cap,
                                      u_max=int(ups.max() + 1))
    assert int(i1["s_star"]) == int(i2["s_star"])
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))


@pytest.mark.parametrize("E", [7, 32, 40])  # 1 word, exact fit, 2 words
def test_budgeted_dp_kernel_packed_decisions_match_ref(E):
    """The kernel's bit-packed (⌈E/32⌉, S, C) i32 decision words equal the
    pure-jnp oracle's, including across the word boundary (bit 31 → sign)."""
    rng = np.random.default_rng(11)
    K = 2
    A = rng.integers(1, 3, (K, E))
    c = rng.integers(1, 3, K)
    A = np.minimum(A, c[:, None])
    ups = rng.integers(0, 5, E).astype(np.int32)
    sig = rng.integers(1, 3000, E).astype(np.int32)
    tables = build_tables(A, c)
    s_cap = int(ups.sum())
    feas, offs = prepare_tables(tables)
    feas, offs = jnp.asarray(feas), jnp.asarray(offs)
    v0 = jnp.full((s_cap + 1, tables.n_states), NEG,
                  jnp.float32).at[0, :].set(0.0)
    V_k, dec_k = dp_forward_pallas(jnp.asarray(ups), jnp.asarray(sig), feas,
                                   offs, v0, n_edges=E,
                                   u_max=int(ups.max() + 1),
                                   off_max=int(offs.max()), interpret=True)
    V_r, dec_r = dp_forward_ref(jnp.asarray(ups), jnp.asarray(sig), feas,
                                offs, v0)
    assert dec_k.shape == ((E + 31) // 32, s_cap + 1, tables.n_states)
    assert dec_k.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(V_k), np.asarray(V_r))
    np.testing.assert_array_equal(np.asarray(dec_k), np.asarray(dec_r))


@pytest.mark.parametrize("tile", ["tight", "padded"])
def test_budgeted_dp_blocked_grid_matches_ref(tile):
    """The C-blocked pipeline (scan over edges × capacity-tile grid, haloed
    left-neighbor loads, C padded to a tile multiple) is bit-exact vs the
    oracle — values and packed decision words.  ``tight`` runs the minimum
    legal tile (= off_max, maximum tile count); ``padded`` a tile width that
    does not divide C, exercising the pad-state masking."""
    rng = np.random.default_rng(13)
    E, K = 14, 3
    A = rng.integers(1, 3, (K, E))
    c = rng.integers(1, 4, K)
    A = np.minimum(A, c[:, None])
    ups = rng.integers(0, 5, E).astype(np.int32)
    sig = rng.integers(1, 3000, E).astype(np.int32)
    tables = build_tables(A, c)
    s_cap = int(ups.sum())
    feas, offs = prepare_tables(tables)
    feas, offs = jnp.asarray(feas), jnp.asarray(offs)
    off_max = int(offs.max())
    block_c = off_max if tile == "tight" else off_max + 3
    v0 = jnp.full((s_cap + 1, tables.n_states), NEG,
                  jnp.float32).at[0, :].set(0.0)
    V_b, dec_b = dp_forward_pallas(
        jnp.asarray(ups), jnp.asarray(sig), feas, offs, v0, n_edges=E,
        u_max=int(ups.max() + 1), off_max=off_max, interpret=True,
        block_c=block_c)
    V_r, dec_r = dp_forward_ref(jnp.asarray(ups), jnp.asarray(sig), feas,
                                offs, v0)
    np.testing.assert_array_equal(np.asarray(V_b), np.asarray(V_r))
    np.testing.assert_array_equal(np.asarray(dec_b), np.asarray(dec_r))


def _tiling_problem(seed=13, E=14, K=3):
    rng = np.random.default_rng(seed)
    A = rng.integers(1, 3, (K, E))
    c = rng.integers(1, 4, K)
    A = np.minimum(A, c[:, None])
    ups = rng.integers(0, 5, E).astype(np.int32)
    sig = rng.integers(1, 3000, E).astype(np.int32)
    return A, c, ups, sig


@pytest.mark.parametrize("tile", ["tight", "padded", "full_c", "single_s"])
def test_budgeted_dp_s_tiled_grid_matches_ref(tile):
    """The 2-D (S-tile × C-tile) pipeline is bit-exact vs the oracle —
    values and packed decision words — across tile geometries: ``tight``
    runs the minimum legal pair (block_s = u_max, block_c = off_max:
    maximum tile counts, every read crosses a halo); ``padded`` tile
    widths that divide neither S nor C (pad-row/pad-state masking);
    ``full_c`` a single full-width capacity tile (S-only tiling);
    ``single_s`` one S tile spanning the padded plane (the 2-D kernel's
    clamp-row branch on every tile)."""
    A, c, ups, sig = _tiling_problem()
    tables = build_tables(A, c)
    s_cap = int(ups.sum())
    S, C = s_cap + 1, tables.n_states
    feas, offs = prepare_tables(tables)
    feas, offs = jnp.asarray(feas), jnp.asarray(offs)
    off_max = int(offs.max())
    u_max = int(ups.max() + 1)
    block_s, block_c = {
        "tight": (u_max, off_max),
        "padded": (u_max + 2, off_max + 3),
        "full_c": (u_max + 1, C),
        "single_s": (S + 3, off_max),
    }[tile]
    v0 = jnp.full((S, C), NEG, jnp.float32).at[0, :].set(0.0)
    V_t, dec_t = dp_forward_pallas(
        jnp.asarray(ups), jnp.asarray(sig), feas, offs, v0, n_edges=len(ups),
        u_max=u_max, off_max=off_max, interpret=True,
        block_c=block_c, block_s=block_s)
    V_r, dec_r = dp_forward_ref(jnp.asarray(ups), jnp.asarray(sig), feas,
                                offs, v0)
    np.testing.assert_array_equal(np.asarray(V_t), np.asarray(V_r))
    np.testing.assert_array_equal(np.asarray(dec_t), np.asarray(dec_r))


def test_budgeted_dp_s_tiled_u_max_halo_edge():
    """u_max == max Υ̂ exactly (the legal minimum): the deepest s-shift
    reads the FIRST halo row of each tile, and block_s == u_max makes the
    halo as tall as the tile itself."""
    A, c, ups, sig = _tiling_problem(seed=17)
    ups[0] = max(int(ups.max()), 1)  # ensure the max is taken
    tables = build_tables(A, c)
    s_cap = int(ups.sum())
    S, C = s_cap + 1, tables.n_states
    feas, offs = prepare_tables(tables)
    feas, offs = jnp.asarray(feas), jnp.asarray(offs)
    u_max = int(ups.max())  # no +1 margin
    v0 = jnp.full((S, C), NEG, jnp.float32).at[0, :].set(0.0)
    V_t, dec_t = dp_forward_pallas(
        jnp.asarray(ups), jnp.asarray(sig), feas, offs, v0, n_edges=len(ups),
        u_max=u_max, off_max=int(offs.max()), interpret=True,
        block_c=int(offs.max()), block_s=u_max)
    V_r, dec_r = dp_forward_ref(jnp.asarray(ups), jnp.asarray(sig), feas,
                                offs, v0)
    np.testing.assert_array_equal(np.asarray(V_t), np.asarray(V_r))
    np.testing.assert_array_equal(np.asarray(dec_t), np.asarray(dec_r))


def test_budgeted_dp_s_tiled_solver_with_allowed_mask():
    """Solver-level S-tiled path: x / s* / value_row match the reference
    backend under an eligibility mask."""
    A, c, ups, sig = _tiling_problem(seed=19)
    rng = np.random.default_rng(19)
    allowed = rng.integers(0, 2, len(ups)).astype(bool)
    allowed[:2] = True
    tables = build_tables(A, c)
    s_cap = int(ups.sum())
    u_max = int(ups.max() + 1)
    x1, i1 = solve_budgeted_dp(jnp.asarray(ups), jnp.asarray(sig), tables,
                               s_cap, jnp.int32(s_cap),
                               allowed=jnp.asarray(allowed))
    x2, i2 = solve_budgeted_dp_pallas(
        ups, sig, tables, s_cap, s_cap, u_max=u_max, allowed=allowed,
        interpret=True, block_c=int(tables.offsets.max()) + 1,
        block_s=u_max + 1)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    assert int(i1["s_star"]) == int(i2["s_star"])
    r1 = np.asarray(i1["value_row"]).astype(np.int64)
    r2 = np.asarray(i2["value_row"])
    np.testing.assert_array_equal(r1 >= 0, r2 >= 0)
    np.testing.assert_array_equal(r1[r1 >= 0], r2[r2 >= 0].astype(np.int64))


def test_budgeted_dp_s_tiled_halo_contract_errors():
    """Tiles thinner than the halos are rejected, and block_s without a
    concrete block_c is a usage error — never a silent wrong answer."""
    A, c, ups, sig = _tiling_problem(seed=23)
    tables = build_tables(A, c)
    s_cap = int(ups.sum())
    feas, offs = prepare_tables(tables)
    feas, offs = jnp.asarray(feas), jnp.asarray(offs)
    off_max = int(offs.max())
    u_max = int(ups.max() + 1)
    v0 = jnp.full((s_cap + 1, tables.n_states), NEG,
                  jnp.float32).at[0, :].set(0.0)
    kwargs = dict(n_edges=len(ups), u_max=u_max, off_max=off_max,
                  interpret=True)
    with pytest.raises(ValueError, match="block_s"):
        dp_forward_pallas(jnp.asarray(ups), jnp.asarray(sig), feas, offs,
                          v0, block_c=off_max, block_s=u_max - 1, **kwargs)
    with pytest.raises(ValueError, match="block_c"):
        dp_forward_pallas(jnp.asarray(ups), jnp.asarray(sig), feas, offs,
                          v0, block_c=None, block_s=u_max, **kwargs)
    # a forced block_s must never be silently overwritten by auto tiling
    with pytest.raises(ValueError, match="auto"):
        solve_budgeted_dp_pallas(ups, sig, tables, s_cap, s_cap,
                                 u_max=u_max, interpret=True,
                                 block_s=u_max)


def test_choose_tiling_decision_table():
    """The tiling chooser: whole-plane when it fits, full-height C blocks
    when they fit, 2-D tiles for long horizons — every returned tiling
    respects the halo floors and the VMEM budget, and every blocked tiling
    carries the largest edge-fused chunk that fits."""
    # paper-default sizes: trivially VMEM-resident (nothing to fuse — the
    # whole-plane kernel already walks edges inside one pallas_call)
    assert choose_tiling(110, 27, 40, 9, 13) == (None, None, None)
    # large C, short S: full-height C-blocking suffices — and because the
    # single-S-row grid keeps no rowh history, the whole edge set fuses
    # even at this plane width
    be, bs, bc = choose_tiling(64, 1 << 16, 16, 8, 100)
    assert bs is None and bc is not None
    assert bc >= 100 and c_blocked_tile_vmem_bytes(64, bc, 8) <= \
        VMEM_BUDGET_BYTES
    assert be == min(16, MAX_BLOCK_E)
    assert fused_tile_vmem_bytes(be, 64, bc, 8, 100, 64, 1 << 16) <= \
        VMEM_BUDGET_BYTES
    # long S with large C: the whole plane and every full-height block
    # are impossible — the 2-D grid is chosen, fused over every edge
    S, C, E, u_max, off_max = 4096, 512, 16, 4, 73
    assert unblocked_vmem_bytes(S, C, E, u_max, off_max) > VMEM_BUDGET_BYTES
    be, bs, bc = choose_tiling(S, C, E, u_max, off_max)
    assert bs is not None and bs >= u_max and bc >= off_max
    assert tiled_vmem_bytes(bs, bc, u_max) <= VMEM_BUDGET_BYTES
    assert be == min(E, MAX_BLOCK_E)  # small histories: whole E fuses
    assert fused_tile_vmem_bytes(be, bs, bc, u_max, off_max, S, C) <= \
        VMEM_BUDGET_BYTES
    # a tighter budget still yields a legal (if smaller) pair
    be2, bs2, bc2 = choose_tiling(S, C, E, u_max, off_max, budget=2 ** 20)
    assert bs2 >= u_max and bc2 >= off_max
    assert bs2 * bc2 <= bs * bc
    assert be2 is None or be2 <= be


def test_fused_hbm_model_cuts_traffic_blockwise():
    """The modeled HBM traffic of the fused pipeline drops ~block_e-fold vs
    the per-edge scan on the same plane tiling — the quantity dp_bench
    records as ``hbm_bytes_streamed`` and the point of the fusion."""
    S, C, E, u_max, off_max = 4096, 512, 16, 4, 73
    be, bs, bc = choose_tiling(S, C, E, u_max, off_max)
    scan = modeled_hbm_bytes(S, C, E, u_max, off_max, None, bs, bc)
    fused = modeled_hbm_bytes(S, C, E, u_max, off_max, be, bs, bc)
    assert fused * 4 <= scan  # the PR-5 acceptance bound
    # whole-plane streams everything exactly once and is the floor
    whole = modeled_hbm_bytes(S, C, E, u_max, off_max, None, None, None)
    assert whole < fused < scan


@pytest.mark.parametrize("block_e", [1, 3, 14, 32])
@pytest.mark.parametrize("tile", ["tight", "padded", "full_c", "single_s"])
def test_budgeted_dp_fused_grid_matches_ref(tile, block_e):
    """The edge-fused pipeline — chunks of block_e consecutive edges per
    pallas_call, tiles resident across the chunk, halos refreshed from the
    persistent history scratches — is bit-exact vs the oracle on values AND
    packed decision words, across every tile geometry of the unfused sweep
    and block_e ∈ {1 (scan-equivalent), 3 (does not divide E=14 — ragged
    inert-padded last chunk), 14 (one single chunk), 32 (the in-word
    packing cap, > E)}."""
    A, c, ups, sig = _tiling_problem()
    tables = build_tables(A, c)
    s_cap = int(ups.sum())
    S, C = s_cap + 1, tables.n_states
    feas, offs = prepare_tables(tables)
    feas, offs = jnp.asarray(feas), jnp.asarray(offs)
    off_max = int(offs.max())
    u_max = int(ups.max() + 1)
    block_s, block_c = {
        "tight": (u_max, off_max),
        "padded": (u_max + 2, off_max + 3),
        "full_c": (u_max + 1, C),
        "single_s": (None, off_max),
    }[tile]
    v0 = jnp.full((S, C), NEG, jnp.float32).at[0, :].set(0.0)
    V_f, dec_f = dp_forward_pallas(
        jnp.asarray(ups), jnp.asarray(sig), feas, offs, v0, n_edges=len(ups),
        u_max=u_max, off_max=off_max, interpret=True,
        block_c=block_c, block_s=block_s, block_e=block_e)
    V_r, dec_r = dp_forward_ref(jnp.asarray(ups), jnp.asarray(sig), feas,
                                offs, v0)
    np.testing.assert_array_equal(np.asarray(V_f), np.asarray(V_r))
    np.testing.assert_array_equal(np.asarray(dec_f), np.asarray(dec_r))


@pytest.mark.parametrize("E", [33, 40])
def test_budgeted_dp_fused_chunks_straddle_word_boundary(E):
    """block_e=5 never divides 32, so with E > 32 some chunk's edges span
    BOTH int32 decision words — the per-chunk word masks must route each
    bit into the right packed word (including bit 31 → the sign bit)."""
    rng = np.random.default_rng(29)
    K = 2
    A = rng.integers(1, 3, (K, E))
    c = rng.integers(1, 3, K)
    A = np.minimum(A, c[:, None])
    ups = rng.integers(0, 4, E).astype(np.int32)
    sig = rng.integers(1, 3000, E).astype(np.int32)
    tables = build_tables(A, c)
    s_cap = int(ups.sum())
    feas, offs = prepare_tables(tables)
    feas, offs = jnp.asarray(feas), jnp.asarray(offs)
    off_max = int(offs.max())
    u_max = int(ups.max() + 1)
    v0 = jnp.full((s_cap + 1, tables.n_states), NEG,
                  jnp.float32).at[0, :].set(0.0)
    V_f, dec_f = dp_forward_pallas(
        jnp.asarray(ups), jnp.asarray(sig), feas, offs, v0, n_edges=E,
        u_max=u_max, off_max=off_max, interpret=True,
        block_c=off_max + 1, block_s=u_max + 2, block_e=5)
    V_r, dec_r = dp_forward_ref(jnp.asarray(ups), jnp.asarray(sig), feas,
                                offs, v0)
    assert dec_f.shape[0] == (E + 31) // 32 >= 2
    np.testing.assert_array_equal(np.asarray(V_f), np.asarray(V_r))
    np.testing.assert_array_equal(np.asarray(dec_f), np.asarray(dec_r))


def test_budgeted_dp_fused_whole_chunk_masked():
    """An ``allowed`` mask can zero EVERY edge of a fused chunk: the chunk
    must be a no-op (the inert-edge argument the ragged pad also relies
    on) and the solver must still match the reference bit for bit."""
    A, c, ups, sig = _tiling_problem(seed=31, E=12)
    allowed = np.ones(12, bool)
    allowed[4:8] = False  # chunk [4, 8) fully masked
    tables = build_tables(A, c)
    s_cap = int(ups.sum())
    u_max = int(ups.max() + 1)
    x1, i1 = solve_budgeted_dp(jnp.asarray(ups), jnp.asarray(sig), tables,
                               s_cap, jnp.int32(s_cap),
                               allowed=jnp.asarray(allowed))
    x2, i2 = solve_budgeted_dp_pallas(
        ups, sig, tables, s_cap, s_cap, u_max=u_max, allowed=allowed,
        interpret=True, block_c=int(tables.offsets.max()),
        block_s=u_max, block_e=4)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    assert int(i1["s_star"]) == int(i2["s_star"])
    assert not np.asarray(x2)[4:8].any()


def test_budgeted_dp_fused_u_max_halo_tracks_in_chunk_updates():
    """The up-neighbor halo must be the neighbor's value at each
    INTERMEDIATE edge of the chunk, not its final value: with every Υ̂ > 0
    and block_s = u_max every edge's s-shift crosses the tile boundary
    into rows the upstream tile updated EARLIER IN THE SAME CHUNK, so a
    stale (initial or final) halo would corrupt values.  Exact-bound
    u_max (no +1 margin) makes the deepest shift read the first history
    row."""
    rng = np.random.default_rng(37)
    E, K = 10, 2
    A = rng.integers(1, 3, (K, E))
    c = rng.integers(2, 4, K)
    A = np.minimum(A, c[:, None])
    ups = rng.integers(1, 4, E).astype(np.int32)  # strictly positive
    sig = rng.integers(1, 3000, E).astype(np.int32)
    tables = build_tables(A, c)
    s_cap = int(ups.sum())
    feas, offs = prepare_tables(tables)
    feas, offs = jnp.asarray(feas), jnp.asarray(offs)
    u_max = int(ups.max())  # exact bound, no margin
    off_max = int(offs.max())
    v0 = jnp.full((s_cap + 1, tables.n_states), NEG,
                  jnp.float32).at[0, :].set(0.0)
    V_f, dec_f = dp_forward_pallas(
        jnp.asarray(ups), jnp.asarray(sig), feas, offs, v0, n_edges=E,
        u_max=u_max, off_max=off_max, interpret=True,
        block_c=off_max, block_s=u_max, block_e=E)  # one chunk, all edges
    V_r, dec_r = dp_forward_ref(jnp.asarray(ups), jnp.asarray(sig), feas,
                                offs, v0)
    np.testing.assert_array_equal(np.asarray(V_f), np.asarray(V_r))
    np.testing.assert_array_equal(np.asarray(dec_f), np.asarray(dec_r))


def test_budgeted_dp_fused_contract_errors():
    """block_e outside [1, 32] and block_e without a concrete block_c are
    usage errors — never a silent wrong answer."""
    A, c, ups, sig = _tiling_problem(seed=23)
    tables = build_tables(A, c)
    s_cap = int(ups.sum())
    feas, offs = prepare_tables(tables)
    feas, offs = jnp.asarray(feas), jnp.asarray(offs)
    off_max = int(offs.max())
    u_max = int(ups.max() + 1)
    v0 = jnp.full((s_cap + 1, tables.n_states), NEG,
                  jnp.float32).at[0, :].set(0.0)
    kwargs = dict(n_edges=len(ups), u_max=u_max, off_max=off_max,
                  interpret=True)
    with pytest.raises(ValueError, match="block_e"):
        dp_forward_pallas(jnp.asarray(ups), jnp.asarray(sig), feas, offs,
                          v0, block_c=off_max, block_e=MAX_BLOCK_E + 1,
                          **kwargs)
    with pytest.raises(ValueError, match="block_e"):
        dp_forward_pallas(jnp.asarray(ups), jnp.asarray(sig), feas, offs,
                          v0, block_c=off_max, block_e=0, **kwargs)
    with pytest.raises(ValueError, match="block_e"):
        dp_forward_pallas(jnp.asarray(ups), jnp.asarray(sig), feas, offs,
                          v0, block_c=None, block_e=4, **kwargs)
    # a forced block_e must never be silently overwritten by auto tiling
    with pytest.raises(ValueError, match="auto"):
        solve_budgeted_dp_pallas(ups, sig, tables, s_cap, s_cap,
                                 u_max=u_max, interpret=True, block_e=4)


# ---------------------------------------------------------------------------
# fleet-batched budgeted_dp (B solves per launch)
# ---------------------------------------------------------------------------

def _iter_eqns(jaxpr):
    """Walk every equation of a jaxpr, descending into nested call/scan/
    cond jaxprs wherever they hide in the params."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            vals = val if isinstance(val, (tuple, list)) else (val,)
            for v in vals:
                if isinstance(v, jex_core.ClosedJaxpr):
                    yield from _iter_eqns(v.jaxpr)
                elif isinstance(v, jex_core.Jaxpr):
                    yield from _iter_eqns(v)


def _pallas_calls(jaxpr):
    return [e for e in _iter_eqns(jaxpr) if e.primitive.name == "pallas_call"]


def _lanes(C):
    return -(-C // 128) * 128


def test_batched_vmap_emits_single_launch_with_shared_tables():
    """jax.vmap of the pallas solve at B=32 lowers to EXACTLY ONE
    pallas_call, and that launch's operands carry the (E, C) feasibility
    plane UNBATCHED — never a replicated (B, E, C) copy.  This is the
    launch-count contract of the fleet-batched megakernel: sharing the
    tables, not stacking the launches."""
    A, c, ups1, sig1 = _tiling_problem()
    E = len(ups1)
    tables = build_tables(A, c)
    C = tables.n_states
    B, s_cap, u_max = 32, int(ups1.sum()), int(ups1.max() + 1)
    rng = np.random.default_rng(41)
    ups = np.broadcast_to(ups1, (B, E)) + 0
    sig = rng.integers(1, 3000, (B, E)).astype(np.int32)
    alw = rng.integers(0, 2, (B, E)).astype(np.int32)
    slim = rng.integers(0, s_cap + 1, B).astype(np.int32)

    def one(u, s, l, a):
        return solve_budgeted_dp_pallas(u, s, tables, s_cap, l, u_max=u_max,
                                        allowed=a, interpret=True)[0]

    jaxpr = jax.make_jaxpr(jax.vmap(one))(
        jnp.asarray(ups), jnp.asarray(sig), jnp.asarray(slim),
        jnp.asarray(alw))
    calls = _pallas_calls(jaxpr.jaxpr)
    assert len(calls) == 1
    shapes = [tuple(v.aval.shape) for v in calls[0].invars]
    Cp = _lanes(C)  # the kernel's plane is padded to whole 128-lane tiles
    assert (E, Cp) in shapes  # feasibility plane, shared
    assert (B, E, Cp) not in shapes  # never replicated per seed
    assert (B, 1, E) in shapes  # per-instance statistics (SMEM rows)


def test_simulate_batch_one_launch_per_slot():
    """The whole batched simulation — vmapped horizon scan over a seed
    batch — contains exactly ONE pallas_call in its jaxpr: the scan body
    solves every seed's slot in one fleet-batched launch (a conventional
    vmap of the kernel would still show one call; a per-seed unroll or a
    replicated-operand lowering would show more, or batched tables)."""
    from repro.core import env as env_mod
    from repro.core import generate_instance, make_esdp_policy

    inst = generate_instance(seed=3, n_ports=4, n_servers=10, edge_prob=0.3)
    tables = build_tables(inst.A, inst.c)
    T, B = 12, 32
    policy = make_esdp_policy(inst, T, tables=tables,
                              solver="pallas_interpret")
    tables_, scenario, params = env_mod._scenario_args(inst, tables, None)
    keys = jnp.stack([jax.random.PRNGKey(s) for s in range(B)])
    jaxpr = jax.make_jaxpr(
        lambda arrays, ks, ps: env_mod._run_batch(
            policy, T, tables_, scenario, inst.n_servers, arrays, ks, ps))(
        env_mod._instance_arrays(inst), keys, params)
    calls = _pallas_calls(jaxpr.jaxpr)
    assert len(calls) == 1
    E, Cp = inst.n_edges, _lanes(tables.n_states)
    shapes = [tuple(v.aval.shape) for v in calls[0].invars]
    assert (E, Cp) in shapes and (B, E, Cp) not in shapes


def test_choose_tiling_batched_decision_table():
    """The 4-tuple chooser: the BATCH axis shrinks before the plane ever
    tiles — full fleet per step when it fits, the largest power-of-two
    sub-fleet when it doesn't, and only when even one instance's plane
    overflows does the tiling fall back to the 3-tuple rule with block_b
    pinned to 1 (batch as the fused pipeline's outermost grid dim)."""
    # paper-default sizes: the whole 32-fleet fits in one grid step
    assert choose_tiling(110, 27, 40, 9, 13, batch=32) == \
        (32, None, None, None)
    assert batched_vmem_bytes(110, 27, 40, 9, 13, 32) <= VMEM_BUDGET_BYTES
    # a degenerate fleet of one stays on the whole-plane kernel
    assert choose_tiling(110, 27, 40, 9, 13, batch=1) == \
        (1, None, None, None)
    # taller planes: the fleet splits (8, then 4, then 1 per step) while
    # every instance's plane stays whole — batch shrinks FIRST
    for S, bb_want in ((128, 8), (256, 4), (512, 1)):
        bb, be, bs, bc = choose_tiling(S, 512, 16, 4, 73, batch=32)
        assert (bb, be, bs, bc) == (bb_want, None, None, None)
        assert batched_vmem_bytes(S, 512, 16, 4, 73, bb) <= \
            VMEM_BUDGET_BYTES
        if bb < 32:  # the next-larger fleet is what overflowed
            assert batched_vmem_bytes(S, 512, 16, 4, 73, 2 * bb) > \
                VMEM_BUDGET_BYTES
    # long horizon: even block_b=1 overflows whole-plane → the plane
    # tiles exactly as the single-instance rule says, block_b pinned to 1
    S, C, E, u_max, off_max = 4096, 512, 16, 4, 73
    assert batched_vmem_bytes(S, C, E, u_max, off_max, 1) > \
        VMEM_BUDGET_BYTES
    four = choose_tiling(S, C, E, u_max, off_max, batch=32)
    assert four == (1,) + choose_tiling(S, C, E, u_max, off_max)
    _, be, bs, bc = four
    assert batched_fused_tile_vmem_bytes(be, bs, bc, u_max, off_max, S, C,
                                         1) <= VMEM_BUDGET_BYTES
    with pytest.raises(ValueError, match="batch"):
        choose_tiling(110, 27, 40, 9, 13, batch=0)


def test_batched_modeled_hbm_shares_tables_once():
    """The batched traffic model: shared operands stream once, so B
    batched solves always model strictly under B× the single-solve
    traffic, and the saving is exactly the (B−1)-fold shared-operand
    re-stream a vmapped-single-launch lowering would pay."""
    for (S, C, E, u_max, off_max), (be, bs, bc) in (
            ((110, 27, 40, 9, 13), (None, None, None)),
            ((4096, 512, 16, 4, 73), choose_tiling(4096, 512, 16, 4, 73))):
        one = modeled_hbm_bytes(S, C, E, u_max, off_max, be, bs, bc)
        for B in (8, 64):
            batched = batched_modeled_hbm_bytes(S, C, E, u_max, off_max, B,
                                                be, bs, bc)
            vmapped = B * one
            assert batched < vmapped
            shared = vmapped - batched
            assert shared % (B - 1) == 0  # (B−1) shared re-streams saved
        assert batched_modeled_hbm_bytes(S, C, E, u_max, off_max, 1,
                                         be, bs, bc) == one


def test_batched_contract_errors():
    """Every illegal batched configuration is a loud ValueError — block_b
    outside [1, B], a forced block under auto tiling, the fused pipeline
    with block_b ≠ 1, and the per-edge-scan tilings that gain nothing
    from sharing a launch — never a silent wrong answer."""
    A, c, ups1, sig1 = _tiling_problem(seed=23)
    E = len(ups1)
    tables = build_tables(A, c)
    s_cap = int(ups1.sum())
    feas, offs = prepare_tables(tables)
    feas, offs = jnp.asarray(feas), jnp.asarray(offs)
    off_max = int(offs.max())
    u_max = int(ups1.max() + 1)
    B = 4
    ups = jnp.broadcast_to(jnp.asarray(ups1), (B, E))
    sig = jnp.broadcast_to(jnp.asarray(sig1), (B, E))
    alw = jnp.ones((B, E), jnp.int32)
    v0 = jnp.full((s_cap + 1, tables.n_states), NEG,
                  jnp.float32).at[0, :].set(0.0)
    kwargs = dict(n_edges=E, u_max=u_max, off_max=off_max, interpret=True)
    for bad_bb in (0, B + 1):
        with pytest.raises(ValueError, match="block_b"):
            dp_forward_pallas_batched(ups, sig, alw, feas, offs, v0,
                                      block_b=bad_bb, **kwargs)
    # fused pipeline: batch is the outermost grid dim, one instance/step
    with pytest.raises(ValueError, match="block_b"):
        dp_forward_pallas_batched(ups, sig, alw, feas, offs, v0, block_b=2,
                                  block_c=off_max, block_e=4, **kwargs)
    # per-edge-scan tilings don't share anything worth batching
    with pytest.raises(ValueError, match="block_e"):
        dp_forward_pallas_batched(ups, sig, alw, feas, offs, v0,
                                  block_c=off_max, **kwargs)
    with pytest.raises(ValueError, match="block_c"):
        dp_forward_pallas_batched(ups, sig, alw, feas, offs, v0,
                                  block_s=u_max, **kwargs)
    # a forced block must never be silently overwritten by auto tiling
    with pytest.raises(ValueError, match="auto"):
        solve_budgeted_dp_batched(ups, sig, tables, s_cap, s_cap,
                                  u_max=u_max, interpret=True, block_b=2)
    with pytest.raises(ValueError, match="block_b"):
        solve_budgeted_dp_batched(ups, sig, tables, s_cap, s_cap,
                                  u_max=u_max, interpret=True,
                                  block_b=B + 1, block_c=None)


def test_batched_ragged_pad_instances_inert():
    """B=5 under block_b=2 pads the grid to 6 instances: the pad rides
    ``allowed ≡ 0`` and must be INERT — and the same argument makes a
    real all-masked instance return the untouched v0 plane and zero
    decision words, which we check directly."""
    A, c, ups1, sig1 = _tiling_problem(seed=43, E=10)
    E = len(ups1)
    tables = build_tables(A, c)
    s_cap = int(ups1.sum())
    S, C = s_cap + 1, tables.n_states
    u_max = int(ups1.max() + 1)
    rng = np.random.default_rng(43)
    B = 5
    ups = rng.integers(0, u_max, (B, E)).astype(np.int32)
    sig = rng.integers(1, 3000, (B, E)).astype(np.int32)
    alw = rng.integers(0, 2, (B, E)).astype(np.int32)
    alw[3] = 0  # a real all-masked instance
    slim = rng.integers(0, s_cap + 1, B).astype(np.int32)
    x, info = solve_budgeted_dp_batched(ups, sig, tables, s_cap, slim,
                                        u_max=u_max, allowed=alw,
                                        interpret=True, block_b=2,
                                        block_c=None)
    assert x.shape == (B, E)  # pad instances dropped
    for b in range(B):
        xr, ir = solve_budgeted_dp(
            jnp.asarray(ups[b]), jnp.asarray(sig[b]), tables, s_cap,
            int(slim[b]), allowed=jnp.asarray(alw[b]))
        np.testing.assert_array_equal(np.asarray(x[b]), np.asarray(xr))
        assert int(info["s_star"][b]) == int(ir["s_star"])
    assert not np.asarray(x[3]).any()
    # the all-masked instance's forward plane is v0, untouched
    feas, offs = prepare_tables(tables)
    v0 = jnp.full((S, C), NEG, jnp.float32).at[0, :].set(0.0)
    V, dec = dp_forward_pallas_batched(
        jnp.asarray(ups), jnp.asarray(sig), jnp.asarray(alw),
        jnp.asarray(feas), jnp.asarray(offs), v0, n_edges=E, u_max=u_max,
        off_max=int(offs.max()), interpret=True, block_b=2)
    np.testing.assert_array_equal(np.asarray(V[3]), np.asarray(v0))
    assert not np.asarray(dec[3]).any()


def test_budgeted_dp_value_rows_share_feasibility_contract():
    """Normalized value rows agree across backends: same feasibility mask
    (value ≥ 0) and identical values on it, despite different NEG sentinels."""
    rng = np.random.default_rng(12)
    E, K = 12, 2
    A = rng.integers(1, 3, (K, E))
    c = rng.integers(1, 4, K)
    A = np.minimum(A, c[:, None])
    ups = rng.integers(0, 6, E)
    sig = rng.integers(1, 5000, E)
    tables = build_tables(A, c)
    s_cap = int(ups.sum())
    _, i1 = solve_budgeted_dp(jnp.asarray(ups, jnp.int32),
                              jnp.asarray(sig, jnp.int32), tables, s_cap,
                              jnp.int32(s_cap))
    _, i2 = solve_budgeted_dp_pallas(ups, sig, tables, s_cap, s_cap,
                                     interpret=True)
    r1 = np.asarray(i1["value_row"]).astype(np.int64)
    r2 = np.asarray(i2["value_row"]).astype(np.int64)
    np.testing.assert_array_equal(r1 >= 0, r2 >= 0)
    np.testing.assert_array_equal(r1[r1 >= 0], r2[r2 >= 0])


def test_budgeted_dp_with_arrival_mask():
    rng = np.random.default_rng(7)
    E, K = 10, 2
    A = rng.integers(1, 3, (K, E))
    c = rng.integers(2, 4, K)
    A = np.minimum(A, c[:, None])
    ups = rng.integers(0, 6, E)
    sig = rng.integers(1, 900, E)
    allowed = rng.integers(0, 2, E).astype(bool)
    tables = build_tables(A, c)
    s_cap = int(ups.sum())
    x1, i1 = solve_budgeted_dp(jnp.asarray(ups, jnp.int32),
                               jnp.asarray(sig, jnp.int32), tables, s_cap,
                               jnp.int32(s_cap), allowed=jnp.asarray(allowed))
    x2, i2 = solve_budgeted_dp_pallas(ups, sig, tables, s_cap, s_cap,
                                      u_max=int(ups.max() + 1),
                                      allowed=allowed)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    assert np.all(np.asarray(x2) <= allowed.astype(int))
