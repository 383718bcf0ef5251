"""Pallas TPU kernel for the ESDP budgeted DP (paper Algorithm 2).

TPU-native design (DESIGN.md §4):
  * the whole (S × C) value plane lives in VMEM, padded to whole (8, 128)
    vreg tiles (S to a multiple of 8 sublanes, C to a multiple of 128
    lanes) so every vector op and every shift acts on native tiles;
  * the edge loop runs INSIDE one pallas_call via fori_loop;
  * BOTH per-edge gathers are uniform shifts done in registers with
    ``pltpu.roll`` by the edge's dynamic distance, plus a mask — no gather
    op, no matmul, and no unaligned dynamic slice (the TPU compiler only
    accepts dynamic sublane/lane offsets it can prove tile-aligned):
      - the capacity transition next(c) = c − offset_e (the mixed-radix
        offset identity validated in core.dp.build_tables) rolls the plane
        right along the state (lane) axis; columns c < offset_e wrap
        around, and those are exactly the states that cannot take edge e —
        infeasible and masked to NEG;
      - the s-shift V[max(s−Υ_e, 0)] rolls the lane-shifted plane down the
        budget (sublane) axis, and rows s < Υ_e (which wrapped) take the
        clamp row V[0] instead.
    The former (E, C, C) one-hot transition operand — 4·E·C² bytes and an
    O(S·C²) MXU matmul per edge — is an (E,) int32 offset vector and an
    O(S·C) VPU update, which is what lets large capacity spaces fit VMEM;
  * backtrack decisions are BIT-PACKED into int32 lanes: word ⌊e/32⌋ of the
    (⌈E/32⌉, S, C) output holds bit (e mod 32) for edge e.  At production
    sizes the unpacked (E, S, C) f32 tensor dominated VMEM (E=64, S=512,
    C=256 ⇒ 32 MB — over the ~16 MB/core budget); packing is 32× smaller.
  * per-edge scalars (Υ̂, Σ̂², offsets, eligibility) live in SMEM and are
    read one element at a time.

When even the (S, C) value plane outgrows VMEM, ``block_c`` switches to a
C-BLOCKED pipeline: a lax.scan over edges, each edge one pallas_call gridded
over capacity tiles.  The offset shift only ever reads LEFT (towards smaller
state ids), so a tile plus its left neighbor — a haloed block load expressed
as two BlockSpec views of the same plane, legal because block_c ≥ OFF_MAX —
covers every read, and the plane streams HBM↔VMEM one (S, block_c) tile at
a time.  Functional double-buffering (the per-edge call maps V → V′) keeps
the pipeline free of in-place aliasing hazards.

Long horizons additionally tile the BUDGET axis: ``block_s`` extends the
pipeline to a 2-D (S-tile × C-tile) grid.  The s-shift only ever reads UP
(towards smaller budgets, by at most u_max ≤ block_s rows), so each tile
needs an up-neighbor halo of u_max rows on top of the left-neighbor halo —
four BlockSpec views of the same plane per grid step ((i−1, j−1), (i−1, j),
(i, j−1), (i, j)).  Both shifts roll the tile and its halo by the same
distance and splice them with a mask (:func:`_lane_shift`,
:func:`_row_shift`).  Tile row 0 has no up neighbor and replicates the
plane's clamp row V[0] instead, exactly like the whole-plane kernel.
Per-tile VMEM is then independent of BOTH plane extents, which is what lets
S ≳ 4096 with large C run at all; ``choose_tiling`` picks the largest
(block_s, block_c) pair that fits the VMEM budget.

``block_e`` FUSES the edge loop into that grid — a temporal blocking of
the DP recurrence.  The per-edge-scan pipelines above re-stream the whole
value plane HBM↔VMEM once per edge; the fused pipeline runs one
pallas_call per chunk of ``block_e`` consecutive edges, and each tile stays
VMEM-resident across the whole chunk, cutting plane traffic
``block_e``-fold.  The price is the halo: by the time tile (i, j) runs,
its up/left neighbors have already advanced through ALL ``block_e`` edges
of the chunk, so their boundary values at each *intermediate* edge must be
preserved.  Two persistent VMEM scratch buffers carry exactly that history
across grid steps (the TPU grid is sequential, and scratch survives grid
iterations):

  * ``lefth`` — (block_e, block_s, LW): the last LW columns of the
    previous C-tile *before* each edge of the chunk, LW = off_max rounded
    up to whole lanes (≤ block_c).  Each tile reads its left halo for edge
    k from ``lefth[k]``, then overwrites it with its own pre-edge-k
    boundary for the next tile (read-then-write within one grid step, so a
    single buffer suffices along C);
  * ``rowh`` — (2 × block_e, UH, C_padded): the bottom UH rows (u_max
    rounded up to whole sublanes, ≤ block_s) of every tile of the previous
    S-row, per edge, double-banked by S-row parity — tile (i, j) reads bank
    (i−1) mod 2 (up halo at columns of tiles j−1 and j, the j−1 part being
    the up-left corner) and writes bank i mod 2, so row i's writes never
    clobber the corner history row i+1 still needs.

Every chunk owns exactly ONE packed decision word: each word's edges are
padded up to whole chunks with inert edges (:func:`_chunk_layout`), so a
chunk never straddles two words whatever ``block_e`` is, and its bits
(global edge id mod 32) are distinct because block_e ≤ 32.  The scan's
flat (⌈E/32⌉·S, C) decision carry is an operand of each chunk's
pallas_call, and its BlockSpec picks tile (w·S/block_s + i, j) of the
chunk's word w, a scalar-prefetched index: the tile starts from the word's
bits so far and each edge ORs its decisions into it, in VMEM.  The word
plane and the value plane are both aliased input to output
(``input_output_aliases``), so a chunk reads and writes one value plane
and one word plane in place and no XLA op touches the carry between
launches.  Aliasing is safe here, unlike for the per-edge-scan kernels
(which read neighbor tiles of the plane they write, and therefore map
V → V′ functionally): grid step (i, j) reads and writes only its own (i,
j) tile of V and of the word plane, and every halo comes from the
``rowh``/``lefth`` VMEM scratches, never from a neighbor tile in HBM.
The pipeline's prefetch of step t+1's tiles therefore never meets a tile
that a step ≤ t has written.

``dp_forward_pallas_batched`` runs a FLEET of B independent solves in one
pallas_call.  The batch rides the grid: ``block_b`` instances advance per
grid step (an in-kernel loop over the block's instances), the shared
operands (feasibility plane, offsets, v0) are loaded through index maps
that ignore the batch index — one copy in HBM, never replicated B-fold the
way folding per-instance eligibility into the feasibility plane under
``jax.vmap`` replicates it — and the per-instance inputs (Υ̂, Σ̂²,
``allowed``) stream as (block_b, 1, E) SMEM rows.  The single-instance
whole-plane solve is this kernel at B = 1.  Ragged batches pad with inert
instances (``allowed ≡ 0`` masks every edge to NEG, so the pads compute v0
and zero decisions).  A fleet of tiled planes maps the single-instance
solve over its instances (``jax.lax.map``), each with its ``allowed`` row
folded into the feasibility plane.  ``choose_tiling(..., batch=B)``
resolves the whole (block_b, block_e, block_s, block_c) split, shrinking
the batch axis BEFORE the plane axes.

Value planes, the halo scratches and the per-edge gains are int32, as in
``core.dp``, with the sentinel ``NEG = core.dp.NEG = −2²⁹``: every value is
exact as long as every reachable sum stays below |NEG| (then a NEG-seeded
chain stays negative and never overflows).  ``ops.check_value_bound``
enforces that bound on concrete statistics, and ``DispatchEngine`` checks
it once at construction for its whole horizon.

Backend resolution: ``interpret=None`` (the default) compiles on TPU and
falls back to the Pallas interpreter elsewhere — the kernel is never
silently interpreted on real TPU hardware.  Pass an explicit bool to force
either mode (``interpret=True`` is how the differential tests exercise the
kernel logic on CPU CI).  Compiled tiled pipelines need tile-aligned tiles
(block_c a multiple of 128, block_s of 8); ``choose_tiling`` only picks
those, and a forced misaligned tile raises instead of reaching the
compiler.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core import dp as core_dp

__all__ = ["NEG", "VMEM_BUDGET_BYTES", "MAX_BLOCK_E", "resolve_interpret",
           "packed_words", "unblocked_vmem_bytes", "c_blocked_tile_vmem_bytes",
           "tiled_vmem_bytes", "fused_tile_vmem_bytes", "batched_vmem_bytes",
           "modeled_hbm_bytes", "batched_modeled_hbm_bytes", "choose_tiling",
           "dp_forward_pallas", "dp_forward_pallas_batched"]

NEG = int(core_dp.NEG)  # −2²⁹, the int32 sentinel of the reference DP

# share of the TPU's default 16 MiB scoped-VMEM limit left to this kernel's
# modeled footprint; the rest covers what the model does not count (the
# compiler's internal scratch and spilled temporaries)
VMEM_BUDGET_BYTES = 13 * 2 ** 20

# fused chunks pack their decision bits into ONE int32 word-plane, so
# in-chunk bit positions (global edge id mod 32) must be distinct
MAX_BLOCK_E = 32

_SUBLANES, _LANES = 8, 128  # one 32-bit vreg tile


def resolve_interpret(
    interpret: bool | None = None, platform: str | None = None
) -> bool:
    """Resolve the kernel execution mode.

    ``None`` → auto: compiled (``False``) on TPU, interpreter (``True``)
    everywhere else.  ``platform`` overrides ``jax.default_backend()`` so the
    resolution table is unit-testable without the hardware.
    """
    if interpret is not None:
        return bool(interpret)
    platform = platform or jax.default_backend()
    return platform != "tpu"


def packed_words(n_edges: int) -> int:
    """Leading dim of the packed decision tensor: ⌈E/32⌉ int32 words."""
    return (n_edges + 31) // 32


def _pad_to(n: int, unit: int) -> int:
    return -(-n // unit) * unit


def _tile_words(rows: int, cols: int) -> int:
    """4-byte words one 2-D VMEM buffer occupies: the compiler lays it out
    in whole (8, 128) tiles."""
    return _pad_to(max(rows, 1), _SUBLANES) * _pad_to(max(cols, 1), _LANES)


def _halo_widths(block_s: int, block_c: int, u_max: int, off_max: int):
    """(UH, LW): rows of up-halo history and columns of left-halo history a
    fused tile keeps — u_max / off_max rounded up to whole sublanes /
    lanes, capped at the tile (block_s ≥ u_max and block_c ≥ off_max, so
    the cap never cuts below the halo)."""
    uh = min(_pad_to(max(u_max, 1), _SUBLANES), block_s)
    lw = min(_pad_to(max(off_max, 1), _LANES), block_c)
    return uh, lw


def unblocked_vmem_bytes(S: int, C: int, n_edges: int, u_max: int, off_max: int) -> int:
    """VMEM footprint of the whole-plane kernel on one instance (see
    :func:`batched_vmem_bytes` — the single solve is its B = 1 case)."""
    return batched_vmem_bytes(S, C, n_edges, u_max, off_max, 1)


def c_blocked_tile_vmem_bytes(S: int, block_c: int, u_max: int) -> int:
    """Per-grid-step VMEM of the C-blocked (full-height) pipeline: two
    haloed (S, block_c) input views + two output tiles, double-buffered,
    + the feasibility tile, plus four plane-sized temporaries of the
    shifted read (both rolls, the take, the decision)."""
    tile = _tile_words(S, block_c)
    return 4 * (2 * (4 * tile + _tile_words(1, block_c)) + 4 * tile)


def tiled_vmem_bytes(block_s: int, block_c: int, u_max: int) -> int:
    """Per-grid-step VMEM of the 2-D (S-tile × C-tile) pipeline: four
    haloed (block_s, block_c) input views + two output tiles,
    double-buffered, + the feasibility tile, plus the shift temporaries —
    independent of both plane extents."""
    tile = _tile_words(block_s, block_c)
    return 4 * (2 * (6 * tile + _tile_words(1, block_c)) + 6 * tile)


def fused_tile_vmem_bytes(
    block_e: int, block_s: int, block_c: int, u_max: int, off_max: int, S: int, C: int
) -> int:
    """Per-grid-step VMEM of the edge-fused pipeline: the (block_s,
    block_c) value and owned-word tiles, each in and out, plus the
    per-chunk feasibility block, double-buffered; four tile-sized shift
    temporaries; and the two persistent halo-history scratches —
    ``lefth`` (block_e, block_s, LW) and the double-banked ``rowh``
    (2·block_e, UH, C_padded), the only term that scales with the plane
    width.  A single-S-row grid (block_s ≥ S, i.e. full-height tiles) has
    no up neighbors: ``rowh`` is neither allocated nor charged, which is
    what keeps large fused chunks affordable at very large C.  All
    4-byte, in whole (8, 128) tiles."""
    uh, lw = _halo_widths(block_s, block_c, u_max, off_max)
    Cp = _pad_to(C, block_c)
    tile = _tile_words(block_s, block_c)
    rowh = 0 if block_s >= S else 2 * block_e * _tile_words(uh, Cp)
    return 4 * (2 * (4 * tile + _tile_words(block_e, block_c))
                + 4 * tile
                + rowh
                + block_e * _tile_words(block_s, lw))


def batched_vmem_bytes(
    S: int, C: int, n_edges: int, u_max: int, off_max: int, block_b: int
) -> int:
    """VMEM footprint of one grid step of the whole-plane kernel over
    ``block_b`` instances: each instance's value plane and packed decision
    words (charged × ``block_b``), the SHARED v0 plane and feasibility
    plane (loaded once per step regardless of the batch), all
    double-buffered, plus four plane-sized temporaries of the shifted
    read (the instances run one after another, so these are charged
    once).  Per-edge scalars sit in SMEM and cost no VMEM.  All 4-byte, in
    whole (8, 128) tiles."""
    W = packed_words(n_edges)
    plane = _tile_words(S, C)
    per = (1 + W) * plane
    shared = plane + _tile_words(n_edges, C)
    return 4 * (2 * (block_b * per + shared) + 4 * plane)


def modeled_hbm_bytes(
    S: int, C: int, n_edges: int, u_max: int, off_max: int, block_e, block_s, block_c
) -> int:
    """Modeled HBM bytes streamed by one DP forward solve under a tiling.

    Counts the plane-sized flows only (operand vectors are O(E)): value
    blocks read/written by the pallas pipeline, the per-step feasibility
    blocks, and the decision bits of the packed (⌈E/32⌉, S, C) words (the
    scan pipelines write a bits plane per edge and the host ORs it into
    one word plane; a fused chunk reads and writes the one word plane it
    owns inside the kernel — see :func:`_chunk_layout`).
    The whole-plane kernel streams everything exactly once.  This is the
    ``hbm_bytes_streamed`` model `benchmarks/dp_bench.py` records — a
    traffic model for the perf trend, not a measurement.
    """
    W = packed_words(n_edges)
    if block_c is None:  # whole-plane, VMEM-resident
        return 4 * (S * C  # v0 in
                    + n_edges * C  # feasibility plane in
                    + S * C  # V out
                    + W * S * C)  # packed decisions out
    Cp = -(-C // block_c) * block_c
    Sp = S if block_s is None else -(-S // block_s) * block_s
    plane = 4 * Sp * Cp
    if block_e is None:
        # one pallas_call per edge: every tile re-loads its halo views
        # (2 for the C-blocked row, 4 for the 2-D grid), writes V' + bits,
        # and the host ORs the bits into one packed word (read + write)
        views = 2 if block_s is None else 4
        per_edge = (views + 2) * plane + 2 * plane + 4 * Cp
        return n_edges * per_edge
    # fused: each chunk streams the value plane and its owned word plane
    # in and out ONCE, plus its feasibility block
    n_chunks = len(_chunk_layout(n_edges, block_e)[1])
    return n_chunks * (2 * plane + 2 * plane + 4 * block_e * Cp)


def batched_modeled_hbm_bytes(
    S: int,
    C: int,
    n_edges: int,
    u_max: int,
    off_max: int,
    batch: int,
    block_e=None,
    block_s=None,
    block_c=None,
) -> int:
    """Modeled HBM bytes streamed by ONE batched forward of ``batch``
    solves: the shared operands stream once, the per-instance flows ×
    ``batch``.  The vmapped-single-launch alternative replicates the
    shared operands per instance (vmap folds per-instance eligibility
    into ``batch`` copies of the feasibility plane), so its model is
    simply ``batch · modeled_hbm_bytes(...)`` — the ratio of the two is
    the ``hbm_reduction_vs_vmapped`` figure dp_bench records.  A tiled
    plane shares nothing: each mapped instance reads its own feasibility
    blocks, so its fleet streams exactly ``batch`` single solves."""
    per = modeled_hbm_bytes(S, C, n_edges, u_max, off_max,
                            block_e, block_s, block_c)
    if block_c is not None:
        return batch * per
    shared = 4 * (S * C + n_edges * C)  # v0 + feasibility plane
    return shared + batch * (per - shared)


def _tile_candidates(extent: int, unit: int, floor: int) -> list:
    """Descending tile widths for one axis: the full extent rounded up to
    whole ``unit``s plus every power-of-two multiple of ``unit`` below it,
    all ≥ ``floor`` (the halo legality bound — off_max along C, u_max along
    S).  Every candidate is a multiple of ``unit``, so the compiled
    kernels only ever see tile-aligned blocks."""
    full = _pad_to(extent, unit)
    cands = {full}
    width = unit
    while width < full:
        if width >= floor:
            cands.add(width)
        width *= 2
    return sorted(cands, reverse=True)


def choose_tiling(
    S: int,
    C: int,
    n_edges: int,
    u_max: int,
    off_max: int,
    budget: int = VMEM_BUDGET_BYTES,
    batch: int | None = None,
):
    """Pick ``(block_e, block_s, block_c)`` for :func:`dp_forward_pallas`.

    With ``batch=B`` the return value is instead the 4-tuple ``(block_b,
    block_e, block_s, block_c)`` for :func:`dp_forward_pallas_batched`,
    and the BATCH axis shrinks FIRST: the largest ``block_b`` ∈ {B} ∪
    {powers of two below B} whose batched whole-plane footprint
    (:func:`batched_vmem_bytes`) fits the budget keeps every instance's
    full plane VMEM-resident — a smaller fleet per grid step is always
    cheaper than giving up plane residency.  Only when even ``block_b =
    1`` overflows does the per-instance plane tile (by the 3-tuple rule
    below) with ``block_b`` pinned to 1 (the instances then run one after
    another through the single-instance solve).

    Returns ``(None, None, None)`` when the whole-plane kernel fits the
    VMEM budget (edges already run inside one pallas_call there — nothing
    to fuse).  Otherwise the plane tiles exactly as before — ``block_s is
    None`` selects the C-blocked (full-height) pipeline when some legal
    capacity tile fits, else the largest 2-D tile pair (maximizing
    block_s·block_c, ties to the wider lane-contiguous block_c) — and
    ``block_e`` is then the largest edge-chunk ≤ min(``MAX_BLOCK_E``, E)
    whose fused pipeline (``fused_tile_vmem_bytes``: the plane tile plus
    the halo-history scratches) still fits the budget, cutting HBM plane
    traffic ``block_e``-fold.  ``block_e is None`` falls back to the
    per-edge-scan pipelines (one pallas_call per edge) — only reachable
    when even a 1-edge chunk's history scratch overflows the budget.

    Tiles respect the halo floors (block_c ≥ off_max, block_s ≥ u_max) and
    are whole multiples of the vreg tile (128 lanes along C, 8 sublanes
    along S), as the compiled kernels require; if even the smallest legal
    pair exceeds the budget it is returned anyway — no smaller tiling
    exists.
    """
    if batch is not None:
        if batch < 1:
            raise ValueError(f"batch={batch} must be >= 1")
        for bb in _tile_candidates(batch, 1, 1):
            if batched_vmem_bytes(S, C, n_edges, u_max, off_max,
                                  bb) <= budget:
                return bb, None, None, None
        return (1,) + choose_tiling(S, C, n_edges, u_max, off_max, budget)
    if unblocked_vmem_bytes(S, C, n_edges, u_max, off_max) <= budget:
        return None, None, None
    c_cands = _tile_candidates(C, _LANES, off_max)
    block_s = block_c = None
    for bc in c_cands:  # widest full-height first
        if c_blocked_tile_vmem_bytes(S, bc, u_max) <= budget:
            block_c = bc
            break
    if block_c is None:
        s_cands = _tile_candidates(S, _SUBLANES, max(u_max, 1))
        best = None
        for bs in s_cands:
            for bc in c_cands:
                if bs == s_cands[0] and bc == c_cands[0]:
                    continue  # that is the whole plane
                if tiled_vmem_bytes(bs, bc, u_max) > budget:
                    continue
                if (best is None or bs * bc > best[0] * best[1]
                        or (bs * bc == best[0] * best[1] and bc > best[1])):
                    best = (bs, bc)
        if best is None:
            best = (s_cands[-1], c_cands[-1])  # floor pair: best possible
        block_s, block_c = best
    bs_eff = _pad_to(S, _SUBLANES) if block_s is None else block_s
    for be in range(min(MAX_BLOCK_E, max(n_edges, 1)), 0, -1):
        if fused_tile_vmem_bytes(be, bs_eff, block_c, u_max, off_max,
                                 S, C) <= budget:
            return be, block_s, block_c
    return None, block_s, block_c


# ---------------------------------------------------------------------------
# the shifted read, in registers
# ---------------------------------------------------------------------------

def _roll(x, shift, axis: int):
    """y[i] = x[(i − shift) mod n] along ``axis`` for a dynamic shift ≥ 0."""
    return pltpu.roll(x, shift % x.shape[axis], axis)


def _lane_shift(x, left, off):
    """y[:, c] = plane[:, c − off] for a tile ``x`` of the plane.

    Columns c < off read the left neighbor: ``left`` holds its last LW ≥
    off columns, rolled by the same distance.  With ``left=None`` (a
    whole-plane or first-column tile) those columns wrap around instead —
    they are the states c < offset_e, infeasible, and masked to NEG by the
    caller."""
    y = _roll(x, off, 1)
    if left is None:
        return y
    lw = left.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, left.shape, 1)
    head = jnp.where(cols < off, _roll(left, off, 1), y[:, :lw])
    if lw == x.shape[1]:
        return head
    return jnp.concatenate([head, y[:, lw:]], axis=1)


def _row_shift(y, up, u):
    """z[s] = plane[max(s − u, 0)] for a (lane-shifted) tile ``y``.

    Rows s < u read above the tile: ``up`` holds the UH ≥ u plane rows
    directly above it (already lane-shifted), rolled by the same distance.
    With ``up=None`` the tile starts at plane row 0, and rows s < u take
    the clamp row y[0] — budgets below 0 clamp to V[0]."""
    z = _roll(y, u, 0)
    if up is None:
        rows = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
        return jnp.where(rows < u, y[0:1, :], z)
    uh = up.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, up.shape, 0)
    head = jnp.where(rows < u, _roll(up, u, 0), z[:uh])
    if uh == y.shape[0]:
        return head
    return jnp.concatenate([head, z[uh:]], axis=0)


def _clamp_or(first_row, y0, up):
    """The up halo of an S-tile: ``up`` below the first tile row, else
    (first_row) the replicated clamp row ``y0`` of the tile itself."""
    return jnp.where(first_row, jnp.broadcast_to(y0, up.shape), up)


def _edge_update(cur, shifted, sig, live):
    """One edge of the recurrence on a tile: returns (V′, decision).  The
    strict compare that makes the decision also selects V′ (ties keep the
    current value), so the update costs one compare and one select."""
    take = jnp.where(live, shifted + sig, NEG)
    dec = take > cur
    return jnp.where(dec, take, cur), dec


# ---------------------------------------------------------------------------
# whole-plane kernel (single solve = a batch of one)
# ---------------------------------------------------------------------------

def _dp_kernel(
    ups_ref,
    sig_ref,
    alw_ref,
    offs_ref,
    feas_ref,
    v0_ref,
    vout_ref,
    dec_ref,
    *,
    n_edges: int,
    u_max: int,
    off_max: int,
):
    """Whole-plane DP forward over the ``block_b`` instances of one grid step.

    Per-instance operands arrive as (block_b, 1, E) SMEM rows; the
    feasibility plane and v0 are the SHARED blocks (their index maps
    ignore the batch index).  Per-instance eligibility multiplies into the
    mask HERE (``live = feasible ∧ allowed``) instead of being folded into
    per-instance feasibility copies on the host.  Instances run one after
    another; each walks its edges word-major, ORing every decision into
    its static-index 32-edge word."""
    block_b = vout_ref.shape[0]
    W = dec_ref.shape[1]

    def instance(b, _):
        vout_ref[b] = v0_ref[...]
        for w in range(W - 1, -1, -1):  # edges E-1 … 0, word-major
            e_lo = w * 32
            e_hi = min(e_lo + 32, n_edges)
            dec_ref[b, w] = jnp.zeros(dec_ref.shape[2:], jnp.int32)

            def edge_step(jj, _, e_hi=e_hi, w=w):
                e = e_hi - 1 - jj
                u = jnp.minimum(ups_ref[b, 0, e], u_max)
                off = jnp.minimum(offs_ref[e], off_max)
                sig = sig_ref[b, 0, e]
                live = ((feas_ref[pl.ds(e, 1), :] > 0)
                        & (alw_ref[b, 0, e] > 0))
                V = vout_ref[b]
                take = _row_shift(_lane_shift(V, None, off), None, u)
                Vn, dec = _edge_update(V, take, sig, live)
                vout_ref[b] = Vn
                bit = jnp.left_shift(jnp.int32(1), e % 32)
                dec_ref[b, w] = dec_ref[b, w] | jnp.where(dec, bit, 0)
                return 0

            jax.lax.fori_loop(0, e_hi - e_lo, edge_step, 0)
        return 0

    jax.lax.fori_loop(0, block_b, instance, 0)


def _whole_plane(
    upsilon,
    sigma2,
    allowed,
    feasible,
    offsets,
    v0,
    *,
    n_edges,
    u_max,
    off_max,
    block_b,
    interpret,
):
    """(B, E) per-instance rows → (V (B, S, C), decisions (B, W, S, C)) on
    the whole-plane kernel, the plane padded to whole vreg tiles (pad
    states are infeasible; pad rows sit below every real row and the
    shifts only read upward, so both are inert and sliced away)."""
    B = upsilon.shape[0]
    S, C = v0.shape
    Sp, Cp = _pad_to(S, _SUBLANES), _pad_to(C, _LANES)
    W = packed_words(n_edges)
    bb = block_b
    Bp = _pad_to(B, bb)
    pad = ((0, Bp - B), (0, 0))
    upsilon = jnp.pad(upsilon, pad)
    sigma2 = jnp.pad(sigma2, pad)
    allowed = jnp.pad(allowed, pad)  # allowed ≡ 0 ⇒ inert
    v0p = jnp.pad(v0, ((0, Sp - S), (0, Cp - C)), constant_values=NEG)
    feas_p = jnp.pad(feasible, ((0, 0), (0, Cp - C)))
    kernel = functools.partial(_dp_kernel, n_edges=n_edges, u_max=u_max,
                               off_max=off_max)
    # per-instance rows ride SMEM as (B, 1, E): the block's last two dims
    # then span the whole array, which any block_b satisfies
    inst = pl.BlockSpec((bb, 1, n_edges), lambda g: (g, 0, 0),
                        memory_space=pltpu.SMEM)
    V, dec = pl.pallas_call(
        kernel,
        grid=(Bp // bb,),
        out_shape=(jax.ShapeDtypeStruct((Bp, Sp, Cp), jnp.int32),
                   jax.ShapeDtypeStruct((Bp, W, Sp, Cp), jnp.int32)),
        in_specs=[
            inst,  # Υ̂ rows
            inst,  # Σ̂² rows
            inst,  # allowed rows
            pl.BlockSpec(memory_space=pltpu.SMEM),  # shared offsets
            pl.BlockSpec((n_edges, Cp), lambda g: (0, 0)),
            pl.BlockSpec((Sp, Cp), lambda g: (0, 0)),
        ],
        out_specs=(pl.BlockSpec((bb, Sp, Cp), lambda g: (g, 0, 0)),
                   pl.BlockSpec((bb, W, Sp, Cp), lambda g: (g, 0, 0, 0))),
        interpret=interpret,
        name="dp_forward",
    )(upsilon[:, None], sigma2[:, None], allowed[:, None], offsets, feas_p,
      v0p)
    return V[:B, :S, :C], dec[:B, :, :S, :C]


# ---------------------------------------------------------------------------
# per-edge-scan pipelines (one pallas_call per edge)
# ---------------------------------------------------------------------------

def _edge_tile_kernel(
    u_ref,
    off_ref,
    sig_ref,
    feas_ref,
    vleft_ref,
    vcur_ref,
    vout_ref,
    bits_ref,
    *,
    u_max: int,
):
    """One edge update on one (S, B) capacity tile.

    ``vleft``/``vcur`` are two views of the SAME value plane: the tile and
    its left neighbor (tile 0 reads itself — those columns are c < offset_e,
    infeasible, masked).  Both shifts are rolls of the tile, spliced with
    the rolled neighbor, exactly like the whole-plane kernel.
    """
    u = jnp.minimum(u_ref[0], u_max)
    off = off_ref[0]
    sig = sig_ref[0]
    cur = vcur_ref[...]
    take = _row_shift(_lane_shift(cur, vleft_ref[...], off), None, u)
    Vn, dec = _edge_update(cur, take, sig, feas_ref[...] > 0)
    bits_ref[...] = dec.astype(jnp.int32)
    vout_ref[...] = Vn


def _edge_stile_kernel(
    u_ref,
    off_ref,
    sig_ref,
    feas_ref,
    vup_left_ref,
    vup_cur_ref,
    vleft_ref,
    vcur_ref,
    vout_ref,
    bits_ref,
    *,
    u_max: int,
):
    """One edge update on one (block_s, block_c) tile of the 2-D grid.

    The four ``v*`` refs are views of the SAME value plane: the tile, its
    left neighbor, and the up-neighbor row of both (S-tile 0 substitutes
    the plane's clamp row V[0] — budgets below 0 clamp to V[0], exactly the
    whole-plane kernel's clamp rows; C-tile 0 reads itself leftward —
    those columns are c < offset_e, infeasible, masked)."""
    u = jnp.minimum(u_ref[0], u_max)
    off = off_ref[0]
    sig = sig_ref[0]
    cur = vcur_ref[...]
    x = _lane_shift(cur, vleft_ref[...], off)
    up = _lane_shift(vup_cur_ref[...], vup_left_ref[...], off)
    take = _row_shift(x, _clamp_or(pl.program_id(0) == 0, x[0:1, :], up),
                      u)
    Vn, dec = _edge_update(cur, take, sig, feas_ref[...] > 0)
    bits_ref[...] = dec.astype(jnp.int32)
    vout_ref[...] = Vn


def _edge_call(
    V, feas_e, u1, off1, sig1, *, u_max: int, block_s, block_c: int, interpret: bool
):
    Sp, Cp = V.shape
    scalar_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    if block_s is None:
        kernel = functools.partial(_edge_tile_kernel, u_max=u_max)
        return pl.pallas_call(
            kernel,
            grid=(Cp // block_c,),
            out_shape=(jax.ShapeDtypeStruct((Sp, Cp), jnp.int32),
                       jax.ShapeDtypeStruct((Sp, Cp), jnp.int32)),
            in_specs=scalar_specs + [
                pl.BlockSpec((1, block_c), lambda j: (0, j)),
                pl.BlockSpec((Sp, block_c),
                             lambda j: (0, jnp.maximum(j - 1, 0))),
                pl.BlockSpec((Sp, block_c), lambda j: (0, j)),
            ],
            out_specs=(pl.BlockSpec((Sp, block_c), lambda j: (0, j)),
                       pl.BlockSpec((Sp, block_c), lambda j: (0, j))),
            interpret=interpret,
            name="dp_forward_edge",
        )(u1, off1, sig1, feas_e, V, V)
    kernel = functools.partial(_edge_stile_kernel, u_max=u_max)

    def up(i):
        return jnp.maximum(i - 1, 0)

    return pl.pallas_call(
        kernel,
        grid=(Sp // block_s, Cp // block_c),
        out_shape=(jax.ShapeDtypeStruct((Sp, Cp), jnp.int32),
                   jax.ShapeDtypeStruct((Sp, Cp), jnp.int32)),
        in_specs=scalar_specs + [
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
            pl.BlockSpec((block_s, block_c), lambda i, j: (up(i), up(j))),
            pl.BlockSpec((block_s, block_c), lambda i, j: (up(i), j)),
            pl.BlockSpec((block_s, block_c), lambda i, j: (i, up(j))),
            pl.BlockSpec((block_s, block_c), lambda i, j: (i, j)),
        ],
        out_specs=(pl.BlockSpec((block_s, block_c), lambda i, j: (i, j)),
                   pl.BlockSpec((block_s, block_c), lambda i, j: (i, j))),
        interpret=interpret,
        name="dp_forward_edge_tiled",
    )(u1, off1, sig1, feas_e, V, V, V, V)


# ---------------------------------------------------------------------------
# edge-fused pipeline (one pallas_call per chunk of block_e edges)
# ---------------------------------------------------------------------------

def _fused_chunk_kernel(
    word_ref,
    ups_ref,
    offs_ref,
    sig_ref,
    bitpos_ref,
    feas_ref,
    vin_ref,
    win_ref,
    vout_ref,
    wout_ref,
    rowh_ref,
    lefth_ref,
    *,
    n_chunk: int,
    u_max: int,
    off_max: int,
    multi_row: bool,
):
    """``n_chunk`` consecutive edges on one (block_s, block_c) tile.

    The tile lives in ``vout`` for the whole chunk — loaded from HBM once,
    written back once.  Per edge k the halos come from the persistent
    history scratches (see the module docstring): ``lefth[k]`` holds the
    left neighbor's last LW columns *before* edge k (read, then
    overwritten with this tile's own pre-edge-k boundary for the next
    C-tile), and ``rowh`` holds the previous S-row's bottom UH rows per
    edge, double-banked by row parity so the up-left corner read never
    races the current row's writes.  S-row 0 replicates the clamp row V[0]
    (the lane-shifted tile's row 0, whose corner columns came from the
    left halo) exactly like the unfused kernels; C-tile 0's left halo is
    garbage by construction — every read landing there is a state c <
    offset_e, infeasible, masked to NEG.  Decision bits of the whole chunk
    OR at bit ``bitpos[k]`` (global edge id mod 32) into the tile of the
    one word plane the chunk owns: ``wout`` starts from ``win``, the
    word's bits so far.  ``word_ref`` (that word's index) is read only by
    the index maps."""
    Bs, Bc = vin_ref.shape
    lw = lefth_ref.shape[-1]
    i = pl.program_id(0)
    j = pl.program_id(1)
    rd = (i + 1) % 2  # rowh bank written by S-row i-1
    wr = i % 2
    col = pl.multiple_of(j * Bc, Bc)
    # up-left corner: the last LW columns of tile j-1 (j == 0 clamps to
    # garbage that only infeasible states c < offset_e ever read)
    corner = pl.multiple_of(jnp.maximum(j * Bc - lw, 0), math.gcd(Bc, lw))
    vout_ref[...] = vin_ref[...]
    wout_ref[...] = win_ref[...]

    def edge_step(k, _):
        u = jnp.minimum(ups_ref[k], u_max)
        off = jnp.minimum(offs_ref[k], off_max)
        sig = sig_ref[k]
        bit = jnp.left_shift(jnp.int32(1), bitpos_ref[k])
        X = vout_ref[...]
        # left halo for edge k, then this tile's own boundary history
        # (pre-edge-k values) — read-then-write keeps one buffer legal
        left = lefth_ref[k]
        lefth_ref[k] = X[:, Bc - lw:]
        x = _lane_shift(X, left, off)
        if multi_row:
            uh = rowh_ref.shape[1]
            bank = rd * n_chunk + k
            up = _lane_shift(rowh_ref[bank, :, pl.ds(col, Bc)],
                             rowh_ref[bank, :, pl.ds(corner, lw)], off)
            # bottom-rows history (pre-edge-k) for S-row i+1
            rowh_ref[wr * n_chunk + k, :, pl.ds(col, Bc)] = X[Bs - uh:, :]
            take = _row_shift(x, _clamp_or(i == 0, x[0:1, :], up), u)
        else:
            # single-S-row grid: no up neighbors exist, no history to keep
            take = _row_shift(x, None, u)
        live = feas_ref[pl.ds(k, 1), :] > 0
        Xn, dec = _edge_update(X, take, sig, live)
        wout_ref[...] = wout_ref[...] | jnp.where(dec, bit, 0)
        vout_ref[...] = Xn
        return 0

    jax.lax.fori_loop(0, n_chunk, edge_step, 0)


def _chunk_layout(n_edges: int, block_e: int):
    """Static edge layout of the fused pipeline: ``(order, words)``.

    Edges are processed in reverse (E-1 … 0), word by word: each word's
    edges are padded up to whole chunks of ``block_e`` with inert edges
    (feasible ≡ 0 masks them to NEG everywhere: they leave V unchanged and
    set no bit), placed ahead of the word's highest edge.  Every chunk then
    lies inside ONE 32-bit word, whatever ``block_e`` is.  When ``block_e``
    divides 32 only the top word takes pads, at the ids above E-1.

    ``order`` (n_chunks, block_e) int32 is each slot's edge id, E for a
    pad; ``words`` (n_chunks,) int32 is the word each chunk owns."""
    order, words = [], []
    for w in range(packed_words(n_edges) - 1, -1, -1):
        edges = list(range(min(32 * w + 32, n_edges) - 1, 32 * w - 1, -1))
        row = [n_edges] * (-len(edges) % block_e) + edges
        order += row
        words += [w] * (len(row) // block_e)
    return (np.array(order, np.int32).reshape(-1, block_e),
            np.array(words, np.int32))


def _dp_forward_fused(
    upsilon,
    sigma2,
    feasible,
    offsets,
    v0,
    *,
    n_edges: int,
    u_max: int,
    off_max: int,
    block_e: int,
    block_s,
    block_c: int,
    interpret: bool,
):
    S, C = v0.shape
    Cp = -(-C // block_c) * block_c
    bs = _pad_to(S, _SUBLANES) if block_s is None else block_s
    Sp = -(-S // bs) * bs
    V0 = jnp.pad(v0, ((0, Sp - S), (0, Cp - C)), constant_values=NEG)
    feas_p = jnp.pad(feasible, ((0, 0), (0, Cp - C)))  # pad states masked
    W = packed_words(n_edges)
    dec0 = jnp.zeros((W * Sp, Cp), jnp.int32)

    # edges processed E-1 … 0 in whole chunks, one word per chunk
    order, words = _chunk_layout(n_edges, block_e)

    def chunked(arr):  # (E, …) → (n_chunks, block_e, …), pads inert zeros
        return jnp.concatenate([arr, jnp.zeros_like(arr[:1])])[order]

    xs = (jnp.asarray(words)[:, None], chunked(upsilon), chunked(offsets),
          chunked(sigma2), jnp.asarray(order % 32), chunked(feas_p))

    n_s = Sp // bs
    kernel = functools.partial(_fused_chunk_kernel, n_chunk=block_e,
                               u_max=u_max, off_max=off_max,
                               multi_row=n_s > 1)
    # halo-history scratches: ``rowh`` is a token buffer on a single-S-row
    # grid, which never reads it (so the large-C fused regime is not
    # charged 2·block_e·UH·Cp for it), and ``lefth``
    uh, lw = _halo_widths(bs, block_c, u_max, off_max)
    rowh_shape = (2 * block_e, uh, Cp) if n_s > 1 else (1, 1, 1)
    tile = pl.BlockSpec((bs, block_c), lambda i, j, w: (i, j))
    # tile (i, j) of the chunk's word plane in the flat (W·Sp, Cp) carry
    word_tile = pl.BlockSpec((bs, block_c),
                             lambda i, j, w: (w[0] * n_s + i, j))
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_s, Cp // block_c),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * 4 + [
                pl.BlockSpec((block_e, block_c), lambda i, j, w: (0, j)),
                tile,
                word_tile,
            ],
            out_specs=(tile, word_tile),
            scratch_shapes=[pltpu.VMEM(rowh_shape, jnp.int32),
                            pltpu.VMEM((block_e, bs, lw), jnp.int32)],
        ),
        out_shape=(jax.ShapeDtypeStruct((Sp, Cp), jnp.int32),
                   jax.ShapeDtypeStruct((W * Sp, Cp), jnp.int32)),
        input_output_aliases={6: 0, 7: 1},  # V and the word carry, in place
        interpret=interpret,
        name="dp_forward_fused",
    )

    def body(carry, x):
        return tuple(call(*x, *carry)), None

    (V, dec), _ = jax.lax.scan(body, (V0, dec0), xs)
    return V[:S, :C], dec.reshape(W, Sp, Cp)[:, :S, :C]


def _dp_forward_blocked(
    upsilon,
    sigma2,
    feasible,
    offsets,
    v0,
    *,
    n_edges: int,
    u_max: int,
    off_max: int,
    block_s,
    block_c: int,
    interpret: bool,
):
    S, C = v0.shape
    Cp = -(-C // block_c) * block_c
    Sp = (_pad_to(S, _SUBLANES) if block_s is None
          else -(-S // block_s) * block_s)
    # pad rows/columns sit at the high end of each axis: both shifts read
    # towards SMALLER indices, so real entries never read a pad entry (pad
    # rows/states compute garbage that is sliced away at the end)
    V0 = jnp.pad(v0, ((0, Sp - S), (0, Cp - C)), constant_values=NEG)
    feas_p = jnp.pad(feasible, ((0, 0), (0, Cp - C)))  # pad states masked
    W = packed_words(n_edges)
    dec0 = jnp.zeros((W, Sp, Cp), jnp.int32)

    rev = slice(None, None, -1)  # edges E-1 … 0
    xs = (upsilon[rev], offsets[rev], sigma2[rev], feas_p[rev],
          jnp.arange(n_edges - 1, -1, -1, dtype=jnp.int32))

    def body(carry, x):
        V, dec = carry
        u, off, sig, feas_e, e = x
        Vn, bits = _edge_call(
            V, feas_e[None, :], u[None], jnp.minimum(off, off_max)[None],
            sig[None], u_max=u_max, block_s=block_s, block_c=block_c,
            interpret=interpret)
        w = e // 32
        word = jax.lax.dynamic_slice(dec, (w, 0, 0), (1, Sp, Cp))
        word = word | (bits << (e % 32))[None]
        return (Vn, jax.lax.dynamic_update_slice(dec, word, (w, 0, 0))), None

    (V, dec), _ = jax.lax.scan(body, (V0, dec0), xs)
    return V[:S, :C], dec[:, :S, :C]


def _check_tiling(
    block_e, block_s, block_c, u_max: int, off_max: int, interpret: bool
) -> None:
    """A plane tiling for ``block_e``/``block_s``, the fused chunk's word
    bound, the halo floors (always) and vreg-tile alignment (compiled
    mode)."""
    if block_c is None:
        if block_e is not None or block_s is not None:
            raise ValueError(
                "block_e and block_s tile the blocked pipeline's plane and "
                "need block_c (pass block_c=C for a single full-width tile)")
        return
    if block_e is not None and not 1 <= block_e <= MAX_BLOCK_E:
        raise ValueError(
            f"block_e={block_e} outside [1, {MAX_BLOCK_E}]: a fused chunk "
            "packs its decision bits into one int32 word plane, so "
            "in-chunk bit positions (edge id mod 32) must stay distinct")
    if block_c < off_max:
        raise ValueError(
            f"block_c={block_c} < off_max={off_max}: the offset shift "
            "would reach past the left-neighbor halo")
    if block_s is not None and block_s < u_max:
        raise ValueError(
            f"block_s={block_s} < u_max={u_max}: the budget shift "
            "would reach past the up-neighbor halo")
    if not interpret and (block_c % _LANES
                          or (block_s is not None and block_s % _SUBLANES)):
        raise ValueError(
            f"tiling (block_s={block_s}, block_c={block_c}) is not "
            f"vreg-aligned: the compiled kernel needs block_c a multiple "
            f"of {_LANES} and block_s a multiple of {_SUBLANES} "
            "(choose_tiling only returns aligned tilings)")


@functools.partial(jax.jit, static_argnames=("n_edges", "u_max", "off_max",
                                             "interpret", "block_c",
                                             "block_s", "block_e"))
def dp_forward_pallas(
    upsilon,
    sigma2,
    feasible,
    offsets,
    v0,
    *,
    n_edges: int,
    u_max: int,
    off_max: int,
    interpret: bool | None = None,
    block_c: int | None = None,
    block_s: int | None = None,
    block_e: int | None = None,
):
    """upsilon/sigma2/offsets: (E,) i32; feasible: (E, C) f32 0/1;
    v0: (S, C) i32 (``NEG`` where unreachable).  Returns (V_final (S, C)
    i32, decisions (⌈E/32⌉, S, C) i32 — bit (e%32) of word (e//32) is
    edge e).  Values are exact while every reachable sum stays below
    |NEG| (``ops.check_value_bound``).

    ``offsets[e]`` is the mixed-radix transition constant (next(c) = c −
    offsets[e] on feasible states; ``off_max`` ≥ max offsets); ``block_c``
    selects the blocked pipelines, ``block_s`` additionally tiles the
    budget axis (2-D grid; requires ``block_c``), and ``block_e`` fuses
    chunks of that many consecutive edges into each pallas_call (temporal
    blocking — tiles stay VMEM-resident across the chunk; requires
    ``block_c``, 1 ≤ block_e ≤ ``MAX_BLOCK_E``; need not divide E).
    ``choose_tiling`` picks all three from the VMEM budget.
    ``interpret=None`` resolves via :func:`resolve_interpret` (compiled on
    TPU, interpreter elsewhere)."""
    interp = resolve_interpret(interpret)
    _check_tiling(block_e, block_s, block_c, u_max, off_max, interp)
    sigma2, v0 = sigma2.astype(jnp.int32), v0.astype(jnp.int32)
    if block_c is not None:
        if block_e is not None:
            return _dp_forward_fused(
                upsilon, sigma2, feasible, offsets, v0, n_edges=n_edges,
                u_max=u_max, off_max=off_max, block_e=block_e,
                block_s=block_s, block_c=block_c, interpret=interp)
        return _dp_forward_blocked(
            upsilon, sigma2, feasible, offsets, v0, n_edges=n_edges,
            u_max=u_max, off_max=off_max, block_s=block_s, block_c=block_c,
            interpret=interp)
    V, dec = _whole_plane(
        upsilon[None], sigma2[None], jnp.ones((1, n_edges), jnp.int32),
        feasible, offsets, v0, n_edges=n_edges, u_max=u_max,
        off_max=off_max, block_b=1, interpret=interp)
    return V[0], dec[0]


@functools.partial(jax.jit, static_argnames=("n_edges", "u_max", "off_max",
                                             "interpret", "block_b",
                                             "block_c", "block_s",
                                             "block_e"))
def dp_forward_pallas_batched(
    upsilon,
    sigma2,
    allowed,
    feasible,
    offsets,
    v0,
    *,
    n_edges: int,
    u_max: int,
    off_max: int,
    interpret: bool | None = None,
    block_b: int | None = None,
    block_c: int | None = None,
    block_s: int | None = None,
    block_e: int | None = None,
):
    """B independent DP forwards against SHARED tables.

    upsilon/sigma2/allowed: (B, E); ``feasible`` (E, C) and ``offsets``
    (E,) are shared across the batch.  Returns ``(V (B, S, C) i32,
    decisions (B, ⌈E/32⌉, S, C) i32)``.

    On the whole-plane kernel (``block_c=None``) the batch rides ONE
    pallas_call's grid: ``block_b`` instances advance per grid step
    (default: the whole batch in one step), per-instance eligibility rides
    the (B, E) ``allowed`` rows and multiplies into the feasibility mask
    INSIDE the kernel, so the plane is never replicated per instance, and
    ragged batches (B not a multiple of block_b) pad with inert ``allowed
    ≡ 0`` instances whose outputs are dropped.  With a plane tiling
    (``block_c``, fused or per-edge) the instances run one after another
    through :func:`dp_forward_pallas` on that tiling (``jax.lax.map``),
    each folding its ``allowed`` row into the feasibility plane, and
    ``block_b`` must be 1.  ``choose_tiling(..., batch=B)`` picks all
    four."""
    interp = resolve_interpret(interpret)
    B = upsilon.shape[0]
    bb = B if block_b is None else block_b
    if not 1 <= bb <= B:
        raise ValueError(
            f"block_b={bb} outside [1, {B}]: the batch grid advances "
            "block_b instances per step and cannot exceed the batch")
    allowed = jnp.asarray(allowed, jnp.int32)
    if block_c is not None:
        if bb != 1:
            raise ValueError(
                f"block_b={bb}: a fleet of tiled planes runs one instance "
                "at a time (block_b=1) — a tiled plane is one that "
                "overflowed the VMEM budget in the first place")

        def one(x):
            ups, sig, alw = x
            return dp_forward_pallas(
                ups, sig, feasible * alw.astype(jnp.float32)[:, None],
                offsets, v0, n_edges=n_edges, u_max=u_max, off_max=off_max,
                interpret=interp, block_c=block_c, block_s=block_s,
                block_e=block_e)

        return jax.lax.map(one, (upsilon, sigma2, allowed))
    _check_tiling(block_e, block_s, None, u_max, off_max, interp)
    sigma2, v0 = sigma2.astype(jnp.int32), v0.astype(jnp.int32)
    return _whole_plane(upsilon, sigma2, allowed, feasible, offsets, v0,
                        n_edges=n_edges, u_max=u_max, off_max=off_max,
                        block_b=bb, interpret=interp)
