"""Pallas TPU kernels: ragged paged attention (mixed prefill/decode rows).

Round 1 (PR 7) gridded over PACKED TOKENS — grid (T, P), one query token
per outer step — which made the structural win (ONE dispatch serves an
arbitrary prefill/decode mix) but paid a bandwidth tax: a prefill row's
pages were streamed HBM→VMEM once PER TOKEN of the chunk. Round 2 is the
block-ragged tiling of the RPA paper (PAPERS.md): query TILES that span
row boundaries, so each KV page a tile needs streams once per tile. Its
grid was (T/TILE, TILE, P): every page of ``max_seq_len`` for every
row-slot of every tile, live or not, which at a table 512 wide was 99 %
of a call's steps. Round 3 (this file) keeps the tiling and walks LIVE
pages only (``page_walk.py``):

* the packed token axis is padded to a multiple of ``Q_TILE`` (pad tokens
  carry ``q_position == -1`` — the SAME pad contract as the pack itself)
  and the wrapper folds each tile's tokens into the query-row axis in
  XLA, so the q/out BlockSpecs move one ``[KV, TILE·G, hd]`` (MLA:
  ``[TILE·H, dc]``) tile per outer step and the kernel body holds no
  shape cast — Mosaic (v5e, jax 0.9) refuses to merge a G-row axis
  smaller than a sublane tile, and has no layout for a rank-1 vector of
  per-token limits, so those are built against a 2-D iota instead;
* FIRST-OCCURRENCE leadership is computed in XLA (``_tile_segments``):
  only the first token of each distinct row in a tile leads that row's
  page walk, up to the row's largest causal limit over the tile's tokens,
  and an item attends EVERY tile token of that row at once (per-token
  causal limits masked in-softmax). A row with a C-token chunk in the
  tile therefore streams its pages once, not C times;
* the grid is ONE axis of dynamic length: the live (leader, block of
  pages) items in tile order, their count the cumulative sum the kernel
  bisects to find its item. Followers, pads and pages past a limit have
  no grid step at all; an all-pad tile keeps one item, which attends
  nothing and writes the tile's zeros. The table's width appears in no
  grid, block or loop bound;
* causal masking is unchanged: token ``t`` attends slots
  ``< min(kv_lens[row_ids[t]], q_positions[t] + 1)``; pad tokens
  (position −1) have limit ≤ 0 → always masked → zero accumulators
  finalize to zero through the denom guard.

Honest cost note: a row of ONE token that leads a walk here shares a
tile of ``Q_TILE`` packed tokens with up to seven other rows, each leading
its own walk in blocks of 64 slots, and every item attends all ``TILE×G``
query rows with seven eighths masked, under index maps that search
(``page_walk.find_item``): several times the decode kernels' time for the
same pages. The step programs send no such row here. Pure-decode batches
are the engine's fused multi-step path's, and a unified step's one-token
rows are handed over as padding: ``models/llama.py::_pool_attention``
gives this kernel the pack with ``q_position == -1`` at their tokens, the
pad contract above, so they lead nothing and have no grid step, and the
decode kernels attend them (``paged_attention_kernel.py``, every other row
at length 0). What walks here is a prompt's chunk: rows of two tokens or
more, whose tile does amortise a page over its tokens. The kernels
themselves take any pack (the tests' and the XLA form's contract): a
one-token row given at its true position is attended like any row.

Same family of int8 variants as the decode kernel: scales fold
algebraically into scores/probs, pages feed the MXU as int8. The MLA
(latent) ragged kernels live here too — same tiling over the ``c/pe``
pools.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rbg_tpu.ops.pallas import page_walk as W

_NEG_INF = -1e30

# Query-tile length of the block-ragged grid. 8 packed tokens per tile
# multiplies the MXU's query rows by 8 (G is small under GQA) and divides
# a prefill chunk's page re-streams by 8.
Q_TILE = 8

# Grid revision — part of the engine's ragged program-cache key
# (warm_ragged): a cache warmed for one grid must not alias programs
# compiled for another. 1: the PR-7 token grid; 2: block-ragged tiles over
# every page of the table; 3: block-ragged tiles over live pages only.
RAGGED_GRID_REV = 3


def _tile_segments(row_ids, q_pos, kv_lens, page, window=None):
    """The ragged kernels' work, in XLA: per packed token (a segment of
    the walk) how many slots of its row it leads the tile through, and
    the cumulative live blocks of that (``page_walk.live_block_starts``).

    A token LEADS its row in its tile if it is the row's first occurrence
    there; it walks up to the row's largest causal limit over the tile's
    tokens (``min(kv_lens[row], q_position + 1)``), and every tile token
    of that row is attended in its items. Followers, and rows whose limit
    is <= 0 (pads carry position −1), lead nothing: they get no items, so
    their grid steps do not exist. Each tile's first token keeps one
    item regardless, which writes the tile's output block.

    In a window layer (``window``) a leader's walk starts at the block
    that holds the oldest slot any tile token of its row attends
    (``position - window + 1`` of the row's first real token in the tile):
    a third value, ``first_block [Tp]``, the block each segment's items
    start at, and ``starts`` count the blocks from there on.

    Returns (lead_tokens [Tp] int32, starts [Tp + 1] int32)."""
    rows = row_ids.reshape(-1, Q_TILE)
    lims = jnp.minimum(kv_lens[row_ids], q_pos + 1).reshape(-1, Q_TILE)
    same = rows[:, :, None] == rows[:, None, :]             # [NT, r, k]
    k_lt_r = jnp.tri(Q_TILE, k=-1, dtype=jnp.bool_)         # k < r
    dup = jnp.any(same & k_lt_r, axis=2)
    row_limit = jnp.max(jnp.where(same, lims[:, None, :], 0), axis=2)
    lead = jnp.where(dup, 0, row_limit).astype(jnp.int32).reshape(-1)
    first_of_tile = jnp.arange(lead.shape[0]) % Q_TILE == 0
    if window is None:
        return lead, W.live_block_starts(lead, page, first_of_tile)
    pos = q_pos.reshape(-1, Q_TILE)[:, None, :]             # pads: -1
    oldest = jnp.min(jnp.where(same & (pos >= 0), pos - window + 1,
                               np.iinfo(np.int32).max), axis=2)
    first_block, below = W.first_live_block(
        jnp.where(lead > 0, oldest.reshape(-1), 0), page)
    starts = W.live_block_starts(lead - below, page, first_of_tile)
    return lead, starts, first_block.astype(jnp.int32)


def _page_id(j, w, table, lens, rows, qpos, lead, starts, *first, page):
    """Physical page of page ``j`` of ragged work item ``w`` (every
    packed token is a segment; only leaders have items). ``first``: a
    window layer's ``first_block``, at which a segment's items start."""
    t, block = W.find_item(starts, w, rows.shape[0])
    if first:
        block = jax.lax.add(block, first[0][t])
    return W.page_of_block(table, rows[t], block, j, lead[t], page)


def _tile_of(rank):
    """Index map of the query/output tile of an item."""
    def index_map(w, table, lens, rows, qpos, lead, starts, *first):
        t, _ = W.find_item(starts, w, rows.shape[0])
        return (jax.lax.div(t, np.int32(Q_TILE)),) + (0,) * (rank - 1)
    return index_map


def _tile_limits(row_ids_ref, kv_lens_ref, q_pos_ref, t0, row, tile, group,
                 window=None):
    """Per-query-row causal limits of one tile as a ``[TILE·group, 1]``
    column (query row ``j`` belongs to tile token ``j // group``). Tokens
    of OTHER rows get limit 0 (fully masked), so every tile token rides
    the same softmax update and only ``row``'s tokens accumulate. Beside
    it, with ``window``, the column of the oldest slot each query row
    attends, ``position - window + 1`` (else None): (limits, lowers).

    Built from SMEM scalars by ``tile`` selects against a 2-D iota: Mosaic
    has no layout for a rank-1 vector of stacked scalars, nor for the
    shape casts that would broadcast one."""
    # In ``lax`` primitives, as ``page_walk.find_item`` is and for its
    # reason: every packed program an engine warms traces these selects
    # anew, and a ``jnp.where`` is a nested ``jit`` each time.
    shape, lax, i32 = (tile * group, 1), jax.lax, np.int32
    j = lax.broadcasted_iota(jnp.int32, shape, 0)

    def column(values):
        """``values[k]`` (scalars) in the query rows of tile token ``k``."""
        col = lax.full(shape, 0, jnp.int32)
        for k, value in enumerate(values):
            col = lax.select(lax.ge(j, i32(k * group)),
                             lax.broadcast_in_dim(value, shape, ()), col)
        return col

    positions = [q_pos_ref[lax.add(t0, i32(k))] for k in range(tile)]
    limits = []
    for k, pos in enumerate(positions):
        rk = row_ids_ref[lax.add(t0, i32(k))]
        limits.append(lax.select(
            lax.eq(rk, row), lax.min(kv_lens_ref[rk], lax.add(pos, i32(1))),
            i32(0)))
    if window is None:
        return column(limits), None
    return column(limits), column(
        [lax.add(pos, i32(1 - window)) for pos in positions])


def _block_ragged_kernel(
    # scalar prefetch
    page_table_ref,   # [R, P] int32 (SMEM)
    kv_lens_ref,      # [R] int32 (SMEM)
    row_ids_ref,      # [Tp] int32 (SMEM) — Tp padded to a Q_TILE multiple
    q_pos_ref,        # [Tp] int32 (SMEM)
    lead_ref,         # [Tp] int32 (SMEM) — slots each token leads (0: none)
    starts_ref,       # [Tp + 1] int32 (SMEM) — cumulative live blocks
    # blocks
    *refs,            # a window layer's one more scalar prefetch first,
                      # first_ref [Tp] int32 (SMEM): the block each
                      # segment's items start at; then q_ref [1, KV, TILE·G,
                      # hd] (VMEM), one query tile, the tile's tokens already
                      # folded into the query-row axis; the item's pages,
                      # picked by index_map: n k refs and n v refs [1, page,
                      # KV, hd] (int8 pools: then n + n scale refs [1, page,
                      # KV] f32); out_ref [1, KV, TILE·G, hd]; scratch —
                      # online softmax state for the WHOLE tile: m, l [KV,
                      # TILE·G, 1], acc [KV, TILE·G, hd]
    tile: int,
    head_dim=None,    # a head's size where ``hd`` is several packed heads
    window=None,      # a window layer's width
):
    first_ref = None
    if window is not None:
        first_ref, *refs = refs
    q_ref, *pages, out_ref, m_ref, l_ref, acc_ref = refs
    w = pl.program_id(0)
    t, block = W.find_item(starts_ref, w, row_ids_ref.shape[0])
    t0 = t // tile * tile
    page = pages[0].shape[1]
    if window is not None:
        block = block + first_ref[t]
    token0 = block * (W.pages_per_block(page) * page)   # the block's first slot

    # A tile's first token always has an item, so the tile's first item
    # is that token's first.
    @pl.when((t == t0) & (block == 0) if window is None
             else w == starts_ref[t0])
    def _init():
        W.init_softmax(m_ref, l_ref, acc_ref)

    # False only in the one item of a tile's first token that leads
    # nothing (a pad, or an empty row).
    @pl.when(token0 < lead_ref[t])
    def _attend():
        rows_q = q_ref.shape[2]
        limits, lowers = _tile_limits(
            row_ids_ref, kv_lens_ref, q_pos_ref, t0, row_ids_ref[t], tile,
            rows_q // tile, window)
        k, v, *scales = W.load_blocks(pages)
        ks, vs = scales or (None, None)
        # The tile's whole query block rides ONE batched dot per block.
        W.gqa_attend(q_ref[0], k, v, ks, vs, token0, limits,
                     m_ref, l_ref, acc_ref, head_dim, lowers)

    @pl.when(w + 1 == starts_ref[t0 + tile])
    def _finalize():
        out_ref[0] = W.finalize_softmax(l_ref, acc_ref, out_ref.dtype)


def _fold_tile(qg):
    """[Tp, KV, G, hd] packed → [Tp/TILE, KV, TILE·G, hd]: each tile's
    tokens folded into the query-row axis (row ``k·G + g``). Done here,
    in XLA, because Mosaic cannot merge a G-row axis smaller than a
    sublane tile inside the kernel."""
    Tp, KV, G, hd = qg.shape
    q5 = qg.reshape(Tp // Q_TILE, Q_TILE, KV, G, hd)
    return jnp.transpose(q5, (0, 2, 1, 3, 4)).reshape(
        Tp // Q_TILE, KV, Q_TILE * G, hd)


def _unfold_tile(out, G):
    """Inverse of ``_fold_tile``: [Tp/TILE, KV, TILE·G, hd] → [Tp, KV·G, hd]."""
    NT, KV, _, hd = out.shape
    o5 = out.reshape(NT, KV, Q_TILE, G, hd)
    return jnp.transpose(o5, (0, 2, 1, 3, 4)).reshape(
        NT * Q_TILE, KV * G, hd)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "head_dim", "window"))
def _block_ragged_call(q, k_pages, v_pages, k_scales, v_scales, page_table,
                       kv_lens, row_ids, q_pos, interpret=False,
                       head_dim=None, window=None):
    """q: [Tp/TILE, KV, TILE·G, hd] folded tiles; pages: [NP, page, KV,
    hd]; scales (int8 pools) [NP, page, KV] f32 or None. Returns q's
    shape. ``head_dim``: a head's size where the pool keeps several side
    by side and ``q`` is ``page_walk.pack_queries``'. ``window``: a
    window layer's width (``_tile_segments``)."""
    NT, KV, rows_q, hd = q.shape
    page = k_pages.shape[1]
    lead, starts, *first = _tile_segments(row_ids, q_pos, kv_lens, page,
                                          window)
    pools = (k_pages, v_pages)
    if k_scales is not None:
        pools += (k_scales, v_scales)
    page_specs, page_operands = W.block_specs(
        pools, functools.partial(_page_id, page=page))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6 + len(first),
        grid=(starts[NT * Q_TILE],),
        in_specs=[pl.BlockSpec((1, KV, rows_q, hd), _tile_of(4))] + page_specs,
        out_specs=pl.BlockSpec((1, KV, rows_q, hd), _tile_of(4)),
        scratch_shapes=[
            pltpu.VMEM((KV, rows_q, 1), jnp.float32),
            pltpu.VMEM((KV, rows_q, 1), jnp.float32),
            pltpu.VMEM((KV, rows_q, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(_block_ragged_kernel, tile=Q_TILE)
    if head_dim is not None:
        kernel = functools.partial(kernel, head_dim=head_dim)
    if window is not None:
        kernel = functools.partial(kernel, window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_table, kv_lens, row_ids, q_pos, lead, starts, *first, q,
      *page_operands)


def _pad_pack(qg, rows, qpos):
    """Pad the packed token axis to a Q_TILE multiple with the pack's own
    pad contract (row 0, position −1): pad tokens mask everywhere and
    their output slice is dropped."""
    T = qg.shape[0]
    Tp = -(-T // Q_TILE) * Q_TILE
    if Tp == T:
        return qg, rows, qpos
    pad = Tp - T
    qg = jnp.concatenate(
        [qg, jnp.zeros((pad,) + qg.shape[1:], qg.dtype)])
    rows = jnp.concatenate([rows, jnp.zeros((pad,), jnp.int32)])
    qpos = jnp.concatenate([qpos, jnp.full((pad,), -1, jnp.int32)])
    return qg, rows, qpos


def _block_ragged(q, k_pages, v_pages, k_scales, v_scales, page_table,
                  q_positions, kv_lens, row_ids, interpret, window=None):
    _, T, H, hd = q.shape
    # heads side by side in the pool (1: the pool is [NP, page, KV, hd])
    p = k_pages.shape[3] // hd
    KV = k_pages.shape[2] * p
    G = H // KV
    qg, rows, qpos = _pad_pack(W.pack_queries(q.reshape(T, KV, G, hd), p),
                               row_ids.astype(jnp.int32),
                               q_positions.reshape(T).astype(jnp.int32))
    out = _block_ragged_call(_fold_tile(qg), k_pages, v_pages,
                             k_scales, v_scales,
                             page_table.astype(jnp.int32),
                             kv_lens.astype(jnp.int32),
                             rows, qpos, interpret=interpret,
                             head_dim=hd if p > 1 else None, window=window)
    out = _unfold_tile(out, p * G)
    if p > 1:
        out = W.unpack_outputs(out.reshape(-1, KV // p, p * G, p * hd), p)
    return out[:T].reshape(1, T, H, hd)


def ragged_paged_attention_pallas(q, k_pages, v_pages, page_table,
                                  q_positions, kv_lens, row_ids,
                                  interpret: bool = False, window=None):
    """Drop-in for ``ragged_paged_attention_xla`` (q packed [1, T, H, hd]),
    block-ragged grid."""
    return _block_ragged(q, k_pages, v_pages, None, None, page_table,
                         q_positions, kv_lens, row_ids, interpret, window)


# ---- int8 (quantized pool) variant ------------------------------------------


def ragged_paged_attention_pallas_q(q, k_pages, v_pages, page_table,
                                    q_positions, kv_lens, row_ids,
                                    k_scales, v_scales,
                                    interpret: bool = False):
    """Quantized-pool drop-in: scales arrive [NP, page, KV, 1] (the pool
    layout) and are squeezed for the kernel."""
    return _block_ragged(q, k_pages, v_pages,
                         k_scales[..., 0], v_scales[..., 0], page_table,
                         q_positions, kv_lens, row_ids, interpret)


# ---- MLA (latent) block-ragged kernels --------------------------------------
#
# Same tiling over the MQA-shaped latent pools: scores = q_lat·c + q_pe·pe
# per slot, values ARE the latents (c), so an active (row, page) step
# streams one (c, pe) page pair and attends every tile token of that row
# across all H heads at once. int8 latent pools fold the c/pe scales
# algebraically — the c scale multiplies both the score's latent term and
# the probs before the value dot (values are c), the pe scale only the
# RoPE term.


def _block_ragged_mla_kernel(
    # scalar prefetch
    page_table_ref, kv_lens_ref, row_ids_ref, q_pos_ref, lead_ref,
    starts_ref,
    # blocks — the tile's tokens are folded into the query-row axis
    ql_ref,           # [TILE·H, dc]
    qp_ref,           # [TILE·H, dr]
    *refs,            # the item's pages: n c refs [1, page, dc] and n
                      # pe refs [1, page, dr rounded up to 128] (int8
                      # pools: then n + n scale refs [1, page, 1] f32);
                      # out_ref [TILE·H, dc];
                      # scratch: m, l [TILE·H, 1], acc [TILE·H, dc]
    scale: float,
    tile: int,
):
    *pages, out_ref, m_ref, l_ref, acc_ref = refs
    w = pl.program_id(0)
    t, block = W.find_item(starts_ref, w, row_ids_ref.shape[0])
    t0 = t // tile * tile
    page = pages[0].shape[1]
    token0 = block * (W.pages_per_block(page) * page)   # the block's first slot

    @pl.when((t == t0) & (block == 0))
    def _init():
        W.init_softmax(m_ref, l_ref, acc_ref)

    @pl.when(token0 < lead_ref[t])
    def _attend():
        rows_q = ql_ref.shape[0]
        limits, _ = _tile_limits(row_ids_ref, kv_lens_ref, q_pos_ref, t0,
                                 row_ids_ref[t], tile, rows_q // tile)
        c, pe, cs, ps = W.load_latent_blocks(pages)
        W.mla_attend(ql_ref[...], qp_ref[...], c, pe, cs, ps, token0,
                     limits, scale, m_ref, l_ref, acc_ref)

    @pl.when(w + 1 == starts_ref[t0 + tile])
    def _finalize():
        out_ref[...] = W.finalize_softmax(l_ref, acc_ref, out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _block_ragged_mla_call(ql, qp, c_pages, pe_pages, c_scales, pe_scales,
                           page_table, kv_lens, row_ids, q_pos, scale,
                           interpret=False):
    """ql: [Tp·H, dc], qp: [Tp·H, dr] packed (Tp a Q_TILE multiple, H a
    static divisor of the block); pages: c [NP, page, 1, dc], pe
    [NP, page, 1, dr rounded up to 128]; scales (int8 pools) [NP, page, 1]
    f32 or None. Returns [Tp·H, dc]."""
    dc = ql.shape[1]
    dr = qp.shape[1]
    Tp = row_ids.shape[0]
    rows_q = ql.shape[0] // Tp * Q_TILE                     # TILE·H
    page = c_pages.shape[1]
    lead, starts = _tile_segments(row_ids, q_pos, kv_lens, page)
    pools = W.latent_pools(c_pages, pe_pages)
    if c_scales is not None:
        pools += (c_scales, pe_scales)
    page_specs, page_operands = W.block_specs(
        pools, functools.partial(_page_id, page=page))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(starts[Tp],),
        in_specs=[pl.BlockSpec((rows_q, dc), _tile_of(2)),
                  pl.BlockSpec((rows_q, dr), _tile_of(2))] + page_specs,
        out_specs=pl.BlockSpec((rows_q, dc), _tile_of(2)),
        scratch_shapes=[
            pltpu.VMEM((rows_q, 1), jnp.float32),
            pltpu.VMEM((rows_q, 1), jnp.float32),
            pltpu.VMEM((rows_q, dc), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_block_ragged_mla_kernel, scale=scale, tile=Q_TILE),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(ql.shape, ql.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_table, kv_lens, row_ids, q_pos, lead, starts, ql, qp,
      *page_operands)


def _block_ragged_mla(q_lat, q_pe, c_pages, pe_pages, c_scales, pe_scales,
                      page_table, q_positions, kv_lens, row_ids, scale,
                      interpret):
    _, T, H, dc = q_lat.shape
    ql, rows, qpos = _pad_pack(q_lat.reshape(T, H, dc),
                               row_ids.astype(jnp.int32),
                               q_positions.reshape(T).astype(jnp.int32))
    qp = q_pe.reshape(T, H, -1)
    Tp = ql.shape[0]
    if Tp != T:
        qp = jnp.concatenate(
            [qp, jnp.zeros((Tp - T,) + qp.shape[1:], qp.dtype)])
    # Row-major [Tp, H, d] → [Tp·H, d] is free in XLA; inside the kernel
    # it would be a shape cast Mosaic has to find a layout for.
    out = _block_ragged_mla_call(ql.reshape(Tp * H, dc),
                                 qp.reshape(Tp * H, -1),
                                 c_pages, pe_pages, c_scales, pe_scales,
                                 page_table.astype(jnp.int32),
                                 kv_lens.astype(jnp.int32), rows, qpos,
                                 scale=float(scale), interpret=interpret)
    return out.reshape(Tp, H, dc)[:T].reshape(1, T, H, dc)


def ragged_paged_mla_attention_pallas(q_lat, q_pe, c_pages, pe_pages,
                                      page_table, q_positions, kv_lens,
                                      row_ids, scale,
                                      interpret: bool = False):
    """Drop-in for ``ragged_paged_mla_attention_xla`` (q_lat packed
    [1, T, H, dc]), block-ragged grid over the latent pools."""
    return _block_ragged_mla(q_lat, q_pe, c_pages, pe_pages, None, None,
                             page_table, q_positions, kv_lens, row_ids,
                             scale, interpret)


def ragged_paged_mla_attention_pallas_q(q_lat, q_pe, c_pages, pe_pages,
                                        page_table, q_positions, kv_lens,
                                        row_ids, scale, c_scales, pe_scales,
                                        interpret: bool = False):
    """Quantized-latent-pool drop-in: scales arrive [NP, page, 1, 1] (the
    pool layout) and are squeezed for the kernel."""
    return _block_ragged_mla(q_lat, q_pe, c_pages, pe_pages,
                             c_scales[..., 0], pe_scales[..., 0],
                             page_table, q_positions, kv_lens, row_ids,
                             scale, interpret)
