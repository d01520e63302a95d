"""Pallas TPU kernels: ragged paged attention (mixed prefill/decode rows).

Round 1 (PR 7) gridded over PACKED TOKENS — grid (T, P), one query token
per outer step — which made the structural win (ONE dispatch serves an
arbitrary prefill/decode mix) but paid a bandwidth tax: a prefill row's
pages were streamed HBM→VMEM once PER TOKEN of the chunk. Round 2 is the
block-ragged tiling of the RPA paper (PAPERS.md): query TILES that span
row boundaries, so each KV page a tile needs streams once per tile.

Block-ragged grid = (T/TILE, TILE, TILE·P inner steps collapsed to
(row-in-tile, page)):

* the packed token axis is padded to a multiple of ``Q_TILE`` (pad tokens
  carry ``q_position == -1`` — the SAME pad contract as the pack itself)
  and the wrapper folds each tile's tokens into the query-row axis in
  XLA, so the q/out BlockSpecs move one ``[KV, TILE·G, hd]`` (MLA:
  ``[TILE·H, dc]``) tile per outer step and the kernel body holds no
  shape cast — Mosaic (v5e, jax 0.9) refuses to merge a G-row axis
  smaller than a sublane tile, and has no layout for a rank-1 vector of
  per-token limits, so those are built against a 2-D iota instead;
* inner step ``(r, p)`` nominates packed token ``t = tile·TILE + r`` and
  logical page ``p`` of ``row_ids[t]``. The kernel computes FIRST-
  OCCURRENCE leadership from the scalar-prefetched ``row_ids``: only the
  first token of each distinct row in the tile activates its row's page
  walk, and an active step attends EVERY tile token of that row at once
  (per-token causal limits masked in-softmax). A row with a C-token chunk
  in the tile therefore streams its pages once, not C times;
* the k/v index_map clamps followers and past-limit pages to the
  previously streamed page index — consecutive grid steps with an equal
  block index make the Pallas pipeline SKIP the copy, so duplicate-row
  and past-limit steps cost loop overhead only, no HBM traffic (the
  token-grid kernel DMA'd dead pages; this one doesn't);
* causal masking is unchanged: token ``t`` attends slots
  ``< min(kv_lens[row_ids[t]], q_positions[t] + 1)``; pad tokens
  (position −1) have limit ≤ 0 → always masked → zero accumulators
  finalize to zero through the denom guard.

Honest cost note: decode rows sharing a tile with a prefill tail attend
with ``TILE×`` the query rows per page (mostly masked) — the tile trades
masked MXU lanes (underfilled at small G anyway) for the page-streaming
win, exactly the RPA paper's trade. Pure-decode batches never reach this
kernel (the engine's fused multi-step path owns them).

The PR-7 token-grid kernel is kept as ``*_tokengrid`` — it is the bench
A/B baseline (``bench.py mixed`` re-runs old-grid vs block-ragged) and a
second correctness reference for the tile math.

Same family of int8 variants as the decode kernel: scales fold
algebraically into scores/probs, pages feed the MXU as int8. The MLA
(latent) ragged kernels live here too — same tiling over the ``c/pe``
pools, re-exported via paged_attention_kernel for ``dispatch_pallas``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

# Query-tile length of the block-ragged grid. 8 packed tokens per tile
# multiplies the MXU's query rows by 8 (G is small under GQA) and divides
# a prefill chunk's page re-streams by 8.
Q_TILE = 8

# Grid revision — part of the engine's ragged program-cache key
# (warm_ragged): a cache warmed for the PR-7 token grid must not alias
# programs compiled for the block-ragged grid.
RAGGED_GRID_REV = 2


def _tile_leadership(row_ids_ref, kv_lens_ref, q_pos_ref, t0, r_off, row,
                     tile):
    """Scalar scan over one tile: is token ``t0 + r_off`` the FIRST
    occurrence of ``row`` in the tile, and what is the row's max causal
    limit across its tile tokens? Returns (dup, row_limit) — ``dup`` True
    means a smaller r_off already leads this row (this step skips), and
    ``row_limit`` bounds the page walk (≤ 0 for all-pad rows: their
    positions are −1, so no page ever activates)."""
    def body(k, carry):
        dup, lim = carry
        rk = row_ids_ref[t0 + k]
        same = rk == row
        dup = dup | (same & (k < r_off))
        tok_lim = jnp.minimum(kv_lens_ref[row], q_pos_ref[t0 + k] + 1)
        lim = jnp.maximum(lim, jnp.where(same, tok_lim, 0))
        return dup, lim
    return jax.lax.fori_loop(
        0, tile, body,
        (jnp.zeros((), jnp.bool_), jnp.zeros((), jnp.int32)))


def _tile_limits(row_ids_ref, kv_lens_ref, q_pos_ref, t0, row, tile, group):
    """Per-query-row causal limits of one tile as a ``[TILE·group, 1]``
    column (query row ``j`` belongs to tile token ``j // group``). Tokens
    of OTHER rows get limit 0 (fully masked), so every tile token rides
    the same softmax update and only ``row``'s tokens accumulate.

    Built from SMEM scalars by ``tile`` selects against a 2-D iota: Mosaic
    has no layout for a rank-1 vector of stacked scalars, nor for the
    shape casts that would broadcast one."""
    j = jax.lax.broadcasted_iota(jnp.int32, (tile * group, 1), 0)
    limits = jnp.zeros((tile * group, 1), jnp.int32)
    for k in range(tile):
        rk = row_ids_ref[t0 + k]
        lim_k = jnp.where(
            rk == row,
            jnp.minimum(kv_lens_ref[rk], q_pos_ref[t0 + k] + 1), 0)
        limits = jnp.where(j >= k * group, lim_k, limits)
    return limits


def _block_ragged_kernel(
    # scalar prefetch
    page_table_ref,   # [R, P] int32 (SMEM)
    kv_lens_ref,      # [R] int32 (SMEM)
    row_ids_ref,      # [Tp] int32 (SMEM) — Tp padded to a Q_TILE multiple
    q_pos_ref,        # [Tp] int32 (SMEM)
    # blocks
    q_ref,            # [1, KV, TILE·G, hd] (VMEM) — one query tile, the
                      # tile's tokens already folded into the query-row axis
    k_ref,            # [1, page, KV, hd] — the page picked by index_map
    v_ref,
    out_ref,          # [1, KV, TILE·G, hd]
    # scratch — online softmax state for the WHOLE tile
    m_ref,            # [KV, TILE·G, 1] running max
    l_ref,            # [KV, TILE·G, 1] running denom
    acc_ref,          # [KV, TILE·G, hd] running numerator
    *,
    tile: int,
    ks_ref=None,      # int8 pools: [1, page, KV] f32 scales
    vs_ref=None,
):
    i = pl.program_id(0)          # tile
    r_off = pl.program_id(1)      # row-slot within the tile
    p = pl.program_id(2)          # logical page of that slot's row
    num_r = pl.num_programs(1)
    num_p = pl.num_programs(2)
    page = k_ref.shape[1]
    quantized = ks_ref is not None

    @pl.when((r_off == 0) & (p == 0))
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    t0 = i * tile
    row = row_ids_ref[t0 + r_off]
    dup, row_limit = _tile_leadership(row_ids_ref, kv_lens_ref, q_pos_ref,
                                      t0, r_off, row, tile)

    # One active step per (row-in-tile, live page): the row's first tile
    # occurrence walks its causal pages; duplicates and past-limit pages
    # skip (their DMAs are elided by the clamped index_map).
    @pl.when(jnp.logical_not(dup) & (p * page < row_limit))
    def _attend():
        rows_q, hd = q_ref.shape[2], q_ref.shape[3]
        limits = _tile_limits(row_ids_ref, kv_lens_ref, q_pos_ref, t0, row,
                              tile, rows_q // tile)         # [TILE·G, 1]

        # The tile's whole query block rides ONE batched dot per page.
        qm = q_ref[0].astype(jnp.float32)                   # [KV,TILE·G,hd]
        k = k_ref[0].astype(jnp.float32)                    # [page, KV, hd]
        v = v_ref[0].astype(jnp.float32)

        k_t = jnp.transpose(k, (1, 0, 2))                   # [KV, page, hd]
        v_t = jnp.transpose(v, (1, 0, 2))
        scores = jax.lax.dot_general(
            qm, k_t,
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * (1.0 / (hd ** 0.5))                             # [KV,TILE·G,page]
        if quantized:
            ks_t = jnp.transpose(ks_ref[0], (1, 0))         # [KV, page]
            scores = scores * ks_t[:, None, :]

        token_idx = p * page + jax.lax.broadcasted_iota(
            jnp.int32, (rows_q, page), dimension=1)
        mask = token_idx < limits                           # [TILE·G, page]
        scores = jnp.where(mask[None], scores, _NEG_INF)

        m_prev = m_ref[:]                                   # [KV, TILE·G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(scores - m_new)                     # fully-masked
        # tokens: m_new == m_prev → alpha 1, probs 0 → their state is a
        # no-op this step (no special casing).
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        pmat = probs
        if quantized:
            vs_t = jnp.transpose(vs_ref[0], (1, 0))         # [KV, page]
            pmat = probs * vs_t[:, None, :]
        pv = jax.lax.dot_general(
            pmat, v_t,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                   # [KV, TILE·G, hd]
        acc_ref[:] = acc_ref[:] * alpha + pv

    @pl.when((r_off == num_r - 1) & (p == num_p - 1))
    def _finalize():
        denom = jnp.maximum(l_ref[:], 1e-30)                # guard pad rows
        out_ref[0] = (acc_ref[:] / denom).astype(out_ref.dtype)


def _kv_page_index(i, r, p, table, lens, rows, *, tile, page):
    """Block index for the k/v (and scale) specs at inner step (r, p).

    RUN-leaders (first token of a consecutive same-row run — a superset
    of the kernel's first-occurrence leaders, so every active step gets
    its real page) stream page ``min(p, last-live-page)``; followers and
    past-limit steps repeat the PREVIOUS step's index, which makes the
    Pallas pipeline elide their copies entirely. A same-row run's last
    leader step and all its follower steps resolve to the same
    ``table[row, last]``, so the chain of equal indices is unbroken."""
    t = i * tile + r
    row = rows[t]
    prev_row = rows[jnp.maximum(t - 1, 0)]
    lead = (r == 0) | (prev_row != row)
    last = jnp.maximum((lens[row] - 1) // page, 0)
    return jnp.where(lead, jnp.minimum(p, last), last), row


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


@functools.partial(jax.jit, static_argnames=("interpret",))
def _block_ragged_call(q, k_pages, v_pages, k_scales, v_scales, page_table,
                       kv_lens, row_ids, q_pos, interpret=False):
    """q: [Tp/TILE, KV, TILE·G, hd] folded tiles; pages: [NP, page, KV,
    hd]; scales (int8 pools) [NP, page, KV] f32 or None. Returns q's
    shape."""
    NT, KV, rows_q, hd = q.shape
    _, page, _, _ = k_pages.shape
    P = page_table.shape[1]
    tile = Q_TILE

    def pick4(i, r, p, table, lens, rows, qpos):
        pidx, row = _kv_page_index(i, r, p, table, lens, rows,
                                   tile=tile, page=page)
        return (table[row, pidx], 0, 0, 0)

    def pick3(i, r, p, table, lens, rows, qpos):
        return pick4(i, r, p, table, lens, rows, qpos)[:3]

    fixed = lambda i, r, p, table, lens, rows, qpos: (i, 0, 0, 0)
    in_specs = [
        pl.BlockSpec((1, KV, rows_q, hd), fixed),
        pl.BlockSpec((1, page, KV, hd), pick4),
        pl.BlockSpec((1, page, KV, hd), pick4),
    ]
    args = (page_table, kv_lens, row_ids, q_pos, q, k_pages, v_pages)
    kernel = _block_ragged_kernel
    if k_scales is not None:
        kernel = _block_ragged_kernel_q
        in_specs += [pl.BlockSpec((1, page, KV), pick3),
                     pl.BlockSpec((1, page, KV), pick3)]
        args += (k_scales, v_scales)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(NT, tile, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KV, rows_q, hd), fixed),
        scratch_shapes=[
            pltpu.VMEM((KV, rows_q, 1), jnp.float32),
            pltpu.VMEM((KV, rows_q, 1), jnp.float32),
            pltpu.VMEM((KV, rows_q, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(kernel, tile=tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(*args)


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
                  q_positions, kv_lens, row_ids, interpret):
    _, T, H, hd = q.shape
    KV = k_pages.shape[2]
    G = H // KV
    qg, rows, qpos = _pad_pack(q.reshape(T, KV, G, hd),
                               row_ids.astype(jnp.int32),
                               q_positions.reshape(T).astype(jnp.int32))
    out = _block_ragged_call(_fold_tile(qg), k_pages, v_pages,
                             k_scales, v_scales,
                             page_table.astype(jnp.int32),
                             kv_lens.astype(jnp.int32),
                             rows, qpos, interpret=interpret)
    return _unfold_tile(out, G)[:T].reshape(1, T, H, hd)


def ragged_paged_attention_pallas(q, k_pages, v_pages, page_table,
                                  q_positions, kv_lens, row_ids,
                                  interpret: bool = False):
    """Drop-in for ``ragged_paged_attention_xla`` (q packed [1, T, H, hd]),
    block-ragged grid."""
    return _block_ragged(q, k_pages, v_pages, None, None, page_table,
                         q_positions, kv_lens, row_ids, interpret)


# ---- int8 (quantized pool) variant ------------------------------------------


def _block_ragged_kernel_q(
    # scalar prefetch
    page_table_ref, kv_lens_ref, row_ids_ref, q_pos_ref,
    # blocks
    q_ref, k_ref, v_ref,
    ks_ref,           # [1, page, KV] f32 scales
    vs_ref,
    out_ref,
    # scratch
    m_ref, l_ref, acc_ref,
    *,
    tile: int,
):
    _block_ragged_kernel(page_table_ref, kv_lens_ref, row_ids_ref,
                         q_pos_ref, q_ref, k_ref, v_ref, out_ref,
                         m_ref, l_ref, acc_ref, tile=tile,
                         ks_ref=ks_ref, vs_ref=vs_ref)


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
    page_table_ref, kv_lens_ref, row_ids_ref, q_pos_ref,
    # blocks — the tile's tokens are folded into the query-row axis
    ql_ref,           # [TILE·H, dc]
    qp_ref,           # [TILE·H, dr]
    c_ref,            # [1, page, 1, dc]
    pe_ref,           # [1, page, 1, dr]
    out_ref,          # [TILE·H, dc]
    # scratch
    m_ref,            # [TILE·H, 1]
    l_ref,            # [TILE·H, 1]
    acc_ref,          # [TILE·H, dc]
    *,
    scale: float,
    tile: int,
    cs_ref=None,      # int8 pools: [1, page, 1] f32 scales
    ps_ref=None,
):
    i = pl.program_id(0)
    r_off = pl.program_id(1)
    p = pl.program_id(2)
    num_r = pl.num_programs(1)
    num_p = pl.num_programs(2)
    page = c_ref.shape[1]
    quantized = cs_ref is not None

    @pl.when((r_off == 0) & (p == 0))
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    t0 = i * tile
    row = row_ids_ref[t0 + r_off]
    dup, row_limit = _tile_leadership(row_ids_ref, kv_lens_ref, q_pos_ref,
                                      t0, r_off, row, tile)

    @pl.when(jnp.logical_not(dup) & (p * page < row_limit))
    def _attend():
        rows_q = ql_ref.shape[0]
        limits = _tile_limits(row_ids_ref, kv_lens_ref, q_pos_ref, t0, row,
                              tile, rows_q // tile)         # [TILE·H, 1]

        ql = ql_ref[...].astype(jnp.float32)                # [TILE·H, dc]
        qp = qp_ref[...].astype(jnp.float32)                # [TILE·H, dr]
        c = c_ref[0, :, 0, :].astype(jnp.float32)           # [page, dc]
        pe = pe_ref[0, :, 0, :].astype(jnp.float32)         # [page, dr]

        s_c = jax.lax.dot_general(ql, c, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        s_pe = jax.lax.dot_general(qp, pe, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        if quantized:
            s_c = s_c * cs_ref[0, :, 0][None, :]
            s_pe = s_pe * ps_ref[0, :, 0][None, :]
        scores = (s_c + s_pe) * scale                       # [TILE·H, page]

        token_idx = p * page + jax.lax.broadcasted_iota(
            jnp.int32, (rows_q, page), dimension=1)
        scores = jnp.where(token_idx < limits, scores, _NEG_INF)

        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(scores - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        pmat = probs
        if quantized:
            # Values are the latents: the c scale folds into probs BEFORE
            # the value dot, same algebra as the GQA v-scale fold.
            pmat = probs * cs_ref[0, :, 0][None, :]
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            pmat, c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [TILE·H, dc]

    @pl.when((r_off == num_r - 1) & (p == num_p - 1))
    def _finalize():
        denom = jnp.maximum(l_ref[:], 1e-30)
        out_ref[...] = (acc_ref[:] / denom).astype(out_ref.dtype)


def _block_ragged_mla_kernel_q(
    page_table_ref, kv_lens_ref, row_ids_ref, q_pos_ref,
    ql_ref, qp_ref, c_ref, pe_ref,
    cs_ref,           # [1, page, 1] f32 scales
    ps_ref,
    out_ref,
    m_ref, l_ref, acc_ref,
    *,
    scale: float,
    tile: int,
):
    _block_ragged_mla_kernel(page_table_ref, kv_lens_ref, row_ids_ref,
                             q_pos_ref, ql_ref, qp_ref, c_ref, pe_ref,
                             out_ref, m_ref, l_ref, acc_ref,
                             scale=scale, tile=tile,
                             cs_ref=cs_ref, ps_ref=ps_ref)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _block_ragged_mla_call(ql, qp, c_pages, pe_pages, c_scales, pe_scales,
                           page_table, kv_lens, row_ids, q_pos, scale,
                           interpret=False):
    """ql: [Tp·H, dc], qp: [Tp·H, dr] packed (Tp a Q_TILE multiple, H a
    static divisor of the block); pages: [NP, page, 1, d]; scales (int8
    pools) [NP, page, 1] f32 or None. Returns [Tp·H, dc]."""
    dc = ql.shape[1]
    dr = qp.shape[1]
    Tp = row_ids.shape[0]
    rows_q = ql.shape[0] // Tp * Q_TILE                     # TILE·H
    _, page, _, _ = c_pages.shape
    P = page_table.shape[1]
    tile = Q_TILE

    def pick4(i, r, p, table, lens, rows, qpos):
        pidx, row = _kv_page_index(i, r, p, table, lens, rows,
                                   tile=tile, page=page)
        return (table[row, pidx], 0, 0, 0)

    def pick3(i, r, p, table, lens, rows, qpos):
        return pick4(i, r, p, table, lens, rows, qpos)[:3]

    fixed = lambda i, r, p, table, lens, rows, qpos: (i, 0)
    in_specs = [
        pl.BlockSpec((rows_q, dc), fixed),
        pl.BlockSpec((rows_q, dr), fixed),
        pl.BlockSpec((1, page, 1, dc), pick4),
        pl.BlockSpec((1, page, 1, dr), pick4),
    ]
    args = (page_table, kv_lens, row_ids, q_pos, ql, qp, c_pages, pe_pages)
    kernel = _block_ragged_mla_kernel
    if c_scales is not None:
        kernel = _block_ragged_mla_kernel_q
        in_specs += [pl.BlockSpec((1, page, 1), pick3),
                     pl.BlockSpec((1, page, 1), pick3)]
        args += (c_scales, pe_scales)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(Tp // tile, tile, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rows_q, dc), fixed),
        scratch_shapes=[
            pltpu.VMEM((rows_q, 1), jnp.float32),
            pltpu.VMEM((rows_q, 1), jnp.float32),
            pltpu.VMEM((rows_q, dc), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(kernel, scale=scale, tile=tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(ql.shape, ql.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(*args)


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


# ---- PR-7 token-grid kernels (retained baseline) ----------------------------
#
# The round-1 grid: (T, P), one packed token per outer step — a prefill
# row's pages stream once per token. Kept (not dispatched) as the bench
# A/B baseline for the block-ragged grid and as a second correctness
# reference; ``bench.py mixed`` interleaves it against the tile grid.


def _ragged_kernel(
    # scalar prefetch
    page_table_ref,   # [R, P] int32 (SMEM)
    kv_lens_ref,      # [R] int32 (SMEM)
    row_ids_ref,      # [T] int32 (SMEM)
    q_pos_ref,        # [T] int32 (SMEM)
    # blocks
    q_ref,            # [1, KV, G, hd] (VMEM) — the packed token t
    k_ref,            # [1, page, KV, hd] — the page picked by index_map
    v_ref,
    out_ref,          # [1, KV, G, hd]
    # scratch
    m_ref,            # [KV, G, 1] running max
    l_ref,            # [KV, G, 1] running denom
    acc_ref,          # [KV, G, hd] running numerator
    *,
    ks_ref=None,      # int8 pools: [1, page, KV] f32 scales
    vs_ref=None,
):
    t = pl.program_id(0)
    p = pl.program_id(1)
    num_p = pl.num_programs(1)
    page = k_ref.shape[1]
    quantized = ks_ref is not None

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Ragged causal limit: within the live cache AND within this token's
    # causal prefix (slot index == absolute position). Pad tokens carry
    # q_position == -1 (the pack contract) → limit ≤ 0 → every page is
    # skipped and the zero accumulators finalize to a zero output.
    limit = jnp.minimum(kv_lens_ref[row_ids_ref[t]], q_pos_ref[t] + 1)

    @pl.when(p * page < limit)
    def _attend():
        q = q_ref[0].astype(jnp.float32)                    # [KV, G, hd]
        k = k_ref[0].astype(jnp.float32)                    # [page, KV, hd]
        v = v_ref[0].astype(jnp.float32)
        hd = q.shape[-1]

        k_t = jnp.transpose(k, (1, 0, 2))                   # [KV, page, hd]
        v_t = jnp.transpose(v, (1, 0, 2))
        scores = jax.lax.dot_general(
            q, k_t,
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * (1.0 / (hd ** 0.5))                             # [KV, G, page]
        if quantized:
            ks_t = jnp.transpose(ks_ref[0], (1, 0))         # [KV, page]
            scores = scores * ks_t[:, None, :]

        token_idx = p * page + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, dimension=2)
        scores = jnp.where(token_idx < limit, scores, _NEG_INF)

        m_prev = m_ref[:]                                   # [KV, G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(scores - m_new)                     # [KV, G, page]

        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        pmat = probs
        if quantized:
            vs_t = jnp.transpose(vs_ref[0], (1, 0))         # [KV, page]
            pmat = probs * vs_t[:, None, :]
        pv = jax.lax.dot_general(
            pmat, v_t,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                   # [KV, G, hd]
        acc_ref[:] = acc_ref[:] * alpha + pv

    @pl.when(p == num_p - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:], 1e-30)                # guard empty rows
        out_ref[0] = (acc_ref[:] / denom).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ragged_call(q, k_pages, v_pages, page_table, kv_lens, row_ids, q_pos,
                 interpret=False):
    """q: [T, KV, G, hd] packed; pages: [NP, page, KV, hd].
    Returns [T, KV, G, hd]."""
    T, KV, G, hd = q.shape
    _, page, _, _ = k_pages.shape
    P = page_table.shape[1]

    pick = lambda t, p, table, lens, rows, qpos: (table[rows[t], p], 0, 0, 0)
    fixed = lambda t, p, table, lens, rows, qpos: (t, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(T, P),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd), fixed),
            pl.BlockSpec((1, page, KV, hd), pick),
            pl.BlockSpec((1, page, KV, hd), pick),
        ],
        out_specs=pl.BlockSpec((1, KV, G, hd), fixed),
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        _ragged_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, KV, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(page_table, kv_lens, row_ids, q_pos, q, k_pages, v_pages)


def ragged_paged_attention_pallas_tokengrid(q, k_pages, v_pages, page_table,
                                            q_positions, kv_lens, row_ids,
                                            interpret: bool = False):
    """PR-7 token-grid variant of ``ragged_paged_attention_pallas`` —
    bench baseline, not dispatched by the engine."""
    _, T, H, hd = q.shape
    KV = k_pages.shape[2]
    G = H // KV
    qg = q.reshape(T, KV, G, hd)
    out = _ragged_call(qg, k_pages, v_pages,
                       page_table.astype(jnp.int32),
                       kv_lens.astype(jnp.int32),
                       row_ids.astype(jnp.int32),
                       q_positions.reshape(T).astype(jnp.int32),
                       interpret=interpret)
    return out.reshape(1, T, H, hd)
