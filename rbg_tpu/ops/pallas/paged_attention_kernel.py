"""Pallas TPU kernel: decode-phase paged attention.

Why a kernel: the XLA fallback (`paged_attention_xla`) materializes the
gathered per-sequence KV view ``[B, S, KV, hd]`` in HBM before attending —
every decode step pays ~3× the pool's live-token traffic (gather write +
attention read, plus the pool read). Decode attention is pure HBM bandwidth,
so this kernel streams each live page HBM→VMEM exactly once and keeps the
flash-style online softmax state in VMEM scratch.

Design (see /opt/skills/guides/pallas_guide.md and ``page_walk.py``):
* the grid is ONE axis of dynamic length: the rows' live blocks of pages,
  row after row ("arbitrary" semantics — scratch accumulators persist
  across a row's walk). The grid used to be (B, P), every page of
  ``max_seq_len`` for every row; a row of 900 tokens under a table 512
  wide spent eight steps in nine stepping over nothing.
* the walk's items are resolved ONCE A CALL, in XLA
  (``page_walk.walk_items``): the row, the block of the row and the
  physical pages of every work item, scalar-prefetched beside kv_lens
  and the rows' cumulative block counts. An index map is one read of
  that table, so the pipeline DMAs the RIGHT physical pages ahead of
  compute (double-buffered by the Pallas pipeline itself) at the cost of
  a load. Until PR 40 every map of every operand bisected the counts and
  walked the page table itself, and the scalar core's work bound the
  walk (1.3-1.5 us a block of 64 cached tokens, 0.8 with the table).
  Entries past a row's live pages are never followed.
* a block is ``page_walk.decode_pages_per_block`` pages (128 slots), twice
  the ragged kernels': with maps that cost nothing to trace the width is
  the kernel's to choose.
* bf16 pools whose pages are whole tiles take neither the grid nor the
  item table (``page_walk.kernel_copies``, read off the pools; PRs 51, 53):
  ``_decode_copies_kernel`` and ``_mla_decode_copies_kernel`` are one
  program over every row, the pools left in HBM, that walk a row's live
  blocks in a loop and issue each block's page copies themselves into
  one double buffer, the next block's before this one is attended
  (``page_walk.walk_with_copies``, the one loop under both). Same blocks,
  same order, same mathematics: their outputs are the pipeline's to the
  bit. Every cell of the benchmark walks this way: K/V pools of 8 or 16
  heads of 128, LFM2's heads of 64 packed two to a lane tile, JoyAI's and
  Kimi's latents. THE GRID KERNELS BELOW (``_decode_kernel``,
  ``_mla_decode_kernel``, ``_walk``) now serve only what that path
  refuses: int8 pools with their scales, float32 pools, and heads under a
  lane tile (hd 64 unpacked, the tests' small presets). They stay for
  those (ROADMAP S2 (b) and the int8 configurations need them), and the
  tests hold the two paths to the same bits.
* GQA via one batched dot per block: [KV, G, hd] × [KV, n·page, hd].
* A row of length 0 (a free slot of the batch) keeps one step that
  attends nothing and writes zeros.

Reference context: this is the TPU analog of the ragged/paged attention
kernels the PAPERS.md "Ragged Paged Attention" paper describes. The engine
uses it for pure-decode batches (T == 1) and for the rows of one token of
a step that holds a prefill chunk (a unified step: the rows that hold the
chunks come in at length 0, an empty item each, and run the block-ragged
kernel of ragged_attention_kernel.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rbg_tpu.ops.pallas import page_walk as W

_I32 = np.int32


def _page_id(j, w, item_row, item_block, item_page, lens, starts):
    """Physical page of page ``j`` of decode work item ``w``."""
    return item_page[j, w]


def _row_of(rank):
    def index_map(w, item_row, item_block, item_page, lens, starts):
        return (item_row[w],) + (0,) * (rank - 1)
    return index_map


def _walk(page_table, kv_lens, page, n, window=None):
    """The scalar-prefetch operands of a decode walk (every row is a
    segment) in blocks of ``n`` pages: ``page_walk.walk_items``' three
    arrays, the rows' lengths and their cumulative live blocks, whose
    last is the grid's length. In a window layer a row's walk starts at
    the block that holds token ``kv_len - window``, the oldest its one
    query attends."""
    if window is None:
        starts = W.live_block_starts(kv_lens, page, True, n)
        return (*W.walk_items(starts, kv_lens, page_table, page, n), kv_lens,
                starts)
    first_block, below = W.first_live_block(kv_lens - window, page, n)
    starts = W.live_block_starts(kv_lens - below, page, True, n)
    return (*W.walk_items(starts, kv_lens, page_table, page, n, first_block),
            kv_lens, starts)


def _decode_kernel(
    # scalar prefetch (``_walk``)
    item_row_ref,     # [W + 1] int32 (SMEM) — the item's row
    item_block_ref,   # [W + 1] int32 (SMEM) — its block of the row
    item_page_ref,    # [n, W + 1] int32 (SMEM) — read by the index maps
    kv_lens_ref,      # [B] int32 (SMEM)
    starts_ref,       # [B + 1] int32 (SMEM) — cumulative live blocks
    # blocks
    q_ref,            # [1, KV, G, hd] (VMEM)
    *refs,            # the item's pages, picked by index_map: n k refs and
                      # n v refs [1, page, KV, hd] (int8 pools: then n + n
                      # scale refs [1, page, KV] f32); out_ref [1, KV, G,
                      # hd]; scratch: m [KV, G, 1] running max, l [KV, G, 1]
                      # running denom, acc [KV, G, hd] running numerator
    head_dim=None,    # a head's size where ``hd`` is several packed heads
    window=None,      # a window layer: the query attends its newest slots
):
    *pages, out_ref, m_ref, l_ref, acc_ref = refs
    w = pl.program_id(0)
    b, block = item_row_ref[w], item_block_ref[w]
    kv_len = kv_lens_ref[b]
    page = pages[0].shape[1]
    n = W.decode_pages_per_block(page)
    token0 = block * (n * page)                     # the block's first slot

    # A row's first item: its block 0, or the first live block of a window.
    @pl.when(block == 0 if window is None else w == starts_ref[b])
    def _init():
        W.init_softmax(m_ref, l_ref, acc_ref)

    # False only in the one item of an empty row.
    @pl.when(token0 < kv_len)
    def _attend():
        # int8 pools: per-(slot, head) absmax scales, folded
        # ALGEBRAICALLY, so the int8 pages feed the MXU directly.
        k, v, *scales = W.load_blocks(pages, n)
        ks, vs = scales or (None, None)
        W.gqa_attend(q_ref[0], k, v, ks, vs, token0, kv_len,
                     m_ref, l_ref, acc_ref, head_dim,
                     None if window is None else kv_len - window)

    @pl.when(w + 1 == starts_ref[b + 1])
    def _finalize():
        out_ref[0] = W.finalize_softmax(l_ref, acc_ref, out_ref.dtype)


def _decode_copies_kernel(
    # scalar prefetch
    table_ref,        # [B, P] int32 (SMEM) — the page table, a line a row
    kv_lens_ref,      # [B] int32 (SMEM)
    # operands
    q_ref,            # [B, KV, G, hd] (VMEM), every row
    k_hbm, v_hbm,     # [NP, page, KV, hd] (HBM): the pools, whole
    out_ref,          # [B, KV, G, hd] (VMEM)
    # scratch
    k_buf, v_buf,     # [2, n·page, KV, hd]: the block attended, the block
                      # on its way
    sems,             # DMA semaphores [2 pools, 2 halves]
    m_ref, l_ref, acc_ref,    # as ``_decode_kernel``'s
    head_dim=None,
    window=None,
):
    """``_decode_kernel``'s walk with the copies issued here
    (``page_walk.walk_with_copies``): same blocks, same order, same
    ``gqa_attend``."""
    slots = k_buf.shape[1]

    def attend(b, half, block, kv_len):
        W.gqa_attend(q_ref[b], k_buf[half], v_buf[half], None, None,
                     lax.mul(block, _I32(slots)), kv_len, m_ref, l_ref,
                     acc_ref, head_dim,
                     None if window is None else kv_len - window)

    W.walk_with_copies((k_hbm, v_hbm), (k_buf, v_buf), sems, table_ref,
                       kv_lens_ref, out_ref, m_ref, l_ref, acc_ref, attend,
                       window)


def _copies_call(kernel, queries, pools, page_table, kv_lens, scratch,
                 interpret):
    """One program over every row for pools a kernel may copy from itself
    (``page_walk.kernel_copies``): the page table and the lengths are the
    scalar operands, ``queries`` (and the output, the first's shape) are
    whole in VMEM, the pools stay in HBM with a double buffer a pool and
    one semaphore a pool a half, and nothing runs beside the kernel in
    XLA. ``scratch``: the softmax state's shapes."""
    page = pools[0].shape[1]
    n = W.decode_pages_per_block(page)
    whole = lambda a: pl.BlockSpec(a.shape, lambda i, *_: (0,) * a.ndim)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[whole(q) for q in queries]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=whole(queries[0]),
        scratch_shapes=[
            *(pltpu.VMEM((2, n * page) + p.shape[2:], p.dtype)
              for p in pools),
            pltpu.SemaphoreType.DMA((len(pools), 2)),
            *(pltpu.VMEM(shape, jnp.float32) for shape in scratch),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(queries[0].shape, queries[0].dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_table, kv_lens, *queries, *pools)


def _decode(q, pools, page_table, kv_lens, interpret, head_dim=None,
            window=None):
    """q: [B, KV, G, hd]; pools: k, v pages [NP, page, KV, hd], and for
    int8 pools their scales [NP, page, KV] f32. Returns q's shape.
    ``head_dim``: a head's size where the pool keeps several side by side
    and ``q`` is ``page_walk.pack_queries``'. ``window``: a window layer's
    width (``_walk``)."""
    B, KV, G, hd = q.shape
    if W.kernel_copies(pools):
        return _copies_call(
            functools.partial(_decode_copies_kernel, head_dim=head_dim,
                              window=window),
            (q,), pools, page_table, kv_lens,
            ((KV, G, 1), (KV, G, 1), (KV, G, hd)), interpret)
    page = pools[0].shape[1]
    n = W.decode_pages_per_block(page)
    walk = _walk(page_table, kv_lens, page, n, window)
    page_specs, page_operands = W.block_specs(pools, _page_id, n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(walk),
        grid=(walk[-1][B],),
        in_specs=[pl.BlockSpec((1, KV, G, hd), _row_of(4))] + page_specs,
        out_specs=pl.BlockSpec((1, KV, G, hd), _row_of(4)),
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, hd), jnp.float32),
        ],
    )
    kernel = _decode_kernel if head_dim is None else functools.partial(
        _decode_kernel, head_dim=head_dim)
    if window is not None:
        kernel = functools.partial(kernel, window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*walk, q, *page_operands)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "head_dim", "window"))
def _decode_call(q, k_pages, v_pages, page_table, kv_lens, interpret=False,
                 head_dim=None, window=None):
    return _decode(q, (k_pages, v_pages), page_table, kv_lens, interpret,
                   head_dim, window)


def paged_attention_pallas(q, k_pages, v_pages, page_table, q_positions,
                           kv_lens, interpret: bool = False, window=None):
    """Drop-in for ``paged_attention_xla``. Decode (T == 1) runs the kernel;
    other shapes fall back to the XLA path (the engine sends prefill
    through the ragged kernel, not through here)."""
    B, T, H, hd = q.shape
    if T != 1:
        from rbg_tpu.ops.paged_attention import paged_attention_xla
        return paged_attention_xla(q, k_pages, v_pages, page_table,
                                   q_positions, kv_lens, window=window)
    # heads side by side in the pool (1: the pool is [NP, page, KV, hd])
    p = k_pages.shape[3] // hd
    KV = k_pages.shape[2] * p
    qg = W.pack_queries(q.reshape(B, KV, H // KV, hd), p)
    out = _decode_call(qg, k_pages, v_pages,
                       page_table.astype(jnp.int32),
                       kv_lens.astype(jnp.int32), interpret=interpret,
                       head_dim=hd if p > 1 else None,
                       window=window)
    return W.unpack_outputs(out, p).reshape(B, T, H, hd)


# ---- int8 (quantized pool) decode ------------------------------------------
#
# The SAME kernel body handles quantized pools: pages arrive int8 with
# per-(slot, head) absmax scales alongside (ops/paged_attention.quantize_kv),
# walked as two more pools. Scales are folded ALGEBRAICALLY — they factor
# out of both dot products (scores[kv,g,t] = (q·k_int8)·ks[t] and
# pv = (probs·vs)·v_int8) — so the [S, KV, hd] blocks are never multiplied
# elementwise and the MXU consumes the int8 pages' values directly after
# cast.
#
# Byte accounting (honest): int8 halves the k/v page DMA, but a page's f32
# scales are (page, KV) — the KV lane dim pads to 128 on real hardware, so
# each moves ~page*128*4 B. At page=16/KV=8/hd=128 that is k+v 64 KB (bf16)
# → 32 KB (int8) + ~16 KB padded scales ≈ a 25% net walk saving, not 50%.
# Packing scales lane-major across pages is the documented follow-up seam.


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_call_q(q, k_pages, v_pages, k_scales, v_scales, page_table,
                   kv_lens, interpret=False):
    return _decode(q, (k_pages, v_pages, k_scales, v_scales), page_table,
                   kv_lens, interpret)


def paged_attention_pallas_q(q, k_pages, v_pages, page_table, q_positions,
                             kv_lens, k_scales, v_scales,
                             interpret: bool = False):
    """Quantized-pool drop-in: decode (T == 1) folds the scales into the
    score/prob tensors (never dequantizing the pages elementwise); other
    shapes fall back to the XLA dequant path. Scales arrive as
    [NP, page, KV, 1] (the pool layout) and are squeezed for the
    kernel."""
    B, T, H, hd = q.shape
    KV = k_pages.shape[2]
    if T != 1:
        from rbg_tpu.ops.paged_attention import paged_attention_xla
        return paged_attention_xla(q, k_pages, v_pages, page_table,
                                   q_positions, kv_lens, k_scales, v_scales)
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    out = _decode_call_q(qg, k_pages, v_pages,
                         k_scales[..., 0], v_scales[..., 0],
                         page_table.astype(jnp.int32),
                         kv_lens.astype(jnp.int32), interpret=interpret)
    return out.reshape(B, T, H, hd)


# ---- MLA (latent) decode ----------------------------------------------------
#
# The latent cache is MQA-shaped — ONE shared latent per token (no head
# axis). scores = q_lat·c + q_pe·pe, values ARE the latents, so the page
# walk streams each (c, pe) page HBM→VMEM once and attends all H query
# heads against it. The XLA fallback instead gathers the rows' pages into
# a [B, S, dc] view in HBM every step — at long context that gather (plus
# its attention re-read) is ~3× the live-latent traffic, same argument as
# the GQA kernel above. int8 latent pools walk their per-slot scales
# [NP, page, 1] as two more pools.


def _mla_decode_kernel(
    # scalar prefetch (``_walk``)
    item_row_ref,     # [W + 1] int32 (SMEM) — the item's row
    item_block_ref,   # [W + 1] int32 (SMEM) — its block of the row
    item_page_ref,    # [n, W + 1] int32 (SMEM) — read by the index maps
    kv_lens_ref,      # [B] int32 (SMEM)
    starts_ref,       # [B + 1] int32 (SMEM) — cumulative live blocks
    # blocks
    ql_ref,           # [1, H, dc] (VMEM) — q_nope absorbed through W_uk
    qp_ref,           # [1, H, dr] — RoPE'd query part
    *refs,            # the item's pages, picked by index_map: n c refs
                      # [1, page, dc] and n pe refs [1, page, dr rounded
                      # up to 128] (kvcache.rope_pool_width)
                      # (int8 pools: then n + n scale refs [1, page, 1]
                      # f32); out_ref [1, H, dc] — latent attention output;
                      # scratch: m [H, 1], l [H, 1], acc [H, dc]
    scale: float,
):
    *pages, out_ref, m_ref, l_ref, acc_ref = refs
    w = pl.program_id(0)
    b, block = item_row_ref[w], item_block_ref[w]
    kv_len = kv_lens_ref[b]
    page = pages[0].shape[1]
    n = W.decode_pages_per_block(page)
    token0 = block * (n * page)                     # the block's first slot

    @pl.when(block == 0)
    def _init():
        W.init_softmax(m_ref, l_ref, acc_ref)

    @pl.when(token0 < kv_len)
    def _attend():
        c, pe, cs, ps = W.load_latent_blocks(pages, n)
        W.mla_attend(ql_ref[0], qp_ref[0], c, pe, cs, ps, token0, kv_len,
                     scale, m_ref, l_ref, acc_ref)

    @pl.when(w + 1 == starts_ref[b + 1])
    def _finalize():
        out_ref[0] = W.finalize_softmax(l_ref, acc_ref, out_ref.dtype)


def _mla_decode_copies_kernel(
    # scalar prefetch
    table_ref,        # [B, P] int32 (SMEM) — the page table, a line a row
    kv_lens_ref,      # [B] int32 (SMEM)
    # operands
    ql_ref,           # [B, H, dc] (VMEM), every row
    qp_ref,           # [B, H, dr]
    c_hbm, pe_hbm,    # [NP, page, dc], [NP, page, 128] (HBM): the pools
    out_ref,          # [B, H, dc] (VMEM)
    # scratch
    c_buf, pe_buf,    # [2, n·page, dc], [2, n·page, 128]
    sems,             # DMA semaphores [2 pools, 2 halves]
    m_ref, l_ref, acc_ref,    # as ``_mla_decode_kernel``'s
    scale: float,
):
    """``_mla_decode_kernel``'s walk with the copies issued here
    (``page_walk.walk_with_copies``): same blocks, same order, same
    ``mla_attend``."""
    slots = c_buf.shape[1]

    def attend(b, half, block, kv_len):
        W.mla_attend(ql_ref[b], qp_ref[b], c_buf[half], pe_buf[half], None,
                     None, lax.mul(block, _I32(slots)), kv_len, scale,
                     m_ref, l_ref, acc_ref)

    W.walk_with_copies((c_hbm, pe_hbm), (c_buf, pe_buf), sems, table_ref,
                       kv_lens_ref, out_ref, m_ref, l_ref, acc_ref, attend)


def _mla_decode(q_lat, q_pe, pools, page_table, kv_lens, scale, interpret):
    """q_lat: [B, H, dc], q_pe: [B, H, dr]; pools: c pages
    [NP, page, 1, dc], pe pages [NP, page, 1, dr rounded up to 128], and
    for int8 pools their scales [NP, page, 1] f32.
    Returns the latent attention output [B, H, dc]."""
    pools = W.latent_pools(*pools[:2]) + tuple(pools[2:])
    B, H, dc = q_lat.shape
    if W.kernel_copies(pools):
        return _copies_call(
            functools.partial(_mla_decode_copies_kernel, scale=scale),
            (q_lat, q_pe), pools, page_table, kv_lens,
            ((H, 1), (H, 1), (H, dc)), interpret)
    dr = q_pe.shape[-1]
    page = pools[0].shape[1]
    n = W.decode_pages_per_block(page)
    walk = _walk(page_table, kv_lens, page, n)
    page_specs, page_operands = W.block_specs(pools, _page_id, n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(walk),
        grid=(walk[-1][B],),
        in_specs=[pl.BlockSpec((1, H, dc), _row_of(3)),
                  pl.BlockSpec((1, H, dr), _row_of(3))] + page_specs,
        out_specs=pl.BlockSpec((1, H, dc), _row_of(3)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, dc), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_decode_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_lat.shape, q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*walk, q_lat, q_pe, *page_operands)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _mla_decode_call(q_lat, q_pe, c_pages, pe_pages, page_table, kv_lens,
                     scale, interpret=False):
    return _mla_decode(q_lat, q_pe, (c_pages, pe_pages), page_table,
                       kv_lens, scale, interpret)


def paged_mla_attention_pallas(q_lat, q_pe, c_pages, pe_pages, page_table,
                               q_positions, kv_lens, scale,
                               interpret: bool = False):
    """Drop-in for ``paged_mla_attention`` (the XLA gather path). Decode
    (T == 1) runs the kernel; prefill falls back to XLA."""
    B, T, H, dc = q_lat.shape
    if T != 1:
        from rbg_tpu.ops.mla_attention import paged_mla_attention_xla
        return paged_mla_attention_xla(q_lat, q_pe, c_pages, pe_pages,
                                       page_table, q_positions, kv_lens,
                                       scale)
    out = _mla_decode_call(q_lat[:, 0], q_pe[:, 0], c_pages, pe_pages,
                           page_table.astype(jnp.int32),
                           kv_lens.astype(jnp.int32),
                           scale=float(scale), interpret=interpret)
    return out[:, None]


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _mla_decode_call_q(q_lat, q_pe, c_pages, pe_pages, c_scales, pe_scales,
                       page_table, kv_lens, scale, interpret=False):
    return _mla_decode(q_lat, q_pe, (c_pages, pe_pages, c_scales, pe_scales),
                       page_table, kv_lens, scale, interpret)


def paged_mla_attention_pallas_q(q_lat, q_pe, c_pages, pe_pages, page_table,
                                 q_positions, kv_lens, scale,
                                 c_scales, pe_scales,
                                 interpret: bool = False):
    """Quantized-latent-pool drop-in: closes the int8-MLA seam — the
    kernel dequantizes in-register, so ``use_pallas='always'`` + int8
    latent pools is a working path. Decode (T == 1) runs the kernel;
    prefill falls back to the XLA dequant gather."""
    B, T, H, dc = q_lat.shape
    if T != 1:
        from rbg_tpu.ops.mla_attention import paged_mla_attention_xla
        return paged_mla_attention_xla(q_lat, q_pe, c_pages, pe_pages,
                                       page_table, q_positions, kv_lens,
                                       scale, c_scales, pe_scales)
    out = _mla_decode_call_q(q_lat[:, 0], q_pe[:, 0], c_pages, pe_pages,
                             c_scales[..., 0], pe_scales[..., 0],
                             page_table.astype(jnp.int32),
                             kv_lens.astype(jnp.int32),
                             scale=float(scale), interpret=interpret)
    return out[:, None]

