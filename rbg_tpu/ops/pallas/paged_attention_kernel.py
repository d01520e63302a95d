"""Pallas TPU kernel: decode-phase paged attention.

Why a kernel: the XLA fallback (`paged_attention_xla`) materializes the
gathered per-sequence KV view ``[B, S, KV, hd]`` in HBM before attending —
every decode step pays ~3× the pool's live-token traffic (gather write +
attention read, plus the pool read). Decode attention is pure HBM bandwidth,
so this kernel streams each page HBM→VMEM exactly once and keeps the
flash-style online softmax state in VMEM scratch.

Design (see /opt/skills/guides/pallas_guide.md):
* grid = (B, P): one sequence per outer step, its pages inner ("arbitrary"
  semantics — scratch accumulators persist across the page walk).
* page_table + kv_lens are scalar-prefetch args: the k/v BlockSpec index_map
  dereferences the page table, so the pipeline DMAs the RIGHT physical page
  ahead of compute (double-buffered by the Pallas pipeline itself).
* GQA via one batched dot per page: [KV, G, hd] × [KV, page, hd].
* Out-of-range pages (beyond a sequence's kv_len) still prefetch page 0 (the
  reserved null page) and are masked in-softmax — no divergent control flow.

Reference context: this is the TPU analog of the ragged/paged attention
kernels the PAPERS.md "Ragged Paged Attention" paper describes; the engine
only uses it for decode (T == 1); prefill chunks stay on the dense XLA path
(MXU-bound, already optimal).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _decode_kernel(
    # scalar prefetch
    page_table_ref,   # [B, P] int32 (SMEM)
    kv_lens_ref,      # [B] int32 (SMEM)
    # blocks
    q_ref,            # [1, KV, G, hd] (VMEM)
    k_ref,            # [1, page, KV, hd] — the page picked by index_map
    v_ref,
    out_ref,          # [1, KV, G, hd]
    # scratch
    m_ref,            # [KV, G, 1] running max
    l_ref,            # [KV, G, 1] running denom
    acc_ref,          # [KV, G, hd] running numerator
    *,
    # int8 pools (the _decode_kernel_q entry): per-(slot, head) absmax
    # scales [1, page, KV]. Folded ALGEBRAICALLY — scales factor out of
    # both dot products, so the int8 page tensors feed the MXU directly.
    ks_ref=None,
    vs_ref=None,
):
    b = pl.program_id(0)
    p = pl.program_id(1)
    num_p = pl.num_programs(1)
    page = k_ref.shape[1]
    quantized = ks_ref is not None

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    kv_len = kv_lens_ref[b]

    # Skip pages entirely past the sequence (still DMA'd, never read).
    @pl.when(p * page < kv_len)
    def _attend():
        q = q_ref[0].astype(jnp.float32)                    # [KV, G, hd]
        k = k_ref[0].astype(jnp.float32)                    # [page, KV, hd]
        v = v_ref[0].astype(jnp.float32)
        hd = q.shape[-1]

        k_t = jnp.transpose(k, (1, 0, 2))                   # [KV, page, hd]
        v_t = jnp.transpose(v, (1, 0, 2))
        # scores[kv, g, t] = q[kv, g, :] · k[kv, t, :]
        scores = jax.lax.dot_general(
            q, k_t,
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * (1.0 / (hd ** 0.5))                             # [KV, G, page]
        if quantized:
            # scores ·= ks[t, kv] (k's scale factors out of the dot).
            ks_t = jnp.transpose(ks_ref[0], (1, 0))         # [KV, page]
            scores = scores * ks_t[:, None, :]

        token_idx = p * page + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, dimension=2)
        scores = jnp.where(token_idx < kv_len, scores, _NEG_INF)

        m_prev = m_ref[:]                                   # [KV, G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)                     # [KV, G, 1]
        probs = jnp.exp(scores - m_new)                     # [KV, G, page]

        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        # acc[kv, g, :] += probs[kv, g, t] * v[kv, t, :]; for int8 v the
        # scale folds into probs BEFORE the dot (pv = (probs·vs)·v_int8).
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
def _decode_call(q, k_pages, v_pages, page_table, kv_lens, interpret=False):
    """q: [B, KV, G, hd]; pages: [NP, page, KV, hd]. Returns [B, KV, G, hd]."""
    B, KV, G, hd = q.shape
    NP, page, _, _ = k_pages.shape
    P = page_table.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd), lambda b, p, table, lens: (b, 0, 0, 0)),
            pl.BlockSpec((1, page, KV, hd),
                         lambda b, p, table, lens: (table[b, p], 0, 0, 0)),
            pl.BlockSpec((1, page, KV, hd),
                         lambda b, p, table, lens: (table[b, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, G, hd),
                               lambda b, p, table, lens: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        _decode_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(page_table, kv_lens, q, k_pages, v_pages)


def paged_attention_pallas(q, k_pages, v_pages, page_table, q_positions,
                           kv_lens, interpret: bool = False):
    """Drop-in for ``paged_attention_xla``. Decode (T == 1) runs the kernel;
    other shapes fall back to the XLA path (prefill is MXU-bound there)."""
    B, T, H, hd = q.shape
    KV = k_pages.shape[2]
    if T != 1:
        from rbg_tpu.ops.paged_attention import paged_attention_xla
        return paged_attention_xla(q, k_pages, v_pages, page_table,
                                   q_positions, kv_lens)
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    out = _decode_call(qg, k_pages, v_pages,
                       page_table.astype(jnp.int32),
                       kv_lens.astype(jnp.int32), interpret=interpret)
    return out.reshape(B, T, H, hd)


# ---- int8 (quantized pool) decode ------------------------------------------
#
# The SAME kernel body handles quantized pools via a static ``quantized``
# flag: pages arrive int8 with per-(slot, head) absmax scales alongside
# (ops/paged_attention.quantize_kv). Scales are folded ALGEBRAICALLY —
# they factor out of both dot products (scores[kv,g,t] = (q·k_int8)·ks[t]
# and pv = (probs·vs)·v_int8) — so the [page, KV, hd] page tensors are
# never multiplied elementwise and the MXU consumes the int8 pages'
# values directly after cast.
#
# Byte accounting (honest): int8 halves the k/v page DMA, but the f32
# scale blocks are (1, page, KV) — the KV lane dim pads to 128 on real
# hardware, so each scale block moves ~page*128*4 B. At page=16/KV=8/
# hd=128 that is k+v 64 KB (bf16) → 32 KB (int8) + ~16 KB padded scales
# ≈ a 25% net walk saving, not 50%. Packing scales lane-major across
# pages is the documented follow-up seam.


def _decode_kernel_q(
    # scalar prefetch
    page_table_ref,   # [B, P] int32 (SMEM)
    kv_lens_ref,      # [B] int32 (SMEM)
    # blocks
    q_ref,            # [1, KV, G, hd] (VMEM)
    k_ref,            # [1, page, KV, hd] int8 — the page picked by index_map
    v_ref,
    ks_ref,           # [1, page, KV] f32 scales
    vs_ref,
    out_ref,          # [1, KV, G, hd]
    # scratch
    m_ref, l_ref, acc_ref,
):
    _decode_kernel(page_table_ref, kv_lens_ref, q_ref, k_ref, v_ref,
                   out_ref, m_ref, l_ref, acc_ref,
                   ks_ref=ks_ref, vs_ref=vs_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_call_q(q, k_pages, v_pages, k_scales, v_scales, page_table,
                   kv_lens, interpret=False):
    """int8 variant: pages int8, scales f32 [NP, page, KV]. Returns
    [B, KV, G, hd]."""
    B, KV, G, hd = q.shape
    _, page, _, _ = k_pages.shape
    P = page_table.shape[1]

    pick4 = lambda b, p, table, lens: (table[b, p], 0, 0, 0)
    pick3 = lambda b, p, table, lens: (table[b, p], 0, 0)
    fixed = lambda b, p, table, lens: (b, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd), fixed),
            pl.BlockSpec((1, page, KV, hd), pick4),
            pl.BlockSpec((1, page, KV, hd), pick4),
            pl.BlockSpec((1, page, KV), pick3),
            pl.BlockSpec((1, page, KV), pick3),
        ],
        out_specs=pl.BlockSpec((1, KV, G, hd), fixed),
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        _decode_kernel_q,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(page_table, kv_lens, q, k_pages, v_pages, k_scales, v_scales)


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
# the GQA kernel above.


def _mla_decode_kernel(
    # scalar prefetch
    page_table_ref,   # [B, P] int32 (SMEM)
    kv_lens_ref,      # [B] int32 (SMEM)
    # blocks
    ql_ref,           # [1, H, dc] (VMEM) — q_nope absorbed through W_uk
    qp_ref,           # [1, H, dr] — RoPE'd query part
    c_ref,            # [1, page, 1, dc] — the page picked by index_map
    pe_ref,           # [1, page, 1, dr]
    out_ref,          # [1, H, dc] — latent attention output
    # scratch
    m_ref,            # [H, 1] running max
    l_ref,            # [H, 1] running denom
    acc_ref,          # [H, dc] running numerator
    *,
    scale: float,
    cs_ref=None,      # int8 pools: [1, page, 1] f32 scales
    ps_ref=None,
):
    b = pl.program_id(0)
    p = pl.program_id(1)
    num_p = pl.num_programs(1)
    page = c_ref.shape[1]
    quantized = cs_ref is not None

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    kv_len = kv_lens_ref[b]

    @pl.when(p * page < kv_len)
    def _attend():
        ql = ql_ref[0].astype(jnp.float32)              # [H, dc]
        qp = qp_ref[0].astype(jnp.float32)              # [H, dr]
        c = c_ref[0, :, 0, :].astype(jnp.float32)       # [page, dc]
        pe = pe_ref[0, :, 0, :].astype(jnp.float32)     # [page, dr]

        s_c = jax.lax.dot_general(ql, c, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        s_pe = jax.lax.dot_general(qp, pe, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        if quantized:
            # int8 latent pool: fold the per-slot scales ALGEBRAICALLY —
            # the latent scale multiplies the latent score term, the RoPE
            # scale the RoPE term; the pages feed the MXU as int8.
            s_c = s_c * cs_ref[0, :, 0][None, :]
            s_pe = s_pe * ps_ref[0, :, 0][None, :]
        scores = (s_c + s_pe) * scale                   # [H, page]

        token_idx = p * page + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, dimension=1)
        scores = jnp.where(token_idx < kv_len, scores, _NEG_INF)

        m_prev = m_ref[:]                               # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(scores - m_new)                 # [H, page]

        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        pmat = probs
        if quantized:
            # Values ARE the latents: their scale folds into the probs
            # before the value dot (same algebra as the GQA v-scale fold).
            pmat = probs * cs_ref[0, :, 0][None, :]
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            pmat, c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [H, dc]

    @pl.when(p == num_p - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:], 1e-30)
        out_ref[0] = (acc_ref[:] / denom).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _mla_decode_call(q_lat, q_pe, c_pages, pe_pages, page_table, kv_lens,
                     scale, interpret=False):
    """q_lat: [B, H, dc], q_pe: [B, H, dr]; pages: [NP, page, 1, d].
    Returns the latent attention output [B, H, dc]."""
    B, H, dc = q_lat.shape
    dr = q_pe.shape[-1]
    _, page, _, _ = c_pages.shape
    P = page_table.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, H, dc), lambda b, p, table, lens: (b, 0, 0)),
            pl.BlockSpec((1, H, dr), lambda b, p, table, lens: (b, 0, 0)),
            pl.BlockSpec((1, page, 1, dc),
                         lambda b, p, table, lens: (table[b, p], 0, 0, 0)),
            pl.BlockSpec((1, page, 1, dr),
                         lambda b, p, table, lens: (table[b, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, dc),
                               lambda b, p, table, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, dc), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_decode_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, dc), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(page_table, kv_lens, q_lat, q_pe, c_pages, pe_pages)


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


def _mla_decode_kernel_q(
    # scalar prefetch
    page_table_ref, kv_lens_ref,
    # blocks
    ql_ref, qp_ref, c_ref, pe_ref,
    cs_ref,           # [1, page, 1] f32 scales
    ps_ref,
    out_ref,
    # scratch
    m_ref, l_ref, acc_ref,
    *,
    scale: float,
):
    _mla_decode_kernel(page_table_ref, kv_lens_ref, ql_ref, qp_ref,
                       c_ref, pe_ref, out_ref, m_ref, l_ref, acc_ref,
                       scale=scale, cs_ref=cs_ref, ps_ref=ps_ref)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _mla_decode_call_q(q_lat, q_pe, c_pages, pe_pages, c_scales, pe_scales,
                       page_table, kv_lens, scale, interpret=False):
    """int8-latent-pool twin of ``_mla_decode_call``: scales ride two
    extra [NP, page, 1] operands blocked alongside their pages."""
    B, H, dc = q_lat.shape
    dr = q_pe.shape[-1]
    _, page, _, _ = c_pages.shape
    P = page_table.shape[1]

    pick4 = lambda b, p, table, lens: (table[b, p], 0, 0, 0)
    pick3 = lambda b, p, table, lens: (table[b, p], 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, H, dc), lambda b, p, table, lens: (b, 0, 0)),
            pl.BlockSpec((1, H, dr), lambda b, p, table, lens: (b, 0, 0)),
            pl.BlockSpec((1, page, 1, dc), pick4),
            pl.BlockSpec((1, page, 1, dr), pick4),
            pl.BlockSpec((1, page, 1), pick3),
            pl.BlockSpec((1, page, 1), pick3),
        ],
        out_specs=pl.BlockSpec((1, H, dc),
                               lambda b, p, table, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, dc), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_decode_kernel_q, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, dc), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(page_table, kv_lens, q_lat, q_pe, c_pages, pe_pages,
      c_scales, pe_scales)


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


# ---- ragged (mixed prefill/decode) kernels ---------------------------------
#
# Re-exported here because ``dispatch_pallas`` resolves every kernel name
# against this module; the implementations live in
# ragged_attention_kernel.py (block-ragged tile grid; the PR-7 token-grid
# variants stay exported as the bench A/B baseline).

from rbg_tpu.ops.pallas.ragged_attention_kernel import (  # noqa: E402,F401
    ragged_paged_attention_pallas,
    ragged_paged_attention_pallas_q,
    ragged_paged_attention_pallas_tokengrid,
    ragged_paged_mla_attention_pallas,
    ragged_paged_mla_attention_pallas_q,
)
