"""Multi-head latent attention (DeepSeek-V2/V3) in the ABSORBED inference
form, over contiguous and paged latent caches.

Reference context: the reference's flagship PD-disagg deployments serve
DeepSeek models via SGLang (``examples/inference/ecosystem/mooncake/*``,
BASELINE.md config 5 deploys DeepSeek-V3); MLA is what makes their KV
transfer cheap — the cache stores one ``kv_lora_rank`` latent plus one
shared ``qk_rope_head_dim`` RoPE key per token instead of per-head K/V.

Absorbed form (the serving identity): with per-head up-projections
``k_nope = c @ W_uk`` and ``v = c @ W_uv``,

    score = q_nope·k_nope + q_pe·k_pe  =  (q_nope @ W_uk^T)·c + q_pe·k_pe

so queries are absorbed into latent space once per step ([B,T,h,dc]) and
attention runs DIRECTLY on the latent cache — no per-head K/V ever
materializes. The value side likewise: ``attn @ v = (attn @ c) @ W_uv``.
This module computes scores/weights/latent-output; the model applies the
W_uk absorption before and the W_uv up-projection after.

TPU notes: two einsums + fused mask/softmax — XLA tiles them onto the MXU;
softmax in f32. The latent cache has no head axis, so it REPLICATES over
``tp`` (it is ~an order of magnitude smaller than GQA K/V); each device
attends its local query heads against the full latent cache.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from rbg_tpu.ops.pallas import dispatch_pallas

_NEG_INF = -1e30


def mla_attention(
    q_lat: jnp.ndarray,       # [B, T, H, dc]  — q_nope absorbed through W_uk
    q_pe: jnp.ndarray,        # [B, T, H, dr]  — RoPE'd query part
    c_cache: jnp.ndarray,     # [B, S, dc]     — latent cache (post-norm)
    pe_cache: jnp.ndarray,    # [B, S, dr]     — shared RoPE key cache
    q_positions: jnp.ndarray,  # [B, T] int32 absolute positions
    kv_valid: jnp.ndarray,    # [B, S] bool — slot holds a real token
    scale: float,             # 1/sqrt(qk_nope_head_dim + qk_rope_head_dim)
) -> jnp.ndarray:
    """Causal MLA over a contiguous latent cache (slot index == position).

    Returns the LATENT attention output [B, T, H, dc] in q_lat.dtype
    (caller up-projects through W_uv)."""
    B, T, H, dc = q_lat.shape
    S = c_cache.shape[1]
    qf = q_lat.astype(jnp.float32)
    pf = q_pe.astype(jnp.float32)
    cf = c_cache.astype(jnp.float32)
    ef = pe_cache.astype(jnp.float32)

    scores = (jnp.einsum("bthc,bsc->bhts", qf, cf)
              + jnp.einsum("bthr,bsr->bhts", pf, ef)) * scale   # [B,H,T,S]
    slot = jnp.arange(S, dtype=jnp.int32)[None, None, None, :]
    ok = (slot <= q_positions[:, None, :, None]) & kv_valid[:, None, None, :]
    scores = jnp.where(ok, scores, _NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bsc->bthc", w, cf)
    return out.astype(q_lat.dtype)


def paged_mla_attention_xla(
    q_lat: jnp.ndarray,       # [B, T, H, dc]
    q_pe: jnp.ndarray,        # [B, T, H, dr]
    c_pages: jnp.ndarray,     # [NP_layer, page, 1, dc] — this layer's pool view
    pe_pages: jnp.ndarray,    # [NP_layer, page, 1, >= dr]: the rotary key in
                              # the first dr channels, zeros up to a whole
                              # lane tile (kvcache.rope_pool_width)
    page_table: jnp.ndarray,  # [B, P] physical page ids (layer-offset applied)
    q_positions: jnp.ndarray,  # [B, T]
    kv_lens: jnp.ndarray,     # [B] — valid tokens post-write
    scale: float,
    c_scales: jnp.ndarray = None,   # [NP_layer, page, 1, 1] (int8 pools)
    pe_scales: jnp.ndarray = None,
) -> jnp.ndarray:
    """Causal MLA over the paged latent pool: gather the rows' pages into a
    contiguous [B, S, dc] view (S = P·page — static), then the same math as
    the contiguous form. Logical slot i lives in page i//page at offset
    i%page, so slot index == absolute position.

    Cost note: the gather MATERIALIZES [B, S, dc] in HBM every step — at
    long context that is ~3× the live-latent traffic (gather write +
    attention read + pool read). The Pallas kernel streams pages instead;
    ``paged_mla_attention`` dispatches."""
    B, P = page_table.shape
    page = c_pages.shape[1]
    S = P * page
    gather = lambda pages: pages[page_table][:, :, :, 0, :].reshape(B, S, -1)
    c = gather(c_pages)
    pe = gather(pe_pages)[..., :q_pe.shape[-1]]
    if c_scales is not None:
        # int8 latent pool: dequantize the gathered view (per-token
        # absmax scales stored alongside the pages).
        c = c.astype(jnp.float32) * gather(c_scales)
        pe = pe.astype(jnp.float32) * gather(pe_scales)
    slot_valid = (jnp.arange(S, dtype=jnp.int32)[None, :]
                  < kv_lens[:, None])
    return mla_attention(q_lat, q_pe, c, pe, q_positions, slot_valid, scale)


def paged_mla_attention(q_lat, q_pe, c_pages, pe_pages, page_table,
                        q_positions, kv_lens, scale,
                        *, use_pallas: str = "auto",
                        c_scales=None, pe_scales=None) -> jnp.ndarray:
    """Dispatch between the Pallas MLA decode kernel and the XLA gather
    fallback (same policy as ``paged_attention``'s GQA dispatch — shared
    via ``dispatch_pallas``). Quantized (int8 + scales) latent pools
    route to the ``_q`` kernel, which folds the per-slot scales
    algebraically like the GQA dequant variant — ``use_pallas='always'``
    + int8 is a working path (the round-2 seam closure)."""
    if c_scales is not None:
        return dispatch_pallas(
            use_pallas, "paged_mla_attention_pallas_q",
            paged_mla_attention_xla,
            (q_lat, q_pe, c_pages, pe_pages, page_table, q_positions,
             kv_lens, scale, c_scales, pe_scales))
    return dispatch_pallas(
        use_pallas, "paged_mla_attention_pallas", paged_mla_attention_xla,
        (q_lat, q_pe, c_pages, pe_pages, page_table, q_positions, kv_lens,
         scale))


def ragged_paged_mla_attention_xla(
    q_lat: jnp.ndarray,        # [1, T, H, dc] packed tokens (row-major)
    q_pe: jnp.ndarray,         # [1, T, H, dr]
    c_pages: jnp.ndarray,      # [NP_layer, page, 1, dc]
    pe_pages: jnp.ndarray,     # [NP_layer, page, 1, >= dr] (first dr used)
    page_table: jnp.ndarray,   # [R, P] int32 — per ROW
    q_positions: jnp.ndarray,  # [1, T] int32 absolute positions
    kv_lens: jnp.ndarray,      # [R] int32 — post-write cache length per row
    row_ids: jnp.ndarray,      # [T] int32 — token → row, contiguous runs
    scale: float,
    c_scales: jnp.ndarray = None,   # [NP_layer, page, 1, 1] (int8 pools)
    pe_scales: jnp.ndarray = None,
    max_q_len=None,            # static bound on any row's q_len
) -> jnp.ndarray:
    """Ragged (mixed prefill/decode pack) MLA: unpack → padded batch MLA →
    repack — the MLA twin of ``ragged_paged_attention_xla``, same pad
    contract (q_position < 0 tokens scatter out of range, dropped). The
    numerics are the SPLIT path's numerics by construction, so the engine's
    unified step stays bit-identical to phase-split for MLA configs."""
    from rbg_tpu.ops.ragged_paged_attention import _unpack_offsets
    _, T, H, dc = q_lat.shape
    R = page_table.shape[0]
    Tmax = T if max_q_len is None else min(max_q_len, T)

    idx_in_row = _unpack_offsets(row_ids)
    scatter_row = jnp.where(q_positions[0] < 0, R, row_ids)
    qlp = jnp.zeros((R, Tmax, H, dc), q_lat.dtype)
    qlp = qlp.at[scatter_row, idx_in_row].set(q_lat[0], mode="drop")
    qpp = jnp.zeros((R, Tmax, H, q_pe.shape[-1]), q_pe.dtype)
    qpp = qpp.at[scatter_row, idx_in_row].set(q_pe[0], mode="drop")
    pp = jnp.zeros((R, Tmax), jnp.int32)
    pp = pp.at[scatter_row, idx_in_row].set(q_positions[0], mode="drop")
    out = paged_mla_attention_xla(qlp, qpp, c_pages, pe_pages, page_table,
                                  pp, kv_lens, scale, c_scales, pe_scales)
    return out[row_ids, idx_in_row][None]                   # [1, T, H, dc]


def ragged_paged_mla_attention(q_lat, q_pe, c_pages, pe_pages, page_table,
                               q_positions, kv_lens, row_ids, scale,
                               *, use_pallas: str = "auto",
                               c_scales=None, pe_scales=None,
                               max_q_len=None) -> jnp.ndarray:
    """Dispatch the ragged MLA latent path: block-ragged Pallas kernel
    over the ``c/pe`` pools vs the XLA unpack/repack fallback — the seam
    that lets ``_unified_step()`` drop its ``mcfg.mla`` exclusion."""

    def xla_fn(*args):
        return ragged_paged_mla_attention_xla(*args, max_q_len=max_q_len)

    if c_scales is not None:
        return dispatch_pallas(
            use_pallas, "ragged_paged_mla_attention_pallas_q", xla_fn,
            (q_lat, q_pe, c_pages, pe_pages, page_table, q_positions,
             kv_lens, row_ids, scale, c_scales, pe_scales))
    return dispatch_pallas(
        use_pallas, "ragged_paged_mla_attention_pallas", xla_fn,
        (q_lat, q_pe, c_pages, pe_pages, page_table, q_positions, kv_lens,
         row_ids, scale))
