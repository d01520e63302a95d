"""Paged attention: GQA over a page-table-indirected KV pool.

Two implementations behind one signature:

* ``paged_attention_xla`` — gather pages into a per-sequence contiguous view,
  then dense attention. Correct everywhere (CPU tests, interpreter), and a
  strong TPU baseline: XLA fuses the gather into the attention matmuls.
* ``paged_attention_pallas`` — Pallas TPU kernel that streams pages through
  VMEM without materializing the gathered [B, S, KV, hd] view (flash-style
  online softmax). Used on TPU for long contexts where the gather's HBM
  round-trip dominates.

``paged_attention`` picks per-platform; both are numerically interchangeable
(tests assert equality vs. the dense reference).
"""

from __future__ import annotations

import jax.numpy as jnp

from rbg_tpu.ops.pallas import dispatch_pallas

_NEG_INF = -1e30


def gather_kv(pages: jnp.ndarray, page_table: jnp.ndarray) -> jnp.ndarray:
    """pages [NP, page, KV, hd] + table [B, P] -> [B, P*page, KV, hd]."""
    B, P = page_table.shape
    page = pages.shape[1]
    g = pages[page_table]  # [B, P, page, KV, hd]
    return g.reshape(B, P * page, *pages.shape[2:])


def paged_attention_xla(
    q: jnp.ndarray,            # [B, T, H, hd]
    k_pages: jnp.ndarray,      # [NP, page, KV, hd] (single layer)
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,   # [B, P] int32
    q_positions: jnp.ndarray,  # [B, T] int32 absolute positions
    kv_lens: jnp.ndarray,      # [B] int32 — valid tokens in cache (post-write)
    k_scales: jnp.ndarray = None,  # [NP, page, KV, 1] f32 (int8 pools)
    v_scales: jnp.ndarray = None,
    window: int = None,        # a window layer: ``slot > position - window``
) -> jnp.ndarray:
    B, T, H, hd = q.shape
    S = page_table.shape[1] * k_pages.shape[1]

    # A pool that keeps small heads side by side (``[NP, page, KV / p,
    # p * hd]``, ``kvcache.heads_per_lane_tile``) is ``[.., KV, hd]`` row-major:
    # the gathered view is reshaped, never the pool.
    k = gather_kv(k_pages, page_table).reshape(B, S, -1, hd).astype(
        jnp.float32)                                        # [B, S, KV, hd]
    v = gather_kv(v_pages, page_table).reshape(B, S, -1, hd).astype(
        jnp.float32)
    KV = k.shape[2]
    G = H // KV
    if k_scales is not None:
        k = k * gather_kv(k_scales, page_table)
        v = v * gather_kv(v_scales, page_table)
    qg = q.reshape(B, T, KV, G, hd).astype(jnp.float32)

    scores = jnp.einsum("btkgh,bskh->bkgts", qg, k) / jnp.sqrt(hd).astype(jnp.float32)
    slot = jnp.arange(S, dtype=jnp.int32)[None, None, :]
    mask = jnp.logical_and(
        slot <= q_positions[:, :, None],          # causal (slot == position)
        slot < kv_lens[:, None, None],            # within the live cache
    )
    if window is not None:      # what lies below was given back: never read
        mask = mask & (slot > q_positions[:, :, None] - window)
    scores = jnp.where(mask[:, None, None, :, :], scores, _NEG_INF)
    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bkgts,bskh->btkgh", probs, v)
    return out.reshape(B, T, H, hd).astype(q.dtype)


def quantize_kv(x: jnp.ndarray):
    """Per-(token, head) absmax int8 quantization. x: [..., hd] →
    (int8 values, f32 scales [..., 1])."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = absmax / 127.0
    q = jnp.round(x.astype(jnp.float32) / jnp.maximum(scale, 1e-10))
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def _as_stored(new, pages):
    """``new [..., KV, hd]`` as the pool ``[NP, page, KV / p, p * hd]`` stores
    a slot: heads under a lane tile side by side (a row-major reshape; the
    same array where the pool is ``[NP, page, KV, hd]``)."""
    return new.astype(pages.dtype).reshape(new.shape[:-2] + pages.shape[2:])


def write_kv_pages(k_pages, v_pages, k_new, v_new, page_table, positions,
                   token_mask, k_scales=None, v_scales=None):
    """Scatter new K/V into the pool (quantizing when the pool is int8).

    k_new/v_new: [B, T, KV, hd]; positions: [B, T] absolute; pad tokens
    (token_mask False) are routed to an out-of-range slot dropped by scatter
    ``mode="drop"``. Returns (k_pages, v_pages, k_scales, v_scales).
    """
    page_size = k_pages.shape[1]
    page_idx = positions // page_size                       # [B, T]
    slot = positions % page_size                            # [B, T]
    phys = jnp.take_along_axis(page_table, page_idx, axis=1)  # [B, T]
    # Route pad writes out of range → dropped.
    NP = k_pages.shape[0]
    phys = jnp.where(token_mask, phys, NP)
    if k_scales is not None:
        k_q, k_s = quantize_kv(k_new)
        v_q, v_s = quantize_kv(v_new)
        k_pages = k_pages.at[phys, slot].set(k_q, mode="drop")
        v_pages = v_pages.at[phys, slot].set(v_q, mode="drop")
        k_scales = k_scales.at[phys, slot].set(k_s, mode="drop")
        v_scales = v_scales.at[phys, slot].set(v_s, mode="drop")
        return k_pages, v_pages, k_scales, v_scales
    k_pages = k_pages.at[phys, slot].set(_as_stored(k_new, k_pages),
                                         mode="drop")
    v_pages = v_pages.at[phys, slot].set(_as_stored(v_new, v_pages),
                                         mode="drop")
    return k_pages, v_pages, None, None


def paged_attention(q, k_pages, v_pages, page_table, q_positions, kv_lens,
                    *, use_pallas: str = "auto", k_scales=None, v_scales=None,
                    window: int = None):
    """Dispatch between the Pallas TPU kernel and the XLA fallback.
    Quantized (int8 + scales) pools route to the dequantizing kernel
    variant — the pool stays int8 in HBM, so the page walk moves half
    the bytes. ``window`` (static, a layer kind's): a query attends its
    ``window`` newest slots, its own included, and the walk starts at the
    block that holds the oldest of them (no int8 form)."""
    kw = {} if window is None else {"window": window}
    if k_scales is not None:
        assert window is None, "a window layer's pool has no int8 form"
        return dispatch_pallas(
            use_pallas, "paged_attention_pallas_q", paged_attention_xla,
            (q, k_pages, v_pages, page_table, q_positions, kv_lens,
             k_scales, v_scales))
    return dispatch_pallas(
        use_pallas, "paged_attention_pallas", paged_attention_xla,
        (q, k_pages, v_pages, page_table, q_positions, kv_lens), **kw)
