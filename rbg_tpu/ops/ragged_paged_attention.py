"""Ragged paged attention: mixed prefill-chunk + decode rows, ONE dispatch.

The split serving engine runs prefill chunks through a dense ``[B, chunk]``
program and decode steps through a ``[B, 1]`` program — two device paths, so
a batch that holds both phases pays two dispatches and the scheduler has to
phase-order them. Per the "Ragged Paged Attention" paper (PAPERS.md), one
kernel can serve an arbitrary mix if queries are PACKED: every live token of
every row lands on a single flat token axis, and per-token metadata says
which row (= which page-table line + kv length) it belongs to.

Layout (the one contract every implementation here shares):

* ``q``            ``[1, T, H, hd]`` — all rows' query tokens, row-major
  packed on the token axis (a prefill row contributes ``chunk`` tokens, a
  decode row exactly one);
* ``row_ids``      ``[T] int32`` — token → batch row;
* ``q_positions``  ``[1, T] int32`` — token's absolute sequence position;
* ``page_table``   ``[R, P] int32`` / ``kv_lens [R] int32`` — per ROW, as in
  ``paged_attention`` (kv_lens is the post-write cache length).

Causal masking is computed from the ragged offsets: token ``t`` attends KV
slots ``< min(kv_lens[row_ids[t]], q_positions[t] + 1)`` — decode steps see
their whole row, mid-chunk prefill tokens see only their causal prefix.

Two implementations behind one signature, mirroring ``paged_attention``:

* ``ragged_paged_attention_xla`` — scatters the pack into a padded
  ``[R, max_q_len]`` layout (offsets recovered from ``row_ids`` with a
  prefix-max scan — the pack must be row-major CONTIGUOUS per row, which
  the engine guarantees) and runs the proven ``paged_attention_xla``
  batch, then gathers the packed tokens back. Cost is therefore ONE
  row-padded dense dispatch — identical KV-gather traffic to the split
  prefill path — never a per-token KV view.
* ``ragged_paged_attention_pallas`` — streams a row's live pages HBM→VMEM
  once a query tile (ops/pallas/ragged_attention_kernel.py), no padding,
  no gathered view.

Quantized (int8 + scales) pools route to the ``_q`` variants, same as the
decode kernel.

Who calls this with what: the contract above is any pack, and the tests
hold both forms to it. A unified step program (``models/llama.py::
_pool_attention``) hands the attend its rows of two tokens or more alone:
the tokens of its one-token rows go in with ``q_position == -1``, padding
for this attend only (the write saw their true positions), and are
attended by ``paged_attention`` over ``[R, 1]`` queries with every other
row's ``kv_lens`` at 0. The XLA forms here share ``paged_attention_xla``
either way, so off the chip the split changes no number.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from rbg_tpu.ops.paged_attention import (_as_stored, paged_attention_xla,
                                         quantize_kv)
from rbg_tpu.ops.pallas import dispatch_pallas


def _unpack_offsets(row_ids: jnp.ndarray) -> jnp.ndarray:
    """Per-token index WITHIN its row for a row-major contiguous pack:
    ``idx[t] = t - (first packed index of row_ids[t])``, the start index
    recovered with a prefix-max over run boundaries (all static-shape
    ops, jit-safe)."""
    T = row_ids.shape[0]
    t_idx = jnp.arange(T, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), row_ids[1:] != row_ids[:-1]])
    row_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, t_idx, -1))
    return t_idx - row_start


def ragged_paged_attention_xla(
    q: jnp.ndarray,            # [1, T, H, hd] packed tokens (row-major)
    k_pages: jnp.ndarray,      # [NP, page, KV, hd] (single layer)
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,   # [R, P] int32 — per ROW
    q_positions: jnp.ndarray,  # [1, T] int32 absolute positions
    kv_lens: jnp.ndarray,      # [R] int32 — post-write cache length per row
    row_ids: jnp.ndarray,      # [T] int32 — token → row, contiguous runs
    k_scales: jnp.ndarray = None,  # [NP, page, KV, 1] f32 (int8 pools)
    v_scales: jnp.ndarray = None,
    max_q_len: Optional[int] = None,  # static bound on any row's q_len
                                      # (the engine's prefill_chunk);
                                      # None = T (always safe)
    window: Optional[int] = None,     # a window layer's width (static)
) -> jnp.ndarray:
    """XLA fallback: unpack → padded batch attention → repack.

    The padded detour reuses ``paged_attention_xla`` unchanged, so the
    ragged path's numerics are the SPLIT path's numerics by construction
    (bit-identity falls out) and the KV gather stays per-ROW ([R, S]),
    never per-token. Pad slots carry position 0 and are dropped on the
    gather back; rows with ``kv_lens == 0`` (bucket padding) produce NaN
    garbage that no packed token maps to."""
    _, T, H, hd = q.shape
    R = page_table.shape[0]
    Tmax = T if max_q_len is None else min(max_q_len, T)

    idx_in_row = _unpack_offsets(row_ids)
    # PAD CONTRACT: packed tokens with q_position < 0 are padding — their
    # scatter routes out of range (dropped), so a pad run tagged with a
    # real row id can never clobber that row's genuine queries.
    scatter_row = jnp.where(q_positions[0] < 0, R, row_ids)
    qp = jnp.zeros((R, Tmax, H, hd), q.dtype)
    qp = qp.at[scatter_row, idx_in_row].set(q[0], mode="drop")
    pp = jnp.zeros((R, Tmax), jnp.int32)
    pp = pp.at[scatter_row, idx_in_row].set(q_positions[0], mode="drop")
    out = paged_attention_xla(qp, k_pages, v_pages, page_table, pp, kv_lens,
                              k_scales, v_scales, window)
    return out[row_ids, idx_in_row][None]                   # [1, T, H, hd]


def write_kv_pages_ragged(k_pages, v_pages, k_new, v_new, page_table,
                          row_ids, positions, token_mask,
                          k_scales=None, v_scales=None):
    """Scatter packed new K/V into the pool (quantizing for int8 pools).

    ``k_new/v_new``: ``[1, T, KV, hd]`` packed; each token's physical page
    comes from ITS row's table line (``page_table[row_ids]``); pad tokens
    (token_mask False) are routed out of range and dropped by the scatter,
    exactly like ``write_kv_pages``. Returns (k_pages, v_pages, k_scales,
    v_scales).
    """
    page_size = k_pages.shape[1]
    pos = positions[0]                                      # [T]
    page_idx = pos // page_size
    slot = pos % page_size
    phys = page_table[row_ids, page_idx]                    # [T]
    NP = k_pages.shape[0]
    phys = jnp.where(token_mask[0], phys, NP)               # pad → dropped
    kn, vn = k_new[0], v_new[0]                             # [T, KV, hd]
    if k_scales is not None:
        k_q, k_s = quantize_kv(kn)
        v_q, v_s = quantize_kv(vn)
        k_pages = k_pages.at[phys, slot].set(k_q, mode="drop")
        v_pages = v_pages.at[phys, slot].set(v_q, mode="drop")
        k_scales = k_scales.at[phys, slot].set(k_s, mode="drop")
        v_scales = v_scales.at[phys, slot].set(v_s, mode="drop")
        return k_pages, v_pages, k_scales, v_scales
    k_pages = k_pages.at[phys, slot].set(_as_stored(kn, k_pages),
                                         mode="drop")
    v_pages = v_pages.at[phys, slot].set(_as_stored(vn, v_pages),
                                         mode="drop")
    return k_pages, v_pages, None, None


def ragged_paged_attention(q, k_pages, v_pages, page_table, q_positions,
                           kv_lens, row_ids, *, use_pallas: str = "auto",
                           k_scales=None, v_scales=None,
                           max_q_len: Optional[int] = None,
                           window: Optional[int] = None):
    """Dispatch between the ragged Pallas kernel and the XLA fallback —
    the same per-platform policy as ``paged_attention``. ``max_q_len``
    (static) only shapes the XLA fallback's padded detour; the kernel is
    padding-free. ``window`` (static): as ``paged_attention``'s."""
    def xla_fn(*args, **kw):
        return ragged_paged_attention_xla(*args, max_q_len=max_q_len, **kw)

    kw = {} if window is None else {"window": window}
    if k_scales is not None:
        assert window is None, "a window layer's pool has no int8 form"
        return dispatch_pallas(
            use_pallas, "ragged_paged_attention_pallas_q", xla_fn,
            (q, k_pages, v_pages, page_table, q_positions, kv_lens, row_ids,
             k_scales, v_scales))
    return dispatch_pallas(
        use_pallas, "ragged_paged_attention_pallas", xla_fn,
        (q, k_pages, v_pages, page_table, q_positions, kv_lens, row_ids),
        **kw)
