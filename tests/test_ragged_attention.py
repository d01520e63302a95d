"""Ragged paged attention: packed mixed prefill/decode rows vs the split
per-row reference (XLA), and the Pallas token-grid kernel vs the XLA ragged
reference (interpret mode on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from rbg_tpu.ops.paged_attention import paged_attention_xla, quantize_kv
from rbg_tpu.ops.pallas.ragged_attention_kernel import (
    ragged_paged_attention_pallas, ragged_paged_attention_pallas_q)
from rbg_tpu.ops.ragged_paged_attention import (ragged_paged_attention_xla,
                                                write_kv_pages_ragged)


def _pool(rng, NP=32, page=8, KV=2, hd=32):
    k = jnp.asarray(rng.randn(NP, page, KV, hd), jnp.float32)
    v = jnp.asarray(rng.randn(NP, page, KV, hd), jnp.float32)
    return k, v


def _pack(rng, q_specs, H=8, hd=32, P=6, NP=32):
    """q_specs: per row (q_len, kv_len); positions are the causal tail
    (the engine's layout: a chunk's tokens end at kv_len - 1, a decode
    token sits at kv_len - 1)."""
    R = len(q_specs)
    perm = rng.permutation(NP - 1)[: R * P] + 1
    table = jnp.asarray(perm.reshape(R, P), jnp.int32)
    kv_lens = jnp.asarray([kv for _, kv in q_specs], jnp.int32)
    T = sum(ql for ql, _ in q_specs)
    q = jnp.asarray(rng.randn(1, T, H, hd), jnp.float32)
    row_ids, q_pos = [], []
    for r, (ql, kv) in enumerate(q_specs):
        row_ids += [r] * ql
        q_pos += list(range(kv - ql, kv))
    return (q, table, jnp.asarray([q_pos], jnp.int32), kv_lens,
            jnp.asarray(row_ids, jnp.int32))


def _split_reference(q, k, v, table, q_pos, kv_lens, row_ids, q_specs):
    """Per-row paged_attention_xla — the legacy split path's math."""
    outs, off = [], 0
    for r, (ql, _) in enumerate(q_specs):
        outs.append(paged_attention_xla(
            q[:, off:off + ql], k, v, table[r:r + 1],
            q_pos[:, off:off + ql], kv_lens[r:r + 1]))
        off += ql
    return jnp.concatenate(outs, axis=1)


@pytest.mark.parametrize("q_specs", [
    [(1, 9), (1, 21), (1, 33)],             # pure decode
    [(8, 8), (8, 24)],                      # pure prefill chunks
    [(6, 14), (1, 30), (1, 5), (4, 4)],     # mixed
])
def test_ragged_xla_matches_split_reference(q_specs):
    rng = np.random.RandomState(0)
    k, v = _pool(rng)
    q, table, q_pos, kv_lens, row_ids = _pack(rng, q_specs)
    got = ragged_paged_attention_xla(q, k, v, table, q_pos, kv_lens, row_ids)
    ref = _split_reference(q, k, v, table, q_pos, kv_lens, row_ids, q_specs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ragged_causal_mask_from_offsets():
    """A mid-chunk token must ignore KV past its own position even though
    the row's kv_len extends further — poisoning the later slots must not
    change its output."""
    rng = np.random.RandomState(1)
    k, v = _pool(rng, NP=16, page=4)
    q_specs = [(4, 12)]                     # chunk tail: positions 8..11
    q, table, q_pos, kv_lens, row_ids = _pack(rng, q_specs, P=4, NP=16)
    base = ragged_paged_attention_xla(q, k, v, table, q_pos, kv_lens,
                                      row_ids)
    # Poison the physical page holding slots 8..11 of this row EXCEPT the
    # slots each token may see; easiest: recompute with kv beyond each
    # token's position zeroed via a second call where kv_lens is clamped
    # to position+1 — per-token outputs must agree with the full call.
    for t in range(4):
        got_t = ragged_paged_attention_xla(
            q[:, t:t + 1], k, v, table, q_pos[:, t:t + 1],
            jnp.asarray([int(q_pos[0, t]) + 1], jnp.int32),
            jnp.asarray([0], jnp.int32))
        np.testing.assert_allclose(np.asarray(base[:, t:t + 1]),
                                   np.asarray(got_t), rtol=1e-5, atol=1e-5)


def test_ragged_pallas_matches_xla_mixed():
    rng = np.random.RandomState(2)
    k, v = _pool(rng)
    q_specs = [(5, 15), (1, 21), (1, 4), (3, 40)]
    q, table, q_pos, kv_lens, row_ids = _pack(rng, q_specs)
    ref = ragged_paged_attention_xla(q, k, v, table, q_pos, kv_lens, row_ids)
    got = ragged_paged_attention_pallas(q, k, v, table, q_pos, kv_lens,
                                        row_ids, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ragged_pallas_edge_lens():
    # kv exactly on a page boundary, len 1, and a full table
    rng = np.random.RandomState(3)
    k, v = _pool(rng, NP=64, page=4)
    q_specs = [(1, 1), (1, 4), (1, 24), (2, 8)]
    q, table, q_pos, kv_lens, row_ids = _pack(rng, q_specs, P=6, NP=64)
    ref = ragged_paged_attention_xla(q, k, v, table, q_pos, kv_lens, row_ids)
    got = ragged_paged_attention_pallas(q, k, v, table, q_pos, kv_lens,
                                        row_ids, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ragged_pallas_quantized_matches_xla():
    rng = np.random.RandomState(4)
    kf, vf = _pool(rng, NP=16, page=4)
    k_q, k_s = quantize_kv(kf)
    v_q, v_s = quantize_kv(vf)
    q_specs = [(4, 8), (1, 13)]
    q, table, q_pos, kv_lens, row_ids = _pack(rng, q_specs, P=4, NP=16)
    ref = ragged_paged_attention_xla(q, k_q, v_q, table, q_pos, kv_lens,
                                     row_ids, k_scales=k_s, v_scales=v_s)
    got = ragged_paged_attention_pallas_q(q, k_q, v_q, table, q_pos,
                                          kv_lens, row_ids, k_s, v_s,
                                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_pad_tokens_never_clobber_real_rows():
    """Pack-contract: pad tokens (position -1) may reuse a REAL row id —
    bucket padding does — and must not perturb that row's outputs in
    either implementation."""
    rng = np.random.RandomState(6)
    k, v = _pool(rng, NP=16, page=4)
    q_specs = [(3, 9), (1, 13)]
    q, table, q_pos, kv_lens, row_ids = _pack(rng, q_specs, P=4, NP=16)
    base = ragged_paged_attention_xla(q, k, v, table, q_pos, kv_lens,
                                      row_ids)
    # Append 4 pad tokens tagged row 0 at position -1.
    qp = jnp.concatenate([q, jnp.asarray(rng.randn(1, 4, 8, 32),
                                         jnp.float32)], axis=1)
    rp = jnp.concatenate([row_ids, jnp.zeros(4, jnp.int32)])
    pp = jnp.concatenate([q_pos, jnp.full((1, 4), -1, jnp.int32)], axis=1)
    padded = ragged_paged_attention_xla(qp, k, v, table, pp, kv_lens, rp)
    np.testing.assert_allclose(np.asarray(padded[:, :4]),
                               np.asarray(base), rtol=1e-6, atol=1e-6)
    padded_k = ragged_paged_attention_pallas(qp, k, v, table, pp, kv_lens,
                                             rp, interpret=True)
    np.testing.assert_allclose(np.asarray(padded_k[:, :4]),
                               np.asarray(base), rtol=1e-5, atol=1e-5)


def test_write_kv_pages_ragged_matches_dense_scatter():
    """Packed ragged writes land exactly where the row-major split path
    would put them; pad tokens are dropped."""
    rng = np.random.RandomState(5)
    NP, page, KV, hd = 8, 4, 2, 16
    k_pages = jnp.zeros((NP, page, KV, hd), jnp.float32)
    v_pages = jnp.zeros((NP, page, KV, hd), jnp.float32)
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    # row 0 writes positions 2..5 (crosses page boundary), row 1 pos 7;
    # one pad token at the end.
    positions = jnp.asarray([[2, 3, 4, 5, 7, 0]], jnp.int32)
    row_ids = jnp.asarray([0, 0, 0, 0, 1, 0], jnp.int32)
    tmask = jnp.asarray([[True] * 5 + [False]])
    k_new = jnp.asarray(rng.randn(1, 6, KV, hd), jnp.float32)
    v_new = jnp.asarray(rng.randn(1, 6, KV, hd), jnp.float32)
    kp, vp, _, _ = write_kv_pages_ragged(k_pages, v_pages, k_new, v_new,
                                         table, row_ids, positions, tmask)
    kp = np.asarray(kp)
    np.testing.assert_allclose(kp[1, 2], np.asarray(k_new[0, 0]))  # pos 2
    np.testing.assert_allclose(kp[1, 3], np.asarray(k_new[0, 1]))  # pos 3
    np.testing.assert_allclose(kp[2, 0], np.asarray(k_new[0, 2]))  # pos 4
    np.testing.assert_allclose(kp[2, 1], np.asarray(k_new[0, 3]))  # pos 5
    np.testing.assert_allclose(kp[4, 3], np.asarray(k_new[0, 4]))  # row 1
    assert np.all(kp[0] == 0) and np.all(kp[5:] == 0)  # pad dropped


# ---- the page walk is over live pages only (PR 25) ----


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("q_specs", [
    [(1, 1)],                               # one token, one slot
    [(1, 12), (4, 8)],                      # exactly k pages
    [(1, 24), (3, 24)],                     # rows that fill all P pages
    [(1, 9), (1, 21)],                      # a pad tile follows
], ids=["len1", "k_pages", "full_table", "pad_tile"])
def test_ragged_pallas_walks_live_pages(q_specs, quantized):
    """Edge lengths of the live-page walk, each under the narrow table
    and under one 512 wide whose dead entries name a NaN page; the pack
    is padded by a whole all-pad tile."""
    rng = np.random.RandomState(7)
    page, P, NP = 4, 6, 64
    k, v = _pool(rng, NP=NP, page=page)
    q, table, q_pos, kv_lens, row_ids = _pack(rng, q_specs, P=P, NP=NP)
    table = jnp.minimum(table, NP - 2)
    T = q.shape[1]
    pad = 2 * 8 - T                          # Q_TILE 8: tile 1 is all pads
    q_p = jnp.concatenate([q, jnp.zeros((1, pad) + q.shape[2:])], axis=1)
    pos_p = jnp.concatenate([q_pos, jnp.full((1, pad), -1, jnp.int32)],
                            axis=1)
    rows_p = jnp.concatenate([row_ids, jnp.zeros(pad, jnp.int32)])
    wide = jnp.concatenate(
        [table, jnp.full((len(q_specs), 512 - P), NP - 1, jnp.int32)], axis=1)
    if quantized:
        k, ks = quantize_kv(k)
        v, vs = quantize_kv(v)
        ks, vs = ks.at[NP - 1].set(jnp.nan), vs.at[NP - 1].set(jnp.nan)
        ref = ragged_paged_attention_xla(q, k, v, table, q_pos, kv_lens,
                                         row_ids, k_scales=ks, v_scales=vs)
        call = lambda t: ragged_paged_attention_pallas_q(
            q_p, k, v, t, pos_p, kv_lens, rows_p, ks, vs, interpret=True)
    else:
        k, v = k.at[NP - 1].set(jnp.nan), v.at[NP - 1].set(jnp.nan)
        ref = ragged_paged_attention_xla(q, k, v, table, q_pos, kv_lens,
                                         row_ids)
        call = lambda t: ragged_paged_attention_pallas(
            q_p, k, v, t, pos_p, kv_lens, rows_p, interpret=True)
    narrow, got = np.asarray(call(table)), np.asarray(call(wide))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(narrow, got)
    np.testing.assert_allclose(got[:, :T], np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
