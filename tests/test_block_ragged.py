"""Block-ragged tiling round 2: query tiles SPAN row boundaries, so the
identity suite pins exactly the layouts the tile grid makes interesting —
a prefill row straddling two tiles, a decode row sharing a tile with a
prefill tail, fully-pad tiles — against the XLA ragged reference, for the
fp, int8, MLA, and int8-MLA kernels (interpret mode on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from rbg_tpu.ops.mla_attention import (paged_mla_attention_xla,
                                       ragged_paged_mla_attention,
                                       ragged_paged_mla_attention_xla)
from rbg_tpu.ops.paged_attention import quantize_kv
from rbg_tpu.ops.pallas.ragged_attention_kernel import (
    Q_TILE, ragged_paged_attention_pallas, ragged_paged_attention_pallas_q,
    ragged_paged_mla_attention_pallas, ragged_paged_mla_attention_pallas_q)
from rbg_tpu.ops.ragged_paged_attention import ragged_paged_attention_xla


def _pool(rng, NP=32, page=8, KV=2, hd=32):
    k = jnp.asarray(rng.randn(NP, page, KV, hd), jnp.float32)
    v = jnp.asarray(rng.randn(NP, page, KV, hd), jnp.float32)
    return k, v


def _pack(rng, q_specs, H=8, hd=32, P=6, NP=32):
    """Engine pack layout: row-major, positions are each row's causal
    tail (see tests/test_ragged_attention.py)."""
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


def _check(q_specs, seed):
    rng = np.random.RandomState(seed)
    k, v = _pool(rng)
    q, table, q_pos, kv_lens, row_ids = _pack(rng, q_specs)
    ref = ragged_paged_attention_xla(q, k, v, table, q_pos, kv_lens,
                                     row_ids)
    got = ragged_paged_attention_pallas(q, k, v, table, q_pos, kv_lens,
                                        row_ids, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_prefill_row_straddles_two_tiles():
    """One prefill row longer than Q_TILE: its tokens occupy (at least)
    two tiles, so the second tile's KV streaming must resume mid-row
    (duplicate-leader suppression in the kernel)."""
    assert Q_TILE == 8  # layouts below are built around this
    _check([(Q_TILE + 4, Q_TILE + 4), (1, 9)], seed=10)


def test_row_boundary_inside_a_tile():
    """A decode row sharing a tile with a prefill tail: tile 0 holds
    7 prefill tokens of row 0 plus row 1's single decode token — the
    per-token row_ids/limits must mask each row's KV independently."""
    _check([(Q_TILE - 1, 19), (1, 33), (2, 12)], seed=11)


def test_three_rows_in_one_tile():
    """Multiple short rows packed into a single tile (the decode-heavy
    mix): every row transition happens inside the tile."""
    _check([(1, 9), (1, 21), (1, 33), (2, 6), (3, 7)], seed=12)


def test_all_pad_tile():
    """Real tokens fill less than one tile; the grid still launches a
    second, fully-pad tile (position -1 everywhere) which must be a
    numeric no-op for every real output."""
    rng = np.random.RandomState(13)
    k, v = _pool(rng, NP=16, page=4)
    q_specs = [(2, 9), (1, 13)]
    q, table, q_pos, kv_lens, row_ids = _pack(rng, q_specs, P=4, NP=16)
    base = ragged_paged_attention_xla(q, k, v, table, q_pos, kv_lens,
                                      row_ids)
    # Pad out past the next tile boundary: 3 real + 13 pads = 2 tiles,
    # tile 1 entirely pads tagged row 0 / position -1.
    n_pad = 2 * Q_TILE - 3
    qp = jnp.concatenate(
        [q, jnp.asarray(rng.randn(1, n_pad, 8, 32), jnp.float32)], axis=1)
    rp = jnp.concatenate([row_ids, jnp.zeros(n_pad, jnp.int32)])
    pp = jnp.concatenate([q_pos, jnp.full((1, n_pad), -1, jnp.int32)],
                         axis=1)
    got = ragged_paged_attention_pallas(qp, k, v, table, pp, kv_lens, rp,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(got[:, :3]), np.asarray(base),
                               rtol=1e-5, atol=1e-5)


def test_gqa_int8_straddling_tiles():
    """GQA (KV < H) + int8 pool + a row straddling tiles, through the
    dequantizing block-ragged kernel."""
    rng = np.random.RandomState(14)
    kf, vf = _pool(rng, NP=32, page=8, KV=2, hd=32)
    k_q, k_s = quantize_kv(kf)
    v_q, v_s = quantize_kv(vf)
    q_specs = [(Q_TILE + 3, Q_TILE + 5), (1, 30), (4, 12)]
    q, table, q_pos, kv_lens, row_ids = _pack(rng, q_specs)
    ref = ragged_paged_attention_xla(q, k_q, v_q, table, q_pos, kv_lens,
                                     row_ids, k_scales=k_s, v_scales=v_s)
    got = ragged_paged_attention_pallas_q(q, k_q, v_q, table, q_pos,
                                          kv_lens, row_ids, k_s, v_s,
                                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# ---- MLA ragged latent path ----


def _mla_pack(rng, q_specs, H=4, dc=128, dr=32, page=4, NP=64, P=8):
    R = len(q_specs)
    c_pages = jnp.asarray(rng.randn(NP, page, 1, dc) * 0.1, jnp.float32)
    pe_pages = jnp.asarray(rng.randn(NP, page, 1, dr) * 0.1, jnp.float32)
    perm = rng.permutation(NP - 1)[: R * P] + 1
    table = jnp.asarray(perm.reshape(R, P), jnp.int32)
    kv_lens = jnp.asarray([kv for _, kv in q_specs], jnp.int32)
    T = sum(ql for ql, _ in q_specs)
    q_lat = jnp.asarray(rng.randn(1, T, H, dc) * 0.1, jnp.float32)
    q_pe = jnp.asarray(rng.randn(1, T, H, dr) * 0.1, jnp.float32)
    row_ids, q_pos = [], []
    for r, (ql, kv) in enumerate(q_specs):
        row_ids += [r] * ql
        q_pos += list(range(kv - ql, kv))
    scale = 1.0 / np.sqrt(128 + dr)
    return (q_lat, q_pe, c_pages, pe_pages, table,
            jnp.asarray([q_pos], jnp.int32), kv_lens,
            jnp.asarray(row_ids, jnp.int32), scale)


def _mla_split_reference(ql, qp, c, pe, table, q_pos, kv_lens, row_ids,
                         scale, q_specs):
    outs, off = [], 0
    for r, (n, _) in enumerate(q_specs):
        outs.append(paged_mla_attention_xla(
            ql[:, off:off + n], qp[:, off:off + n], c, pe,
            table[r:r + 1], q_pos[:, off:off + n], kv_lens[r:r + 1],
            scale))
        off += n
    return jnp.concatenate(outs, axis=1)


def test_mla_ragged_xla_matches_split_reference():
    rng = np.random.RandomState(20)
    q_specs = [(6, 14), (1, 30), (1, 5), (4, 4)]
    ql, qp, c, pe, table, q_pos, lens, rows, scale = _mla_pack(rng, q_specs)
    got = ragged_paged_mla_attention_xla(ql, qp, c, pe, table, q_pos,
                                         lens, rows, scale)
    ref = _mla_split_reference(ql, qp, c, pe, table, q_pos, lens, rows,
                               scale, q_specs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_mla_ragged_pallas_matches_xla_straddling():
    """Block-ragged MLA kernel vs XLA reference — prefill row straddling
    tiles plus a tile-sharing decode row."""
    rng = np.random.RandomState(21)
    q_specs = [(Q_TILE + 4, Q_TILE + 6), (1, 21), (3, 9)]
    ql, qp, c, pe, table, q_pos, lens, rows, scale = _mla_pack(rng, q_specs)
    ref = ragged_paged_mla_attention_xla(ql, qp, c, pe, table, q_pos,
                                         lens, rows, scale)
    got = ragged_paged_mla_attention_pallas(ql, qp, c, pe, table, q_pos,
                                            lens, rows, scale,
                                            interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_mla_ragged_pallas_pad_tile():
    rng = np.random.RandomState(22)
    q_specs = [(2, 7), (1, 13)]
    ql, qp, c, pe, table, q_pos, lens, rows, scale = _mla_pack(rng, q_specs)
    base = ragged_paged_mla_attention_xla(ql, qp, c, pe, table, q_pos,
                                          lens, rows, scale)
    n_pad = 2 * Q_TILE - 3
    qlp = jnp.concatenate(
        [ql, jnp.asarray(rng.randn(1, n_pad, 4, 128), jnp.float32)], axis=1)
    qpp = jnp.concatenate(
        [qp, jnp.asarray(rng.randn(1, n_pad, 4, 32), jnp.float32)], axis=1)
    rp = jnp.concatenate([rows, jnp.zeros(n_pad, jnp.int32)])
    pp = jnp.concatenate([q_pos, jnp.full((1, n_pad), -1, jnp.int32)],
                         axis=1)
    got = ragged_paged_mla_attention_pallas(qlp, qpp, c, pe, table, pp,
                                            lens, rp, scale,
                                            interpret=True)
    np.testing.assert_allclose(np.asarray(got[:, :3]), np.asarray(base),
                               rtol=1e-5, atol=1e-5)


def test_mla_ragged_pallas_quantized_matches_xla():
    rng = np.random.RandomState(23)
    q_specs = [(Q_TILE + 1, Q_TILE + 1), (1, 17)]
    ql, qp, c, pe, table, q_pos, lens, rows, scale = _mla_pack(rng, q_specs)
    c_q, c_s = quantize_kv(c)
    pe_q, pe_s = quantize_kv(pe)
    ref = ragged_paged_mla_attention_xla(ql, qp, c_q, pe_q, table, q_pos,
                                         lens, rows, scale,
                                         c_scales=c_s, pe_scales=pe_s)
    got = ragged_paged_mla_attention_pallas_q(ql, qp, c_q, pe_q, table,
                                              q_pos, lens, rows, scale,
                                              c_s, pe_s, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_mla_ragged_dispatcher_never_matches_xla():
    """use_pallas='never' through the public dispatcher equals the raw
    XLA reference (and exercises the scatter/gather detour)."""
    rng = np.random.RandomState(24)
    q_specs = [(3, 11), (1, 6)]
    ql, qp, c, pe, table, q_pos, lens, rows, scale = _mla_pack(rng, q_specs)
    ref = ragged_paged_mla_attention_xla(ql, qp, c, pe, table, q_pos,
                                         lens, rows, scale)
    got = ragged_paged_mla_attention(ql, qp, c, pe, table, q_pos, lens,
                                     rows, scale, use_pallas="never")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


# ---- the page walk is over live pages only (PR 25) ----

_WALK_P, _WALK_NP = 4, 64
# Pages of 4 slots: a leader's walk is one work item (a block holds 16
# such pages, more than the narrow table). Pages of 64: a block is one
# page, so the walks below take one to four items.
_WALK_PAGES = [4, 64]


def _walk_specs(page):
    """(q_len, kv_len): a chunk straddling tiles on a row that fills every
    page of the narrow table, a one-token row, a row of exactly two pages,
    one slot more; the pack then runs on into an all-pad tile."""
    return [(Q_TILE + 2, _WALK_P * page), (1, 1), (2, 2 * page),
            (1, 2 * page + 1)]


_RAGGED_KERNELS = ["gqa", "gqa_q", "mla", "mla_q"]


def _widen(table, width, poison):
    R, P = table.shape
    return jnp.concatenate(
        [table, jnp.full((R, width - P), poison, jnp.int32)], axis=1)


def _pad_to_tiles(n_tiles, q_pos, row_ids, *qs):
    """Pad a pack to ``n_tiles`` whole tiles with the pack's own pad
    contract (row 0, position −1, arbitrary queries)."""
    T = row_ids.shape[0]
    pad = n_tiles * Q_TILE - T
    rng = np.random.RandomState(99)
    qs = [jnp.concatenate([q, jnp.asarray(
        rng.randn(1, pad, *q.shape[2:]) * 0.1, jnp.float32)], axis=1)
        for q in qs]
    return (jnp.concatenate([q_pos, jnp.full((1, pad), -1, jnp.int32)],
                            axis=1),
            jnp.concatenate([row_ids, jnp.zeros(pad, jnp.int32)]), *qs)


def _ragged_case(kernel, page):
    """(call(table) -> real tokens' output, narrow table, XLA reference)
    for one of the four block-ragged kernels; the pool's last page is all
    NaN (int8 pools: its scales)."""
    rng = np.random.RandomState(30)
    specs = _walk_specs(page)
    T = sum(ql for ql, _ in specs)
    n_tiles = -(-T // Q_TILE) + 1                       # one all-pad tile
    nan_last = lambda a: a.at[_WALK_NP - 1].set(jnp.nan)
    if kernel.startswith("mla"):
        ql, qp, c, pe, table, q_pos, lens, rows, scale = _mla_pack(
            rng, specs, page=page, NP=_WALK_NP, P=_WALK_P)
        table = jnp.minimum(table, _WALK_NP - 2)
        pos_p, rows_p, ql_p, qp_p = _pad_to_tiles(n_tiles, q_pos, rows,
                                                  ql, qp)
        if kernel == "mla_q":
            cq, cs = quantize_kv(c)
            peq, pes = quantize_kv(pe)
            cs, pes = nan_last(cs), nan_last(pes)
            ref = ragged_paged_mla_attention_xla(
                ql, qp, cq, peq, table, q_pos, lens, rows, scale,
                c_scales=cs, pe_scales=pes)
            return (lambda t: ragged_paged_mla_attention_pallas_q(
                ql_p, qp_p, cq, peq, t, pos_p, lens, rows_p, scale, cs, pes,
                interpret=True)[:, :T]), table, ref
        c, pe = nan_last(c), nan_last(pe)
        ref = ragged_paged_mla_attention_xla(ql, qp, c, pe, table, q_pos,
                                             lens, rows, scale)
        return (lambda t: ragged_paged_mla_attention_pallas(
            ql_p, qp_p, c, pe, t, pos_p, lens, rows_p, scale,
            interpret=True)[:, :T]), table, ref
    k, v = _pool(rng, NP=_WALK_NP, page=page)
    q, table, q_pos, lens, rows = _pack(rng, specs, P=_WALK_P, NP=_WALK_NP)
    table = jnp.minimum(table, _WALK_NP - 2)
    pos_p, rows_p, q_p = _pad_to_tiles(n_tiles, q_pos, rows, q)
    if kernel == "gqa_q":
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        ks, vs = nan_last(ks), nan_last(vs)
        ref = ragged_paged_attention_xla(q, kq, vq, table, q_pos, lens, rows,
                                         k_scales=ks, v_scales=vs)
        return (lambda t: ragged_paged_attention_pallas_q(
            q_p, kq, vq, t, pos_p, lens, rows_p, ks, vs,
            interpret=True)[:, :T]), table, ref
    k, v = nan_last(k), nan_last(v)
    ref = ragged_paged_attention_xla(q, k, v, table, q_pos, lens, rows)
    return (lambda t: ragged_paged_attention_pallas(
        q_p, k, v, t, pos_p, lens, rows_p, interpret=True)[:, :T]), table, ref


@pytest.mark.parametrize("page", _WALK_PAGES)
@pytest.mark.parametrize("kernel", _RAGGED_KERNELS)
def test_ragged_output_is_the_same_under_a_wide_table(kernel, page):
    """The same live rows under a table of width 4 and of width 512 whose
    dead entries name a page of NaNs: the same finite output, equal to
    the XLA reference — with a row that fills the narrow table, a row of
    one token, one of exactly two pages, and an all-pad tile."""
    call, table, ref = _ragged_case(kernel, page)
    narrow = np.asarray(call(table))
    wide = np.asarray(call(_widen(table, 512, poison=_WALK_NP - 1)))
    assert np.isfinite(wide).all()
    np.testing.assert_array_equal(narrow, wide)
    np.testing.assert_allclose(narrow, np.asarray(ref), rtol=1e-4, atol=1e-4)


def _pallas_grids(fn, *args):
    """The grid of every ``pallas_call`` in ``fn``'s jaxpr."""
    import jax
    grids = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return grids


@pytest.mark.parametrize("kernel", _RAGGED_KERNELS)
def test_ragged_grid_does_not_hold_the_table_width(kernel):
    call, table, _ = _ragged_case(kernel, 4)
    g24, g96 = (_pallas_grids(call, _widen(table, w, poison=0))
                for w in (24, 96))
    assert len(g24) == 1 and g24 == g96
    assert 24 not in g24[0] and 96 not in g96[0]


def test_tile_segments_count_live_blocks_only():
    """The work list behind the grid: a leader per distinct row of a
    tile walks that row's slots up to the tile's largest causal limit, a
    block of pages an item; followers and pads have no items, except that
    each tile's first token keeps one."""
    from rbg_tpu.ops.pallas.page_walk import pages_per_block
    from rbg_tpu.ops.pallas.ragged_attention_kernel import _tile_segments
    page = 16
    block = pages_per_block(page) * page
    # tile 0: row 0 x3 (the last three positions of 300), row 1 x1 (kv
    # 128), row 0 again (a second run: still a follower), pads; tile 1:
    # all pads.
    row_ids = jnp.asarray([0, 0, 0, 1, 0, 0, 0, 0] + [0] * 8, jnp.int32)
    q_pos = jnp.asarray([297, 298, 299, 127, 3, -1, -1, -1] + [-1] * 8,
                        jnp.int32)
    kv_lens = jnp.asarray([300, 128], jnp.int32)
    lead, starts = _tile_segments(row_ids, q_pos, kv_lens, page)
    assert lead.tolist() == [300, 0, 0, 128] + [0] * 12
    # the two leaders' blocks; the pad tile's first token: 1 item.
    assert np.diff(np.asarray(starts)).tolist() == (
        [-(-300 // block), 0, 0, -(-128 // block), 0, 0, 0, 0]
        + [1] + [0] * 7)


# ---- one-token rows handed over as padding (the packed step's split) ----

# (q_len, kv_len) a row, in pack order
_SPLIT_PACKS = {
    "a chunk among one-token rows": [(1, 40), (Q_TILE + 4, 70), (1, 9),
                                     (1, 130), (3, 3), (1, 64), (1, 1)],
    "one-token rows alone": [(1, 5), (1, 64), (1, 65), (1, 130)],
    "chunks alone": [(5, 5), (Q_TILE + 1, 30), (2, 130)],
}


def _as_padding(q_specs, q_pos):
    """``q_pos`` with -1 at every one-token row's token, and which tokens
    those are."""
    lone = np.repeat([n == 1 for n, _ in q_specs], [n for n, _ in q_specs])
    return jnp.where(jnp.asarray(lone)[None], -1, q_pos), lone


@pytest.mark.parametrize("window", [None, 24], ids=["full", "window"])
@pytest.mark.parametrize("pack", sorted(_SPLIT_PACKS))
def test_a_one_token_row_given_as_padding_leads_no_item(pack, window):
    """A unified step hands the ragged kernels its one-token rows' tokens
    with position -1 (``models/llama.py::_pool_attention``; the decode
    kernels attend them): ``_tile_segments`` gives such a token no item,
    and the grid is the chunk rows' live blocks, tile by tile, plus one
    for every tile whose first token leads nothing."""
    from rbg_tpu.ops.pallas.page_walk import pages_per_block
    from rbg_tpu.ops.pallas.ragged_attention_kernel import (_pad_pack,
                                                            _tile_segments)
    page = 8
    block = pages_per_block(page) * page
    q_specs = _SPLIT_PACKS[pack]
    rng = np.random.RandomState(50)
    q, _, q_pos, lens, rows = _pack(rng, q_specs, P=20, NP=161)
    given, lone = _as_padding(q_specs, q_pos)
    _, rows_p, pos_p = _pad_pack(q[0], rows, given[0])
    lead, starts, *_ = _tile_segments(rows_p, pos_p, lens, page, window)
    items = np.diff(np.asarray(starts))
    assert not np.asarray(lead)[:lone.size][lone].any()

    chunks = np.zeros_like(items)       # the chunk rows' live blocks
    at = 0
    for n, kv in q_specs:
        for tile in range(at // Q_TILE, (at + max(n, 1) - 1) // Q_TILE + 1):
            lo, hi = max(at, tile * Q_TILE), min(at + n, (tile + 1) * Q_TILE)
            if n > 1:       # its first token there leads up to its last's limit
                first = 0 if window is None else max(
                    kv - n + (lo - at) - window + 1, 0) // block
                chunks[lo] = -(-(kv - n + hi - at) // block) - first
        at += n
    heads = np.arange(chunks.size) % Q_TILE == 0
    idle = heads & (chunks == 0)        # a tile's first token that leads nothing
    assert items.tolist() == (chunks + idle).tolist()
    assert int(starts[-1]) == chunks.sum() + idle.sum()


@pytest.mark.parametrize("kernel", _RAGGED_KERNELS + ["gqa_window"])
@pytest.mark.parametrize("pack", sorted(_SPLIT_PACKS))
def test_ragged_kernels_attend_the_chunk_rows_beside_rows_given_as_padding(
        kernel, pack):
    """With the one-token rows' tokens as padding a ragged kernel's output
    is the XLA reference's at every chunk row's token; theirs are padding's
    (finite: zeros from an all-pad tile's one item, an even weighing of a
    neighbour's block where they share its tile), which the step drops."""
    q_specs = _SPLIT_PACKS[pack]
    rng = np.random.RandomState(51)
    page, scales, kw = 8, {}, {}
    if kernel.startswith("mla"):
        ql, qp, c, pe, table, q_pos, lens, rows, scale = _mla_pack(
            rng, q_specs, page=page, NP=161, P=20)
        if kernel == "mla_q":
            (c, cs), (pe, pes) = quantize_kv(c), quantize_kv(pe)
            scales = dict(c_scales=cs, pe_scales=pes)
        given, lone = _as_padding(q_specs, q_pos)
        ref = ragged_paged_mla_attention_xla(ql, qp, c, pe, table, q_pos,
                                             lens, rows, scale, **scales)
        fn = (ragged_paged_mla_attention_pallas_q if scales
              else ragged_paged_mla_attention_pallas)
        got = fn(ql, qp, c, pe, table, given, lens, rows, scale,
                 *scales.values(), interpret=True)
    else:
        k, v = _pool(rng, NP=161, page=page)
        q, table, q_pos, lens, rows = _pack(rng, q_specs, P=20, NP=161)
        if kernel == "gqa_q":
            (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
            scales = dict(k_scales=ks, v_scales=vs)
        if kernel == "gqa_window":
            kw = {"window": 24}
        given, lone = _as_padding(q_specs, q_pos)
        ref = ragged_paged_attention_xla(q, k, v, table, q_pos, lens, rows,
                                         **scales, **kw)
        fn = (ragged_paged_attention_pallas_q if scales
              else ragged_paged_attention_pallas)
        got = fn(q, k, v, table, given, lens, rows, *scales.values(),
                 interpret=True, **kw)
    got, ref = np.asarray(got)[0], np.asarray(ref)[0]
    np.testing.assert_allclose(got[~lone], ref[~lone], rtol=1e-4, atol=1e-4)
    assert np.isfinite(got).all()


# ---- the rotary key's pool a whole lane tile wide (PR 33) ----


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_mla_ragged_reads_the_same_from_a_padded_rotary_pool(form, quantized):
    """The pool as the engine holds it (``dr`` rounded up to 128 channels,
    zeros beyond the key) against the pool ``dr`` wide: the same output,
    ``dr`` taken from ``q_pe``."""
    rng = np.random.RandomState(41)
    q_specs = [(Q_TILE + 1, Q_TILE + 9), (1, 17), (3, 3)]
    ql, qp, c, pe, table, q_pos, lens, rows, scale = _mla_pack(rng, q_specs)
    scales = {}
    if quantized:
        (c, cs), (pe, pes) = quantize_kv(c), quantize_kv(pe)
        scales = dict(c_scales=cs, pe_scales=pes)

    def attend(pool):
        if form == "xla":
            return ragged_paged_mla_attention_xla(
                ql, qp, c, pool, table, q_pos, lens, rows, scale, **scales)
        if quantized:
            return ragged_paged_mla_attention_pallas_q(
                ql, qp, c, pool, table, q_pos, lens, rows, scale, cs, pes,
                interpret=True)
        return ragged_paged_mla_attention_pallas(
            ql, qp, c, pool, table, q_pos, lens, rows, scale, interpret=True)

    wide = jnp.pad(pe, ((0, 0),) * 3 + ((0, 128 - pe.shape[-1]),))
    narrow, wide = attend(pe), attend(wide)
    assert np.abs(np.asarray(narrow)).max() > 0
    np.testing.assert_array_equal(np.asarray(narrow), np.asarray(wide))
