"""Pallas paged-attention kernel vs XLA reference (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rbg_tpu.ops.paged_attention import paged_attention_xla
from rbg_tpu.ops.pallas.paged_attention_kernel import paged_attention_pallas


def _setup(B=3, H=8, KV=2, hd=32, page=8, NP=32, P=6, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, 1, H, hd), jnp.float32)
    k_pages = jnp.asarray(rng.randn(NP, page, KV, hd), jnp.float32)
    v_pages = jnp.asarray(rng.randn(NP, page, KV, hd), jnp.float32)
    # Distinct physical pages per sequence (as the allocator guarantees).
    perm = rng.permutation(NP - 1)[: B * P] + 1
    table = jnp.asarray(perm.reshape(B, P), jnp.int32)
    kv_lens = jnp.asarray(rng.randint(1, P * page, size=B), jnp.int32)
    q_pos = (kv_lens - 1)[:, None]
    return q, k_pages, v_pages, table, q_pos, kv_lens


def test_decode_kernel_matches_xla():
    q, k, v, table, q_pos, lens = _setup()
    ref = paged_attention_xla(q, k, v, table, q_pos, lens)
    got = paged_attention_pallas(q, k, v, table, q_pos, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=1e-5, atol=1e-5)


def test_decode_kernel_gqa_and_edge_lens():
    # G=1 (MHA-like) and kv_len exactly on a page boundary + len 1
    q, k, v, table, _, _ = _setup(B=4, H=4, KV=4, hd=16, page=4, NP=64, P=8,
                                  seed=1)
    lens = jnp.asarray([1, 4, 32, 17], jnp.int32)  # 1, boundary, full, mid
    q_pos = (lens - 1)[:, None]
    ref = paged_attention_xla(q, k, v, table, q_pos, lens)
    got = paged_attention_pallas(q, k, v, table, q_pos, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=1e-5, atol=1e-5)


def test_prefill_falls_back_to_xla():
    """T > 1 routes to the XLA path (same function, so trivially equal)."""
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(2, 4, 8, 32), jnp.float32)
    k = jnp.asarray(rng.randn(16, 8, 2, 32), jnp.float32)
    v = jnp.asarray(rng.randn(16, 8, 2, 32), jnp.float32)
    table = jnp.asarray(rng.randint(1, 16, size=(2, 4)), jnp.int32)
    lens = jnp.asarray([10, 20], jnp.int32)
    q_pos = jnp.asarray([[6, 7, 8, 9], [16, 17, 18, 19]], jnp.int32)
    ref = paged_attention_xla(q, k, v, table, q_pos, lens)
    got = paged_attention_pallas(q, k, v, table, q_pos, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got))


# ---- MLA (latent) decode kernel (VERDICT r4 #8) ----


from rbg_tpu.ops.mla_attention import (paged_mla_attention,
                                       paged_mla_attention_xla)
from rbg_tpu.ops.pallas.paged_attention_kernel import paged_mla_attention_pallas


def _mla_setup(B=3, H=16, dc=512, dr=64, page=8, NP=32, P=6, seed=3):
    """DeepSeek-V2-Lite latent dims by default: kv_lora_rank 512,
    qk_rope_head_dim 64, 16 heads."""
    rng = np.random.RandomState(seed)
    q_lat = jnp.asarray(rng.randn(B, 1, H, dc) * 0.1, jnp.float32)
    q_pe = jnp.asarray(rng.randn(B, 1, H, dr) * 0.1, jnp.float32)
    c_pages = jnp.asarray(rng.randn(NP, page, 1, dc) * 0.1, jnp.float32)
    pe_pages = jnp.asarray(rng.randn(NP, page, 1, dr) * 0.1, jnp.float32)
    perm = rng.permutation(NP - 1)[: B * P] + 1
    table = jnp.asarray(perm.reshape(B, P), jnp.int32)
    kv_lens = jnp.asarray(rng.randint(1, P * page, size=B), jnp.int32)
    q_pos = (kv_lens - 1)[:, None]
    scale = 1.0 / np.sqrt(128 + dr)  # qk_nope_head_dim + qk_rope_head_dim
    return q_lat, q_pe, c_pages, pe_pages, table, q_pos, kv_lens, scale


def test_mla_decode_kernel_matches_xla_v2lite_dims():
    ql, qp, c, pe, table, q_pos, lens, scale = _mla_setup()
    ref = paged_mla_attention_xla(ql, qp, c, pe, table, q_pos, lens, scale)
    got = paged_mla_attention_pallas(ql, qp, c, pe, table, q_pos, lens,
                                     scale, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=1e-5, atol=1e-5)


def test_mla_decode_kernel_edge_lens():
    ql, qp, c, pe, table, _, _, scale = _mla_setup(B=4, page=4, NP=64, P=8,
                                                   dc=128, dr=32, H=4, seed=4)
    lens = jnp.asarray([1, 4, 32, 17], jnp.int32)  # 1, boundary, full, mid
    q_pos = (lens - 1)[:, None]
    ref = paged_mla_attention_xla(ql, qp, c, pe, table, q_pos, lens, scale)
    got = paged_mla_attention_pallas(ql, qp, c, pe, table, q_pos, lens,
                                     scale, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=1e-5, atol=1e-5)


def test_mla_prefill_falls_back_to_xla():
    ql, qp, c, pe, table, _, lens, scale = _mla_setup(dc=64, dr=16, H=4)
    T = 3
    rng = np.random.RandomState(5)
    ql = jnp.asarray(rng.randn(3, T, 4, 64) * 0.1, jnp.float32)
    qp = jnp.asarray(rng.randn(3, T, 4, 16) * 0.1, jnp.float32)
    q_pos = jnp.stack([lens - 3, lens - 2, lens - 1], axis=1)
    ref = paged_mla_attention_xla(ql, qp, c, pe, table, q_pos, lens, scale)
    got = paged_mla_attention_pallas(ql, qp, c, pe, table, q_pos, lens,
                                     scale, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got))


def test_mla_dispatcher_routes_and_preserves_args(monkeypatch):
    """The dispatcher must route 'never' to the XLA path and 'always' to
    the kernel WITH the arguments in the right order — a swapped
    c_pages/pe_pages would only surface in TPU serving otherwise."""
    from rbg_tpu.ops.pallas import paged_attention_kernel as K

    ql, qp, c, pe, table, q_pos, lens, scale = _mla_setup(dc=64, dr=16, H=4)
    ref = paged_mla_attention_xla(ql, qp, c, pe, table, q_pos, lens, scale)
    never = paged_mla_attention(ql, qp, c, pe, table, q_pos, lens, scale,
                                use_pallas="never")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(never))

    calls = []

    def spy(*args, **kw):
        calls.append(args)
        return paged_mla_attention_pallas(*args, interpret=True, **kw)

    monkeypatch.setattr(K, "paged_mla_attention_pallas", spy)
    always = paged_mla_attention(ql, qp, c, pe, table, q_pos, lens, scale,
                                 use_pallas="always")
    assert len(calls) == 1
    np.testing.assert_allclose(np.asarray(ref), np.asarray(always),
                               rtol=1e-5, atol=1e-5)

    # The config guard is gone: 'always' is legal for MLA models now.
    from rbg_tpu.engine.config import EngineConfig
    EngineConfig(model="deepseek-v2-lite", use_pallas="always").validate()


# ---- int8 (quantized pool) decode kernel ----


from rbg_tpu.ops.paged_attention import quantize_kv
from rbg_tpu.ops.pallas.paged_attention_kernel import paged_attention_pallas_q


def _quantize_pages(k, v):
    kq, ks = quantize_kv(np.asarray(k))
    vq, vs = quantize_kv(np.asarray(v))
    return (jnp.asarray(kq), jnp.asarray(vq),
            jnp.asarray(ks), jnp.asarray(vs))


def test_int8_decode_kernel_matches_xla_dequant():
    q, k, v, table, q_pos, lens = _setup(seed=7)
    kq, vq, ks, vs = _quantize_pages(k, v)
    ref = paged_attention_xla(q, kq, vq, table, q_pos, lens, ks, vs)
    got = paged_attention_pallas_q(q, kq, vq, table, q_pos, lens, ks, vs,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=1e-5, atol=1e-5)


def test_int8_decode_kernel_edge_lens():
    q, k, v, table, _, _ = _setup(B=4, H=4, KV=4, hd=16, page=4, NP=64, P=8,
                                  seed=8)
    lens = jnp.asarray([1, 4, 32, 17], jnp.int32)
    q_pos = (lens - 1)[:, None]
    kq, vq, ks, vs = _quantize_pages(k, v)
    ref = paged_attention_xla(q, kq, vq, table, q_pos, lens, ks, vs)
    got = paged_attention_pallas_q(q, kq, vq, table, q_pos, lens, ks, vs,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=1e-5, atol=1e-5)


def test_mla_int8_decode_kernel_matches_xla_dequant():
    """The dequantizing MLA decode kernel (round 16): int8 latent pools
    + per-slot scales vs the XLA dequant gather."""
    from rbg_tpu.ops.pallas.paged_attention_kernel import \
        paged_mla_attention_pallas_q

    ql, qp, c, pe, table, q_pos, lens, scale = _mla_setup(seed=11)
    cq, cs = quantize_kv(c)
    peq, pes = quantize_kv(pe)
    ref = paged_mla_attention_xla(ql, qp, cq, peq, table, q_pos, lens,
                                  scale, c_scales=cs, pe_scales=pes)
    got = paged_mla_attention_pallas_q(ql, qp, cq, peq, table, q_pos,
                                       lens, scale, cs, pes,
                                       interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=1e-4, atol=1e-4)


def test_mla_int8_dispatch_routes_to_quantized_kernel(monkeypatch):
    """use_pallas='always' + int8 MLA now routes to the dequantizing
    kernel instead of raising (the last 'dequantize first' guard fell in
    round 16)."""
    from rbg_tpu.ops.pallas import paged_attention_kernel as K
    from rbg_tpu.ops.pallas.paged_attention_kernel import \
        paged_mla_attention_pallas_q

    ql, qp, c, pe, table, q_pos, lens, scale = _mla_setup(dc=64, dr=16,
                                                          H=4, seed=12)
    cq, cs = quantize_kv(c)
    peq, pes = quantize_kv(pe)
    calls = []

    def spy(*args, **kw):
        calls.append(args)
        return paged_mla_attention_pallas_q(*args, interpret=True, **kw)

    monkeypatch.setattr(K, "paged_mla_attention_pallas_q", spy)
    got = paged_mla_attention(ql, qp, cq, peq, table, q_pos, lens, scale,
                              use_pallas="always", c_scales=cs,
                              pe_scales=pes)
    assert len(calls) == 1
    ref = paged_mla_attention_xla(ql, qp, cq, peq, table, q_pos, lens,
                                  scale, c_scales=cs, pe_scales=pes)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=1e-4, atol=1e-4)


def test_int8_dispatch_routes_to_quantized_kernel(monkeypatch):
    from rbg_tpu.ops import paged_attention as PA
    from rbg_tpu.ops.pallas import paged_attention_kernel as K

    q, k, v, table, q_pos, lens = _setup(seed=9)
    kq, vq, ks, vs = _quantize_pages(k, v)
    calls = []

    def spy(*args, **kw):
        calls.append(args)
        return paged_attention_pallas_q(*args, interpret=True, **kw)

    monkeypatch.setattr(K, "paged_attention_pallas_q", spy)
    got = PA.paged_attention(q, kq, vq, table, q_pos, lens,
                             use_pallas="always", k_scales=ks, v_scales=vs)
    assert len(calls) == 1
    ref = paged_attention_xla(q, kq, vq, table, q_pos, lens, ks, vs)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=1e-5, atol=1e-5)


# ---- the page walk is over live pages only (PR 25) ----
#
# The table is as wide as max_seq_len allows; a row's live pages are a
# small prefix of its line. The kernels' grids used to hold the table's
# width; now a call must neither read past the live pages nor grow with
# the width.

from rbg_tpu.ops.pallas.paged_attention_kernel import (
    paged_mla_attention_pallas_q)

_WALK_P = 4
# Pages of 4 slots: a row is one work item (a decode block holds 32 such
# pages, more than the narrow table). Pages of 64: a block is two pages,
# so the rows below take one or two items.
_WALK_PAGES = [4, 64]


def _walk_lens(page):
    """One token, exactly k pages, one slot more, every page of the
    narrow table."""
    return [1, 2 * page, 2 * page + 1, _WALK_P * page]


def _widen(table, width, poison):
    """The same live pages under a table ``width`` wide whose other
    entries all name the ``poison`` page."""
    B, P = table.shape
    return jnp.concatenate(
        [table, jnp.full((B, width - P), poison, jnp.int32)], axis=1)


def _decode_case(kernel, page):
    """(call(table) -> output, narrow table, XLA reference) for one of
    the four decode kernels, with the pool's last page all NaN (int8
    pools: its scales)."""
    lens = jnp.asarray(_walk_lens(page), jnp.int32)
    B, NP = lens.shape[0], 32
    q_pos = (lens - 1)[:, None]
    nan_last = lambda a: a.at[NP - 1].set(jnp.nan)
    if kernel.startswith("mla"):
        ql, qp, c, pe, table, _, _, scale = _mla_setup(
            B=B, H=4, dc=128, dr=32, page=page, NP=NP, P=_WALK_P,
            seed=21)
        table = jnp.minimum(table, NP - 2)
        if kernel == "mla_q":
            cq, cs = quantize_kv(c)
            peq, pes = quantize_kv(pe)
            cs, pes = nan_last(cs), nan_last(pes)
            ref = paged_mla_attention_xla(ql, qp, cq, peq, table, q_pos, lens,
                                          scale, c_scales=cs, pe_scales=pes)
            return (lambda t: paged_mla_attention_pallas_q(
                ql, qp, cq, peq, t, q_pos, lens, scale, cs, pes,
                interpret=True)), table, ref
        c, pe = nan_last(c), nan_last(pe)
        ref = paged_mla_attention_xla(ql, qp, c, pe, table, q_pos, lens,
                                      scale)
        return (lambda t: paged_mla_attention_pallas(
            ql, qp, c, pe, t, q_pos, lens, scale, interpret=True)), table, ref
    q, k, v, table, _, _ = _setup(B=B, H=4, KV=2, hd=16, page=page,
                                  NP=NP, P=_WALK_P, seed=20)
    table = jnp.minimum(table, NP - 2)
    if kernel == "gqa_q":
        kq, vq, ks, vs = _quantize_pages(k, v)
        ks, vs = nan_last(ks), nan_last(vs)
        ref = paged_attention_xla(q, kq, vq, table, q_pos, lens, ks, vs)
        return (lambda t: paged_attention_pallas_q(
            q, kq, vq, t, q_pos, lens, ks, vs, interpret=True)), table, ref
    k, v = nan_last(k), nan_last(v)
    ref = paged_attention_xla(q, k, v, table, q_pos, lens)
    return (lambda t: paged_attention_pallas(
        q, k, v, t, q_pos, lens, interpret=True)), table, ref


_DECODE_KERNELS = ["gqa", "gqa_q", "mla", "mla_q"]


@pytest.mark.parametrize("page", _WALK_PAGES)
@pytest.mark.parametrize("kernel", _DECODE_KERNELS)
def test_decode_output_is_the_same_under_a_wide_table(kernel, page):
    """Lengths of 1, of exactly k pages, and one that fills the narrow
    table: a table of width 4 and one of width 512, whose dead entries
    name a page of NaNs, give the same finite output."""
    call, table, ref = _decode_case(kernel, page)
    narrow = np.asarray(call(table))
    wide = np.asarray(call(_widen(table, 512, poison=31)))
    assert np.isfinite(wide).all()
    np.testing.assert_array_equal(narrow, wide)
    np.testing.assert_allclose(narrow, np.asarray(ref), rtol=1e-4, atol=1e-4)


def _pallas_grids(fn, *args):
    """The grid of every ``pallas_call`` in ``fn``'s jaxpr."""
    grids = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return grids


@pytest.mark.parametrize("kernel", _DECODE_KERNELS)
def test_decode_grid_does_not_hold_the_table_width(kernel):
    call, table, _ = _decode_case(kernel, 4)
    g24, g96 = (_pallas_grids(call, _widen(table, w, poison=0))
                for w in (24, 96))
    assert len(g24) == 1 and g24 == g96
    assert 24 not in g24[0] and 96 not in g96[0]


def test_decode_empty_row_finalizes_to_zero():
    """A row of length 0 (a free slot of the batch) keeps one work item
    that attends nothing; its output is zero, its neighbours' untouched."""
    q, k, v, table, _, _ = _setup(B=3, seed=22)
    lens = jnp.asarray([9, 0, 20], jnp.int32)
    q_pos = jnp.maximum(lens - 1, 0)[:, None]
    got = np.asarray(paged_attention_pallas(q, k, v, table, q_pos, lens,
                                            interpret=True))
    ref = np.asarray(paged_attention_xla(q, k, v, table, q_pos, lens))
    assert np.all(got[1] == 0)
    np.testing.assert_allclose(got[[0, 2]], ref[[0, 2]], rtol=1e-5, atol=1e-5)


# ---- the rotary key's pool a whole lane tile wide (PR 33) ----
#
# The engine holds that pool ``dr`` rounded up to 128 channels, zeros
# beyond the key; an attend takes ``dr`` from ``q_pe`` and uses the pool's
# first ``dr`` channels, whatever its width.


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_mla_decode_reads_the_same_from_a_padded_rotary_pool(form, quantized):
    ql, qp, c, pe, table, q_pos, lens, scale = _mla_setup(seed=31)
    scales = {}
    if quantized:
        (c, cs), (pe, pes) = quantize_kv(c), quantize_kv(pe)
        scales = dict(c_scales=cs, pe_scales=pes)

    def attend(pool):
        if form == "xla":
            return paged_mla_attention_xla(ql, qp, c, pool, table, q_pos,
                                           lens, scale, **scales)
        if quantized:
            return paged_mla_attention_pallas_q(
                ql, qp, c, pool, table, q_pos, lens, scale, cs, pes,
                interpret=True)
        return paged_mla_attention_pallas(ql, qp, c, pool, table, q_pos,
                                          lens, scale, interpret=True)

    wide = jnp.pad(pe, ((0, 0),) * 3 + ((0, 128 - pe.shape[-1]),))
    narrow, wide = attend(pe), attend(wide)
    assert np.abs(np.asarray(narrow)).max() > 0
    np.testing.assert_array_equal(np.asarray(narrow), np.asarray(wide))


# ---- the decode walk that copies its own pages (PRs 51, 53) ----
#
# bf16 pools whose pages are whole tiles (``page_walk.kernel_copies``: K/V
# pools of 8 or 16 heads of 128, of heads of 64 packed two to a lane tile,
# and the latent pools) are walked by a kernel that issues the page copies
# itself; every other pool keeps the pipeline. Same blocks, same order,
# same ``gqa_attend`` / ``mla_attend``: the two agree to the bit.

from rbg_tpu.ops.pallas import page_walk
from rbg_tpu.ops.pallas import paged_attention_kernel as decode_kernel

_COPY_PAGE = 16
_BLOCK = page_walk.decode_pages_per_block(_COPY_PAGE) * _COPY_PAGE   # 128

# id: (the pool's heads, queries a pool's head, table width, window, the
# rows' lengths, a head's size where the pool packs two to a lane tile).
# Every case holds an empty row; "last" where it ends the call, "between"
# where live rows follow it.
_COPY_CASES = {
    "kv8-g8-table8-one-block": (8, 8, 8, None, [1, 0, _BLOCK, 77, 0], None),
    "kv8-g6-table512-whole-table": (
        8, 6, 512, None, [_BLOCK + 1, 0, 3 * _BLOCK, 512 * _COPY_PAGE, 1],
        None),
    "kv16-g1-table32": (16, 1, 32, None,
                        [_BLOCK, _BLOCK + 1, 0, 300, 32 * _COPY_PAGE, 0],
                        None),
    "kv8-g1-table8": (8, 1, 8, None, [0, 5, _BLOCK], None),
    "kv8-g8-window512-starts-past-block-0": (
        8, 8, 64, 512, [700, 1000, 0, 513, 40, 1024, 0], None),
    "kv16-g1-window128": (16, 1, 32, 128, [129, 0, 128, 400, 1], None),
    # LFM2's pool: 8 heads of 64 as 4 of 128, four queries a head as eight
    "packed-kv4-g8-table256-empty-last": (
        4, 8, 256, None, [_BLOCK, 2047, 1, 0, 130, 0], 64),
    "packed-kv4-g8-table32-whole-table": (
        4, 8, 32, None, [0, 32 * _COPY_PAGE, 0, _BLOCK + 1, 77], 64),
    "packed-kv2-g2-table8-empty-between": (
        2, 2, 8, None, [5, 0, 0, _BLOCK, 1], 64),
    "packed-kv4-g4-window128": (4, 4, 32, 128, [129, 0, 128, 400, 0], 64),
}


def _copy_pools(rng, lens, P, window, *tails):
    """bf16 pools ``[NP, page, *tail]`` whose LAST page is NaN, and a
    table ``P`` wide over them whose dead entries all name it (a window
    layer's pages wholly below the window too: they were given back)."""
    page = _COPY_PAGE
    live = [-(-n // page) for n in lens]
    NP = sum(live) + 2
    pools = tuple(jnp.asarray(rng.randn(NP, page, *tail), jnp.bfloat16)
                  .at[NP - 1].set(jnp.nan) for tail in tails)
    table = np.full((len(lens), P), NP - 1, np.int32)
    pages, at = rng.permutation(NP - 2) + 1, 0
    for b, n in enumerate(live):
        below = 0 if window is None else max(lens[b] - window, 0) // _BLOCK \
            * (_BLOCK // page)
        table[b, below:n] = pages[at + below:at + n]
        at += n
    return pools, jnp.asarray(table), jnp.asarray(lens, jnp.int32)


def _copy_case(KV, G, P, window, lens, head_dim, seed):
    """Queries, K/V pools of whole-tile pages and their table
    (``_copy_pools``). ``head_dim``: the queries are
    ``page_walk.pack_queries``' of heads that size."""
    rng = np.random.RandomState(seed)
    pools, table, kv_lens = _copy_pools(rng, lens, P, window,
                                        (KV, 128), (KV, 128))
    p = 1 if head_dim is None else 128 // head_dim
    q = page_walk.pack_queries(jnp.asarray(
        rng.randn(len(lens), KV * p, G // p, 128 // p), jnp.bfloat16), p)
    return q, pools, table, kv_lens


@pytest.mark.parametrize("case", sorted(_COPY_CASES))
def test_decode_walk_with_kernel_copies_equals_the_pipeline_to_the_bit(
        case, monkeypatch):
    KV, G, P, window, lens, head_dim = _COPY_CASES[case]
    q, pools, table, kv_lens = _copy_case(KV, G, P, window, lens, head_dim,
                                          seed=len(case))
    assert page_walk.kernel_copies(pools)
    walk = lambda: np.asarray(decode_kernel._decode(
        q, pools, table, kv_lens, True, head_dim, window), np.float32)
    copied = walk()
    monkeypatch.setattr(page_walk, "kernel_copies", lambda pools: False)
    piped = walk()
    assert np.isfinite(copied).all()
    np.testing.assert_array_equal(copied, piped)
    empty = np.asarray(lens) == 0
    p = 1 if head_dim is None else 128 // head_dim
    own = np.asarray(page_walk.unpack_outputs(jnp.asarray(copied), p))
    assert np.all(copied[empty] == 0) and np.abs(own[~empty]).min() > 0
    # and both are the XLA form's answer (which reads a packed pool as the
    # heads it holds)
    H, hd = KV * G, 128 // p
    ref = paged_attention_xla(
        page_walk.unpack_outputs(q, p).reshape(
            len(lens), 1, H, hd).astype(jnp.float32),
        *(jnp.nan_to_num(pool.astype(jnp.float32)) for pool in pools), table,
        jnp.maximum(kv_lens - 1, 0)[:, None], kv_lens, window=window)
    np.testing.assert_allclose(
        own.reshape(ref.shape)[~empty], np.asarray(ref)[~empty],
        rtol=2e-2, atol=2e-2)


# id: (heads, latent width, rotary key's width, table width, the rows'
# lengths): JoyAI's and Kimi's pools ``[NP, 16, 1, 512]`` / ``[NP, 16, 1,
# 128]`` and a narrower latent, an empty row last and between.
_LATENT_COPY_CASES = {
    "h32-dc512-table256-empty-last": (
        32, 512, 64, 256, [_BLOCK, 2047, 1, 0, 130, 0]),
    "h4-dc512-table32-whole-table": (
        4, 512, 64, 32, [0, 32 * _COPY_PAGE, 0, _BLOCK + 1, 77]),
    "h8-dc128-dr32-table8-empty-between": (
        8, 128, 32, 8, [5, 0, 0, _BLOCK, 1]),
    "h16-dc256-table8-one-block": (16, 256, 64, 8, [1, 0, _BLOCK, 77, 0]),
}


@pytest.mark.parametrize("case", sorted(_LATENT_COPY_CASES))
def test_latent_decode_walk_with_kernel_copies_equals_the_pipeline_to_the_bit(
        case, monkeypatch):
    """The latent twin: bf16 latent pools whose LAST page is NaN and a
    table whose dead entries all name it; the kernel that copies its own
    pages, the pipeline's and the XLA form."""
    H, dc, dr, P, lens = _LATENT_COPY_CASES[case]
    rng, B = np.random.RandomState(len(case)), len(lens)
    (c, pe), table, kv_lens = _copy_pools(rng, lens, P, None, (1, dc),
                                          (1, 128))
    # the rotary key's pool a lane tile wide, zero beyond the key
    pe = pe.at[:-1, ..., dr:].set(0)
    ql = jnp.asarray(rng.randn(B, H, dc), jnp.bfloat16)
    qp = jnp.asarray(rng.randn(B, H, dr), jnp.bfloat16)
    scale = (dc + dr) ** -0.5
    assert page_walk.kernel_copies(page_walk.latent_pools(c, pe))
    walk = lambda: np.asarray(decode_kernel._mla_decode(
        ql, qp, (c, pe), table, kv_lens, scale, True), np.float32)
    copied = walk()
    monkeypatch.setattr(page_walk, "kernel_copies", lambda pools: False)
    piped = walk()
    assert np.isfinite(copied).all()
    np.testing.assert_array_equal(copied, piped)
    empty = np.asarray(lens) == 0
    assert np.all(copied[empty] == 0) and np.abs(copied[~empty]).min() > 0
    ref = paged_mla_attention_xla(
        ql[:, None].astype(jnp.float32), qp[:, None].astype(jnp.float32),
        *(jnp.nan_to_num(a.astype(jnp.float32)) for a in (c, pe)), table,
        jnp.maximum(kv_lens - 1, 0)[:, None], kv_lens, scale)
    np.testing.assert_allclose(copied[~empty], np.asarray(ref)[~empty, 0],
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("pools,copies", [
    ([((64, 16, 8, 128), jnp.bfloat16)] * 2, True),      # mixtral, laguna
    ([((64, 16, 16, 128), jnp.bfloat16)] * 2, True),     # ouro
    ([((64, 16, 8, 256), jnp.bfloat16)] * 2, True),
    ([((64, 16, 4, 128), jnp.bfloat16)] * 2, True),      # lfm2: packed heads
    ([((64, 16, 2, 128), jnp.bfloat16)] * 2, True),
    ([((64, 16, 1, 128), jnp.bfloat16)] * 2, False),     # qwen2's 2 as 1
    ([((64, 16, 12, 128), jnp.bfloat16)] * 2, False),
    ([((64, 16, 8, 64), jnp.bfloat16)] * 2, False),
    ([((64, 16, 2, 32), jnp.bfloat16)] * 2, False),      # the tiny presets
    ([((64, 16, 8, 128), jnp.float32)] * 2, False),
    ([((64, 16, 8, 128), jnp.int8)] * 2
     + [((64, 16, 8), jnp.float32)] * 2, False),         # int8 and scales
    ([((64, 16, 512), jnp.bfloat16), ((64, 16, 128), jnp.bfloat16)],
     True),                                              # latents
    ([((64, 16, 512), jnp.bfloat16), ((64, 16, 64), jnp.bfloat16)], False),
    ([((64, 4, 512), jnp.bfloat16), ((64, 4, 128), jnp.bfloat16)], False),
    ([((64, 16, 512), jnp.int8), ((64, 16, 128), jnp.int8)]
     + [((64, 16, 1), jnp.float32)] * 2, False),         # int8 latents
], ids=["kv8", "kv16", "hd256", "packed", "packed-kv2", "packed-kv1", "kv12",
        "hd64", "tiny", "f32", "int8", "latent", "latent-key-of-64",
        "latent-page4", "latent-int8"])
def test_kernel_copies_is_read_off_the_pools(pools, copies):
    assert page_walk.kernel_copies(
        [jax.ShapeDtypeStruct(*p) for p in pools]) is copies
