"""The decode kernels' item table (``page_walk.walk_items``) and the
kernels that read it.

A decode walk's (row, block, physical page) of every work item is
resolved once a call in XLA; the kernels' index maps are one read of that
table each. Here the table is held to a plain numpy walk and to the
bisection it replaced (``find_item`` / ``page_of_block``, which the ragged
kernels and ``_kda_decode_call`` still run), the kernels' outputs to the
parent's kernel, kept below as the oracle, to the bit, and the lowered
index maps to holding no arithmetic.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rbg_tpu.ops.paged_attention import quantize_kv
from rbg_tpu.ops.pallas import page_walk as W
from rbg_tpu.ops.pallas import paged_attention_kernel as K

POISON = 9999            # what every dead entry of a table names


def _special_lens(page, P):
    """An empty row, one token, a page's end, a block's end, a block's end
    + 1 and the whole table (what the table cannot hold is cut to it)."""
    block = W.decode_pages_per_block(page) * page
    return [min(n, P * page) for n in (0, 1, page, block, block + 1, P * page)]


def _table(lens, page, P, rng):
    """Distinct live pages row by row; every entry past a row's live
    pages names ``POISON`` (an empty row keeps its first entry: its one
    item names that page, and attends nothing)."""
    table = np.full((len(lens), P), POISON, np.int32)
    live = np.maximum(-(-np.asarray(lens) // page), 1)
    pages = rng.permutation(POISON)[:live.sum()]
    at = 0
    for r, n in enumerate(live):
        table[r, :n] = pages[at:at + n]
        at += n
    return table


def _numpy_walk(lens, table, page):
    """(row, block, [page of each of the block's pages]) of every item of
    the grid, in order; then of the items at and past its end, up to the
    table's capacity: the last row's, as the bisection resolved them."""
    S, P = table.shape
    n = W.decode_pages_per_block(page)

    def pages_of(row, block):
        last = -(-int(lens[row]) // page) - 1
        return [int(table[row, min(max(min(block * n + j, last), 0), P - 1)])
                for j in range(n)]

    items, first_of_last = [], 0
    for row in range(S):
        first_of_last = len(items)
        for block in range(max(-(-int(lens[row]) // (n * page)), 1)):
            items.append((row, block, pages_of(row, block)))
    total = len(items)
    for w in range(total, S * -(-P // n) + 1):
        items.append((S - 1, w - first_of_last,
                      pages_of(S - 1, w - first_of_last)))
    return items, total


@functools.partial(jax.jit, static_argnames="page")
def _items(lens, table, page):
    n = W.decode_pages_per_block(page)
    starts = W.live_block_starts(lens, page, True, n)
    return starts, W.walk_items(starts, lens, table, page, n)


@pytest.mark.parametrize("B", [1, 8, 32])
@pytest.mark.parametrize("P", [4, 512])
@pytest.mark.parametrize("page", [4, 16, 64])
def test_items_are_the_plain_walk(page, P, B):
    special = _special_lens(page, P)
    n = W.decode_pages_per_block(page)
    for shift in range(len(special)):
        rng = np.random.default_rng(100 * page + P + B + shift)
        lens = np.asarray([special[(r + shift) % len(special)]
                           for r in range(B)], np.int32)
        table = _table(lens, page, P, rng)
        starts, (row, block, pages) = jax.tree_util.tree_map(
            np.asarray, _items(jnp.asarray(lens), jnp.asarray(table), page))
        want, total = _numpy_walk(lens, table, page)
        assert starts[-1] == total
        assert row.shape == block.shape == (B * -(-P // n) + 1,)
        assert pages.shape == (n,) + row.shape
        assert len(want) == row.shape[0] and total < row.shape[0]
        got = [(int(row[w]), int(block[w]), pages[:, w].tolist())
               for w in range(len(want))]
        assert got == want
        # no entry beyond a row's live pages, whatever lies there
        assert POISON not in pages


@pytest.mark.parametrize("page,P,B", [(4, 24, 3), (16, 96, 8), (64, 5, 32)])
def test_items_are_what_the_bisection_found(page, P, B):
    """Item by item what every index map used to compute, and the ragged
    kernels' still do: ``find_item`` and ``page_of_block`` over the same
    starts, lengths and table, at their block's width."""
    rng = np.random.default_rng(page + B)
    lens = rng.integers(0, P * page + 1, size=B).astype(np.int32)
    lens[rng.integers(B)] = 0
    table = jnp.asarray(_table(lens, page, P, rng))
    lens = jnp.asarray(lens)
    n = W.pages_per_block(page)
    starts = W.live_block_starts(lens, page, True)
    row, block, pages = W.walk_items(starts, lens, table, page, n)

    def bisected(w):
        b, blk = W.find_item(starts, w, B)
        return b, blk, jnp.stack([
            W.page_of_block(table, b, blk, j, lens[b], page)
            for j in range(n)])

    b, blk, pg = jax.vmap(bisected)(jnp.arange(row.shape[0], dtype=jnp.int32))
    np.testing.assert_array_equal(np.asarray(row), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(block), np.asarray(blk))
    np.testing.assert_array_equal(np.asarray(pages), np.asarray(pg).T)


# ---- the parent's kernel (PRs 25-39) as oracle -------------------------------
#
# Its walk: every index map, and the body, bisect the rows' cumulative
# blocks, and every page's map walks the page table again. The softmax
# update, the block fetch and the order of a row's blocks are the
# kernels' own (``gqa_attend``, ``mla_attend``, ``load_blocks``), so the
# outputs have to be equal to the bit: only addressing differs.


def _parent_call(queries, pools, table, lens, attend, scratch):
    """One call of the parent's decode kernel, at the decode kernels'
    block (the parent's was the ragged kernels' 64 slots; a block's width
    is the order the softmax sums in): ``queries`` are ``[B, ...]``
    arrays fetched a row at a time, the output has the first's shape, and
    ``attend(query_refs, page_refs, n, token0, kv_len, m, l, acc)`` is
    the family's update."""
    B, page = lens.shape[0], pools[0].shape[1]
    n = W.decode_pages_per_block(page)

    def page_id(j, w, table, lens, starts):      # ``page_of_block``, n wide
        b, block = W.find_item(starts, w, B)
        last = (lens[b] + (page - 1)) // page - 1
        return table[b, jnp.clip(jnp.minimum(block * n + j, last), 0,
                                 table.shape[1] - 1)]

    def row_of(rank):
        return lambda w, table, lens, starts: (
            (W.find_item(starts, w, B)[0],) + (0,) * (rank - 1))

    def kernel(table_ref, lens_ref, starts_ref, *refs):
        q_refs = refs[:len(queries)]
        *pages, out_ref, m_ref, l_ref, acc_ref = refs[len(queries):]
        w = pl.program_id(0)
        b, block = W.find_item(starts_ref, w, B)
        kv_len = lens_ref[b]
        token0 = block * (n * page)

        @pl.when(block == 0)
        def _init():
            W.init_softmax(m_ref, l_ref, acc_ref)

        @pl.when(token0 < kv_len)
        def _attend():
            attend(q_refs, pages, n, token0, kv_len, m_ref, l_ref, acc_ref)

        @pl.when(w + 1 == starts_ref[b + 1])
        def _finalize():
            out_ref[0] = W.finalize_softmax(l_ref, acc_ref, out_ref.dtype)

    starts = W.live_block_starts(lens, page, True, n)
    page_specs, page_operands = W.block_specs(pools, page_id, n)
    row_spec = lambda a: pl.BlockSpec((1,) + a.shape[1:], row_of(a.ndim))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(starts[B],),
            in_specs=[row_spec(a) for a in queries] + page_specs,
            out_specs=row_spec(queries[0]),
            scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in scratch]),
        out_shape=jax.ShapeDtypeStruct(queries[0].shape, queries[0].dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=True,
    )(table, lens, starts, *queries, *page_operands)


def _parent_decode(q, pools, table, lens, head_dim=None):
    _, KV, G, hd = q.shape

    def attend(q_refs, pages, n, token0, kv_len, m_ref, l_ref, acc_ref):
        k, v, *scales = W.load_blocks(pages, n)
        ks, vs = scales or (None, None)
        W.gqa_attend(q_refs[0][0], k, v, ks, vs, token0, kv_len,
                     m_ref, l_ref, acc_ref, head_dim)

    return _parent_call((q,), pools, table, lens, attend,
                        [(KV, G, 1), (KV, G, 1), (KV, G, hd)])


def _parent_mla_decode(q_lat, q_pe, pools, table, lens, scale):
    pools = W.latent_pools(*pools[:2]) + tuple(pools[2:])
    _, H, dc = q_lat.shape

    def attend(q_refs, pages, n, token0, kv_len, m_ref, l_ref, acc_ref):
        c, pe, cs, ps = W.load_latent_blocks(pages, n)
        W.mla_attend(q_refs[0][0], q_refs[1][0], c, pe, cs, ps, token0,
                     kv_len, scale, m_ref, l_ref, acc_ref)

    return _parent_call((q_lat, q_pe), pools, table, lens, attend,
                        [(H, 1), (H, 1), (H, dc)])


_PAGE, _P, _NP = 16, 24, 64
_FAMILIES = ["gqa", "gqa_packed", "gqa_int8", "latent", "latent_int8"]


def _family_case(family, seed=40):
    """(the kernel as it is, the parent's, (table, lens)) of one family:
    a call each of ``f(table, lens)``. The rows: empty, one token, a
    page's end, a block's end, one more, several blocks and a ragged
    tail."""
    block = W.decode_pages_per_block(_PAGE) * _PAGE
    lens = jnp.asarray([0, 1, _PAGE, block, block + 1, 2 * block + 5],
                       jnp.int32)
    B = lens.shape[0]
    rng = np.random.default_rng(seed)
    table = jnp.asarray(np.minimum(_table(np.asarray(lens), _PAGE, _P, rng),
                                   _NP - 1))
    keys = jax.random.split(jax.random.key(seed), 4)
    if family.startswith("latent"):
        H, dc, dr, scale = 4, 128, 32, 0.11
        ql = jax.random.normal(keys[0], (B, H, dc), jnp.float32)
        qp = jax.random.normal(keys[1], (B, H, dr), jnp.float32)
        pools = (jax.random.normal(keys[2], (_NP, _PAGE, 1, dc), jnp.float32),
                 jax.random.normal(keys[3], (_NP, _PAGE, 1, dr), jnp.float32))
        if family == "latent_int8":
            (c, cs), (pe, ps) = quantize_kv(pools[0]), quantize_kv(pools[1])
            pools = (c, pe, cs[..., 0], ps[..., 0])
        return (lambda t, l: K._mla_decode(ql, qp, pools, t, l, scale, True),
                lambda t, l: _parent_mla_decode(ql, qp, pools, t, l, scale),
                (table, lens))
    KV, G, hd = 2, 2, 16
    q = jax.random.normal(keys[0], (B, KV, G, hd), jnp.float32)
    pools = (jax.random.normal(keys[1], (_NP, _PAGE, KV, hd), jnp.float32),
             jax.random.normal(keys[2], (_NP, _PAGE, KV, hd), jnp.float32))
    head_dim = None
    if family == "gqa_packed":       # two heads of 8 a tile of 16 lanes
        q, head_dim = W.pack_queries(q.reshape(B, 2 * KV, G, hd // 2), 2), 8
    if family == "gqa_int8":
        (k, ks), (v, vs) = quantize_kv(pools[0]), quantize_kv(pools[1])
        pools = (k, v, ks[..., 0], vs[..., 0])
    return (lambda t, l: K._decode(q, pools, t, l, True, head_dim),
            lambda t, l: _parent_decode(q, pools, t, l, head_dim),
            (table, lens))


@pytest.mark.parametrize("family", _FAMILIES)
def test_decode_kernel_equals_the_parents_to_the_bit(family):
    kernel, parent, args = _family_case(family)
    got, want = np.asarray(kernel(*args)), np.asarray(parent(*args))
    assert np.isfinite(got).all() and np.abs(got[1:]).max() > 0
    assert np.all(got[0] == 0)                      # the empty row
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("family", _FAMILIES)
def test_rows_handed_over_at_length_zero_cost_an_item_each(family):
    """A unified step hands the decode kernels its one-token rows alone,
    every other row at length 0 (``models/llama.py::_pool_attention``; the
    ragged kernels attend those): such a row is one item that attends
    nothing and writes zeros (it names its line's first page, which a
    row with tokens in the step has), and the rows that stay read as they
    did, to the bit."""
    kernel, _, (table, lens) = _family_case(family)
    whole = np.asarray(kernel(table, lens))
    keep = np.asarray([False, True, False, True, False, True])
    some = jnp.where(keep, lens, 0)
    got = np.asarray(kernel(table, some))
    np.testing.assert_array_equal(got[keep], whole[keep])
    assert not got[~keep].any()
    n = W.decode_pages_per_block(_PAGE)
    starts = np.asarray(W.live_block_starts(some, _PAGE, True, n))
    blocks = -(-np.asarray(lens) // (n * _PAGE))
    assert np.diff(starts).tolist() == np.where(keep, blocks, 1).tolist()
    assert not np.asarray(kernel(table, jnp.zeros_like(lens))).any()


# ---- what a decode kernel's index maps lower to ------------------------------


def _pallas_calls(fn, *args):
    calls = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                calls.append(eqn.params["grid_mapping"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return calls


def _map_primitives(grid_mapping):
    """The primitives of each operand's index map."""
    return [[e.primitive.name for e in bm.index_map_jaxpr.jaxpr.eqns]
            for bm in grid_mapping.block_mappings]


def _widen(table, width):
    return jnp.concatenate(
        [table, jnp.full((table.shape[0], width - table.shape[1]), POISON,
                         jnp.int32)], axis=1)


@pytest.mark.parametrize("family", _FAMILIES)
def test_decode_index_maps_are_one_read_each(family, monkeypatch):
    """No index map of a decode kernel computes: each is one read of the
    item table (the parent's, traced the same way, hold the bisection's
    compare / select chain), and neither the maps nor the body call
    ``find_item`` or ``page_of_block``. The grid holds the table's width
    no more than it did: 24 and 96 wide give one grid."""
    kernel, parent, (table, lens) = _family_case(family)
    chains = _map_primitives(_pallas_calls(parent, table, lens)[0])
    # (a select a trip of the search: three over six rows)
    assert all(m.count("select_n") >= 3 for m in chains)

    def refused(*a, **kw):
        raise AssertionError("a decode kernel bisects")

    monkeypatch.setattr(W, "find_item", refused)
    monkeypatch.setattr(W, "page_of_block", refused)
    grids = []
    for width in (24, 96):
        (mapping,) = _pallas_calls(kernel, _widen(table, width), lens)
        grids.append(tuple(mapping.grid))
        maps = _map_primitives(mapping)
        assert len(maps) == len(chains)
        for prims in maps:
            reads = [p for p in prims if p == "get"]
            assert len(reads) == 1 and len(prims) <= 3, prims
            assert not {"select_n", "le", "lt", "div", "min", "max", "clamp",
                        "shift_right_arithmetic", "while"} & set(prims)
    assert grids[0] == grids[1] and len(grids[0]) == 1
    assert 24 not in grids[0] and 96 not in grids[1]
