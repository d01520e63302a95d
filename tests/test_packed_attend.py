"""A packed step's attend, split by what each row holds
(``models/llama.py::_pool_attention``, the packed branch): the rows of ONE
token take the decode step's walk, the ragged walk is given the rows of two
tokens or more, they alone.

The packs are ``tests/kda_packed_case.py::STEPS``, the ones the delta-rule
layers' split is held to (a chunk row among one-token rows and a row with
no token; no row holds a chunk; every row holds a chunk; all padding; a
short chunk at the end of the bucket), run for a GQA layer, a window layer
(``tiny-laguna``'s), a latent layer (``tiny-mla``) and an int8 pool. The
oracle is the unsplit path, written out here: the one write, then the
ragged XLA attend over the whole pack at its true positions. Every row bucket is split, the smallest too (``FEW_ROWS``).
"""

import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from kda_packed_case import STEPS

from rbg_tpu.engine.kvcache import (PagedKVCache, heads_per_lane_tile,
                                    rope_pool_width)
from rbg_tpu.models import get_config, init_params, llama
from rbg_tpu.ops.mla_attention import ragged_paged_mla_attention_xla
from rbg_tpu.ops.paged_attention import quantize_kv

# (the packages' attributes of these names are the functions)
ragged_ops = importlib.import_module("rbg_tpu.ops.ragged_paged_attention")
paged_ops = importlib.import_module("rbg_tpu.ops.paged_attention")
mla_ops = importlib.import_module("rbg_tpu.ops.mla_attention")

I32 = jnp.int32
PAGE, LAYERS, LAYER = 8, 2, 1
# kind of layer -> (preset, the mixer its group config names, int8 pool)
KINDS = {"gqa": ("tiny", "full", False),
         "window": ("tiny-laguna", "window", False),
         "latent": ("tiny-mla", "full", False),
         "int8": ("tiny", "full", True)}
# what a change of block width may move (the decode walk's blocks are 128
# slots, the ragged walk's 64: the order a softmax sums in)
KERNEL_TOLERANCE = {"gqa": 2e-5, "window": 2e-5, "latent": 2e-5,
                    "int8": 2e-4}


@functools.lru_cache(maxsize=None)
def _layer(kind):
    """(the layer kind's group config, one layer's mixer parameters)."""
    preset, mixer, _ = KINDS[kind]
    cfg = get_config(preset)
    params = init_params(cfg, jax.random.key(3))
    if not cfg.by_kind:
        g = cfg.layer_groups[0][1]
        return g, {k: v[0] for k, v in params[cfg.layer_groups[0][0]].items()}
    g, key, at, _, _ = next(h for h in cfg.layer_halves
                            if h[0].attention == mixer)
    return g, {k: v[at] for k, v in params[key].items()}


# The row buckets a server meets while it fills or drains, as ``STEPS``
# writes a pack: (R, T, C, [(tokens, fresh), ...]).
FEW_ROWS = {"one row of one token": (1, 8, 8, [(1, 0)]),
            "a chunk row and a one-token row": (2, 32, 16, [(1, 0), (16, 0)])}


def _step(name, kind, seed=0):
    """``_pool_attention``'s arguments for the pack ``name`` (of ``STEPS``
    or ``FEW_ROWS``) on a layer of ``kind``: ``(g, blk, x, pool, table,
    addr)``, the pool that of ``LAYERS`` layers, flat and full of other
    rows' values, ``table`` the rows' lines of layer ``LAYER``; and which
    packed tokens are real."""
    R, T, C, lens = {**STEPS, **FEW_ROWS}[name]
    g, blk = _layer(kind)
    rng = np.random.default_rng(seed)
    P = -(-(130 + C) // PAGE)
    NP = R * P + 1
    if g.mla:       # the latents, and the rotary key a whole lane tile wide
        tails = (1, g.kv_lora_rank), (1, rope_pool_width(g))
    else:           # heads under a lane tile side by side
        p = 1 if KINDS[kind][2] else heads_per_lane_tile(g)
        tails = ((g.num_kv_heads // p, p * g.head_dim_),) * 2
    k, v = (jnp.asarray(rng.normal(size=(LAYERS * NP, PAGE) + tail),
                        jnp.float32) for tail in tails)
    pool = (k, v, None, None)
    if KINDS[kind][2]:
        (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
        pool = (k, v, ks, vs)
    x = jnp.asarray(rng.normal(size=(1, T, g.hidden_size)), jnp.float32)
    pos = np.full((1, T), -1, np.int32)
    mask = np.zeros((1, T), bool)
    row_ids = np.zeros(T, np.int32)
    kv_lens = np.zeros(R, np.int32)
    off = 0
    for r, (n, fresh) in enumerate(lens):
        at = 0 if fresh else int(rng.integers(1, 130))
        pos[0, off:off + n] = at + np.arange(n)
        mask[0, off:off + n] = True
        row_ids[off:off + n] = r
        kv_lens[r] = at + n if n else 0
        off += n
    table = jnp.asarray(
        1 + rng.permutation(R * P).reshape(R, P) + LAYER * NP, I32)
    addr = llama.PoolAddr(jnp.asarray(pos), jnp.asarray(mask),
                          jnp.asarray(kv_lens), table, jnp.asarray(row_ids),
                          C)
    return (g, blk, x, pool, table, addr), mask[0]


def _unsplit(g, blk, x, pool, table, addr):
    """The packed branch without the split, in its XLA forms: the write of
    the step's slots, then the ragged attend over every row at its true
    positions."""
    pos, mask, lens, rows = (addr.positions, addr.token_mask, addr.kv_lens,
                             addr.row_ids)
    if g.mla:
        *q, c, k_pe = llama._mla_qkv(g, blk, x, pos, None, None)
        k_pe = jnp.pad(k_pe, ((0, 0), (0, 0),
                              (0, pool[1].shape[-1] - k_pe.shape[-1])))
        k, v = c[:, :, None, :], k_pe[:, :, None, :]
    else:
        q, k, v = llama._qkv(g, blk, x, pos, None, None)
    kpf, vpf, ksf, vsf = pool = ragged_ops.write_kv_pages_ragged(
        *pool[:2], k, v, table, rows, pos, mask, *pool[2:])
    if g.mla:
        return llama._mla_out(g, blk, ragged_paged_mla_attention_xla(
            *q, kpf, vpf, table, pos, lens, rows, llama._mla_scale(g), ksf,
            vsf, addr.max_q_len)), pool
    return llama._attn_gate(g, blk, x, ragged_ops.ragged_paged_attention_xla(
        q, kpf, vpf, table, pos, lens, rows, ksf, vsf, addr.max_q_len,
        g.sliding_window or None)), pool


def _record(monkeypatch, kind, seen):
    """Wrap the two attends a layer of ``kind`` reaches so that ``seen``
    holds what each was given: (the positions, the lengths)."""
    latent = kind == "latent"
    at = 5 if latent else 4     # the positions' place; the lengths follow
    for key, module, name in (
            ("by_row", mla_ops if latent else paged_ops,
             "paged_mla_attention" if latent else "paged_attention"),
            ("ragged", mla_ops if latent else ragged_ops,
             "ragged_paged_mla_attention" if latent
             else "ragged_paged_attention")):
        def attend(*a, _real=getattr(module, name), _key=key, **kw):
            seen[_key] = a[at:at + 2]
            return _real(*a, **kw)

        monkeypatch.setattr(module, name, attend)


def _assert_pools_equal(got, want):
    assert [a is None for a in got] == [a is None for a in want]
    for a, b in zip(got, want):
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("step", sorted(STEPS))
def test_the_split_equals_the_unsplit_ragged_attend(monkeypatch, step, kind):
    """Every real token's line of the layer is the unsplit path's, and the
    pool after the step is the same to the bit; the decode attend saw the
    one-token rows alone (every other row at length 0) and the ragged
    attend the chunk rows alone (every one-token row's token as padding)."""
    args, real = _step(step, kind)
    addr = args[-1]
    want, want_pool = _unsplit(*args)
    seen = {}
    _record(monkeypatch, kind, seen)
    got, pool = llama._pool_attention(*args, "never")
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got)[0, real],
                               np.asarray(want)[0, real],
                               rtol=1e-5, atol=1e-6)
    _assert_pools_equal(pool, want_pool)

    _, _, _, lens = STEPS[step]
    n = np.zeros(addr.kv_lens.shape[0], int)
    n[:len(lens)] = [tokens for tokens, _ in lens]
    _, by_row_lens = seen["by_row"]
    np.testing.assert_array_equal(
        np.asarray(by_row_lens), np.where(n == 1, np.asarray(addr.kv_lens), 0))
    ragged_pos, ragged_lens = seen["ragged"]
    lone = (n == 1)[np.asarray(addr.row_ids)] & real
    np.testing.assert_array_equal(
        np.asarray(ragged_pos)[0],
        np.where(lone, -1, np.asarray(addr.positions)[0]))
    np.testing.assert_array_equal(np.asarray(ragged_lens),
                                  np.asarray(addr.kv_lens))


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("step", sorted(STEPS))
def test_the_kernels_split_reads_as_the_xla_forms(interpreted, step, kind):
    """The same step by the two kernels, interpreted: the decode kernel
    over the one-token rows and a row of length 0 for every other, the
    ragged kernel over a pack whose one-token rows are padding."""
    args, real = _step(step, kind, seed=1)
    want, want_pool = llama._pool_attention(*args, "never")
    got, pool = llama._pool_attention(*args, "always")
    tol = KERNEL_TOLERANCE[kind]
    got, want = (np.asarray(a)[0, real] for a in (got, want))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    _assert_pools_equal(pool, want_pool)


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("step", sorted(FEW_ROWS))
def test_a_step_of_few_rows_is_split_like_any_other(monkeypatch, step, kind):
    """The rule is a row's token count at every row bucket, one and two
    rows too: both attends are reached, the ragged one is handed -1 where
    a row holds one token, and the layer reads as the unsplit path."""
    args, real = _step(step, kind)
    addr = args[-1]
    want, want_pool = _unsplit(*args)
    seen = {}
    _record(monkeypatch, kind, seen)
    got, pool = llama._pool_attention(*args, "never")
    np.testing.assert_allclose(np.asarray(got)[0, real],
                               np.asarray(want)[0, real],
                               rtol=1e-5, atol=1e-6)
    _assert_pools_equal(pool, want_pool)
    assert set(seen) == {"by_row", "ragged"}
    n = np.array([tokens for tokens, _ in FEW_ROWS[step][3]])
    lone = (n == 1)[np.asarray(addr.row_ids)] & real
    np.testing.assert_array_equal(
        np.asarray(seen["ragged"][0])[0],
        np.where(lone, -1, np.asarray(addr.positions)[0]))
    np.testing.assert_array_equal(
        np.asarray(seen["by_row"][1]),
        np.where(n == 1, np.asarray(addr.kv_lens), 0))


def test_the_rows_spans_are_read_once_a_step():
    """``forward_ragged`` reads each row's token count and first packed
    offset off ``row_ids`` and ``token_mask`` once, outside the layer
    loops; a mixer that is handed none reads them itself, the same."""
    (_, _, _, _, _, addr), _ = _step("a chunk row among one-token rows",
                                     "gqa")
    q_len, start = llama._row_spans(addr)
    R, T, _, lens = STEPS["a chunk row among one-token rows"]
    assert R == addr.kv_lens.shape[0]
    n = [tokens for tokens, _ in lens] + [0] * (R - len(lens))
    first = np.cumsum([0] + n[:-1])
    assert q_len.tolist() == n
    assert start.tolist() == [int(f) if k else T for f, k in zip(first, n)]
    given = (q_len + 1, start)
    assert llama._row_spans(addr._replace(spans=given)) is given

    cfg = dataclasses.replace(get_config("tiny"), num_layers=3)
    params = init_params(cfg, jax.random.key(0))
    cache = PagedKVCache.create(cfg, 8, PAGE)
    jaxpr = jax.make_jaxpr(lambda: llama.forward_ragged(
        params, cfg, jnp.ones((1, 16), I32), jnp.zeros((1, 16), I32),
        jnp.ones((1, 16), bool), jnp.zeros(16, I32), jnp.full(8, 16, I32),
        jnp.zeros((8, 2), I32), cache.k_pages, cache.v_pages,
        use_pallas="never", max_q_len=16))().jaxpr

    def mins(jaxpr, in_loop=False):
        """reduce_min is ``_row_spans``' alone in this program"""
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "reduce_min":
                yield in_loop
            inner = in_loop or eqn.primitive.name in ("while", "scan")
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from mins(sub, inner)

    assert list(mins(jaxpr)) == [False]
