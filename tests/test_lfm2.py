"""The LFM2-shaped model (``tiny-lfm2``): two dense layers with the gated
short convolution, then expert layers A C C C A C C C (A: grouped-query
attention with head norms over heads of 64, whose pages keep two heads a
lane tile; C: the convolution, a state of two gated inputs a sequence),
bias-selected sigmoid experts of which this device holds a range, a tied
head.

Each new rule is held to its definition here; the served path against the
benchmark's plain reference of the architecture is the contract every model
is a case of (``model_contract.py``, ``test_lfm2_contract.py``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rbg_tpu.engine.kvcache import (PagedKVCache, StatePool,
                                    heads_per_lane_tile)
from rbg_tpu.models import get_config, init_params
from rbg_tpu.models.llama import _hybrid_plan
from rbg_tpu.ops import kda, short_conv
from rbg_tpu.ops.pallas import page_walk

from model_contract import read

CFG = get_config("tiny-lfm2")
PARAMS = init_params(CFG, jax.random.key(0))

CONV_DENSE, ATT_MOE, CONV_MOE = (("conv_mixers", "dense_mlps"),
                                 ("mixers", "moe_mlps"),
                                 ("conv_mixers", "moe_mlps"))


# ---- the layers come in kinds ------------------------------------------------


def test_layer_types_give_the_runs_of_kinds_and_the_half_layer_stacks():
    assert CFG.mixer_kinds == ("conv", "conv", "full", "conv", "conv", "conv",
                               "full", "conv", "conv", "conv")
    assert [(k, lo, hi) for k, _, lo, hi in CFG.layer_groups] == [
        ("conv_dense_blocks", 0, 2), ("blocks", 2, 3), ("conv_blocks", 3, 6),
        ("blocks", 6, 7), ("conv_blocks", 7, 10)]
    by_key = {key: g for key, g, _, _ in CFG.layer_groups}
    assert [by_key[k].attention for k in by_key] == ["conv", "full", "conv"]
    assert by_key["conv_dense_blocks"].num_experts == 0
    assert by_key["blocks"].experts_held == (4, 12)
    assert CFG.recurrent and CFG.num_moe_layers == 8
    assert (CFG.mixer_count("conv"), CFG.mixer_count("full"),
            CFG.mixer_count("kda")) == (8, 2, 0)
    assert [(k, n, g.half) for k, g, n in CFG.param_groups] == [
        ("conv_mixers", 8, "mixer"), ("dense_mlps", 2, "mlp"),
        ("mixers", 2, "mixer"), ("moe_mlps", 8, "mlp")]
    assert set(PARAMS) == {"embed", "final_norm", "conv_mixers", "mixers",
                           "dense_mlps", "moe_mlps"}         # a tied head
    assert {k: v.shape for k, v in PARAMS["conv_mixers"].items()} == {
        "attn_norm": (8, 128), "conv_in": (8, 128, 384),
        "conv_w": (8, 3, 128), "wo": (8, 128, 128)}
    assert PARAMS["mixers"]["q_head_norm"].shape == (2, 64)
    assert PARAMS["moe_mlps"]["moe_gate"].shape == (8, 8, 128, 48)   # held
    assert PARAMS["moe_mlps"]["router"].shape == (8, 128, 16)        # whole
    assert "w_gate" not in PARAMS["moe_mlps"]           # no shared expert
    n = sum(a.size for a in jax.tree_util.tree_leaves(PARAMS))
    assert CFG.num_params == n


def test_kda_layers_still_say_which_layers_are_recurrent():
    kimi = get_config("tiny-kimi-linear")
    assert kimi.mixer_kinds == ("kda", "kda", "kda", "full", "kda", "kda",
                                "kda", "full")
    assert get_config("tiny").mixer_kinds == ("full", "full")


@pytest.mark.parametrize("kw,match", [
    (dict(layer_types=("conv", "full_attention")), "got 2 entries"),
    (dict(layer_types=("conv",) * 9 + ("kda",)), r"unknown \['kda'\]"),
    (dict(kda_layers=(1,)), r"kda_layers \(1,\)")])
def test_layer_types_that_name_no_kind_or_the_wrong_count_are_refused(
        kw, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CFG, **kw)


def test_the_published_forty_layers_are_one_run_and_ten_turns():
    types = read("configs", "lfm2-24b-a2b.json")["layer_types"]
    full = dataclasses.replace(CFG, num_layers=40, layer_types=tuple(types))
    assert [i for i, t in enumerate(types) if t == "full_attention"] == list(
        range(2, 40, 4))
    lead, (_, a, b, turns) = _hybrid_plan(full)
    assert lead == ("run", CONV_DENSE, 0, 2)
    assert (a, b) == (ATT_MOE, CONV_MOE)
    # an attention layer, then three convolution layers; the last turn one
    assert turns == [[1, 3, 2 + 4 * t] for t in range(9)] + [[1, 1, 38]]
    assert len(full.layer_groups) == 21 and len(full.param_groups) == 4
    assert _hybrid_plan(CFG) == [
        ("run", CONV_DENSE, 0, 2),
        ("turns", ATT_MOE, CONV_MOE, [[1, 3, 2], [1, 3, 6]])]


# ---- the state pool holds what the mixers present keep -----------------------


def test_the_state_pool_holds_the_tails_alone_and_pages_pack_two_heads():
    pool = StatePool(CFG, 4)
    assert {k: (v.shape, v.dtype) for k, v in pool.arrays.items()} == {
        "tail": ((8, 4, 2 * 128), jnp.float32)}
    assert StatePool.hbm_bytes(CFG, 4) == 8 * 4 * 256 * 4
    assert pool.row_bytes == 2 * 8 * 256 * 4
    kimi = StatePool(get_config("tiny-kimi-linear"), 2)
    assert set(kimi.arrays) == {"s", "conv"}
    assert kimi.arrays["s"].dtype == jnp.float32
    # pages: the attention layers alone, two heads of 64 a lane tile
    cache = PagedKVCache.create(CFG, 16, 8)
    assert heads_per_lane_tile(CFG) == 2
    assert cache.k_pages.shape == (2, 16, 8, 1, 128)
    assert PagedKVCache.hbm_bytes(CFG, 16, 8, 4) == 2 * cache.k_pages.nbytes
    # heads of 32 fill no tile with the two there are; int8 keeps (slot, head)
    assert heads_per_lane_tile(get_config("tiny")) == 1
    assert heads_per_lane_tile(get_config("llama3-1b")) == 2
    assert heads_per_lane_tile(get_config("llama3-1b"), tp=8) == 1
    assert heads_per_lane_tile(get_config("llama3-8b")) == 1
    assert heads_per_lane_tile(get_config("tiny-mla")) == 1
    quant = PagedKVCache.create(CFG, 16, 8, quantize=True)
    assert quant.k_pages.shape == (2, 16, 8, 2, 64)


# ---- the convolution ---------------------------------------------------------


def test_the_gated_convolution_is_the_published_sum_and_keeps_two_inputs():
    rng = np.random.default_rng(0)
    R, C, ch, K = 3, 7, 8, 3
    b, c, x = (jnp.asarray(rng.normal(size=(R, C, ch)), jnp.float32)
               for _ in range(3))
    w = jnp.asarray(rng.normal(size=(K, ch)), jnp.float32)
    tail = jnp.asarray(rng.normal(size=(R, K - 1, ch)), jnp.float32)
    lens = jnp.asarray([7, 4, 0], jnp.int32)
    out, new = short_conv.gated_short_conv(b, c, x, tail, w, lens)
    u = np.concatenate([np.asarray(tail), np.asarray(b * x)], axis=1)
    want = np.stack([sum(np.asarray(w)[j] * u[:, t + j] for j in range(K))
                     for t in range(C)], axis=1) * np.asarray(c)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-6)
    # the new tail: the last two real gated inputs; a row of none keeps its own
    np.testing.assert_allclose(np.asarray(new[0]), u[0, 7:9], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(new[1]), u[1, 4:6], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(new[2]), np.asarray(tail[2]))
    # causal: a later input moves no earlier output
    x2 = x.at[:, 5].add(1.0)
    out2, _ = short_conv.gated_short_conv(b, c, x2, tail, w, lens)
    np.testing.assert_array_equal(np.asarray(out2[:, :5]),
                                  np.asarray(out[:, :5]))
    # nothing is activated: linear in the gated input
    out3, _ = short_conv.gated_short_conv(2 * b, c, x, 2 * tail, w, lens)
    np.testing.assert_allclose(np.asarray(out3), 2 * np.asarray(out),
                               rtol=1e-5, atol=1e-6)


def test_kdas_convolution_is_the_same_walk_then_silu():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 5, 6)), jnp.float32)
    tail = jnp.asarray(rng.normal(size=(2, 3, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    lens = jnp.asarray([5, 2], jnp.int32)
    y, t1 = short_conv.causal_conv(x, tail, w, lens)
    z, t2 = kda.short_conv(x, tail, w, lens)
    np.testing.assert_allclose(np.asarray(z), np.asarray(jax.nn.silu(y)),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))


# ---- heads side by side in the pool ------------------------------------------


def test_packed_queries_score_their_own_head_alone():
    rng = np.random.default_rng(2)
    KV, G, hd, S = 4, 3, 8, 5
    q = jnp.asarray(rng.normal(size=(2, KV, G, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(S, KV, hd)), jnp.float32)
    qp = page_walk.pack_queries(q, 2)
    assert qp.shape == (2, KV // 2, 2 * G, 2 * hd)
    kp = k.reshape(S, KV // 2, 2 * hd)          # as the pool stores a slot
    got = jnp.einsum("bkrc,skc->bkrs", qp, kp).reshape(2, KV, G, S)
    want = jnp.einsum("bkgd,skd->bkgs", q, k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)
    # an output's own lanes come back; one head a tile changes nothing
    o = jnp.asarray(rng.normal(size=(2, KV // 2, 2 * G, 2 * hd)), jnp.float32)
    back = page_walk.unpack_outputs(o, 2)
    o6 = np.asarray(o).reshape(2, KV // 2, 2, G, 2, hd)
    np.testing.assert_array_equal(np.asarray(back[:, 1]), o6[:, 0, 1, :, 1])
    np.testing.assert_array_equal(np.asarray(back[:, 2]), o6[:, 1, 0, :, 0])
    assert page_walk.pack_queries(q, 1) is q


@pytest.mark.parametrize("ragged", [False, True])
def test_the_kernels_on_a_packed_pool_equal_plain_attention(ragged):
    """8 KV heads of 64 held as 4 x 128: the decode and the block-ragged
    kernel (interpreted) against the XLA path on the same packed pool and
    against plain attention on the pool's ``[.., KV, hd]`` view."""
    from rbg_tpu.ops.paged_attention import paged_attention_xla
    from rbg_tpu.ops.pallas.paged_attention_kernel import \
        paged_attention_pallas
    from rbg_tpu.ops.pallas.ragged_attention_kernel import \
        ragged_paged_attention_pallas
    from rbg_tpu.ops.ragged_paged_attention import ragged_paged_attention_xla
    rng = np.random.default_rng(3)
    NP, page, KV, hd, H, R, P = 13, 8, 4, 64, 8, 3, 4
    pool = [jnp.asarray(rng.normal(size=(NP, page, KV, hd)), jnp.float32)
            for _ in range(2)]
    packed = [p.reshape(NP, page, KV // 2, 2 * hd) for p in pool]
    table = jnp.asarray(rng.permutation(NP - 1)[:R * P].reshape(R, P) + 1,
                        jnp.int32)
    lens = jnp.asarray([29, 8, 17], jnp.int32)
    if ragged:
        q_len = [5, 1, 3]
        rows = jnp.asarray(np.repeat(np.arange(R), q_len), jnp.int32)
        pos = jnp.asarray(np.concatenate(
            [np.arange(n - m, n) for n, m in zip([29, 8, 17], q_len)])[None],
            jnp.int32)
        q = jnp.asarray(rng.normal(size=(1, 9, H, hd)), jnp.float32)
        got = ragged_paged_attention_pallas(q, *packed, table, pos, lens,
                                            rows, interpret=True)
        xla = ragged_paged_attention_xla(q, *packed, table, pos, lens, rows)
        plain = ragged_paged_attention_xla(q, *pool, table, pos, lens, rows)
    else:
        pos = (lens - 1)[:, None]
        q = jnp.asarray(rng.normal(size=(R, 1, H, hd)), jnp.float32)
        got = paged_attention_pallas(q, *packed, table, pos, lens,
                                     interpret=True)
        xla = paged_attention_xla(q, *packed, table, pos, lens)
        plain = paged_attention_xla(q, *pool, table, pos, lens)
    np.testing.assert_array_equal(np.asarray(xla), np.asarray(plain))
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain), rtol=2e-5,
                               atol=2e-6)
