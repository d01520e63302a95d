"""The Solar-Open2-shaped model (``tiny-solar-open2``): expert layers A K K K
A K K K with no dense layer (A: grouped-query attention without positions
and with an output gate, on K/V pages; K: the gated delta rule with a write
strength of ``2 sigmoid``, its states beside those pages), sigmoid experts
picked by a bias beside a shared one, of which this device holds a range.

Each new rule is held to its definition here; the served path against the
benchmark's plain reference of the architecture, and each of the three rules
left out of it, is the contract every model is a case of
(``model_contract.py``, ``test_solar_open2_contract.py``)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rbg_tpu.engine.kvcache import PagedKVCache, StatePool
from rbg_tpu.models import get_config, init_params
from rbg_tpu.models import llama
from rbg_tpu.models.config import ModelConfig
from rbg_tpu.models.llama import _hybrid_plan
from rbg_tpu.ops import kda

from kda_packed_case import STEPS as PACKED_STEPS
from kda_packed_case import (assert_no_line_and_no_state_for_every_row,
                             assert_rows_equal_the_recurrence, kda_inputs)

from model_contract import read

CFG = get_config("tiny-solar-open2")
PARAMS = init_params(CFG, jax.random.key(0))

ATT_MOE, KDA_MOE = ("mixers", "moe_mlps"), ("kda_mixers", "moe_mlps")


# ---- the layers come in kinds, attention first -------------------------------


def test_attention_leads_the_turns_and_no_layer_is_dense():
    assert CFG.mixer_kinds == ("full", "kda", "kda", "kda") * 2
    assert [(k, lo, hi) for k, _, lo, hi in CFG.layer_groups] == [
        ("blocks", 0, 1), ("kda_blocks", 1, 4), ("blocks", 4, 5),
        ("kda_blocks", 5, 8)]
    assert CFG.recurrent and CFG.num_moe_layers == 8
    assert (CFG.mixer_count("full"), CFG.mixer_count("kda")) == (2, 6)
    assert [(k, n, g.half) for k, g, n in CFG.param_groups] == [
        ("mixers", 2, "mixer"), ("moe_mlps", 8, "mlp"),
        ("kda_mixers", 6, "mixer")]
    assert set(PARAMS) == {"embed", "lm_head", "final_norm", "mixers",
                           "kda_mixers", "moe_mlps"}
    assert {k: v.shape for k, v in PARAMS["mixers"].items()} == {
        "attn_norm": (2, 128), "wq": (2, 128, 128), "wk": (2, 128, 64),
        "wv": (2, 128, 64), "wo": (2, 128, 128), "wg": (2, 128, 128)}
    assert PARAMS["moe_mlps"]["moe_gate"].shape == (8, 4, 128, 48)   # held
    assert PARAMS["moe_mlps"]["router"].shape == (8, 128, 16)        # whole
    assert PARAMS["moe_mlps"]["w_gate"].shape == (8, 128, 48)        # shared
    # the gate is counted, and nothing else is
    n = sum(a.size for a in jax.tree_util.tree_leaves(PARAMS))
    assert CFG.num_params == n
    ungated = dataclasses.replace(CFG, attn_gate=False)
    assert CFG.num_params - ungated.num_params == 2 * 128 * 128
    assert _hybrid_plan(CFG) == [
        ("turns", ATT_MOE, KDA_MOE, [[1, 3, 0], [1, 3, 4]])]


def test_the_published_48_layers_are_twelve_turns_and_a_cut_keeps_whole_ones():
    """The published 0-based ``gqa_layers`` become the 1-based ``kda_layers``
    of whatever depth is kept: the complement among the layers served."""
    cell = read("configs", "solar-open2-250b.json")
    gqa = cell["gqa_layers"]
    assert gqa == list(range(0, 48, 4)) and cell["gqa_interval"] == 3

    def at_depth(L):
        return dataclasses.replace(CFG, num_layers=L, kda_layers=tuple(
            n + 1 for n in range(L) if n not in gqa))

    (_, a, b, turns), = _hybrid_plan(at_depth(48))
    assert (a, b) == (ATT_MOE, KDA_MOE)
    assert turns == [[1, 3, 4 * t] for t in range(12)]
    assert at_depth(8) == CFG
    assert list(at_depth(8).kda_layers) == cell["preset"]["kda_layers"] == \
        cell["linear_attn_config"]["kda_layers"]
    # a cut inside a period ends on a shorter turn, and is still walked
    (_, _, _, turns), = _hybrid_plan(at_depth(6))
    assert turns == [[1, 3, 0], [1, 1, 4]]


def test_a_gate_on_latent_attention_is_refused_by_name():
    with pytest.raises(ValueError, match="attn_gate gates grouped-query"):
        dataclasses.replace(get_config("tiny-mla"), attn_gate=True)


def test_the_gates_leaf_has_a_sharding_and_no_hf_import(tmp_path):
    from rbg_tpu.models.checkpoint import load_hf_llama
    from rbg_tpu.parallel.sharding import _block_specs
    from jax.sharding import PartitionSpec as P
    gated = dataclasses.replace(get_config("tiny"), attn_gate=True)
    assert _block_specs(gated)["wg"] == _block_specs(gated)["wq"] == \
        P(None, None, "tp")
    assert "wg" not in _block_specs(get_config("tiny"))
    with pytest.raises(NotImplementedError, match=r"attn_gate.*\(wg\)"):
        load_hf_llama(str(tmp_path), gated)


# ---- states beside K/V pages -------------------------------------------------


def test_the_state_pool_stands_beside_kv_pages_of_the_attention_layers():
    pool = StatePool(CFG, 4)
    assert {k: (v.shape, v.dtype) for k, v in pool.arrays.items()} == {
        "s": ((6, 4, 4, 32, 32), jnp.float32),
        "conv": ((6, 4, 3 * 3 * 128), jnp.float32)}
    assert StatePool.hbm_bytes(CFG, 4) == 6 * 4 * (4 * 32 * 32 + 1152) * 4
    cache = PagedKVCache.create(CFG, 16, 8)
    assert cache.k_pages.shape == cache.v_pages.shape == (2, 16, 8, 2, 32)
    assert PagedKVCache.hbm_bytes(CFG, 16, 8, 4) == 2 * cache.k_pages.nbytes


# ---- the delta rule with a write strength in (0, 2) --------------------------


# ``b = 2 sigmoid(.)``: drawn over (0, 2), a third of it above 1.2
_kda_inputs = functools.partial(kda_inputs, b_scale=2.0, b_spread=1.5)


def test_a_write_strength_of_two_reflects_the_state_along_the_key():
    """``I - b k k^T`` at ``b = 2`` is a reflection: eigenvalue -1 along
    ``k``, 1 across it; ``kda_step`` with no decay and ``v = 0`` is that
    matrix on the state."""
    (q, k, _, _, _, S), _ = _kda_inputs(1, 1, 2, 8, [1], seed=3)
    zero = jnp.zeros((1, 2, 8))
    _, S1 = kda.kda_step(q[:, 0], k[:, 0], zero, zero, jnp.full((1, 2), 2.0),
                         S)
    for h in range(2):
        kk = np.asarray(k[0, 0, h], np.float64)
        old, new = np.asarray(S[0, h], np.float64), np.asarray(S1[0, h])
        np.testing.assert_allclose(kk @ new, -(kk @ old), rtol=1e-4,
                                   atol=1e-5)
        across = np.eye(8) - np.outer(kk, kk)
        np.testing.assert_allclose(across @ new, across @ old, rtol=1e-4,
                                   atol=1e-5)


@pytest.mark.parametrize("C,lens", [(64, [64, 17, 0, 1]), (40, [40, 33, 5, 16]),
                                    (16, [16, 16, 3, 0])])
def test_the_chunked_form_equals_the_recurrence_with_b_up_to_two(C, lens):
    """``_unit_lower_inverse`` and the ``e^80`` bound with ``b A`` twice as
    large as a sigmoid alone makes it."""
    args, real = _kda_inputs(4, C, 2, 16, lens, seed=C)
    assert float(args[4].max()) > 1.7
    o_tok, S_tok = kda.kda_recurrence(*args)
    o_chk, S_chk = jax.jit(kda.kda_chunk)(*args)
    np.testing.assert_allclose(np.asarray(o_chk)[real],
                               np.asarray(o_tok)[real], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(S_chk, S_tok, rtol=2e-4, atol=2e-5)


def test_the_decode_kernel_walks_64_heads_in_four_blocks_with_b_up_to_two():
    """The kernel (interpreted) at the cell's head count: 64 heads in blocks
    of ``HEADS_PER_BLOCK`` = 16, four a row where Kimi's 32 heads make two;
    against ``kda_step`` between a gather and a scatter, and against the
    recurrence's one token."""
    from rbg_tpu.ops.pallas.kda_kernel import HEADS_PER_BLOCK, kda_decode_pallas
    H, dk, slots = 64, 16, [2, 5, 0, 3]
    assert H // HEADS_PER_BLOCK == 4
    (q, k, v, g, b, _), _ = _kda_inputs(4, 1, H, dk, [1, 0, 1, 1], seed=9)
    pool = jax.random.normal(jax.random.key(10), (2, 4, H, dk, dk))
    args = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], b[:, 0], pool, jnp.int32(1),
            jnp.asarray(slots, jnp.int32), jnp.asarray([0, 0, 1, 0], bool))
    o_ref, pool_ref = kda.kda_step_in_pool(*args)
    o, new = kda_decode_pallas(*args, interpret=True)
    live = np.asarray(slots) < 4
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(o_ref)[live],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(new, pool_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(new)[0], np.asarray(pool)[0])
    # row 0 alone, as the recurrence's one token from its slot's state
    o_tok, S_tok = kda.kda_recurrence(q[:1], k[:1], v[:1], g[:1], b[:1],
                                      pool[1, 2][None])
    np.testing.assert_allclose(o[0], o_tok[0, 0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(new[1, 2], S_tok[0], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("use_pallas", ["never", "always"])
@pytest.mark.parametrize("step", sorted(PACKED_STEPS))
def test_a_packed_steps_rows_equal_the_recurrence_at_64_heads_and_b_to_two(
        step, use_pallas, interpreted):
    """``_kda_packed`` as the cell's layers run it: 64 heads (four head
    blocks a row in the kernel), a write strength up to 2; rows of one
    token in place on the pool, rows of 2, 17 and 64 by the chunked form,
    they alone, every other slot as it was."""
    assert_rows_equal_the_recurrence(step, 64, 2.0, use_pallas, seed=3)


def test_the_packed_program_walks_chunk_rows_in_a_loop_and_no_row_line(
        interpreted):
    assert_no_line_and_no_state_for_every_row(CFG, PARAMS)


# ---- the new fields cost the other models nothing ---------------------------


def test_without_the_new_fields_nothing_is_added_to_a_program():
    """``kda_beta_scale`` 1.0, no gate and ``use_rope`` true cost the other
    models no operation: the gate hands back the very array it was given,
    and a Kimi-shaped KDA layer's jaxpr holds no multiplication by a
    constant 1."""
    attn = jnp.ones((1, 2, 4, 32))
    assert llama._attn_gate(get_config("tiny"), {}, None, attn) is attn
    kimi = get_config("tiny-kimi-linear")
    assert kimi.kda_beta_scale == 1.0 and not kimi.attn_gate
    assert get_config("tiny").use_rope and ModelConfig().kda_beta_scale == 1.0
