"""The Kimi-Linear-shaped model (``tiny-kimi-linear``): a dense recurrent
layer, then expert layers K K M K K K M (K: Kimi Delta Attention, a gated
delta rule over a fixed state a sequence; M: latent attention without
positions), sigmoid-routed experts of which this device holds a range.

Each new rule is held to its definition here; the served path against the
benchmark's plain reference of the architecture is the contract every model
is a case of (``model_contract.py``, ``test_kimi_linear_contract.py``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rbg_tpu.engine.kvcache import PagedKVCache, StatePool
from rbg_tpu.models import get_config, init_params
from rbg_tpu.models import llama
from rbg_tpu.models.llama import (_EXPERT_STACKS, _hybrid_plan, _mla_qkv,
                                  _moe_mlp, _route)
from rbg_tpu.ops import kda

from kda_packed_case import STEPS as PACKED_STEPS
from kda_packed_case import (assert_no_line_and_no_state_for_every_row,
                             assert_rows_equal_the_recurrence, eqns,
                             kda_inputs, step_jaxpr)

CFG = get_config("tiny-kimi-linear")
PARAMS = init_params(CFG, jax.random.key(0))


# ---- the layers come in kinds ------------------------------------------------


def test_layers_that_differ_in_their_mixer_are_runs_of_kinds():
    runs = [(key, lo, hi) for key, _, lo, hi in CFG.layer_groups]
    assert runs == [("kda_dense_blocks", 0, 1), ("kda_blocks", 1, 3),
                    ("blocks", 3, 4), ("kda_blocks", 4, 7), ("blocks", 7, 8)]
    by_key = {key: g for key, g, _, _ in CFG.layer_groups}
    assert [by_key[k].attention for k in ("kda_dense_blocks", "kda_blocks",
                                          "blocks")] == ["kda", "kda", "full"]
    assert by_key["kda_dense_blocks"].num_experts == 0
    assert by_key["blocks"].experts_held == (4, 12)
    assert CFG.recurrent and CFG.num_moe_layers == 7
    # the parameters are stacked by half-layer, each kind in layer order
    assert [(k, n, g.half) for k, g, n in CFG.param_groups] == [
        ("kda_mixers", 6, "mixer"), ("dense_mlps", 1, "mlp"),
        ("moe_mlps", 7, "mlp"), ("mixers", 2, "mixer")]
    assert [h[1:] for h in CFG.layer_halves] == [
        ("kda_mixers", 0, "dense_mlps", 0), ("kda_mixers", 1, "moe_mlps", 0),
        ("kda_mixers", 2, "moe_mlps", 1), ("mixers", 0, "moe_mlps", 2),
        ("kda_mixers", 3, "moe_mlps", 3), ("kda_mixers", 4, "moe_mlps", 4),
        ("kda_mixers", 5, "moe_mlps", 5), ("mixers", 1, "moe_mlps", 6)]
    assert set(PARAMS) == {"embed", "final_norm", "lm_head", "kda_mixers",
                           "mixers", "dense_mlps", "moe_mlps"}
    assert PARAMS["kda_mixers"]["kda_qkv"].shape == (6, 128, 3 * 4 * 32)
    assert set(PARAMS["mixers"]) == {"attn_norm", "wq", "w_dkv", "kv_norm",
                                     "w_uk", "w_uv", "wo"}
    assert PARAMS["dense_mlps"]["w_up"].shape == (1, 128, 320)
    assert "attn_norm" not in PARAMS["moe_mlps"]
    assert PARAMS["moe_mlps"]["moe_gate"].shape == (7, 8, 128, 48)   # held
    assert PARAMS["moe_mlps"]["router"].shape == (7, 128, 16)        # whole


def test_a_one_kind_model_and_a_dense_prefix_are_the_groups_they_were():
    tiny, joyai = get_config("tiny"), get_config("tiny-joyai")
    assert tiny.layer_groups == (("blocks", tiny, 0, 2),)
    assert [(k, lo, hi) for k, _, lo, hi in joyai.layer_groups] == [
        ("dense_blocks", 0, 1), ("blocks", 1, 3)]
    assert [(k, n, g.half) for k, g, n in joyai.param_groups] == [
        ("dense_blocks", 1, ""), ("blocks", 2, "")]


KDA_DENSE, KDA_MOE, MLA_MOE = (("kda_mixers", "dense_mlps"),
                               ("kda_mixers", "moe_mlps"),
                               ("mixers", "moe_mlps"))


def _published(**kw):
    """The published pattern at the tiny widths: 27 layers, every fourth
    and the last with latent attention."""
    return dataclasses.replace(
        CFG, num_layers=27,
        kda_layers=tuple(n for n in range(1, 27) if n % 4), **kw)


def test_mixers_that_take_turns_are_walked_a_turn_at_a_time():
    # (layers of A, layers of B, first layer) a turn; the dense first layer
    # is a segment of its own, and the first turn one recurrent layer shorter
    assert _hybrid_plan(CFG) == [
        ("run", KDA_DENSE, 0, 1),
        ("turns", KDA_MOE, MLA_MOE, [[2, 1, 1], [3, 1, 4]])]
    # the published pattern: 2 3 3 3 3 3 2 recurrent layers a turn
    full = _published()
    alone, (_, a, b, turns) = _hybrid_plan(full)
    assert alone == ("run", KDA_DENSE, 0, 1)
    assert (a, b) == (KDA_MOE, MLA_MOE)
    assert [t[0] for t in turns] == [2, 3, 3, 3, 3, 3, 2]
    assert all(t[1] == 1 for t in turns)
    assert [t[2] for t in turns] == [1, 4, 8, 12, 16, 20, 24]
    assert len(full.layer_groups) == 15 and len(full.param_groups) == 4
    # a kind in one run is a segment of its own, whatever its length
    lead = dataclasses.replace(CFG, kda_layers=(1, 2, 3))
    assert _hybrid_plan(lead) == [("run", KDA_DENSE, 0, 1),
                                  ("run", KDA_MOE, 1, 3),
                                  ("run", MLA_MOE, 3, 8)]
    # dense layers that fill the first turn are two runs before the turns
    assert _hybrid_plan(_published(first_dense_layers=4))[:2] == [
        ("run", KDA_DENSE, 0, 3), ("run", ("mixers", "dense_mlps"), 3, 4)]


@pytest.mark.parametrize("dense,third,pattern", [
    (5, "(kda_mixers+dense_mlps, kda_mixers+moe_mlps, mixers+moe_mlps)",
     "kda_mixers+dense_mlps:3 mixers+dense_mlps:1 kda_mixers+dense_mlps:1 "
     "kda_mixers+moe_mlps:2 mixers+moe_mlps:1 kda_mixers+moe_mlps:3"),
    (8, "(kda_mixers+dense_mlps, mixers+dense_mlps, kda_mixers+moe_mlps)",
     "kda_mixers+dense_mlps:3 mixers+dense_mlps:1 kda_mixers+dense_mlps:3 "
     "mixers+dense_mlps:1 kda_mixers+moe_mlps:3 mixers+moe_mlps:1")],
    ids=["dense-into-the-second-turn", "two-dense-turns"])
def test_a_pattern_of_three_kinds_in_turns_raises_and_names_it(
        dense, third, pattern):
    """Leading dense layers that reach past the first turn: a kind that
    comes back after another has begun taking turns."""
    with pytest.raises(NotImplementedError) as e:
        _hybrid_plan(_published(first_dense_layers=dense))
    said = str(e.value)
    assert "more than two kinds take turns" in said
    assert third in said and pattern in said


@pytest.mark.parametrize("program", ["decode", "ragged"])
def test_no_loop_body_branches_over_an_mlps_weights(program):
    """A ``lax.cond``'s operands are copied whole in every trip of the loop
    that holds it, whichever branch runs (PERF.md, PR 38): no loop of the
    walk hands one a matrix of an MLP, whole or a layer's slice."""
    jaxpr = step_jaxpr(CFG, PARAMS, program)
    matrices = {a.shape[cut:] for key in ("dense_mlps", "moe_mlps")
                for a in PARAMS[key].values() if a.ndim > 2
                for cut in (0, 1)}
    assert (1, 320, 128) in matrices and (320, 128) in matrices
    # the walk is there: loops, and in them the sampler-free step's only
    # conds are those of the operations (none takes a matrix of an MLP)
    assert any(e.primitive.name in ("while", "scan") for e in jaxpr.eqns)
    fed = [(e.params["branches"][0].jaxpr.eqns[:1], v.aval.shape)
           for e, inside in eqns(jaxpr)
           if inside and e.primitive.name == "cond" for v in e.invars
           if getattr(v.aval, "shape", None) in matrices]
    assert not fed


def test_pages_are_the_attention_layers_and_states_the_recurrent_ones():
    cache = PagedKVCache.create(CFG, 64, 8)
    assert cache.k_pages.shape[0] == 2 and cache.v_pages.shape[0] == 2
    assert PagedKVCache.hbm_bytes(CFG, 64, 8, 4) == (
        cache.k_pages.nbytes + cache.v_pages.nbytes)
    pool = StatePool(CFG, 4)
    assert pool.arrays["s"].shape == (6, 4, 4, 32, 32)
    assert pool.arrays["s"].dtype == jnp.float32
    assert pool.arrays["conv"].shape == (6, 4, 3 * 3 * 4 * 32)
    assert StatePool.hbm_bytes(CFG, 4) == sum(
        a.nbytes for a in pool.arrays.values())
    taken = [pool.take() for _ in range(4)]
    assert sorted(taken) == [0, 1, 2, 3] and pool.held == 4
    pool.release(2)
    assert pool.take() == 2
    with pytest.raises(AssertionError, match="double free"):
        pool.release(1), pool.release(1)


# ---- the recurrence and its forms --------------------------------------------


def test_one_token_is_the_delta_rule_as_written():
    (q, k, v, g, b, S), _ = kda_inputs(2, 1, 3, 8, [1, 1])
    o, S1 = kda.kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], b[:, 0], S)
    for r in range(2):
        for h in range(3):
            kk, vv, qq = (np.asarray(a[r, 0, h], np.float64)
                          for a in (k, v, q))
            a, bb = np.exp(np.asarray(g[r, 0, h], np.float64)), float(b[r, 0, h])
            want = ((np.eye(8) - bb * np.outer(kk, kk)) @ np.diag(a)
                    @ np.asarray(S[r, h], np.float64) + bb * np.outer(kk, vv))
            np.testing.assert_allclose(S1[r, h], want, rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(o[r, h], want.T @ qq, rtol=1e-5,
                                       atol=1e-5)


@pytest.mark.parametrize("C,lens", [(64, [64, 17, 0, 1]), (40, [40, 33, 5, 16]),
                                    (16, [16, 16, 3, 0])])
def test_the_chunked_form_equals_the_token_by_token_recurrence(C, lens):
    args, real = kda_inputs(4, C, 2, 16, lens, seed=C)
    o_tok, S_tok = kda.kda_recurrence(*args)
    o_chk, S_chk = jax.jit(kda.kda_chunk)(*args)
    np.testing.assert_allclose(np.asarray(o_chk)[real], np.asarray(o_tok)[real],
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(S_chk, S_tok, rtol=1e-4, atol=1e-5)
    # a row of padding alone leaves its state as it was
    for r, n in enumerate(lens):
        if n == 0:
            np.testing.assert_array_equal(S_chk[r], args[-1][r])


def test_a_prompt_in_chunks_carries_the_state_from_each_to_the_next():
    args, _ = kda_inputs(2, 48, 2, 16, [48, 48], seed=7)
    o_all, S_all = kda.kda_chunk(*args)
    *xs, S = args
    outs = []
    for lo in (0, 16, 32):
        o, S = kda.kda_chunk(*(a[:, lo:lo + 16] for a in xs), S)
        outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), o_all, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(S, S_all, rtol=1e-4, atol=1e-5)


# ---- the decode step as a kernel, in place on the pool -----------------------

POOL_LAYERS, POOL_SLOTS, POOL_LAYER = 3, 6, 1


def _pool_case(slots, fresh, H=4, dk=16, seed=0):
    """One token a row against a pool every slot of which holds an old
    state; a row of padding names a slot out of range and has ``g = 0``,
    ``b = 0`` (as ``_kda_attention`` masks them)."""
    (q, k, v, g, b, _), _ = kda_inputs(
        len(slots), 1, H, dk, [s < POOL_SLOTS for s in slots], seed)
    pool = jax.random.normal(jax.random.key(seed + 1),
                             (POOL_LAYERS, POOL_SLOTS, H, dk, dk))
    return (q[:, 0], k[:, 0], v[:, 0], g[:, 0], b[:, 0], pool,
            jnp.int32(POOL_LAYER), jnp.asarray(slots, jnp.int32),
            jnp.asarray(fresh, bool))


POOL_CASES = {
    "slots permuted against rows": ([3, 0, 5, 1, 2, 4], [0] * 6),
    "a fresh row on a slot that holds an old state": ([2, 4, 1], [0, 1, 0]),
    "a row of padding alone": ([POOL_SLOTS], [0]),
    # rows 1 and 3 clip to slot 5, which row 0 advances
    "padding whose clipped slot is a live row's": ([5, 6, 0, 9], [0] * 4),
    "fewer live rows than slots": ([1, 6, 4, 6], [0, 0, 1, 0]),
}


@pytest.mark.parametrize("heads_per_block", [2, 4])
@pytest.mark.parametrize("case", sorted(POOL_CASES))
def test_the_decode_kernel_is_kda_step_on_the_pools_slots(case,
                                                          heads_per_block):
    from rbg_tpu.ops.pallas.kda_kernel import kda_decode_pallas
    slots, fresh = POOL_CASES[case]
    args = _pool_case(slots, fresh, seed=len(case))
    o_ref, pool_ref = kda.kda_step_in_pool(*args)
    o, pool = kda_decode_pallas(*args, interpret=True,
                                heads_per_block=heads_per_block)
    assert o.dtype == pool.dtype == jnp.float32
    live = np.asarray(slots) < POOL_SLOTS
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(o_ref)[live],
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(o)[~live].any()       # padding's lines: zeros
    np.testing.assert_allclose(pool, pool_ref, rtol=1e-5, atol=1e-6)
    # and a second step goes on from the first one's states
    o2_ref, pool2_ref = kda.kda_step_in_pool(*args[:5], pool_ref, *args[6:])
    o2, pool2 = kda_decode_pallas(*args[:5], pool, *args[6:], interpret=True,
                                  heads_per_block=heads_per_block)
    np.testing.assert_allclose(pool2, pool2_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(o2)[live], np.asarray(o2_ref)[live],
                               rtol=1e-5, atol=1e-5)


def test_the_decode_kernel_moves_no_other_slot_and_no_other_layer():
    from rbg_tpu.ops.pallas.kda_kernel import kda_decode_pallas
    slots = [4, POOL_SLOTS, 1, 7]
    args = _pool_case(slots, [0, 0, 1, 0], seed=11)
    before = np.asarray(args[5])
    _, pool = kda_decode_pallas(*args, interpret=True, heads_per_block=2)
    after = np.asarray(pool)
    touched = np.zeros(before.shape[:2], bool)
    touched[POOL_LAYER, [4, 1]] = True
    np.testing.assert_array_equal(after[~touched], before[~touched])
    assert (after[touched] != before[touched]).mean() > 0.99


def test_a_step_of_one_token_a_row_takes_the_kernel_by_the_one_policy(
        monkeypatch):
    """``kda_decode`` is ``dispatch_pallas``'s: 'never' (and 'auto' off a
    TPU) is plain XLA, 'always' the kernel with the arguments in order."""
    from rbg_tpu.ops.pallas import kda_kernel as K
    args = _pool_case([2, POOL_SLOTS, 0], [0, 0, 1], seed=5)
    want = kda.kda_step_in_pool(*args)
    real, calls = K.kda_decode_pallas, []

    def spy(*a):
        calls.append(a)
        return real(*a, interpret=True)

    monkeypatch.setattr(K, "kda_decode_pallas", spy)
    for policy in ("never", "auto"):
        got = kda.kda_decode(*args, use_pallas=policy)
        assert not calls
        for a, w in zip(got, want):
            np.testing.assert_array_equal(a, w)
    o, pool = kda.kda_decode(*args, use_pallas="always")
    assert len(calls) == 1 and len(calls[0]) == 9
    np.testing.assert_allclose(pool, want[1], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(o[0], want[0][0], rtol=1e-5, atol=1e-5)


# ---- a packed step: the rows that hold a chunk are walked, they alone ---------


@pytest.mark.parametrize("use_pallas", ["never", "always"])
@pytest.mark.parametrize("step", sorted(PACKED_STEPS))
def test_a_packed_steps_rows_equal_the_recurrence_row_by_row(
        step, use_pallas, interpreted):
    """``_kda_packed`` at 32 heads, ``b`` < 1: rows of one token through
    the decode step's path (plain XLA, or the kernel in place), rows of 2,
    17 and 64 through the chunked form, fresh ones of each kind, a row
    with no token, padding; no other slot moves."""
    assert_rows_equal_the_recurrence(step, 32, 1.0, use_pallas)


def test_a_packed_step_lays_out_no_line_and_no_state_for_every_row(
        interpreted):
    assert_no_line_and_no_state_for_every_row(CFG, PARAMS)


def test_the_convolution_is_causal_and_keeps_the_last_inputs():
    ks = jax.random.split(jax.random.key(3), 3)
    x = jax.random.normal(ks[0], (3, 10, 5))
    tail = jax.random.normal(ks[1], (3, 3, 5))
    w = jax.random.normal(ks[2], (4, 5))
    lens = jnp.asarray([10, 4, 0])
    y, new = kda.short_conv(x, tail, w, lens)
    xx = np.concatenate([tail, x], axis=1)
    for t in range(10):
        pre = sum(xx[:, t + j] * np.asarray(w[j]) for j in range(4))
        np.testing.assert_allclose(y[:, t], pre / (1 + np.exp(-pre)),
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(new[0], x[0, 7:])
    np.testing.assert_array_equal(new[1], x[1, 1:4])
    np.testing.assert_array_equal(new[2], tail[2])
    # in two pieces, the tail carried: the same outputs
    y1, t1 = kda.short_conv(x[:, :6], tail, w, jnp.asarray([6, 6, 6]))
    y2, _ = kda.short_conv(x[:, 6:], t1, w, jnp.asarray([4, 4, 4]))
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y, rtol=1e-6)


def test_latent_attention_without_positions_rotates_nothing():
    blk = jax.tree_util.tree_map(lambda a: a[0], PARAMS["mixers"])
    g = CFG.layer_groups[2][1]
    x = jax.random.normal(jax.random.key(1), (2, 5, CFG.hidden_size))
    here = jnp.broadcast_to(jnp.arange(5)[None], (2, 5))
    a = _mla_qkv(g, blk, x, here)
    b = _mla_qkv(g, blk, x, here + 1000)
    for u, v in zip(a, b):
        np.testing.assert_array_equal(u, v)
    rotated = _mla_qkv(dataclasses.replace(g, use_rope=True), blk, x,
                       here + 1000)
    assert not np.allclose(rotated[1], a[1])     # q_pe
    np.testing.assert_array_equal(rotated[2], a[2])     # the latent


# ---- a chip's share of the experts -------------------------------------------


def _expert_layer(held):
    """(group config, one layer's weights) of an expert layer that holds
    the range ``held`` of one set of 16 experts."""
    whole = dataclasses.replace(CFG.layer_groups[2][1], experts_held=None)
    full = llama._init_blocks(whole, jax.random.key(5), 1,
                              lambda k, shape, scale: jax.random.normal(
                                  k, shape, jnp.float32) * scale, 0.5, 0.5)
    blk = jax.tree_util.tree_map(lambda a: a[0], full)
    lo, hi = held
    blk = dict(blk, **{k: blk[k][lo:hi] for k in _EXPERT_STACKS})
    return dataclasses.replace(whole, experts_held=held), blk


def test_exact_zeros_off_the_chosen_set_survive_a_held_range():
    cfg, blk = _expert_layer((4, 12))
    x = jax.random.normal(jax.random.key(4), (9, 1, CFG.hidden_size))
    w = np.asarray(_route(cfg, blk, x))[:, 0]
    assert ((w > 0).sum(-1) == cfg.experts_per_token).all()
    assert (w[w <= 0] == 0).all()
    np.testing.assert_allclose(w.sum(-1), cfg.moe_routed_scale, rtol=1e-5)
    held = np.asarray(llama._held(cfg, jnp.asarray(w)))
    np.testing.assert_array_equal(held, w[:, 4:12])
    # a row none of whose experts is held gets the shared expert alone
    only = np.flatnonzero((held > 0).sum(-1) == 0)
    out = np.asarray(_moe_mlp(cfg, blk, x))
    shared = np.asarray(llama._shared_expert(blk, x))
    for r in only:
        np.testing.assert_allclose(out[r], shared[r], rtol=1e-5, atol=1e-7)


def test_hit_experts_pay_reckons_with_the_published_count():
    g = CFG.layer_groups[1][1]
    assert g.experts_here == 8 and g.num_experts == 16
    assert llama.hit_experts_pay(g, 16) and not llama.hit_experts_pay(g, 17)
    cell = dataclasses.replace(g, num_experts=256, experts_per_token=8,
                               experts_held=(0, 16))
    assert llama.hit_experts_pay(cell, 16) and llama.hit_experts_pay(cell, 64)
    with pytest.raises(ValueError, match="no range"):
        dataclasses.replace(g, experts_held=(8, 17))
