"""The Kimi-Linear-shaped model (``tiny-kimi-linear``): a dense recurrent
layer, then expert layers K K M K K K M (K: Kimi Delta Attention, a gated
delta rule over a fixed state a sequence; M: latent attention without
positions), sigmoid-routed experts of which this device holds a range.

The served path (chunked prefill then decode through the state pool, packed
and by row, through preemption and the reuse of a slot) is held to the
benchmark's plain reference of the architecture
(``benchmark/references/kimi_linear.py``, which shares no code with the
program), and each new rule to its definition."""

import dataclasses
import functools
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rbg_tpu.engine import Engine, EngineConfig, SamplingParams
from rbg_tpu.engine.kvcache import PagedKVCache, StatePool
from rbg_tpu.models import get_config, init_params
from rbg_tpu.models import llama
from rbg_tpu.models.llama import (_EXPERT_STACKS, _hybrid_plan, _mla_qkv,
                                  _moe_mlp, _moe_mlp_hit, _route)
from rbg_tpu.ops import kda

from kda_packed_case import STEPS as PACKED_STEPS
from kda_packed_case import (assert_rows_equal_the_recurrence,
                             inside_the_mixer)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.append(BENCH)          # the benchmark's ``harness`` package

CFG = get_config("tiny-kimi-linear")
PARAMS = init_params(CFG, jax.random.key(0))
TINY_FILE = os.path.join(BENCH, "tests", "rehearse", "configs",
                         "tiny-kimi-linear.json")
NAME = "tiny-kimi-linear-file"


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


def test_num_params_counts_what_init_makes():
    n = sum(a.size for a in jax.tree_util.tree_leaves(PARAMS))
    assert CFG.num_params == n


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


def _eqns(jaxpr, in_loop=False):
    """Every equation of ``jaxpr`` at any depth, each with whether it sits
    inside the body of a ``while`` or a ``scan``."""
    for eqn in jaxpr.eqns:
        yield eqn, in_loop
        inner = in_loop or eqn.primitive.name in ("while", "scan")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, inner)


def _step_jaxpr(program, R=4, use_pallas="auto"):
    """The jaxpr of ``tiny-kimi-linear``'s decode step (by row, the hit
    experts' form) or its ragged step (packed), ``R`` rows."""
    T = 1 if program == "decode" else 16
    cache, pool = PagedKVCache.create(CFG, 64, 8), StatePool(CFG, R)
    I32 = jnp.int32
    table = jnp.zeros((R, 8), I32)
    slots = {"state": pool.arrays, "state_slots": jnp.arange(R, dtype=I32),
             "use_pallas": use_pallas}
    if program == "decode":
        fn = functools.partial(llama.forward_paged, PARAMS, CFG,
                               experts_whole=True, **slots)
        args = (jnp.ones((R, T), I32), jnp.zeros((R, T), I32),
                jnp.ones((R, T), bool), jnp.ones(R, I32), table)
    else:
        fn = functools.partial(llama.forward_ragged, PARAMS, CFG,
                               max_q_len=T, **slots)
        args = (jnp.ones((1, T), I32), jnp.zeros((1, T), I32),
                jnp.ones((1, T), bool), jnp.zeros(T, I32),
                jnp.full(R, T, I32), table)
    return jax.make_jaxpr(fn)(*args, cache.k_pages, cache.v_pages).jaxpr


@pytest.mark.parametrize("program", ["decode", "ragged"])
def test_no_loop_body_branches_over_an_mlps_weights(program):
    """A ``lax.cond``'s operands are copied whole in every trip of the loop
    that holds it, whichever branch runs (PERF.md, PR 38): no loop of the
    walk hands one a matrix of an MLP, whole or a layer's slice."""
    jaxpr = _step_jaxpr(program)
    matrices = {a.shape[cut:] for key in ("dense_mlps", "moe_mlps")
                for a in PARAMS[key].values() if a.ndim > 2
                for cut in (0, 1)}
    assert (1, 320, 128) in matrices and (320, 128) in matrices
    # the walk is there: loops, and in them the sampler-free step's only
    # conds are those of the operations (none takes a matrix of an MLP)
    assert any(e.primitive.name in ("while", "scan") for e in jaxpr.eqns)
    fed = [(e.params["branches"][0].jaxpr.eqns[:1], v.aval.shape)
           for e, inside in _eqns(jaxpr)
           if inside and e.primitive.name == "cond" for v in e.invars
           if getattr(v.aval, "shape", None) in matrices]
    assert not fed


@pytest.mark.parametrize("program", ["decode", "ragged"])
def test_a_step_program_traces_the_recurrent_mixer_once(program, monkeypatch):
    """The walk meets the recurrent mixer in two places, the dense first
    layer alone and the expert layers' loop: ``_kda_mixer`` makes them one
    trace (a third body cost 16 s of warm set-up on the chip; PERF.md,
    PR 38); none where an earlier program of these shapes left it one."""
    traced = []
    mixer = llama._kda_attention
    monkeypatch.setattr(llama, "_kda_attention", lambda *a, **kw: (
        traced.append(a[4]), mixer(*a, **kw))[1])
    jaxpr = _step_jaxpr(program, R=3)
    assert len(traced) <= 1
    calls = [e for e, _ in _eqns(jaxpr)
             if e.params.get("name") == "_kda_mixer"]
    assert len(calls) == 2              # layer 0, and the loop's body


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


def _kda_inputs(R, C, H, dk, lens, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (R, C, H, dk))
    k = jax.random.normal(ks[1], (R, C, H, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (R, C, H, dk))
    g = -jax.random.uniform(ks[3], (R, C, H, dk)) * 0.7
    b = jax.nn.sigmoid(jax.random.normal(ks[4], (R, C, H)))
    real = jnp.arange(C)[None] < jnp.asarray(lens)[:, None]
    g = jnp.where(real[..., None, None], g, 0.0)
    b = jnp.where(real[..., None], b, 0.0)
    S = jax.random.normal(ks[5], (R, H, dk, dk))
    return (q, k, v, g, b, S), np.asarray(real)


def test_one_token_is_the_delta_rule_as_written():
    (q, k, v, g, b, S), _ = _kda_inputs(2, 1, 3, 8, [1, 1])
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
    args, real = _kda_inputs(4, C, 2, 16, lens, seed=C)
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
    args, _ = _kda_inputs(2, 48, 2, 16, [48, 48], seed=7)
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
    (q, k, v, g, b, _), _ = _kda_inputs(
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
    from rbg_tpu.ops.pallas import paged_attention_kernel as K
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
    """The unified program with the kernel in holds no float32 array of
    ``[R, C, H, dk]`` (every row's line) or ``[R, H, dk, dv]`` (every
    row's state), which the decode step by row, in plain XLA, has; it has
    the kernel, and a loop over the rows that hold a chunk, a row a trip."""
    R, C, H, dk = 5, 16, CFG.kda_num_heads, CFG.kda_head_dim
    wide = {(R, C, H, dk), (R, H, dk, dk)}
    shapes, names = inside_the_mixer(_step_jaxpr("ragged", R,
                                                 use_pallas="always"))
    assert not shapes & wide and (1, C, H, dk) in shapes
    assert {"while", "pallas_call"} <= names
    shapes, names = inside_the_mixer(_step_jaxpr("decode", R,
                                                 use_pallas="never"))
    assert (R, H, dk, dk) in shapes and "pallas_call" not in names


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
    if held is None:
        return whole, blk
    lo, hi = held
    blk = dict(blk, **{k: blk[k][lo:hi] for k in _EXPERT_STACKS})
    return dataclasses.replace(whole, experts_held=held), blk


def test_the_shares_partial_results_add_up_to_the_whole_expert_layer():
    x = jax.random.normal(jax.random.key(2), (6, 1, CFG.hidden_size))
    whole_cfg, whole_blk = _expert_layer(None)
    whole = np.asarray(_moe_mlp(whole_cfg, whole_blk, x), np.float64)
    shared = np.asarray(llama._shared_expert(whole_blk, x), np.float64)
    parts = np.zeros_like(whole)
    for lo in (0, 4, 8, 12):
        cfg, blk = _expert_layer((lo, lo + 4))
        part = np.asarray(_moe_mlp(cfg, blk, x), np.float64)
        parts += part - shared          # the shared expert counted once
        # the hit form computes the same share, and visits held experts only
        stacks = {k: blk[k][None] for k in _EXPERT_STACKS}
        got, visited = jax.jit(lambda b, x, s: _moe_mlp_hit(
            cfg, b, x, s, jnp.int32(0), jnp.ones((6, 1), bool)))(
                blk, x, stacks)
        np.testing.assert_allclose(got, part, rtol=1e-4,
                                   atol=1e-5 * np.abs(part).max())
        w = np.asarray(_route(cfg, blk, x))[:, 0]
        assert w.shape == (6, 16)       # the router keeps its width
        assert int(visited) == (w[:, lo:lo + 4] > 0).any(0).sum() <= 4
    np.testing.assert_allclose(parts + shared, whole, rtol=1e-4,
                               atol=1e-5 * np.abs(whole).max())


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


# ---- the served path against the plain reference -----------------------------


@pytest.fixture(scope="module")
def bench():
    from harness import serve
    from rbg_tpu.models import config as presets
    with open(TINY_FILE) as f:
        cfg = json.load(f)
    reference = serve.load_reference(cfg)
    params = reference.make_params(cfg, 3000000019)
    presets._PRESETS[NAME] = serve.model_config(cfg, NAME)
    return cfg, reference, params


def _engine(cfg, params, **kw):
    return Engine(EngineConfig(model=NAME, **{**cfg["server"], **kw}),
                  params=params)


def _drive(eng, ids=None, between=None):
    out = {}
    while eng.has_work():
        for ev in eng.step():
            toks, lps = out.setdefault(ev.request_id, ([], []))
            toks.append(ev.token)
            lps.append(ev.logprob)
        if between is not None:
            between(eng, out)
    return out if ids is None else [out[i] for i in ids]


def _serve(eng, prompts, new):
    ids = [eng.add_request(p, SamplingParams(max_new_tokens=new,
                                             logprobs=True)) for p in prompts]
    return _drive(eng, ids)


def _rms(a, b):
    d = np.asarray(a, np.float64) - np.asarray(b, np.float64)
    return math.sqrt(float(np.mean(d * d)))


def _prompts(cfg, lens, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg["vocab_size"], n).tolist() for n in lens]


@pytest.fixture()
def interpreted(monkeypatch):
    """``use_pallas="always"`` off the chip: the kernels a served
    ``tiny-kimi-linear`` reaches, in interpret mode."""
    from rbg_tpu.ops.pallas import paged_attention_kernel as K
    for name in ("kda_decode_pallas", "paged_mla_attention_pallas",
                 "ragged_paged_mla_attention_pallas", "moe_visit_pallas"):
        monkeypatch.setattr(K, name, functools.partial(getattr(K, name),
                                                       interpret=True))


def test_the_file_reaches_the_preset_the_tests_use(bench):
    from rbg_tpu.models import config as presets
    got = dataclasses.replace(presets._PRESETS[NAME], name="tiny-kimi-linear",
                              max_seq_len=256, head_dim=None)
    assert got == CFG


@pytest.mark.parametrize("ragged,hit,use_pallas", [
    ("auto", True, "auto"), ("off", True, "auto"), ("auto", False, "auto"),
    ("auto", True, "always")],
    ids=["packed-hit", "rows-hit", "packed-dense", "packed-hit-kernels"])
def test_served_path_agrees_with_the_plain_reference(
        bench, monkeypatch, interpreted, ragged, hit, use_pallas):
    """Three prompts side by side, the longest of three prefill chunks: the
    state carried from chunk to chunk (packed with the other rows' decode
    steps, or by row), then decode steps through the state pool: in plain
    XLA, and by the kernel that advances the states in place."""
    cfg, reference, params = bench
    if not hit:
        monkeypatch.setattr(llama, "hit_experts_pay", lambda c, rows: False)
    prompts = _prompts(cfg, (80, 23, 40))
    eng = _engine(cfg, params, ragged=ragged, use_pallas=use_pallas)
    served = _serve(eng, prompts, 8)
    assert (eng.metrics["moe_experts_visited"] > 0) == hit
    for prompt, (toks, lps) in zip(prompts, served):
        assert len(toks) == 8
        ref = reference.chosen_logprobs(cfg, params, prompt, toks)
        assert _rms(lps, ref) <= cfg["correct"]["limit"]
    assert eng.state.held == 0 and eng.allocator.free_pages == 255


def test_the_controls_fail_the_tiny_limits(bench):
    cfg, reference, params = bench
    prompt, = _prompts(cfg, (80,))
    (toks, lps), = _serve(_engine(cfg, params), [prompt], 8)
    ref = reference.chosen_logprobs(cfg, params, prompt, toks)
    for quant in ("bf16", "int8", "kv_int8"):
        ctl = reference.chosen_logprobs(cfg, params, prompt, toks, quant)
        assert _rms(ctl, ref) > 3 * cfg["correct"]["limit"], quant


@pytest.mark.parametrize("use_pallas", ["auto", "always"])
@pytest.mark.parametrize("fault", ["state not carried", "slot not zeroed"])
def test_a_wrong_state_fails_the_tiny_limits(bench, monkeypatch, interpreted,
                                             fault, use_pallas):
    """What the check's six-chunk prompt is for: a program that starts
    every chunk from zeros, or one that goes on from what a slot's last
    row left, is far from the reference (the decode steps in plain XLA or
    by the kernel, which takes its ``fresh`` rows from the same
    positions)."""
    cfg, reference, params = bench
    real = llama._kda_attention

    def broken(g, blk, x, state, layer, addr, use_pallas):
        pos = addr.positions
        if fault == "state not carried":
            if x.shape[1] > 1:      # every chunk of a prompt looks first
                pos = pos - pos[..., :1] if addr.row_ids is None else \
                    jnp.where(addr.token_mask, 0, pos)
        else:
            pos = jnp.where(pos == 0, 1 << 20, pos)     # never looks first
        return real(g, blk, x, state, layer, addr._replace(positions=pos),
                    use_pallas)

    monkeypatch.setattr(llama, "_kda_attention", broken)
    eng = _engine(cfg, params, max_batch=1, use_pallas=use_pallas)
    first, second = _prompts(cfg, (80, 72), seed=5)
    (toks, lps), = _serve(eng, [first], 8)
    if fault == "slot not zeroed":          # the second row inherits a state
        (toks, lps), = _serve(eng, [second], 8)
        first = second
    ref = reference.chosen_logprobs(cfg, params, first, toks)
    assert _rms(lps, ref) > 100 * cfg["correct"]["limit"]


def test_a_slot_reused_after_finish_and_after_preemption_starts_from_zero(
        bench):
    cfg, reference, params = bench
    a, b, c = _prompts(cfg, (70, 50, 33), seed=2)
    alone = _serve(_engine(cfg, params, max_batch=1), [b], 6)[0]
    eng = _engine(cfg, params, max_batch=1)
    _serve(eng, [a], 6)                     # leaves its state in slot 0
    assert eng.state.held == 0
    again = _serve(eng, [b], 6)[0]          # the same slot
    assert again[0] == alone[0] and _rms(again[1], alone[1]) < 1e-5
    # a row preempted in the middle of its prompt, then another in its slot
    rid = eng.add_request(a, SamplingParams(max_new_tokens=6, logprobs=True))
    eng.step()
    req = eng.requests[rid]
    assert req.state == "prefill" and req.state_slot == 0
    eng._preempt(req)
    assert req.state_slot is None and eng.state.held == 0
    eng.waiting.remove(req)
    eng.requests.pop(rid)
    after = _serve(eng, [c], 6)[0]
    ref = reference.chosen_logprobs(cfg, params, c, after[0])
    assert _rms(after[1], ref) <= cfg["correct"]["limit"]
    assert eng.metrics["state_resets"] == 4


def test_a_preempted_requests_second_run_gives_the_first_runs_logits(bench):
    cfg, reference, params = bench
    prompt, other = _prompts(cfg, (60, 30), seed=3)
    whole = _serve(_engine(cfg, params), [prompt], 12)[0]

    eng = _engine(cfg, params)
    ids = [eng.add_request(p, SamplingParams(max_new_tokens=12,
                                             logprobs=True))
           for p in (prompt, other)]
    done = []

    def preempt_once(eng, out):
        req = eng.requests.get(ids[0])
        if not done and req is not None and len(out.get(ids[0], ([],))[0]) >= 5:
            for ev in eng._drain_decode():      # tokens in flight first
                out[ev.request_id][0].append(ev.token)
                out[ev.request_id][1].append(ev.logprob)
            if req.state == "running":
                eng._preempt(req)
                done.append(len(req.prompt))

    toks, lps = _drive(eng, ids, preempt_once)[0]
    assert done and done[0] > len(prompt)       # prefilled again from token 0
    assert eng.metrics["preemptions"] == 1
    assert toks == whole[0] and _rms(lps, whole[1]) < 1e-4
    ref = reference.chosen_logprobs(cfg, params, prompt, toks)
    assert _rms(lps, ref) <= cfg["correct"]["limit"]


def test_a_cached_prefix_matches_nothing_and_still_agrees(bench):
    cfg, reference, params = bench
    first, tail = _prompts(cfg, (64, 20), seed=4)
    eng = _engine(cfg, params)
    assert eng.radix is not None
    _serve(eng, [first], 4)
    second = first + tail                   # its first 64 tokens were served
    (toks, lps), = _serve(eng, [second], 6)
    m = eng.metrics
    assert m["radix_hit_tokens"] == 0 and m["prefix_skipped"] == 2
    assert eng.radix.match(first[:-1])[0] == 0      # nothing was inserted
    assert m["prefill_tokens"] == len(first) + len(second)
    ref = reference.chosen_logprobs(cfg, params, second, toks)
    assert _rms(lps, ref) <= cfg["correct"]["limit"]


def test_state_counters_count_slots_rows_and_bytes(bench):
    cfg, _, params = bench
    eng = _engine(cfg, params)
    _serve(eng, _prompts(cfg, (40, 20)), 5)
    m = eng.metrics
    assert m["state_resets"] == 2
    assert 0 < m["state_slots_live"] <= m["state_slots_held"]
    row = 2 * (6 * 4 * 32 * 32 * 4 + 6 * 3 * 3 * 4 * 32 * 4)
    assert eng.state.row_bytes == row
    assert m["state_bytes_moved"] % row == 0
    # every row-step of a decode or unified step moved one row's state
    assert m["state_bytes_moved"] // row >= m["decode_tokens"]
    # experts: slots count the held experts, routed pairs the held share
    assert m["moe_expert_slots"] % (7 * 8) == 0
    # (row, chosen expert) pairs: rows x top 2 x 7 expert layers x 8 / 16
    assert m["moe_routed_rows"] % 7 == 0
    assert 0 < m["moe_routed_rows"] <= 7 * m["decode_tokens"]
    assert m["moe_experts_visited"] <= m["moe_expert_slots"]


# ---- what is not built for a recurrent model is refused, with a message ------


@pytest.mark.parametrize("kw,match", [
    (dict(speculative="ngram"), "speculative decoding"),
    (dict(kv_dtype="int8"), "the state pool has no quantised form"),
    (dict(mode="prefill"), "PD bundle carries pages"),
    (dict(mode="decode"), "PD bundle carries pages"),
    (dict(host_tier_bytes=1 << 20), "host tier keeps prefixes"),
])
def test_engine_refuses_what_a_recurrent_model_does_not_support(kw, match):
    with pytest.raises(ValueError, match=match):
        Engine(EngineConfig(model="tiny-kimi-linear", page_size=8,
                            num_pages=32, max_seq_len=64, max_batch=2,
                            prefill_chunk=16, **kw), params=PARAMS)


def test_other_paths_refuse_a_recurrent_model():
    eng = Engine(EngineConfig(model="tiny-kimi-linear", page_size=8,
                              num_pages=32, max_seq_len=64, max_batch=2,
                              prefill_chunk=16), params=PARAMS)
    with pytest.raises(ValueError, match="groups of layers"):
        eng.load_lora("a", {"wo": (np.zeros((8, 128, 4), np.float32),
                                   np.zeros((8, 4, 128), np.float32))})
    with pytest.raises(ValueError, match="state at its end"):
        eng.add_request_with_prefix(list(range(1, 20)), None, 8, None, None)
    tokens = jnp.ones((1, 4), jnp.int32)
    with pytest.raises(NotImplementedError, match="contiguous cache"):
        llama.forward(PARAMS, CFG, tokens, llama.KVCache(
            k=jnp.zeros((8, 1, 8, 1, 64)), v=jnp.zeros((8, 1, 8, 1, 16)),
            length=jnp.zeros((1,), jnp.int32)))
    with pytest.raises(NotImplementedError, match="cache-free forward"):
        llama.forward_train(PARAMS, CFG, tokens)
    with pytest.raises(NotImplementedError, match="walked whole"):
        llama.paged_layers(PARAMS, CFG, None, (), None, layers=(0, 4))
    from rbg_tpu.parallel import pipeline
    with pytest.raises(NotImplementedError, match="groups"):
        pipeline.pipeline_forward_train(PARAMS, CFG, tokens, mesh=None)
