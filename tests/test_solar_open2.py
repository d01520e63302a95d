"""The Solar-Open2-shaped model (``tiny-solar-open2``): expert layers A K K K
A K K K with no dense layer (A: grouped-query attention without positions
and with an output gate, on K/V pages; K: the gated delta rule with a write
strength of ``2 sigmoid``, its states beside those pages), sigmoid experts
picked by a bias beside a shared one, of which this device holds a range.

The served path (chunked prefill then decode through the pages and the
state pool, packed and by row, in plain XLA and by the kernels) is held to
the benchmark's plain reference of the architecture
(``benchmark/references/solar_open2.py``, which shares no code with the
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
from rbg_tpu.models.config import ModelConfig
from rbg_tpu.models.llama import _hybrid_plan, _moe_mlp
from rbg_tpu.ops import kda

from kda_packed_case import STEPS as PACKED_STEPS
from kda_packed_case import (assert_rows_equal_the_recurrence,
                             inside_the_mixer)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.append(BENCH)          # the benchmark's ``harness`` package

CFG = get_config("tiny-solar-open2")
PARAMS = init_params(CFG, jax.random.key(0))
TINY_FILE = os.path.join(BENCH, "tests", "rehearse", "configs",
                         "tiny-solar-open2.json")
CELL_FILE = os.path.join(BENCH, "configs", "solar-open2-250b.json")
NAME = "tiny-solar-open2-file"

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
    with open(CELL_FILE) as f:
        cell = json.load(f)
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


def _kda_inputs(R, C, H, dk, lens, seed=0):
    """As ``test_kimi_linear._kda_inputs`` with ``b = 2 sigmoid(.)``: drawn
    over (0, 2), a third of it above 1.2."""
    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (R, C, H, dk))
    k = jax.random.normal(ks[1], (R, C, H, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (R, C, H, dk))
    g = -jax.random.uniform(ks[3], (R, C, H, dk)) * 0.7
    b = 2.0 * jax.nn.sigmoid(1.5 * jax.random.normal(ks[4], (R, C, H)))
    real = jnp.arange(C)[None] < jnp.asarray(lens)[:, None]
    g = jnp.where(real[..., None, None], g, 0.0)
    b = jnp.where(real[..., None], b, 0.0)
    S = jax.random.normal(ks[5], (R, H, dk, dk))
    return (q, k, v, g, b, S), np.asarray(real)


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
    """``tiny-solar-open2``'s unified program with the kernel in: no
    float32 ``[R, C, H, dk]`` or ``[R, H, dk, dv]``, a ``while`` in the
    mixer's program and the decode kernel beside it."""
    R, C, H, dk = 5, 16, CFG.kda_num_heads, CFG.kda_head_dim
    cache, pool = PagedKVCache.create(CFG, 64, 8), StatePool(CFG, R)
    I32 = jnp.int32
    jaxpr = jax.make_jaxpr(functools.partial(
        llama.forward_ragged, PARAMS, CFG, max_q_len=C, use_pallas="always",
        state=pool.arrays, state_slots=jnp.arange(R, dtype=I32)))(
        jnp.ones((1, C), I32), jnp.zeros((1, C), I32), jnp.ones((1, C), bool),
        jnp.zeros(C, I32), jnp.full(R, C, I32), jnp.zeros((R, 8), I32),
        cache.k_pages, cache.v_pages).jaxpr
    shapes, names = inside_the_mixer(jaxpr)
    assert not shapes & {(R, C, H, dk), (R, H, dk, dk)}
    assert (1, C, H, dk) in shapes                  # a trip's one row
    assert {"while", "pallas_call"} <= names


# ---- the held experts --------------------------------------------------------


def test_four_shares_of_four_and_the_shared_expert_once_make_the_layer():
    g = dict((k, c) for k, c, _, _ in CFG.layer_groups)["blocks"]
    whole = dataclasses.replace(g, experts_held=None)
    blk = llama._init_blocks(dataclasses.replace(whole, half="mlp"),
                             jax.random.key(3), 1, lambda k, s, sc: (
                                 jax.random.normal(k, s) * sc), 0.2, 0.2)
    blk = {k: v[0] for k, v in blk.items()}
    assert blk["router"].shape == (128, 16) and "w_gate" in blk
    xm = jax.random.normal(jax.random.key(4), (2, 5, 128))
    total = _moe_mlp(whole, blk, xm)
    shared = llama._shared_expert(blk, xm)
    parts = 0
    for lo in range(0, 16, 4):
        share = dataclasses.replace(g, experts_held=(lo, lo + 4))
        held = {k: (v[lo:lo + 4] if k in llama._EXPERT_STACKS else v)
                for k, v in blk.items()}
        parts = parts + _moe_mlp(share, held, xm) - shared
    # every share computes the shared expert alike: counted once
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(total),
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(shared).max()) > 1e-3


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


def _engine(cfg, params, model=NAME, **kw):
    return Engine(EngineConfig(model=model, **{**cfg["server"], **kw}),
                  params=params)


def _serve(eng, prompts, new):
    ids = [eng.add_request(p, SamplingParams(max_new_tokens=new,
                                             logprobs=True)) for p in prompts]
    out = {}
    while eng.has_work():
        for ev in eng.step():
            toks, lps = out.setdefault(ev.request_id, ([], []))
            toks.append(ev.token)
            lps.append(ev.logprob)
    return [out[i] for i in ids]


def _rms(a, b):
    d = np.asarray(a, np.float64) - np.asarray(b, np.float64)
    return math.sqrt(float(np.mean(d * d)))


def _prompts(cfg, lens, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg["vocab_size"], n).tolist() for n in lens]


@pytest.fixture()
def interpreted(monkeypatch):
    """``use_pallas="always"`` off the chip: the kernels a served
    ``tiny-solar-open2`` reaches, in interpret mode."""
    from rbg_tpu.ops.pallas import paged_attention_kernel as K
    for name in ("kda_decode_pallas", "paged_attention_pallas",
                 "ragged_paged_attention_pallas", "moe_visit_pallas"):
        monkeypatch.setattr(K, name, functools.partial(getattr(K, name),
                                                       interpret=True))


@pytest.fixture()
def fresh_mixers():
    """``_kda_mixer`` is a program of its own and keeps what it traced: a
    test that changes what it calls clears the caches around itself."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_the_file_reaches_the_preset_the_tests_use(bench):
    from rbg_tpu.models import config as presets
    got = dataclasses.replace(presets._PRESETS[NAME], name="tiny-solar-open2",
                              max_seq_len=256)
    assert got == CFG
    cfg, reference, params = bench
    own = jax.eval_shape(lambda: init_params(presets._PRESETS[NAME],
                                             jax.random.key(0)))
    assert jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), params) == \
        jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), own)


@pytest.mark.parametrize("ragged,hit,use_pallas", [
    ("auto", True, "auto"), ("off", True, "auto"), ("auto", False, "auto"),
    ("auto", True, "always")],
    ids=["packed-hit", "rows-hit", "packed-dense", "packed-hit-kernels"])
def test_served_path_agrees_with_the_plain_reference(
        bench, monkeypatch, interpreted, ragged, hit, use_pallas):
    """Three prompts side by side, the longest of three prefill chunks: the
    state and the convolution's tail carried from chunk to chunk (packed
    with the other rows' decode steps, or by row) while the attention
    layers fill their pages, then decode steps through both: in plain XLA,
    and by the delta rule's kernel and the page walk's in one step."""
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


@pytest.mark.parametrize("chunk", [16, 48, 128])
def test_a_prompt_in_chunks_equals_it_whole(bench, chunk):
    cfg, reference, params = bench
    prompt, = _prompts(cfg, (90,), seed=7)
    whole = _serve(_engine(cfg, params, prefill_chunk=128), [prompt], 6)[0]
    got = _serve(_engine(cfg, params, prefill_chunk=chunk), [prompt], 6)[0]
    assert got[0] == whole[0] and _rms(got[1], whole[1]) < 1e-5
    ref = reference.chosen_logprobs(cfg, params, prompt, got[0])
    assert _rms(got[1], ref) <= cfg["correct"]["limit"]


RULES = {"the gate dropped": dict(attn_gate=False),
         "b not doubled": dict(kda_beta_scale=1.0),
         "the attention layers rotated": dict(use_rope=True)}


@pytest.mark.parametrize("rule", sorted(RULES))
def test_each_new_rule_left_out_moves_the_logits_far_past_the_limit(
        bench, fresh_mixers, rule):
    """The three rules the architecture adds, left out of the served path
    one at a time, as the chip's controls leave them out: each is far from
    the reference, which keeps all three."""
    from rbg_tpu.models import config as presets
    cfg, reference, params = bench
    name = NAME + "-broken"
    presets._PRESETS[name] = dataclasses.replace(
        presets._PRESETS[NAME], name=name, **RULES[rule])
    prompt, = _prompts(cfg, (80,), seed=5)
    (toks, lps), = _serve(_engine(cfg, params, model=name), [prompt], 8)
    ref = reference.chosen_logprobs(cfg, params, prompt, toks)
    # (rotation moves this toy least, 30 limits: its scores are near uniform)
    assert _rms(lps, ref) > 10 * cfg["correct"]["limit"]
    # and the sound program on the same prompt is within it
    (toks, lps), = _serve(_engine(cfg, params), [prompt], 8)
    ref = reference.chosen_logprobs(cfg, params, prompt, toks)
    assert _rms(lps, ref) <= cfg["correct"]["limit"]


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


def test_the_controls_fail_the_tiny_limits(bench):
    cfg, reference, params = bench
    prompt, = _prompts(cfg, (80,))
    (toks, lps), = _serve(_engine(cfg, params), [prompt], 8)
    ref = reference.chosen_logprobs(cfg, params, prompt, toks)
    # kv_int8 rounds the cached K and V AND the recurrent state
    for quant in ("bf16", "int8", "fp8", "kv_int8"):
        ctl = reference.chosen_logprobs(cfg, params, prompt, toks, quant)
        assert _rms(ctl, ref) > 3 * cfg["correct"]["limit"], quant


@pytest.mark.parametrize("use_pallas", ["auto", "always"])
@pytest.mark.parametrize("fault", ["state not carried", "slot not zeroed"])
def test_a_wrong_state_fails_the_tiny_limits(bench, monkeypatch, interpreted,
                                             fresh_mixers, fault, use_pallas):
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


def test_a_slot_reused_after_finish_starts_from_zeros(bench):
    cfg, reference, params = bench
    a, b = _prompts(cfg, (70, 50), seed=2)
    alone = _serve(_engine(cfg, params, max_batch=1), [b], 6)[0]
    eng = _engine(cfg, params, max_batch=1)
    _serve(eng, [a], 6)                     # leaves its states in slot 0
    assert eng.state.held == 0
    assert float(jnp.abs(eng.state.arrays["s"][:, 0]).max()) > 0
    again = _serve(eng, [b], 6)[0]          # the same slot
    assert again[0] == alone[0] and _rms(again[1], alone[1]) < 1e-5
    assert eng.metrics["state_resets"] == 2


def test_state_counters_count_slots_rows_and_bytes(bench):
    cfg, _, params = bench
    eng = _engine(cfg, params)
    _serve(eng, _prompts(cfg, (40, 20)), 5)
    m = eng.metrics
    assert m["state_resets"] == 2
    assert 0 < m["state_slots_live"] <= m["state_slots_held"]
    # read and written: 6 layers x (4 x 32 x 32 state + 3 x 384 tail), f32
    row = 2 * 6 * (4 * 32 * 32 + 1152) * 4
    assert eng.state.row_bytes == row and m["state_bytes_moved"] % row == 0
    assert m["state_bytes_moved"] // row >= m["decode_tokens"]
    assert m["moe_expert_slots"] % (4 * 8) == 0     # 4 held x 8 layers
    assert m["moe_experts_visited"] <= m["moe_expert_slots"]
    assert m["prefix_skipped"] == 2 and m["radix_hit_tokens"] == 0
