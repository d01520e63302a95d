"""The LFM2-shaped model (``tiny-lfm2``): two dense layers with the gated
short convolution, then expert layers A C C C A C C C (A: grouped-query
attention with head norms over heads of 64, whose pages keep two heads a
lane tile; C: the convolution, a state of two gated inputs a sequence),
bias-selected sigmoid experts of which this device holds a range, a tied
head.

The served path (chunked prefill then decode through the state pool, packed
and by row, through the reuse of a slot) is held to the benchmark's plain
reference of the architecture (``benchmark/references/lfm2_moe.py``, which
shares no code with the program), and each new rule to its definition."""

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
from rbg_tpu.engine.kvcache import (PagedKVCache, StatePool,
                                    heads_per_lane_tile)
from rbg_tpu.models import get_config, init_params
from rbg_tpu.models import llama
from rbg_tpu.models.llama import _hybrid_plan, _moe_mlp
from rbg_tpu.ops import kda, short_conv
from rbg_tpu.ops.pallas import page_walk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.append(BENCH)          # the benchmark's ``harness`` package

CFG = get_config("tiny-lfm2")
PARAMS = init_params(CFG, jax.random.key(0))
TINY_FILE = os.path.join(BENCH, "tests", "rehearse", "configs",
                         "tiny-lfm2.json")
CELL_FILE = os.path.join(BENCH, "configs", "lfm2-24b-a2b.json")
NAME = "tiny-lfm2-file"

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
    with open(CELL_FILE) as f:
        types = json.load(f)["layer_types"]
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


@pytest.mark.parametrize("program", ["decode", "ragged"])
def test_a_step_program_traces_each_mixer_once(program, monkeypatch,
                                               fresh_mixers):
    """The convolution mixer is walked in the dense layers' loop and in the
    expert layers' turns: ``_conv_mixer`` makes them one trace."""
    from test_layer_walk import _lower
    eng = Engine(EngineConfig(model="tiny-lfm2", page_size=8, num_pages=64,
                              max_seq_len=128, max_batch=4, prefill_chunk=16,
                              use_pallas="never"))
    calls = []
    real = llama._conv_attention
    monkeypatch.setattr(llama, "_conv_attention",
                        lambda *a: calls.append(1) or real(*a))
    text = _lower(eng, program).as_text()
    assert len(calls) == 1
    assert "_conv_mixer" in text and "_kda_mixer" not in text


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
    from rbg_tpu.ops.pallas import paged_attention_kernel as K
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
        got = K.ragged_paged_attention_pallas(q, *packed, table, pos, lens,
                                              rows, interpret=True)
        xla = ragged_paged_attention_xla(q, *packed, table, pos, lens, rows)
        plain = ragged_paged_attention_xla(q, *pool, table, pos, lens, rows)
    else:
        pos = (lens - 1)[:, None]
        q = jnp.asarray(rng.normal(size=(R, 1, H, hd)), jnp.float32)
        got = K.paged_attention_pallas(q, *packed, table, pos, lens,
                                       interpret=True)
        xla = paged_attention_xla(q, *packed, table, pos, lens)
        plain = paged_attention_xla(q, *pool, table, pos, lens)
    np.testing.assert_array_equal(np.asarray(xla), np.asarray(plain))
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain), rtol=2e-5,
                               atol=2e-6)


# ---- the held experts --------------------------------------------------------


def test_the_shares_partial_results_add_up_to_the_whole_expert_layer():
    g = dict((k, c) for k, c, _, _ in CFG.layer_groups)["blocks"]
    whole = dataclasses.replace(g, experts_held=None)
    blk = llama._init_blocks(dataclasses.replace(whole, half="mlp"),
                             jax.random.key(3), 1, lambda k, s, sc: (
                                 jax.random.normal(k, s) * sc), 0.2, 0.2)
    blk = {k: v[0] for k, v in blk.items()}
    xm = jax.random.normal(jax.random.key(4), (2, 5, 128))
    total = _moe_mlp(whole, blk, xm)
    parts = 0
    for lo in range(0, 16, 4):
        share = dataclasses.replace(g, experts_held=(lo, lo + 4))
        held = {k: (v[lo:lo + 4] if k in llama._EXPERT_STACKS else v)
                for k, v in blk.items()}
        parts = parts + _moe_mlp(share, held, xm)
    # no shared expert: nothing is counted by every share
    np.testing.assert_allclose(np.asarray(parts), np.asarray(total),
                               rtol=1e-5, atol=1e-6)


# ---- the served path against the plain reference -----------------------------


@pytest.fixture(scope="module")
def bench():
    from harness import serve
    from rbg_tpu.models import config as presets
    with open(TINY_FILE) as f:
        cfg = json.load(f)
    reference = serve.load_reference(cfg)
    params = reference.make_params(cfg, 3000000019)
    # At 128 channels ``W_in``'s outputs are 0.02 sqrt(128) = 0.23 where
    # the published 2048 give 0.9, and the mixer's output, a product of
    # three of them, is 60 times smaller beside the embedding: scaled back,
    # so that the convolution weighs in these logits as it does deployed.
    params["conv_mixers"]["conv_in"] = 4.0 * params["conv_mixers"]["conv_in"]
    presets._PRESETS[NAME] = serve.model_config(cfg, NAME)
    return cfg, reference, params


def _engine(cfg, params, **kw):
    return Engine(EngineConfig(model=NAME, **{**cfg["server"], **kw}),
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
    ``tiny-lfm2`` reaches, in interpret mode."""
    from rbg_tpu.ops.pallas import paged_attention_kernel as K
    for name in ("paged_attention_pallas", "ragged_paged_attention_pallas",
                 "moe_visit_pallas"):
        monkeypatch.setattr(K, name, functools.partial(getattr(K, name),
                                                       interpret=True))


def test_the_file_reaches_the_preset_the_tests_use(bench):
    from rbg_tpu.models import config as presets
    got = dataclasses.replace(presets._PRESETS[NAME], name="tiny-lfm2",
                              max_seq_len=256)
    assert got == CFG


@pytest.mark.parametrize("ragged,hit,use_pallas", [
    ("auto", True, "auto"), ("off", True, "auto"), ("auto", False, "auto"),
    ("auto", True, "always")],
    ids=["packed-hit", "rows-hit", "packed-dense", "packed-hit-kernels"])
def test_served_path_agrees_with_the_plain_reference(
        bench, monkeypatch, interpreted, ragged, hit, use_pallas):
    """Three prompts side by side, the longest of three prefill chunks: the
    convolution's tail carried from chunk to chunk (packed with the other
    rows' decode steps, or by row), then decode steps through the state
    pool and the pages of packed heads: in plain XLA, and by the kernels."""
    cfg, reference, params = bench
    if not hit:
        monkeypatch.setattr(llama, "hit_experts_pay", lambda c, rows: False)
    prompts = _prompts(cfg, (80, 23, 40))
    eng = _engine(cfg, params, ragged=ragged, use_pallas=use_pallas)
    assert eng.cache.k_pages.shape[3:] == (1, 128)
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


def test_the_controls_fail_the_tiny_limits(bench):
    cfg, reference, params = bench
    prompt, = _prompts(cfg, (80,))
    (toks, lps), = _serve(_engine(cfg, params), [prompt], 8)
    ref = reference.chosen_logprobs(cfg, params, prompt, toks)
    for quant in ("bf16", "int8", "fp8"):
        ctl = reference.chosen_logprobs(cfg, params, prompt, toks, quant)
        assert _rms(ctl, ref) > 3 * cfg["correct"]["limit"], quant
    # the cached K, V and gated inputs alone in int8 move this toy less
    ctl = reference.chosen_logprobs(cfg, params, prompt, toks, "kv_int8")
    assert _rms(ctl, ref) > cfg["correct"]["limit"]


@pytest.fixture()
def fresh_mixers():
    """``_conv_mixer`` is a program of its own and keeps what it traced: a
    test that patches what it calls clears the caches around itself."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("fault", ["tail not carried", "slot not zeroed"])
def test_a_wrong_tail_fails_the_tiny_limits(bench, fresh_mixers, monkeypatch,
                                            fault):
    """What the check's several-chunk prompt is for: a program that starts
    every chunk from a zero tail, or one that goes on from what a slot's
    last row left, is far from the reference."""
    cfg, reference, params = bench
    real = llama._conv_attention

    def broken(g, blk, x, state, layer, addr, use_pallas):
        pos = addr.positions
        if fault == "tail not carried":
            if x.shape[1] > 1:      # every chunk of a prompt looks first
                pos = pos - pos[..., :1] if addr.row_ids is None else \
                    jnp.where(addr.token_mask, 0, pos)
        else:
            pos = jnp.where(pos == 0, 1 << 20, pos)     # never looks first
        return real(g, blk, x, state, layer, addr._replace(positions=pos),
                    use_pallas)

    monkeypatch.setattr(llama, "_conv_attention", broken)
    eng = _engine(cfg, params, max_batch=1)
    first, second = _prompts(cfg, (80, 72), seed=5)
    (toks, lps), = _serve(eng, [first], 8)
    if fault == "slot not zeroed":          # the second row inherits a tail
        (toks, lps), = _serve(eng, [second], 8)
        first = second
    ref = reference.chosen_logprobs(cfg, params, first, toks)
    assert _rms(lps, ref) > 10 * cfg["correct"]["limit"]


def test_a_slot_reused_after_finish_starts_from_zeros(bench):
    cfg, reference, params = bench
    a, b = _prompts(cfg, (70, 50), seed=2)
    alone = _serve(_engine(cfg, params, max_batch=1), [b], 6)[0]
    eng = _engine(cfg, params, max_batch=1)
    _serve(eng, [a], 6)                     # leaves its tails in slot 0
    assert eng.state.held == 0
    assert float(jnp.abs(eng.state.arrays["tail"][:, 0]).max()) > 0
    again = _serve(eng, [b], 6)[0]          # the same slot
    assert again[0] == alone[0] and _rms(again[1], alone[1]) < 1e-5
    assert eng.metrics["state_resets"] == 2


def test_state_counters_count_slots_rows_and_the_tails_bytes(bench):
    cfg, _, params = bench
    eng = _engine(cfg, params)
    _serve(eng, _prompts(cfg, (40, 20)), 5)
    m = eng.metrics
    assert m["state_resets"] == 2
    assert 0 < m["state_slots_live"] <= m["state_slots_held"]
    row = 2 * 8 * 2 * 128 * 4       # read and written: 8 layers x 2 x d, f32
    assert eng.state.row_bytes == row and m["state_bytes_moved"] % row == 0
    assert m["state_bytes_moved"] // row >= m["decode_tokens"]
    assert m["moe_expert_slots"] % (8 * 8) == 0
    assert m["moe_experts_visited"] <= m["moe_expert_slots"]
    assert m["prefix_skipped"] == 2 and m["radix_hit_tokens"] == 0


# ---- what is not built for a recurrent model is refused, with a message ------


KINDS = pytest.mark.parametrize("model,kinds", [
    ("tiny-kimi-linear", "kda"), ("tiny-lfm2", "conv"),
    ("tiny-solar-open2", "kda")])
TINY_KW = dict(page_size=8, num_pages=32, max_seq_len=64, max_batch=2,
               prefill_chunk=16)


@KINDS
@pytest.mark.parametrize("kw,match", [
    (dict(speculative="ngram"), "speculative decoding: a rejected draft"),
    (dict(kv_dtype="int8"), "the state pool has no quantised form"),
    (dict(mode="prefill"), "PD bundle carries pages, not the recurrent state"),
    (dict(mode="decode"), "PD bundle carries pages, not the recurrent state"),
    (dict(host_tier_bytes=1 << 20), "host tier keeps prefixes"),
    (dict(mesh=True), "a device mesh: the state pool has no sharding"),
])
def test_engine_refuses_what_a_recurrent_model_does_not_support(
        model, kinds, kw, match):
    kw, mesh = dict(kw), None
    if kw.pop("mesh", False):
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                    ("dp", "tp"))
    with pytest.raises(ValueError, match=match) as e:
        Engine(EngineConfig(model=model, **TINY_KW, **kw), mesh=mesh)
    assert f"has recurrent layers ({kinds})" in str(e.value)


@KINDS
def test_other_paths_refuse_a_recurrent_model(model, kinds):
    cfg = get_config(model)
    params = init_params(cfg, jax.random.key(0))
    eng = Engine(EngineConfig(model=model, **TINY_KW), params=params)
    with pytest.raises(ValueError, match="groups of layers"):
        eng.load_lora("a", {"wo": (np.zeros((8, 128, 4), np.float32),
                                   np.zeros((8, 4, 128), np.float32))})
    with pytest.raises(ValueError, match="state at its end"):
        eng.add_request_with_prefix(list(range(1, 20)), None, 8, None, None)
    tokens = jnp.ones((1, 4), jnp.int32)
    named = rf"has recurrent layers \({kinds}\)"
    with pytest.raises(NotImplementedError,
                       match=named + ": the contiguous cache"):
        llama.forward(params, cfg, tokens, llama.KVCache(
            k=jnp.zeros((8, 1, 8, 1, 64)), v=jnp.zeros((8, 1, 8, 1, 16)),
            length=jnp.zeros((1,), jnp.int32)))
    with pytest.raises(NotImplementedError,
                       match=named + ": the cache-free forward"):
        llama.forward_train(params, cfg, tokens)
    with pytest.raises(NotImplementedError, match="walked whole"):
        llama.paged_layers(params, cfg, None, (), None, layers=(0, 4))
    from rbg_tpu.parallel import pipeline
    with pytest.raises(NotImplementedError, match="groups"):
        pipeline.pipeline_forward_train(params, cfg, tokens, mesh=None)
