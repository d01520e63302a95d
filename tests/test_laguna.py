"""The Laguna-shaped model (``tiny-laguna``): two periods F W W W (F: full
attention of 6 heads that rotates half of each head's channels under YaRN;
W: 8 heads within a window of 8 tokens, plain RoPE over the whole head, its
pages a second class of the cache), 2 KV heads in both, a gate a head, a
dense first layer, bias-selected sigmoid experts beside a shared one.

Each new rule is held to its definition here; the served path against the
benchmark's plain reference, each rule left out of it, preemption and the
refusals are the contract every model is a case of (``model_contract.py``,
``test_laguna_contract.py``, ``test_laguna_state_contract.py``)."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rbg_tpu.engine import Engine, EngineConfig
from rbg_tpu.engine.kvcache import paged_layer_count
from rbg_tpu.models import get_config, init_params
from rbg_tpu.models import llama
from rbg_tpu.models.llama import _hybrid_plan
from rbg_tpu.ops import rope
from rbg_tpu.ops.paged_attention import paged_attention
from rbg_tpu.ops.ragged_paged_attention import ragged_paged_attention

from model_contract import Case, engine, load, prompts, read, rms, serve

CFG = get_config("tiny-laguna")
PARAMS = init_params(CFG, jax.random.key(0))
CASE = Case(tiny="tiny-laguna", controls=())

FULL_DENSE, WIN_MOE, FULL_MOE = (("mixers", "dense_mlps"),
                                 ("window_mixers", "moe_mlps"),
                                 ("mixers", "moe_mlps"))


# ---- the layers come in kinds, and a kind has its own heads and positions ----


def test_a_window_kind_beside_the_full_one_and_what_differs_by_kind():
    assert CFG.mixer_kinds == ("full", "window", "window", "window") * 2
    assert CFG.by_kind and not CFG.recurrent and CFG.recurrent_kinds == ()
    assert "window layers (sliding_window 8)" in CFG.unbuilt_for
    assert (paged_layer_count(CFG), paged_layer_count(CFG, "window")) == (2, 6)
    assert [(k, n, g.half) for k, g, n in CFG.param_groups] == [
        ("mixers", 2, "mixer"), ("dense_mlps", 1, "mlp"),
        ("window_mixers", 6, "mixer"), ("moe_mlps", 7, "mlp")]
    kinds = {k: g for k, g, _ in CFG.param_groups}
    full, window = kinds["mixers"], kinds["window_mixers"]
    assert (full.num_heads, full.sliding_window, full.rope_scaling,
            full.partial_rotary_factor, full.rope_theta) == (
                6, 0, "yarn", 0.5, 50000.0)
    assert (window.num_heads, window.sliding_window, window.rope_scaling,
            window.partial_rotary_factor, window.rope_theta) == (
                8, 8, "", 1.0, 10000.0)
    assert {k: v.shape for k, v in PARAMS["mixers"].items()} == {
        "attn_norm": (2, 128), "wq": (2, 192, 128), "wk": (2, 64, 128),
        "wv": (2, 64, 128), "wo": (2, 192, 128), "wg": (2, 128, 6)}
    # q, k, v held [out, in] (ROADMAP S23) in BOTH kinds
    assert CFG.proj_out_in and full.proj_out_in and window.proj_out_in
    assert PARAMS["window_mixers"]["wq"].shape == (6, 256, 128)
    assert PARAMS["window_mixers"]["wg"].shape == (6, 128, 8)
    # the plan: the dense first layer its full mixer leads; with two
    # periods the one full expert layer is a run between two stretches of
    # window layers, with the cell's five the two kinds take turns
    assert _hybrid_plan(CFG) == [
        ("run", FULL_DENSE, 0, 1), ("turns", WIN_MOE, None, [[3, 0, 1]]),
        ("run", FULL_MOE, 4, 5), ("turns", WIN_MOE, None, [[3, 0, 5]])]
    cell = dataclasses.replace(CFG, num_layers=20,
                               layer_types=CFG.layer_types[:4] * 5)
    assert _hybrid_plan(cell) == [
        ("run", FULL_DENSE, 0, 1),
        ("turns", WIN_MOE, FULL_MOE, [[3, 1, 1], [3, 1, 5], [3, 1, 9],
                                      [3, 1, 13], [3, 0, 17]])]


def test_num_params_counts_heads_by_kind_and_the_gate_by_its_form():
    n = sum(a.size for a in jax.tree_util.tree_leaves(PARAMS))
    assert CFG.num_params == n
    ungated = dataclasses.replace(CFG, attn_gate=False)
    assert CFG.num_params - ungated.num_params == 128 * (2 * 6 + 6 * 8)
    by_channel = dataclasses.replace(CFG, attn_gate="channel")
    assert by_channel.num_params - ungated.num_params == \
        128 * 32 * (2 * 6 + 6 * 8)
    assert dataclasses.replace(CFG, attn_gate=True).num_params == \
        by_channel.num_params
    with pytest.raises(ValueError, match="attn_gate is one of"):
        dataclasses.replace(CFG, attn_gate="row")
    # the published sizes, from the benchmark's file: the row's 33.4B-A3B
    from harness import serve as harness
    cell = read("configs", "laguna-xs2.json")
    held = harness.model_config(cell, "laguna-cell")
    assert held.num_params == 2_799_622_912
    whole = dataclasses.replace(
        held, num_layers=40, vocab_size=100352, experts_held=None,
        layer_types=cell["layer_types"])
    assert round(whole.num_params / 1e9, 2) == 33.44


def test_a_window_needs_its_layers_and_a_window_layer_its_window():
    with pytest.raises(ValueError, match="sliding_window 0 and layer_types"):
        dataclasses.replace(CFG, sliding_window=0)
    with pytest.raises(ValueError, match="sliding_window 8 and layer_types"):
        dataclasses.replace(get_config("tiny"), sliding_window=8)
    with pytest.raises(ValueError, match="window_layer names"):
        dataclasses.replace(CFG, window_layer={"heads": 8})
    with pytest.raises(ValueError, match="only 'yarn' is built"):
        dataclasses.replace(CFG, rope_scaling="llama3")


def test_a_preset_names_one_window_and_does_not_choose_the_projections_layout():
    # the model's mask and the engine's pages read one window: the fault
    # of the contract that sets them apart is built behind the constructor
    kind = dict(CFG.window_layer)
    with pytest.raises(ValueError, match="is not sliding_window 8"):
        dataclasses.replace(CFG, window_layer={**kind, "sliding_window": 4})
    assert dataclasses.replace(
        CFG, window_layer={**kind, "sliding_window": 8}).layer_groups[1][
            1].sliding_window == 8
    # ``proj_out_in`` is read off the window layers, never given: not to
    # the constructor, not to ``replace``, not through ``window_layer``
    with pytest.raises(TypeError, match="proj_out_in"):
        type(CFG)(name="x", vocab_size=8, hidden_size=8, proj_out_in=True)
    with pytest.raises((TypeError, ValueError), match="proj_out_in"):
        dataclasses.replace(get_config("tiny"), proj_out_in=True)
    for preset in ("tiny", "tiny-solar-open2"):
        m = get_config(preset)
        assert not m.proj_out_in
        assert not any(g.proj_out_in for _, g, _ in m.param_groups)


# ---- rotary settings by kind -------------------------------------------------


def _closed_form(r, theta, factor, original, fast, slow):
    """ISSUE 46's equations, term by term."""
    f = [theta ** (-2 * i / r) for i in range(r // 2)]
    c = lambda n: r * math.log(original / (2 * math.pi * n)) / (
        2 * math.log(theta))
    lo, hi = max(math.floor(c(fast)), 0), min(math.ceil(c(slow)), r - 1)
    ramp = [min(max((i - lo) / (hi - lo), 0.0), 1.0) for i in range(r // 2)]
    return lo, hi, [(fi / factor) * a + fi * (1 - a) for fi, a in zip(f, ramp)]


def test_yarn_frequencies_and_factor_equal_the_closed_form():
    # the published settings of a full layer
    lo, hi, want = _closed_form(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    assert (lo, hi) == (5, 16)
    got = rope.yarn_frequencies(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[5] == np.float32(want[5]) and got[16] * 64 == pytest.approx(
        500000.0 ** (-32 / 64), rel=1e-6)      # kept below lo, /64 from hi
    cell = get_config("tiny-laguna", head_dim=128, rope_theta=500000.0,
                      rope_factor=64.0, rope_original_max=4096,
                      rope_beta_fast=64.0, rope_attention_factor=0.0)
    tables = rope.rotary_tables(cell)
    assert tables.rotary_dim == 64
    assert tables.scale == pytest.approx(1.4158883083359672, rel=1e-12)
    # the tiny preset's, and its window kind's plain ones
    full, window = (g for k, g, _ in CFG.param_groups
                    if k in ("mixers", "window_mixers"))
    t = rope.rotary_tables(full)
    assert t.rotary_dim == 16 and t.scale == pytest.approx(0.1 * math.log(8)
                                                           + 1)
    np.testing.assert_allclose(
        t.inv_freq, _closed_form(16, 50000.0, 8.0, 32, 4.0, 1.0)[2], rtol=1e-6)
    assert rope.rotary_tables(window) == rope.Rotary(32, None, 1.0)


def test_only_the_prefix_is_rotated_and_plain_rope_is_the_case_without():
    x = jax.random.normal(jax.random.key(1), (2, 5, 3, 32))
    pos = jnp.asarray([[0, 1, 2, 3, 40], [7, 8, 9, 10, 11]])
    plain = rope.apply_rope(x, pos, 10000.0)
    np.testing.assert_array_equal(
        plain, rope.apply_rope(x, pos, 10000.0,
                               rotary=rope.Rotary(32, None, 1.0)))
    half = rope.apply_rope(x, pos, 10000.0, rotary=rope.Rotary(16, None, 1.0))
    np.testing.assert_array_equal(half[..., 16:], x[..., 16:])
    np.testing.assert_allclose(
        half[..., :16], rope.apply_rope(x[..., :16], pos, 10000.0), rtol=1e-6)
    # frequencies and factor: each pair (i, i + r/2) turns by pos x inv_freq
    inv = np.asarray([0.5, 0.25, 0.125, 0.0625], np.float32)
    got = rope.apply_rope(x, pos, 10000.0, rotary=rope.Rotary(8, inv, 1.5))
    ang = np.asarray(pos, np.float32)[..., None, None] * inv
    a, b = np.asarray(x[..., :4]), np.asarray(x[..., 4:8])
    np.testing.assert_allclose(got[..., :4], 1.5 * (a * np.cos(ang)
                                                    - b * np.sin(ang)),
                               rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(got[..., 4:8], 1.5 * (b * np.cos(ang)
                                                     + a * np.sin(ang)),
                               rtol=2e-5, atol=1e-6)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    # position 0 is the identity, times the factor on the rotated prefix
    at0 = rope.apply_rope(x, jnp.zeros((2, 5), jnp.int32), 10000.0,
                          rotary=rope.Rotary(8, inv, 1.5))
    np.testing.assert_allclose(at0[..., :8], 1.5 * x[..., :8], rtol=1e-6)


def test_the_gate_is_one_scalar_a_head():
    g = next(g for k, g, _ in CFG.param_groups if k == "window_mixers")
    blk = {k: v[0] for k, v in PARAMS["window_mixers"].items()}
    x = jax.random.normal(jax.random.key(2), (2, 3, 128))
    attn = jax.random.normal(jax.random.key(3), (2, 3, 8, 32))
    got = llama._attn_gate(g, blk, x, attn)
    xa = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    want = attn * jax.nn.sigmoid(xa @ blk["wg"])[..., None]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(got - attn).max()) > 0.1


# ---- the walks keep to the window --------------------------------------------


def _pool(rng, NP, page, KV, hd):
    return tuple(jnp.asarray(rng.normal(size=(NP, page, KV, hd)), jnp.float32)
                 for _ in range(2))


def _line(table, row, free, lo, hi):
    for col in range(lo, hi):
        table[row, col] = free.pop()


@pytest.mark.parametrize("heads,window", [(12, 8), (12, 150), (16, 40)],
                         ids=["groups-of-6", "groups-of-6-wide",
                              "groups-of-8"])
def test_the_kernels_walk_only_the_window_and_equal_the_dense_mask(
        interpreted, heads, window):
    """Rows whose pages below the window are GONE (their entries 0): the
    decode walk and the ragged walk, interpreted, start at the first live
    block and agree with the XLA form, which agrees with a plain mask over
    a line that still holds every page."""
    rng = np.random.default_rng(0)
    page, KV, hd, NP, P = 4, 2, 32, 320, 100
    kp, vp = _pool(rng, NP, page, KV, hd)
    lens = np.array([1, 7, 8, 9, 24, 129, 300, 0, 261], np.int32)
    B = len(lens)
    table, whole = (np.zeros((B, P), np.int32) for _ in range(2))
    free = list(range(1, NP))
    for b, n in enumerate(lens):
        _line(whole, b, free, 0, -(-n // page))
    table[:] = whole
    for b, n in enumerate(lens):
        table[b, :max(n - window, 0) // page] = 0       # given back
    q = jnp.asarray(rng.normal(size=(B, 1, heads, hd)), jnp.float32)
    pos = jnp.asarray(np.maximum(lens - 1, 0))[:, None]
    live = lens > 0

    def decode(tbl, how):
        return np.asarray(paged_attention(
            q, kp, vp, jnp.asarray(tbl), pos, jnp.asarray(lens),
            use_pallas=how, window=window))[live]

    np.testing.assert_allclose(decode(table, "always"),
                               decode(table, "never"), atol=2e-6)
    np.testing.assert_allclose(decode(table, "never"),
                               decode(whole, "never"), atol=2e-6)
    # and a plain softmax over the last ``window`` keys of row 6
    k6 = np.asarray(kp)[whole[6, :75]].reshape(300, KV, hd)[300 - window:]
    v6 = np.asarray(vp)[whole[6, :75]].reshape(300, KV, hd)[300 - window:]
    q6 = np.asarray(q)[6, 0].reshape(KV, heads // KV, hd)
    s = np.einsum("kgd,skd->kgs", q6, k6) / math.sqrt(hd)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("kgs,skd->kgd", p / p.sum(-1, keepdims=True), v6)
    np.testing.assert_allclose(decode(table, "always")[6].reshape(want.shape),
                               want, atol=2e-6)

    # packed: chunks that cross a window's edge beside decode rows
    rows = [(0, 300, 13), (1, 129, 1), (2, 24, 24), (3, 9, 3), (4, 261, 16),
            (5, 40, 1)]
    lens2 = np.zeros(8, np.int32)
    table2, whole2 = (np.zeros((8, P), np.int32) for _ in range(2))
    free, rid, qp = list(range(1, NP)), [], []
    for r, n, c in rows:
        lens2[r] = n
        _line(whole2, r, free, 0, -(-n // page))
        rid += [r] * c
        qp += list(range(n - c, n))
    table2[:] = whole2
    for r, n, c in rows:
        table2[r, :max(n - c - window + 1, 0) // page] = 0
    T, Tb = len(rid), 64
    rid += [0] * (Tb - T)
    qp += [-1] * (Tb - T)
    q = jnp.asarray(rng.normal(size=(1, Tb, heads, hd)), jnp.float32)

    def packed(tbl, how):
        return np.asarray(ragged_paged_attention(
            q, kp, vp, jnp.asarray(tbl), jnp.asarray(qp, jnp.int32)[None],
            jnp.asarray(lens2), jnp.asarray(rid, jnp.int32), use_pallas=how,
            window=window, max_q_len=32))[0, :T]

    np.testing.assert_allclose(packed(table2, "always"),
                               packed(table2, "never"), atol=2e-6)
    np.testing.assert_allclose(packed(table2, "never"),
                               packed(whole2, "never"), atol=2e-6)


def test_a_walk_without_a_window_is_the_walk_it_was():
    """``window=None`` takes the code it took: the same jaxpr as a call that
    does not name it, and a model without window layers holds one class of
    page and hands its step programs nothing more."""
    from rbg_tpu.ops.pallas import paged_attention_kernel as K
    S = jax.ShapeDtypeStruct
    args = (S((4, 2, 2, 32), jnp.float32), S((9, 4, 2, 32), jnp.float32),
            S((9, 4, 2, 32), jnp.float32), S((4, 6), jnp.int32),
            S((4,), jnp.int32))
    text = lambda **kw: str(jax.make_jaxpr(lambda *a: K._decode(
        a[0], a[1:3], a[3], a[4], True, **kw))(*args))
    assert text() == text(window=None) != text(window=8)
    for model in ("tiny", "tiny-solar-open2"):
        eng = Engine(EngineConfig(model=model, page_size=8, num_pages=32,
                                  max_seq_len=64, max_batch=2,
                                  prefill_chunk=16))
        assert eng.window_allocator is None and eng.cache.window_k is None
        assert "window" not in eng._state_kw([], 2)
        assert "window" not in eng._donate_state


@pytest.mark.parametrize("chunk", [5, 8, 24])
def test_a_prompt_in_chunks_across_a_windows_edge_equals_it_whole(chunk):
    """Chunks that end inside a page, on the window's width and past two of
    them: a chunk's oldest query still finds its oldest key."""
    bench = load(CASE)
    prompt, = prompts(bench.cfg, (61,), seed=13)
    whole, = serve(engine(bench, prefill_chunk=64), [prompt], 5)
    got, = serve(engine(bench, prefill_chunk=chunk), [prompt], 5)
    assert got[0] == whole[0] and rms(got[1], whole[1]) < 1e-5


def test_a_checkpoint_of_the_dense_family_does_not_load_into_window_layers(
        tmp_path):
    from rbg_tpu.models.checkpoint import load_hf_llama
    dense = dataclasses.replace(
        CFG, num_experts=0, experts_held=None, first_dense_layers=0,
        moe_shared_expert=False, attn_gate=False)
    with pytest.raises(NotImplementedError, match="window layers"):
        load_hf_llama(str(tmp_path), dense)
