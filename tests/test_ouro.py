"""The Ouro-shaped model (``tiny-ouro``): 2 layers run 3 times a token over
the same weights, a cache entry of its own for every (pass, layer) (6 over 2
layers of weights), plain multi-head attention (4 x 4 heads: a group of ONE
query head a key/value head), a norm after the mixer and one after the MLP,
the final norm closing every pass, the exit gate held and not computed.

The mechanism is held to its definition here: what each entry of the pool
holds, logits through chunks and pools against the plain reference's whole
pass, what a shared prefix reads, what is kept (the host tier) and what is
refused. The served contract every model is a case of (the reference in five
forms, chunked prompts, the controls, an int8 cache, each rule left out,
prefix reuse, the engine's refusals) is ``test_ouro_contract.py``'s."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rbg_tpu.engine import Engine, EngineConfig, SamplingParams
from rbg_tpu.engine.kvcache import PagedKVCache, paged_layer_count
from rbg_tpu.models import get_config, init_params
from rbg_tpu.models import llama

from model_contract import (Case, TINY_KW, engine, error, load, prompts, rms,
                            serve)

CFG = get_config("tiny-ouro")
CASE = Case(tiny="tiny-ouro", controls=())
T, L = CFG.loop_steps, CFG.num_layers


# ---- the configuration ---------------------------------------------------------


def test_a_looped_stack_is_one_kind_of_layer_with_a_cache_entry_a_pass():
    assert (T, L, CFG.cache_layers) == (3, 2, 6)
    assert CFG.post_norms and CFG.exit_gate and CFG.proj_out_in
    assert not CFG.by_kind and not CFG.unbuilt_for       # prefix reuse stays
    assert "runs its layers 3 times" in CFG.looped_for
    assert [(k, n) for k, _, n in CFG.param_groups] == [("blocks", L)]
    assert paged_layer_count(CFG) == T * L
    assert PagedKVCache.hbm_bytes(CFG, 10) == T * PagedKVCache.hbm_bytes(
        dataclasses.replace(CFG, loop_steps=1), 10)
    made = jax.eval_shape(lambda: init_params(CFG, jax.random.key(0)))
    assert made["exit_gate"]["w"].shape == (CFG.hidden_size,)
    assert {"attn_norm", "attn_post_norm", "mlp_norm",
            "mlp_post_norm"} <= set(made["blocks"])
    # the four norms a layer and the gate's d + 1 are counted
    once = dataclasses.replace(CFG, post_norms=False, exit_gate=False)
    assert CFG.num_params - once.num_params == (
        2 * L * CFG.hidden_size + CFG.hidden_size + 1)
    # a model that runs its layers once says nothing new
    assert get_config("tiny").looped_for == "" and \
        get_config("tiny").cache_layers == get_config("tiny").num_layers


@pytest.mark.parametrize("fields, named", [
    (dict(early_exit_threshold=0.5), "early_exit_threshold 0.5"),
    (dict(loop_steps=0), "loop_steps 0"),
    (dict(mla=True), "looped stack is built for"),
    (dict(num_experts=4), "looped stack is built for"),
    (dict(num_layers=4, kda_layers=(1, 2), kda_num_heads=4),
     "looped stack is built for")])
def test_the_preset_refuses_what_a_looped_stack_is_not_built_for(fields,
                                                                 named):
    with pytest.raises(ValueError, match=named):
        dataclasses.replace(CFG, **fields)


# ---- what each entry of the pool holds -----------------------------------------


def _independent(cfg, params, tokens):
    """The equations of the model in a few lines of their own (float64
    numpy, no code of the program or of the benchmark): log-probabilities
    ``[len(tokens), vocab]`` and every pass's rotated keys ``{(t, l): [n,
    heads, hd]}``."""
    z = {"/".join(str(p.key) for p in path): np.asarray(leaf, np.float64)
         for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}
    eps, hd, h = cfg.rms_norm_eps, cfg.head_dim_, cfg.num_heads
    n = len(tokens)

    def norm(x, w):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * w

    def rope(x):
        inv = 1.0 / cfg.rope_theta ** (np.arange(0, hd, 2) / hd)
        ang = np.arange(n)[:, None] * inv[None]
        cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
        a, b = x[..., :hd // 2], x[..., hd // 2:]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    x, keys = z["embed"][np.asarray(tokens)], {}
    mask = np.tril(np.ones((n, n), bool))
    for t in range(cfg.loop_steps):
        for l in range(cfg.num_layers):
            w = lambda name: z["blocks/" + name][l]
            a = norm(x, w("attn_norm"))
            q = rope((a @ w("wq").T).reshape(n, h, hd))
            k = rope((a @ w("wk").T).reshape(n, h, hd))
            v = (a @ w("wv").T).reshape(n, h, hd)
            keys[t, l] = k
            s = np.einsum("thd,shd->hts", q, k) / np.sqrt(hd)
            s = np.where(mask[None], s, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            o = np.einsum("hts,shd->thd", p, v).reshape(n, h * hd)
            x = x + norm(o @ w("wo"), w("attn_post_norm"))
            m = norm(x, w("mlp_norm"))
            g = m @ w("w_gate")
            y = (g / (1 + np.exp(-g)) * (m @ w("w_up"))) @ w("w_down")
            x = x + norm(y, w("mlp_post_norm"))
        x = norm(x, z["final_norm"])
    logits = x @ z["lm_head"]
    logits -= logits.max(-1, keepdims=True)
    return logits - np.log(np.exp(logits).sum(-1, keepdims=True)), keys


def _paged(cfg, params, pool, tokens, start, table):
    """``forward_paged`` of ``tokens`` at positions ``start ..`` of one row
    whose table is ``table``: (logits ``[n, V]``, pool)."""
    n = len(tokens)
    pos = jnp.arange(start, start + n, dtype=jnp.int32)[None]
    logits, k, v, *_ = llama.forward_paged(
        params, cfg, jnp.asarray([tokens], jnp.int32), pos,
        jnp.ones((1, n), bool), jnp.asarray([start + n], jnp.int32),
        jnp.asarray([table], jnp.int32), *pool, use_pallas="never")
    return logits[0], (k, v)


def test_prefill_in_chunks_then_decode_through_the_pools_gives_the_references_logits():
    """Logits against logits: three chunks of a prompt and two decode steps
    through ``forward_paged`` over a pool of ``T x L`` entries agree with
    the benchmark's plain reference AND with a few-line loop of this
    file's own, over one whole sequence with no cache."""
    bench = load(CASE)
    cfg, params = bench.preset, bench.params
    toks = np.random.default_rng(5).integers(1, cfg.vocab_size, 42).tolist()
    cache = PagedKVCache.create(cfg, 8, page_size=8)
    assert cache.k_pages.shape[0] == T * L
    pool, table, got, at = (cache.k_pages, cache.v_pages), [3, 1, 6, 2, 5, 7], \
        [], 0
    for n in (16, 16, 8, 1, 1):     # chunks, then a token a step
        logits, pool = _paged(cfg, params, pool, toks[at:at + n], at, table)
        got.append(logits)
        at += n
    got = jax.nn.log_softmax(jnp.concatenate(got), -1)
    want, _ = _independent(cfg, params, toks)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    ref = bench.reference
    with jax.default_matmul_precision("highest"):
        plain = ref._forward(params, jnp.asarray(toks, jnp.int32),
                             jnp.int32(0), tuple(sorted(ref.sizes(
                                 bench.cfg).items())), len(toks), None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain), atol=2e-5)


def test_pass_t_of_layer_l_writes_entry_t_L_plus_l_and_no_other():
    """One chunk through an empty pool: entry ``t L + l`` of the row's
    pages holds the keys pass ``t`` of layer ``l`` made (each pass's differ:
    the hidden state that makes them does), every other page of every
    entry is untouched; and a pass reads its own entry: with the entries of
    pass 1 wiped between a prompt's chunks the next chunk's logits move,
    with those of a page the row does not hold they do not."""
    bench = load(CASE)
    cfg, params = bench.preset, bench.params
    toks = np.random.default_rng(6).integers(1, cfg.vocab_size, 24).tolist()
    cache = PagedKVCache.create(cfg, 6, page_size=8)
    empty = (cache.k_pages, cache.v_pages)
    table = [4, 2, 5]
    _, (k, _) = _paged(cfg, params, empty, toks[:16], 0, table)
    _, keys = _independent(cfg, params, toks[:16])
    k = np.asarray(k)                   # [T L, NP, page, 1, heads x hd]
    for t in range(T):
        for l in range(L):
            held = k[t * L + l][table[:2]].reshape(16, cfg.num_heads, -1)
            np.testing.assert_allclose(held, keys[t, l], atol=1e-5)
    assert rms(keys[0, 0], keys[1, 0]) > 0.1      # a pass's keys are its own
    assert not k[:, [0, 1, 3, 5]].any()           # no other page, of any entry

    def next_chunk(pool):
        return _paged(cfg, params, pool, toks[16:], 16, table)[0]

    _, pool = _paged(cfg, params, empty, toks[:16], 0, table)
    sound = next_chunk(pool)
    wipe = lambda pages: tuple(p.at[L:2 * L, pages].set(0.0) for p in pool)
    assert rms(next_chunk(wipe([4, 2])), sound) > 1e-2
    assert rms(next_chunk(wipe([0, 1, 3])), sound) == 0.0


def test_a_shared_prefix_reads_every_entry_of_the_shared_pages():
    """Prefix reuse is on for this model: a prompt that shares a served
    prompt's first pages takes them, all ``T x L`` entries behind each page
    id. Whichever ONE entry of the shared pages is spoiled in the pool, the
    sharing prompt's logits move; untouched, they are a cold prefill's."""
    bench = load(CASE)
    first, = prompts(bench.cfg, (64,), seed=8)
    tails = prompts(bench.cfg, (12,) * (T * L + 1), seed=9)
    eng = engine(bench)
    serve(eng, [first], 2)
    shared = eng.radix.match(first[:-1])[1]
    assert len(shared) >= 3
    spoil = lambda entry, by: dataclasses.replace(
        eng.cache, k_pages=eng.cache.k_pages.at[
            entry, jnp.asarray(shared)].add(by))
    for entry, tail in enumerate(tails):    # the last: nothing spoiled
        if entry < T * L:
            eng.cache = spoil(entry, 1.0)
        hits = eng.metrics["radix_hit_tokens"]
        got, = serve(eng, [first + tail], 3)
        assert eng.metrics["radix_hit_tokens"] - hits >= 48
        far = error(bench, first + tail, got)
        if entry < T * L:
            assert far > 20 * bench.cfg["correct"]["limit"], entry
            eng.cache = spoil(entry, -1.0)
        else:
            assert far <= bench.cfg["correct"]["limit"]


# ---- what is kept, and what is refused -------------------------------------------


def test_the_host_tier_spills_and_promotes_pages_of_every_entry():
    """Kept: the host tier copies a page id's slice of the pool's own
    leading axis, so an evicted prefix comes back with all ``T x L``
    entries and decodes as a cold prefill does."""
    bench = load(CASE)
    asked = prompts(bench.cfg, (40,) * 5, seed=7)
    sp = SamplingParams(max_new_tokens=6)
    kw = dict(page_size=8, max_batch=2, max_seq_len=256, prefill_chunk=16)
    expect = [engine(bench, num_pages=256, enable_radix_cache=False,
                     **kw).generate([p], sp)[0] for p in asked]
    eng = engine(bench, num_pages=24, host_tier_bytes=1 << 26, **kw)
    assert [eng.generate([p], sp)[0] for p in asked] == expect
    assert eng.host_tier.stats()["spilled_pages"] > 0
    assert [eng.generate([p], sp)[0] for p in asked] == expect
    assert eng.host_tier.stats()["promoted_pages"] > 0
    assert eng.metrics["host_hit_tokens"] > 0
    assert eng.host_tier.accounting_closes()


def test_the_cells_arithmetic_is_the_programs_own_shapes():
    """What a step of ``ouro.longgen4`` reads and what a token keeps are
    constants of its configuration (no counter carries them: while an exit
    from the loop is refused nothing in the program can move them), stated
    in the file's ``arithmetic`` and recounted here from the shapes the
    program makes for the file's preset."""
    from model_contract import read     # (puts ``harness`` on the path)
    from harness import serve
    cfg = read("configs", "ouro-2.6b.json")
    m = serve.model_config(cfg, "ouro-arithmetic")
    made = jax.eval_shape(lambda: init_params(m, jax.random.key(0)))
    held = lambda tree: sum(a.size * a.dtype.itemsize
                            for a in jax.tree_util.tree_leaves(tree))
    assert sum(a.size for a in jax.tree_util.tree_leaves(made)) == \
        m.num_params == 2_667_974_657
    # a decode step: the 48 layers' stack once a pass, the head's once
    step = m.loop_steps * held(made["blocks"]) + held(made["lm_head"])
    assert (m.loop_steps, step) == (4, 19_934_478_336)
    pages, size = cfg["server"]["num_pages"], cfg["server"]["page_size"]
    assert m.cache_layers == 192
    assert PagedKVCache.hbm_bytes(m, pages) == pages * size * 1_572_864
    for said in ("19.93 GB", "1,572,864 B a token", "2,667,974,657"):
        assert said in cfg["arithmetic"], said


def test_other_walks_refuse_a_looped_model_by_what_it_has():
    params = init_params(CFG, jax.random.key(0))
    eng = Engine(EngineConfig(model="tiny-ouro", **TINY_KW), params=params)
    D = CFG.hidden_size
    named = "runs its layers 3 times a token"
    with pytest.raises(ValueError, match=named + ".*LoRA"):
        eng.load_lora("a", {"wo": (np.zeros((L, D, 4), np.float32),
                                   np.zeros((L, 4, D), np.float32))})
    with pytest.raises(NotImplementedError, match=named + ".*walked whole"):
        llama.paged_layers(params, CFG, None, (jnp.zeros((T * L, 4, 8, 1,
                                                          128)),), None,
                           layers=(0, 1))
    from rbg_tpu.parallel import pipeline
    with pytest.raises(NotImplementedError, match=named + ".*pipeline"):
        pipeline.pipeline_forward_train(params, CFG, jnp.ones((1, 4),
                                                              jnp.int32),
                                        mesh=None)
    from rbg_tpu.models.checkpoint import load_hf_llama
    with pytest.raises(NotImplementedError, match="model_type ouro"):
        load_hf_llama("/nonexistent", CFG)
