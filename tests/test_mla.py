"""Multi-head latent attention (DeepSeek-V2/V3 family).

Reference context: the reference's flagship ecosystem deployments serve
DeepSeek via SGLang (``examples/inference/ecosystem/mooncake/*``,
BASELINE.md config 5); MLA's compressed latent cache is what makes their
KV transfer economical. Implemented in the absorbed inference form
(ops/mla_attention.py) — per-head K/V never materializes.

Load-bearing invariants mirrored from the GQA tests: full-context forward
== incremental decode, paged engine == contiguous greedy, and the
absorbed form == the naive materialized-K/V form."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rbg_tpu.engine import Engine, EngineConfig, SamplingParams
from rbg_tpu.engine.kvcache import PagedKVCache, rope_pool_width
from rbg_tpu.models import get_config, init_params
from rbg_tpu.models.llama import (KVCache, forward, forward_train,
                                  prefill_and_decode_greedy)

CFG = get_config("tiny-mla")
PARAMS = init_params(CFG, jax.random.key(0))


@pytest.mark.slow
def test_prefill_decode_equivalence():
    B, T = 2, 12
    toks = jax.random.randint(jax.random.key(1), (B, T), 0, CFG.vocab_size)
    full, _ = forward(PARAMS, CFG, toks, KVCache.create(CFG, B, 32))
    cache = KVCache.create(CFG, B, 32)
    outs = []
    for t in range(T):
        lg, cache = forward(PARAMS, CFG, toks[:, t:t + 1], cache)
        outs.append(lg[:, 0])
    inc = jnp.stack(outs, axis=1)
    assert float(jnp.max(jnp.abs(full - inc))) < 2e-4


def test_absorbed_equals_naive_attention():
    """score = q_nope·(c@W_uk) + q_pe·k_pe must equal the absorbed
    q_lat·c + q_pe·k_pe — checked by materializing per-head K/V."""
    from rbg_tpu.models.llama import _mla_qkv, _mla_scale
    B, T = 1, 6
    x = jax.random.normal(jax.random.key(2), (B, T, CFG.hidden_size),
                          jnp.float32)
    pos = jnp.arange(T, dtype=jnp.int32)[None, :]
    blk = jax.tree_util.tree_map(lambda a: a[0], PARAMS["blocks"])
    q_lat, q_pe, c, k_pe = _mla_qkv(CFG, blk, x, pos)
    h, dn = CFG.num_heads, CFG.qk_nope_head_dim
    dc, dr = CFG.kv_lora_rank, CFG.qk_rope_head_dim
    # naive: materialize k_nope per head and recompute q_nope
    from rbg_tpu.ops.norms import rms_norm
    from rbg_tpu.ops.rope import apply_rope
    xa = rms_norm(x, blk["attn_norm"], CFG.rms_norm_eps)
    q = (xa @ blk["wq"]).reshape(B, T, h, dn + dr)
    q_nope = q[..., :dn]
    k_nope = jnp.einsum("btc,chn->bthn", c,
                        blk["w_uk"].reshape(dc, h, dn))
    naive = jnp.einsum("bthn,bshn->bhts", q_nope, k_nope)
    absorbed = jnp.einsum("bthc,bsc->bhts", q_lat, c)
    assert float(jnp.max(jnp.abs(naive - absorbed))) < 1e-4


@pytest.mark.slow
def test_paged_engine_matches_contiguous_greedy():
    ref = prefill_and_decode_greedy(PARAMS, CFG, jnp.asarray([[1, 2, 3, 4]]),
                                    steps=8)
    eng = Engine(EngineConfig(model="tiny-mla", page_size=8, num_pages=96,
                              max_seq_len=128, use_pallas="never",
                              enable_radix_cache=False), params=PARAMS)
    got = eng.generate([[1, 2, 3, 4]], SamplingParams(max_new_tokens=8))[0]
    assert np.asarray(ref).reshape(-1).tolist() == got


@pytest.mark.slow
def test_engine_features_compose_with_mla():
    def mk(**kw):
        return Engine(EngineConfig(model="tiny-mla", page_size=8,
                                   num_pages=96, max_seq_len=128,
                                   use_pallas="never",
                                   enable_radix_cache=False, **kw),
                      params=PARAMS)
    prompt = [1, 2, 3, 4] * 4
    sp = SamplingParams(max_new_tokens=10)
    base = mk().generate([prompt], sp)[0]
    assert mk(multi_step=4).generate([prompt], sp)[0] == base
    assert mk(speculative="ngram").generate([prompt], sp)[0] == base


def test_mla_kv_pool_is_smaller():
    mla_big = get_config("deepseek-v2-lite")
    gqa_same = get_config("llama3-8b")
    mla_per_tok = (PagedKVCache.hbm_bytes(mla_big, 100)
                   / (100 * 16 * mla_big.num_layers))
    gqa_per_tok = (PagedKVCache.hbm_bytes(gqa_same, 100)
                   / (100 * 16 * gqa_same.num_layers))
    # 640 * 2 bytes (the rotary key held 128 wide) vs 2*8*128*2 bytes per
    # token-layer → 3.2x smaller
    assert mla_per_tok * 3 < gqa_per_tok


def test_num_params_matches_init():
    real = sum(int(np.prod(v.shape))
               for v in jax.tree_util.tree_leaves(PARAMS))
    assert CFG.num_params == real


def test_deepseek_v2_lite_param_count():
    # Real model: ~15.7B (the ~3% overcount is the dense first layer the
    # homogeneous-scan architecture does not special-case).
    n = get_config("deepseek-v2-lite").num_params
    assert 15e9 < n < 16.6e9, n
    n3 = get_config("deepseek-v3").num_params
    assert 650e9 < n3 < 740e9, n3   # real: 671B (no q-LoRA modeled)


def test_training_forward_runs_with_mla():
    B, T = 2, 8
    toks = jax.random.randint(jax.random.key(3), (B, T), 0, CFG.vocab_size)
    logits = forward_train(PARAMS, CFG, toks)
    assert logits.shape == (B, T, CFG.vocab_size)
    assert bool(jnp.isfinite(logits).all())


def test_mla_moe_combined_forward():
    cfg = get_config("tiny-moe", mla=True, kv_lora_rank=64,
                     qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32)
    params = init_params(cfg, jax.random.key(4))
    toks = jnp.asarray([[1, 2, 3, 4, 5]])
    logits, _ = forward(params, cfg, toks, KVCache.create(cfg, 1, 16))
    assert logits.shape == (1, 5, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.slow
def test_mla_sharded_engine_tp2():
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:2]).reshape(1, 2)
    mesh = Mesh(devs, ("dp", "tp"))
    eng = Engine(EngineConfig(model="tiny-mla", page_size=8, num_pages=96,
                              max_seq_len=128, use_pallas="never",
                              enable_radix_cache=False),
                 params=PARAMS, mesh=mesh)
    got = eng.generate([[1, 2, 3, 4]], SamplingParams(max_new_tokens=8))[0]
    single = Engine(EngineConfig(model="tiny-mla", page_size=8, num_pages=96,
                                 max_seq_len=128, use_pallas="never",
                                 enable_radix_cache=False), params=PARAMS)
    assert got == single.generate([[1, 2, 3, 4]],
                                  SamplingParams(max_new_tokens=8))[0]


def test_mla_config_guards():
    # Both round-4 MLA guards fell in round 5 (latent decode kernel,
    # quantized latent pool) and the last one fell in round 16: the
    # latent kernel grew a dequantizing _q variant, so int8 + 'always'
    # is a working combination — no MLA-specific config guard remains.
    EngineConfig(model="tiny-mla", kv_dtype="int8").validate()
    EngineConfig(model="tiny-mla", use_pallas="always").validate()
    EngineConfig(model="tiny-mla", kv_dtype="int8",
                 use_pallas="always").validate()
    with pytest.raises(ValueError, match="unified"):
        EngineConfig(model="tiny-mla", kv_dtype="int8",
                     mode="prefill").validate()


@pytest.mark.slow
def test_pd_disagg_ships_latent_bundles():
    """PD-disagg with MLA: the KV bundle carries the compressed latent
    pages (the Mooncake-economics point of MLA) and decodes identically."""
    from rbg_tpu.engine.pd import PDPair
    base = dict(model="tiny-mla", page_size=8, num_pages=96, max_seq_len=128,
                use_pallas="never", enable_radix_cache=False)
    uni = Engine(EngineConfig(**base), params=PARAMS)
    expect = uni.generate([[1, 2, 3, 4, 5]],
                          SamplingParams(max_new_tokens=8))[0]
    pair = PDPair(EngineConfig(**base), params=PARAMS)
    got = pair.generate([[1, 2, 3, 4, 5]], SamplingParams(max_new_tokens=8))
    assert got[0] == expect


def test_mla_decode_service_warm_bundle_shapes():
    """DecodeService._warm_item must derive each bundle half from its OWN
    pool: under MLA the v pool (shared RoPE key) has a different channel
    dim than the k pool (latent) — deriving both from k_pages failed
    every MLA decode replica's {"op": "warmup"} at the inject scatter."""
    from rbg_tpu.engine.service import DecodeService
    svc = DecodeService(EngineConfig(
        model="tiny-mla", page_size=8, num_pages=64, max_batch=2,
        max_seq_len=128, prefill_chunk=16, use_pallas="never",
        decode_buckets=(1, 2)), params=PARAMS)
    try:
        b = svc._warm_item(16, 0, 0)
        assert b.k_data.shape[4] == CFG.kv_lora_rank
        assert b.v_data.shape[4] == rope_pool_width(CFG) == 128
        # And the bundle actually injects + decodes (the crash site).
        toks = svc.submit_bundle(b, SamplingParams(max_new_tokens=2),
                                 timeout=240)
        assert len(toks) == 2
    finally:
        svc.stop()


@pytest.mark.slow
def test_mla_int8_latent_pool_numerics():
    """int8-quantized latent pool (round 5): half the already-compressed
    latent HBM; bounded deviation vs the fp32 pool and greedy agreement
    (the GQA int8 invariants, on the latent shape)."""
    mk = lambda dtype: Engine(
        EngineConfig(model="tiny-mla", page_size=8, num_pages=96,
                     max_seq_len=128, use_pallas="never",
                     enable_radix_cache=False, kv_dtype=dtype),
        params=PARAMS)
    prompt = [1, 2, 3, 4, 5, 6, 7, 8]

    ref = mk("model")
    q = mk("int8")
    assert q.cache.quantized and q.cache.k_pages.dtype == jnp.int8
    assert q.cache.k_pages.shape[-1] == CFG.kv_lora_rank
    assert q.cache.k_scales.shape[-1] == 1

    sp = SamplingParams(max_new_tokens=12)
    ref_out = ref.generate([prompt], sp)[0]
    q_out = q.generate([prompt], sp)[0]
    agree = sum(a == b for a, b in zip(ref_out, q_out)) / len(ref_out)
    assert agree >= 0.75, (ref_out, q_out)

    # Pages balance after generation (quantized pool accounting intact).
    assert not q.running and not q.waiting
    assert q.allocator.free_pages == q.cfg.num_pages - 1  # null page


# ---- the rotary key's pool, a whole lane tile wide (PR 33) -------------------


@pytest.mark.parametrize("quantize", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("model", ["tiny-mla", "tiny-joyai",
                                   "deepseek-v2-lite"])
def test_latent_pool_is_whole_lane_tiles_wide(model, quantize):
    """``v_pages`` of a latent model is ``dr`` rounded up to 128 channels
    (a last dim under a lane tile gives the pool a page-minor layout on the
    chip, and every step program transposed it whole twice); the latent's
    pool and the scales keep their shapes; ``hbm_bytes`` counts what
    ``create`` allocates."""
    cfg = get_config(model)
    cache = PagedKVCache.create(cfg, 8, 4, quantize=quantize)
    width = cache.v_pages.shape[-1]
    assert width % 128 == 0 and 0 <= width - cfg.qk_rope_head_dim < 128
    assert cache.k_pages.shape == (cfg.num_layers, 8, 4, 1, cfg.kv_lora_rank)
    assert cache.v_pages.shape[:-1] == cache.k_pages.shape[:-1]
    assert cache.v_pages.dtype == cache.k_pages.dtype == (
        jnp.int8 if quantize else cfg.jax_dtype)
    if quantize:
        assert cache.v_scales.shape == cache.k_scales.shape == (
            cfg.num_layers, 8, 4, 1, 1)
    assert PagedKVCache.hbm_bytes(
        cfg, 8, 4, dtype_bytes=cache.k_pages.dtype.itemsize) == (
        cache.k_pages.nbytes + cache.v_pages.nbytes)


@pytest.mark.parametrize("quantize", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("packed", [False, True], ids=["row", "ragged"])
def test_latent_step_leaves_the_pool_padding_zero(packed, quantize):
    """After a step of a latent model (``write_kv_pages`` for rows,
    ``write_kv_pages_ragged`` for a packed batch) the rotary-key pool holds
    the keys in its first ``dr`` channels and zeros beyond, in every slot."""
    from rbg_tpu.models.llama import forward_paged, forward_ragged
    cache = PagedKVCache.create(CFG, 16, 4, quantize=quantize)
    dr = CFG.qk_rope_head_dim
    table = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0]], jnp.int32)
    lens = jnp.asarray([9, 5], jnp.int32)
    pool = (cache.k_pages, cache.v_pages)
    scales = dict(k_scales=cache.k_scales, v_scales=cache.v_scales)
    if packed:
        T = 14
        rows = jnp.asarray([0] * 9 + [1] * 5, jnp.int32)
        pos = jnp.asarray([list(range(9)) + list(range(5))], jnp.int32)
        toks = (jnp.arange(T, dtype=jnp.int32)[None] * 7) % CFG.vocab_size
        out = forward_ragged(PARAMS, CFG, toks, pos, jnp.ones((1, T), bool),
                             rows, lens, table, *pool, use_pallas="never",
                             max_q_len=9, **scales)
    else:
        pos = jnp.broadcast_to(jnp.arange(9, dtype=jnp.int32), (2, 9))
        mask = pos < lens[:, None]
        toks = (pos * 7 + 3) % CFG.vocab_size
        out = forward_paged(PARAMS, CFG, toks, pos, mask, lens, table, *pool,
                            use_pallas="never", **scales)
    v = np.asarray(out[2].astype(jnp.float32))
    assert v.shape[-1] == 128 and not v[..., dr:].any()
    written = np.abs(v[..., :dr]).sum(-1)[:, :, :, 0]       # [L, NP, page]
    assert (written[:, [1, 2, 4]] > 0).all() and (written[:, 3, 0] > 0).all()
    assert (written[:, 5, 0] > 0).all() and not written[:, 5, 1:].any()
    assert not written[:, [0] + list(range(6, 16))].any()


@pytest.mark.parametrize("packed", [False, True], ids=["row", "ragged"])
def test_int8_rotary_scales_are_those_of_the_unpadded_keys(packed):
    """The int8 pool's per-token scale is an absmax, which zero channels do
    not move: the padded key's values and scales are bit-equal to
    ``quantize_kv`` of the key as the model made it."""
    from rbg_tpu.ops.paged_attention import quantize_kv, write_kv_pages
    from rbg_tpu.ops.ragged_paged_attention import write_kv_pages_ragged
    rng = np.random.RandomState(5)
    T, dc, dr, page, NP = 6, 64, 16, 4, 8
    c = jnp.asarray(rng.randn(1, T, 1, dc), jnp.float32)
    k_pe = jnp.asarray(rng.randn(1, T, 1, dr), jnp.float32)
    padded = jnp.pad(k_pe, ((0, 0),) * 3 + ((0, 128 - dr),))
    pools = (jnp.zeros((NP, page, 1, dc), jnp.int8),
             jnp.zeros((NP, page, 1, 128), jnp.int8))
    scales = (jnp.zeros((NP, page, 1, 1), jnp.float32),) * 2
    table = jnp.asarray([[3, 5]], jnp.int32)
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    mask = jnp.ones((1, T), bool)
    if packed:
        _, v, _, vs = write_kv_pages_ragged(
            *pools, c, padded, table, jnp.zeros(T, jnp.int32), pos, mask,
            *scales)
    else:
        _, v, _, vs = write_kv_pages(*pools, c, padded, table, pos, mask,
                                     *scales)
    want_q, want_s = quantize_kv(k_pe[0])                   # [T, 1, dr], [T,1,1]
    got = np.asarray(v)[[3, 5]].reshape(2 * page, 1, 128)[:T]
    got_s = np.asarray(vs)[[3, 5]].reshape(2 * page, 1, 1)[:T]
    np.testing.assert_array_equal(got[..., :dr], np.asarray(want_q))
    assert not got[..., dr:].any()
    np.testing.assert_array_equal(got_s, np.asarray(want_s))
