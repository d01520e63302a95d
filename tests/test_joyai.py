"""The DeepSeek-V3-shaped block (``tiny-joyai``): a dense layer before expert
layers, latent attention with a low-rank query and interleaved rotary pairs,
sigmoid routing with a selection bias and a scaling factor, a shared expert.

Each new rule is held to its definition here; the served path against the
benchmark's plain reference of the architecture is the contract every model
is a case of (``model_contract.py``, ``test_joyai_contract.py``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rbg_tpu.engine import Engine, EngineConfig
from rbg_tpu.models import get_config, init_params
from rbg_tpu.models import llama
from rbg_tpu.models.llama import (_EXPERT_STACKS, _mla_qkv, _moe_mlp,
                                  _moe_mlp_hit, _route)

from model_contract import read

CFG = get_config("tiny-joyai")
PARAMS = init_params(CFG, jax.random.key(0))
CELL_FILE = "configs", "joyai-llm-flash.json"


def _expert_block(layer=0):
    return jax.tree_util.tree_map(lambda a: a[layer], PARAMS["blocks"])


def _x(rows, seed=0):
    return jax.random.normal(jax.random.key(seed),
                             (rows, 1, CFG.hidden_size), jnp.float32)


# ---- the parameters come in groups ------------------------------------------


def test_a_dense_layer_before_expert_layers_is_two_groups():
    groups = [(name, g.num_experts, lo, hi)
              for name, g, lo, hi in CFG.layer_groups]
    assert groups == [("dense_blocks", 0, 0, 1), ("blocks", 16, 1, 3)]
    assert CFG.num_moe_layers == 2
    dense, experts = PARAMS["dense_blocks"], PARAMS["blocks"]
    assert dense["w_gate"].shape == (1, 128, CFG.intermediate_size)
    assert experts["w_gate"].shape == (2, 128, CFG.moe_shared_f)
    assert "moe_gate" not in dense and "router" not in dense
    assert experts["router_bias"].shape == (2, 16)
    assert experts["router_bias"].dtype == jnp.float32
    assert "wq" not in experts
    assert experts["wq_a"].shape == (2, 128, CFG.q_lora_rank)
    assert experts["wq_b"].shape == (2, CFG.q_lora_rank, 4 * (32 + 16))
    # a model of one kind of layer is the one group it always was
    one = get_config("tiny-moe")
    assert [(n, lo, hi) for n, _, lo, hi in one.layer_groups] == [
        ("blocks", 0, 2)]
    assert one.layer_groups[0][1] is one


def test_param_specs_match_the_parameters():
    from rbg_tpu.parallel.sharding import param_specs
    specs = param_specs(CFG)
    is_spec = lambda s: isinstance(s, jax.sharding.PartitionSpec)
    assert (jax.tree_util.tree_structure(PARAMS)
            == jax.tree_util.tree_structure(specs, is_leaf=is_spec))
    for leaf, spec in zip(jax.tree_util.tree_leaves(PARAMS),
                          jax.tree_util.tree_leaves(specs, is_leaf=is_spec)):
        assert len(spec) == leaf.ndim


def test_lora_is_refused_on_a_model_of_two_groups():
    eng = Engine(EngineConfig(model="tiny-joyai", page_size=8, num_pages=32,
                              max_seq_len=64, max_batch=2),
                 params=PARAMS)
    with pytest.raises(ValueError, match="groups of layers"):
        eng.load_lora("a", {"wo": (np.zeros((3, 128, 4), np.float32),
                                   np.zeros((3, 4, 128), np.float32))})


# ---- the router's rule -------------------------------------------------------


def test_route_is_zero_off_the_chosen_set_and_sums_to_the_factor():
    blk, xm = _expert_block(), _x(9)
    w = np.asarray(_route(CFG, blk, xm))[:, 0]                     # [9, E]
    assert ((w > 0).sum(-1) == CFG.experts_per_token).all()
    assert (w[w <= 0] == 0).all()
    np.testing.assert_allclose(w.sum(-1), CFG.moe_routed_scale, rtol=1e-5)
    # the definition, row by row
    logits = np.asarray(xm[:, 0], np.float64) @ np.asarray(blk["router"],
                                                           np.float64)
    s = 1 / (1 + np.exp(-logits))
    for r in range(9):
        top = np.argsort(-(s[r] + np.asarray(blk["router_bias"])))[:4]
        want = np.zeros(16)
        want[top] = 2.5 * s[r, top] / s[r, top].sum()
        np.testing.assert_allclose(w[r], want, rtol=1e-4, atol=1e-6)


def test_the_bias_changes_the_set_and_never_a_weight():
    blk, xm = _expert_block(), _x(32, seed=3)
    with_bias = np.asarray(_route(CFG, blk, xm))[:, 0]
    flat = dict(blk, router_bias=jnp.zeros_like(blk["router_bias"]))
    without = np.asarray(_route(CFG, flat, xm))[:, 0]
    moved = ((with_bias > 0) != (without > 0)).any(-1)
    assert moved.mean() > 0.5            # picks other experts at most rows
    # A large bias on one expert: every row picks it, at the weight its
    # own score earns among the chosen.
    spiked = dict(blk, router_bias=blk["router_bias"].at[5].add(100.0))
    w = np.asarray(_route(CFG, spiked, xm))[:, 0]
    assert (w[:, 5] > 0).all()
    s = np.asarray(jax.nn.sigmoid(xm[:, 0] @ blk["router"]))
    chosen = w > 0
    want = 2.5 * s[:, 5] / (s * chosen).sum(-1)
    np.testing.assert_allclose(w[:, 5], want, rtol=1e-4)
    assert (w[:, 5] < 2.5).all()         # the 100 weighs nothing


@pytest.mark.parametrize("scoring,bias,renorm,scale", [
    ("softmax", False, True, 1.0), ("softmax", False, False, 1.0),
    ("sigmoid", False, True, 2.5), ("sigmoid", True, False, 1.0),
    ("softmax", True, True, 16.0)])
def test_route_reads_its_rule_from_the_configuration(scoring, bias, renorm,
                                                     scale):
    cfg = dataclasses.replace(CFG, moe_scoring=scoring, moe_select_bias=bias,
                              moe_renormalize=renorm, moe_routed_scale=scale)
    blk, xm = _expert_block(1), _x(7, seed=1)
    w = np.asarray(_route(cfg, blk, xm))[:, 0]
    logits = np.asarray(xm[:, 0] @ blk["router"], np.float64)
    if scoring == "softmax":
        e = np.exp(logits - logits.max(-1, keepdims=True))
        s = e / e.sum(-1, keepdims=True)
    else:
        s = 1 / (1 + np.exp(-logits))
    sel = s + (np.asarray(blk["router_bias"]) if bias else 0)
    for r in range(7):
        top = np.argsort(-sel[r])[:4]
        want = np.zeros(16)
        want[top] = s[r, top] / (s[r, top].sum() if renorm else 1) * scale
        np.testing.assert_allclose(w[r], want, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("rows,live", [(1, None), (4, None), (8, None),
                                       (8, [True] * 3 + [False] * 5)])
def test_hit_experts_form_equals_the_dense_dispatch_under_the_new_rule(
        rows, live):
    blk, xm = _expert_block(1), _x(rows, seed=rows)
    live = jnp.asarray([True] * rows if live is None else live)
    stacks = {k: PARAMS["blocks"][k] for k in _EXPERT_STACKS}
    dense = np.asarray(_moe_mlp(CFG, blk, xm))
    got, visited = jax.jit(lambda b, x, s, m: _moe_mlp_hit(
        CFG, b, x, s, jnp.int32(1), m[:, None]))(blk, xm, stacks, live)
    on = np.asarray(live)
    np.testing.assert_allclose(np.asarray(got)[on], dense[on], rtol=1e-4,
                               atol=1e-5 * np.abs(dense).max())
    hit = (np.asarray(_route(CFG, blk, xm))[on] > 0).any(axis=(0, 1))
    assert int(visited) == hit.sum()
    # a dead row gets the shared expert alone
    if not on.all():
        shared = np.asarray(llama._shared_expert(blk, xm))
        np.testing.assert_allclose(np.asarray(got)[~on], shared[~on],
                                   rtol=1e-5, atol=1e-7)


def test_the_programs_init_draws_the_selection_bias_as_the_cell_does():
    """An engine started without the benchmark's weights (``chip_smoke.py``,
    these tests) routes like the measured cell: the bias moves the chosen
    set at most positions and leaves routing near uniform. At the first
    runs' 0.1 it concentrated routing on the favoured experts."""
    want = read(*CELL_FILE)["assumed"]["e_score_correction_bias_scale"]
    wide = dataclasses.replace(CFG, num_experts=256, experts_per_token=8)
    blk = jax.tree_util.tree_map(
        lambda a: a[0], init_params(wide, jax.random.key(1))["blocks"])
    assert np.asarray(blk["router_bias"]).std() == pytest.approx(want,
                                                                 rel=0.15)
    xm = jax.random.normal(jax.random.key(2), (64, 1, CFG.hidden_size))
    with_bias = np.asarray(_route(wide, blk, xm)) > 0
    flat = dict(blk, router_bias=jnp.zeros_like(blk["router_bias"]))
    without = np.asarray(_route(wide, flat, xm)) > 0
    moved = (with_bias != without).any(axis=-1).mean()
    assert moved > 0.5
    # 16 rows x top 8 of 256 hit 39.8 % of the experts when unbiased

    def hit(chosen):
        return np.mean([chosen[i:i + 16].any(axis=(0, 1)).mean()
                        for i in range(0, 64, 16)])

    concentrated = dict(blk, router_bias=blk["router_bias"] * (0.1 / want))
    # at this toy width: 0.390 unbiased, 0.305 at the cell's scale, 0.169
    # at 0.1 (scores lie closer here than at the cell's width, where the
    # first two read 39.8 and 38 %)
    assert hit(with_bias) > 0.7 * hit(without)
    assert (hit(np.asarray(_route(wide, concentrated, xm)) > 0)
            < 0.7 * hit(with_bias))


# ---- latent attention: the low-rank query, the pairing ----------------------


def test_interleaved_pairs_rotate_as_complex_numbers():
    from rbg_tpu.ops.rope import apply_rope
    x = jax.random.normal(jax.random.key(5), (1, 6, 2, 16))
    pos = jnp.asarray([[0, 1, 2, 7, 30, 31]], jnp.int32)
    got = np.asarray(apply_rope(x, pos, 10000.0, interleave=True))
    z = np.asarray(x[..., 0::2]) + 1j * np.asarray(x[..., 1::2])
    ang = np.asarray(pos)[..., None] * 10000.0 ** (-np.arange(0, 16, 2) / 16)
    w = z * np.exp(1j * ang)[:, :, None, :]
    want = np.stack([w.real, w.imag], axis=-1).reshape(x.shape)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the same rotation as rotate-half on the de-interleaved vector
    half = np.asarray(apply_rope(
        jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1), pos, 10000.0))
    np.testing.assert_allclose(
        np.concatenate([got[..., 0::2], got[..., 1::2]], -1), half,
        rtol=1e-5, atol=1e-6)
    # position 0 rotates nothing
    np.testing.assert_array_equal(got[0, 0], np.asarray(x[0, 0]))


def test_absorbed_low_rank_query_equals_the_materialised_form():
    """``q_lat . c + q_pe . k_pe`` against per-head ``k_nope`` from
    ``c W_uk`` and the query through ``wq_a``, its norm and ``wq_b``."""
    from rbg_tpu.ops.norms import rms_norm
    from rbg_tpu.ops.rope import apply_rope
    B, T = 1, 6
    x = jax.random.normal(jax.random.key(2), (B, T, CFG.hidden_size))
    pos = jnp.arange(T, dtype=jnp.int32)[None, :]
    blk = _expert_block()
    q_lat, q_pe, c, k_pe = _mla_qkv(CFG, blk, x, pos)
    h, dn, dr, dc = (CFG.num_heads, CFG.qk_nope_head_dim,
                     CFG.qk_rope_head_dim, CFG.kv_lora_rank)
    xa = rms_norm(x, blk["attn_norm"], CFG.rms_norm_eps)
    c_q = rms_norm(xa @ blk["wq_a"], blk["q_norm"], CFG.rms_norm_eps)
    q = (c_q @ blk["wq_b"]).reshape(B, T, h, dn + dr)
    k_nope = jnp.einsum("btc,chn->bthn", c, blk["w_uk"].reshape(dc, h, dn))
    naive = jnp.einsum("bthn,bshn->bhts", q[..., :dn], k_nope)
    absorbed = jnp.einsum("bthc,bsc->bhts", q_lat, c)
    assert float(jnp.max(jnp.abs(naive - absorbed))) < 1e-4
    want_pe = apply_rope(q[..., dn:], pos, CFG.rope_theta, True)
    np.testing.assert_allclose(np.asarray(q_pe), np.asarray(want_pe),
                               rtol=1e-5, atol=1e-6)


# ---- the benchmark's configuration file --------------------------------------


def test_cell_file_holds_the_catalog_and_its_preset_follows_its_keys():
    from harness import serve
    cfg = read(*CELL_FILE)
    published = {
        "first_k_dense_replace": 1, "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "moe_intermediate_size": 768, "n_group": 1, "n_routed_experts": 256,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_interleave": True,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "topk_group": 1, "topk_method": "noaux_tc",
        "v_head_dim": 128, "vocab_size": 129280, "head_dim": 64,
        "max_position_embeddings": 131072, "qk_head_dim": 192}
    assert {k: cfg[k] for k in published} == published
    assert cfg["num_hidden_layers"] == 5
    assert cfg["published"] == {"num_hidden_layers": 40}
    assert set(cfg["reduced"]) == {"num_hidden_layers"}
    m = serve.model_config(cfg, "cell")
    assert (m.num_experts, m.experts_per_token, m.moe_f, m.moe_shared_f) == (
        256, 8, 768, 768)
    assert (m.q_lora_rank, m.kv_lora_rank, m.qk_nope_head_dim,
            m.qk_rope_head_dim, m.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (m.moe_scoring, m.moe_select_bias, m.moe_renormalize,
            m.moe_routed_scale, m.rope_interleave, m.first_dense_layers) == (
        "sigmoid", True, True, 2.5, True, 1)
    assert [(n, lo, hi) for n, _, lo, hi in m.layer_groups] == [
        ("dense_blocks", 0, 1), ("blocks", 1, 5)]
    assert abs(2 * m.num_params / 1e9 - 11.12) < 0.01       # bf16 bytes
    s, c = cfg["server"], cfg["correct"]
    assert 2 + len(c["other_lens"]) == s["max_batch"]
    assert max(c["first_len"], *c["other_lens"]) + c["new_tokens"] \
        <= s["max_seq_len"]
