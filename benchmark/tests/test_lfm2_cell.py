"""``lfm2-24b-a2b`` at tiny widths (``rehearse/configs/tiny-lfm2.json``):
its reference against the program, the controls that must fail, the whole
``run.py --rehearse`` flow with the cell's new metric files, and the cell's
own file against the catalog row it was made from. The cases
``test_reference.py`` and ``test_run_rehearse.py`` would take as one more
parameter, in a file of their own: a PR that adds a configuration may add
files here and edit none."""

import json
import os

import jax
import numpy as np
import pytest

from harness import opsbytes, serve
from test_reference import _cfg, _rms, _served
from test_run_rehearse import ROOT, _run

CELLS = "benchmark/tests/rehearse/cells_lfm2.json"
CELL_FILE = os.path.join(ROOT, "benchmark", "configs", "lfm2-24b-a2b.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_program_agrees_with_the_lfm2_reference_and_controls_do_not():
    cfg = _cfg("tiny-lfm2")
    limit = cfg["correct"]["limit"]
    reference = serve.load_reference(cfg)
    assert reference.__file__.endswith("benchmark/references/lfm2_moe.py")
    params = reference.make_params(cfg, 3000000019)
    assert set(params) == {"embed", "final_norm", "conv_mixers", "mixers",
                           "dense_mlps", "moe_mlps"}
    prompt = np.random.default_rng(1).integers(
        1, cfg["vocab_size"], 80).tolist()
    toks, lps = _served(cfg, "tiny-lfm2", params, prompt, 8)
    assert len(toks) == 8
    ref = reference.chosen_logprobs(cfg, params, prompt, toks)
    assert _rms(lps, ref) <= limit
    for quant in ("bf16", "int8", "fp8"):
        ctl = reference.chosen_logprobs(cfg, params, prompt, toks, quant)
        assert _rms(ctl, ref) > 3 * limit, quant
    # K, V and the convolution's cached inputs alone, rounded
    ctl = reference.chosen_logprobs(cfg, params, prompt, toks, "kv_int8")
    assert _rms(ctl, ref) > limit


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "references",
                           "lfm2_moe.py")) as f:
        src = f.read()
    code = src.split('"""', 2)[2]            # past the module's docstring
    assert "rbg_tpu" not in code
    assert "base._moe" not in code and "base._attention" not in code


def test_lfm2_weights_follow_the_seed_and_the_served_layout():
    cfg = _cfg("tiny-lfm2")
    reference = serve.load_reference(cfg)
    a, b = reference.make_params(cfg, 7), reference.make_params(cfg, 7)
    c = reference.make_params(cfg, 2 ** 31 + 7)
    assert np.array_equal(a["conv_mixers"]["conv_in"],
                          b["conv_mixers"]["conv_in"])
    assert not np.array_equal(a["mixers"]["wq"], c["mixers"]["wq"])
    # the layout is the program's own initialiser's, leaf for leaf
    from rbg_tpu.models import init_params
    own = jax.eval_shape(lambda: init_params(
        serve.model_config(cfg, "tiny-lfm2-shapes"), jax.random.key(0)))
    assert jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), a) == \
        jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), own)
    assert a["dense_mlps"]["w_up"].shape == (2, 128, 320)
    assert a["moe_mlps"]["moe_up"].shape == (8, 8, 128, 48)
    assert a["moe_mlps"]["router"].shape == (8, 128, 16)
    assert a["moe_mlps"]["router_bias"].dtype == np.float32
    assert abs(float(np.std(a["conv_mixers"]["conv_w"])) - 3 ** -0.5) < 0.03


def test_the_reference_routes_by_the_bias_and_weighs_by_the_score():
    cfg = _cfg("tiny-lfm2")
    reference = serve.load_reference(cfg)
    z = reference.sizes(cfg)
    params = reference.make_params(cfg, 9)
    blk = jax.tree_util.tree_map(lambda x: x[0], params["moe_mlps"])
    m = jax.random.normal(jax.random.key(0), (5, z["d"]))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(reference._combine_weights(z, blk, m, None))
    s = 1 / (1 + np.exp(-(np.asarray(m, np.float64)
                          @ np.asarray(blk["router"], np.float64))))
    for t in range(5):
        top = np.argsort(-(s[t] + np.asarray(blk["router_bias"])))[:z["K"]]
        want = np.zeros(z["E"])
        want[top] = s[t][top] / (s[t][top].sum() + 1e-6)
        np.testing.assert_allclose(got[t], want, rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_lfm2_cell_rehearses_with_its_metric_files(trace):
    r = _run("--rehearse", "--workload", "lfm2.closed", "--seed",
             "2147483659", "--seconds", "5", "--trace", str(trace),
             cells=CELLS)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    if trace:
        # device metrics read nothing on the CPU and are left out; the
        # counters' metrics read
        m = line["metrics"]
        assert set(m) == {"engine.tokens_per_step", "setup.compiles_in_window",
                          "moe.experts_visited_share", "moe.rows_per_visit",
                          "kv.state_fill_share"}
        assert 0 < m["kv.state_fill_share"]["value"] <= 100
        assert 0 < m["moe.experts_visited_share"]["value"] <= 100
    else:
        assert line["metrics"]["out_tok_s"]["value"] > 0


def test_the_hybrid_count_is_the_attention_layers_and_heads_as_held():
    with open(CELL_FILE) as f:
        cfg = json.load(f)
    model = opsbytes.models()["paged_attention_hybrid"]
    rows = [(1, 900)] * 32
    flops, nbytes = model(cfg, rows)
    # 10 of 40 layers; 8 KV heads of 64 in bf16, K and V: 2048 B a token
    assert nbytes == 10 * (32 * 900 * 2048 + 32 * 2 * 32 * 64 * 2)
    assert flops == 10 * 4 * 32 * 64 * 32 * 900
    every = opsbytes.paged_attention(dict(cfg, head_dim=64), rows)
    assert (flops * 4, nbytes * 4) == every       # which counts 40 layers


def test_cell_file_holds_the_catalog_and_its_preset_follows_its_keys():
    with open(CELL_FILE) as f:
        cfg = json.load(f)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LFM2-24B-A2B")
        assert cfg["source"] == row["source_url"]
        # every key as published: nothing of the catalog's config is cut
        assert {k: cfg[k] for k in row["config"]} == row["config"]
    assert set(cfg["reduced"]) == {"experts_held"} == set(cfg["published"])
    assert "v5e-8" in cfg["deployment"] and "8 chips" in cfg["deployment"]
    m = serve.model_config(cfg, "lfm2-cell-test")
    assert list(m.layer_types) == cfg["layer_types"] and m.num_layers == 40
    assert (m.mixer_count("conv"), m.mixer_count("full")) == (30, 10)
    assert (m.head_dim_, m.num_heads, m.num_kv_heads, m.qk_norm) == (
        cfg["hidden_size"] // cfg["num_attention_heads"], 32, 8, True)
    assert (m.conv_kernel, m.rope_theta, m.rms_norm_eps) == (
        cfg["conv_L_cache"], cfg["rope_parameters"]["rope_theta"],
        cfg["norm_eps"])
    assert (m.num_experts, m.experts_per_token, m.experts_held) == (
        cfg["num_experts"], cfg["num_experts_per_tok"], (0, 8))
    assert (m.moe_f, m.moe_shared_expert, m.moe_routed_scale,
            m.first_dense_layers, m.moe_scoring, m.moe_select_bias,
            m.moe_renormalize) == (
        cfg["moe_intermediate_size"], False, cfg["routed_scaling_factor"],
        cfg["num_dense_layers"], "sigmoid", cfg["use_expert_bias"],
        cfg["norm_topk_prob"])
    assert m.tie_word_embeddings and m.dtype == "bfloat16"
    assert [(k, n) for k, _, n in m.param_groups] == [
        ("conv_mixers", 30), ("dense_mlps", 2), ("mixers", 10),
        ("moe_mlps", 38)]
    # the bytes the file reckons: 7.52 GB of weights, 2.68 GB of pages,
    # 7.9 MB of tails (245,760 B a row)
    assert m.num_params == 3_761_333_888
    assert "3,761,333,888" in cfg["reduced"]["experts_held"]
    from rbg_tpu.engine.kvcache import PagedKVCache, StatePool
    s = cfg["server"]
    assert PagedKVCache.hbm_bytes(m, s["num_pages"], s["page_size"]) == \
        8192 * 16 * 10 * 2 * 8 * 64 * 2
    assert StatePool.hbm_bytes(m, s["max_batch"]) == 32 * 245_760
    assert s["max_batch"] == 2 + len(cfg["correct"]["other_lens"])
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "longgen32.json")) as f:
        traffic = json.load(f)
    assert traffic["clients"] == s["max_batch"] == traffic["block"]
