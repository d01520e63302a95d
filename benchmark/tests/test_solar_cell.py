"""``solar-open2-250b`` at tiny widths
(``rehearse/configs/tiny-solar-open2.json``): its reference against the
program, the controls that must fail, the whole ``run.py --rehearse`` flow
with the cell's new metric files, the new count, and the cell's own file
against the catalog row it was made from. The cases ``test_reference.py``
and ``test_run_rehearse.py`` would take as one more parameter, in a file of
their own: a PR that adds a configuration may add files here and edit
none."""

import json
import os

import jax
import numpy as np
import pytest

from harness import opsbytes, serve
from test_reference import _cfg, _rms, _served
from test_run_rehearse import ROOT, _run

CELLS = "benchmark/tests/rehearse/cells_solar.json"
CELL_FILE = os.path.join(ROOT, "benchmark", "configs",
                         "solar-open2-250b.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_program_agrees_with_the_solar_reference_and_controls_do_not():
    cfg = _cfg("tiny-solar-open2")
    limit = cfg["correct"]["limit"]
    reference = serve.load_reference(cfg)
    assert reference.__file__.endswith("benchmark/references/solar_open2.py")
    params = reference.make_params(cfg, 3000000019)
    assert set(params) == {"embed", "lm_head", "final_norm", "kda_mixers",
                           "mixers", "moe_mlps"}
    prompt = np.random.default_rng(1).integers(
        1, cfg["vocab_size"], 80).tolist()
    toks, lps = _served(cfg, "tiny-solar-open2", params, prompt, 8)
    assert len(toks) == 8
    ref = reference.chosen_logprobs(cfg, params, prompt, toks)
    assert _rms(lps, ref) <= limit
    # kv_int8 rounds the cached K and V AND the recurrent state
    for quant in ("bf16", "int8", "fp8", "kv_int8"):
        ctl = reference.chosen_logprobs(cfg, params, prompt, toks, quant)
        assert _rms(ctl, ref) > 3 * limit, quant


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "references",
                           "solar_open2.py")) as f:
        src = f.read()
    code = src.split('"""', 2)[2]            # past the module's docstring
    assert "rbg_tpu" not in code
    assert "base._rope" not in code and "base._moe" not in code
    assert "base._attention" not in code


def test_the_reference_refuses_a_file_that_is_not_this_architecture():
    cfg = _cfg("tiny-solar-open2")
    reference = serve.load_reference(cfg)
    for key, value in (("use_rope", True), ("use_gqa_gate", False),
                       ("first_k_dense_replace", 1)):
        with pytest.raises(ValueError, match="published solar_open2"):
            reference.param_shapes(dict(cfg, **{key: value}))


def test_solar_weights_follow_the_seed_and_the_served_layout():
    cfg = _cfg("tiny-solar-open2")
    reference = serve.load_reference(cfg)
    a, b = reference.make_params(cfg, 7), reference.make_params(cfg, 7)
    c = reference.make_params(cfg, 2 ** 31 + 7)
    assert np.array_equal(a["kda_mixers"]["kda_qkv"],
                          b["kda_mixers"]["kda_qkv"])
    assert not np.array_equal(a["mixers"]["wg"], c["mixers"]["wg"])
    # the layout is the program's own initialiser's, leaf for leaf
    from rbg_tpu.models import init_params
    own = jax.eval_shape(lambda: init_params(
        serve.model_config(cfg, "tiny-solar-shapes"), jax.random.key(0)))
    assert jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), a) == \
        jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), own)
    # half-layers in layer order; the held experts alone, a whole router
    assert a["mixers"]["wq"].shape == (2, 128, 128)
    assert a["moe_mlps"]["moe_up"].shape == (8, 4, 128, 48)
    assert a["moe_mlps"]["router"].shape == (8, 128, 16)
    # the sliced vocabulary is the file's: ids below it, a head over it
    assert a["embed"].shape == (256, 128) and a["lm_head"].shape == (128, 256)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_solar_cell_rehearses_with_its_metric_files(trace):
    r = _run("--rehearse", "--workload", "solar-open2.closed", "--seed",
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
                          "kv.state_fill_share", "kda.state_gb_per_step"}
        assert 0 < m["kv.state_fill_share"]["value"] <= 100
        # 4 rows at most, 2 x 6 x (4 x 32 x 32 + 3 x 384) x 4 bytes a row
        assert 0 < m["kda.state_gb_per_step"]["value"] <= 4 * 251904e-9
        assert 0 < m["moe.experts_visited_share"]["value"] <= 100
    else:
        assert line["metrics"]["out_tok_s"]["value"] > 0


def test_the_counts_are_the_layers_of_each_kind_among_those_served():
    with open(CELL_FILE) as f:
        cfg = json.load(f)
    models = opsbytes.models()
    rows = [(1, 900)] * 32
    flops, nbytes = models["paged_attention_gqa_layers"](cfg, rows)
    # 2 of 8 layers; 8 KV heads of 128 in bf16, K and V: 4096 B a token
    assert nbytes == 2 * (32 * 900 * 4096 + 32 * 2 * 64 * 128 * 2)
    assert flops == 2 * 4 * 64 * 128 * 32 * 900
    every = opsbytes.paged_attention(cfg, rows)
    assert (flops * 4, nbytes * 4) == every       # which counts 8 layers
    # uncut, twelve of 48 layers attend
    whole = dict(cfg, num_hidden_layers=48)
    assert models["paged_attention_gqa_layers"](whole, rows)[0] == 6 * flops
    # the delta rule's kernel: 6 layers x 32 rows x the state twice
    flops, nbytes = models["kda_decode_step"](cfg, rows)
    state = 2 * 64 * 128 * 128 * 4
    assert flops == 6 * 32 * 8 * 64 * 128 * 128
    assert nbytes == 6 * 32 * (state + (5 * 64 * 128 + 64) * 4)
    assert models["kda_decode_step"](cfg, [(64, 64)]) == (0, 0)


def test_cell_file_holds_the_catalog_and_its_preset_follows_its_keys():
    with open(CELL_FILE) as f:
        cfg = json.load(f)
    cut = {"num_hidden_layers", "vocab_size", "linear_attn_config"}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Solar-Open2-250B")
        assert cfg["source"] == row["source_url"]
        # every key as published but the ones cut, which ``published`` holds
        assert {k: cfg[k] for k in row["config"] if k not in cut} == {
            k: v for k, v in row["config"].items() if k not in cut}
        assert {k: cfg["published"][k] for k in cut} == {
            k: row["config"][k] for k in cut}
        # the group keeps every published number and gains one derived key
        lin = dict(cfg["linear_attn_config"])
        assert lin.pop("kda_layers") == [2, 3, 4, 6, 7, 8]
        assert lin == row["config"]["linear_attn_config"]
    assert set(cfg["reduced"]) == cut | {"experts_held"} == \
        set(cfg["published"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "solar-open2-250b")
    assert set(entry["reduced"]) == set(cfg["reduced"])
    assert "v5e-16" in cfg["deployment"] and "16 chips" in cfg["deployment"]
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (8, 196608 // 8)
    m = serve.model_config(cfg, "solar-cell-test")
    assert m.mixer_kinds == ("full", "kda", "kda", "kda") * 2
    assert [n for n, kind in enumerate(m.mixer_kinds) if kind == "full"] == \
        [n for n in cfg["gqa_layers"] if n < m.num_layers]
    lin = cfg["linear_attn_config"]
    assert (m.kda_num_heads, m.kda_head_dim, m.kda_conv_kernel) == (
        lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"])
    assert (m.num_heads, m.num_kv_heads, m.head_dim_) == (64, 8, 128)
    assert (m.use_rope, m.attn_gate, m.kda_beta_scale) == (
        cfg["use_rope"], cfg["use_gqa_gate"],
        2.0 if cfg["kda_allow_neg_eigval"] else 1.0) == (False, True, 2.0)
    assert (m.num_experts, m.experts_per_token, m.experts_held) == (
        cfg["n_routed_experts"], cfg["num_experts_per_tok"], (0, 20))
    assert (m.moe_f, m.moe_shared_f, m.moe_routed_scale, m.moe_renormalize,
            m.first_dense_layers, m.moe_scoring, m.moe_select_bias) == (
        cfg["moe_intermediate_size"],
        cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        cfg["routed_scaling_factor"], cfg["norm_topk_prob"],
        cfg["first_k_dense_replace"], "sigmoid", True)
    assert not m.tie_word_embeddings and m.dtype == "bfloat16"
    assert [(k, n) for k, _, n in m.param_groups] == [
        ("mixers", 2), ("moe_mlps", 8), ("kda_mixers", 6)]
    # the bytes the file reckons: 7.80 GB of weights, 1.07 GB of pages,
    # 0.83 GB of states and tails (4,341,760 B a row a layer)
    assert m.num_params == 3_898_793_600
    assert "3,898,793,600" in cfg["arithmetic"]
    from rbg_tpu.engine.kvcache import PagedKVCache, StatePool
    s = cfg["server"]
    assert PagedKVCache.hbm_bytes(m, s["num_pages"], s["page_size"]) == \
        8192 * 16 * 2 * 2 * 8 * 128 * 2 == 1 << 30
    assert StatePool.hbm_bytes(m, s["max_batch"]) == 32 * 6 * (
        64 * 128 * 128 * 4 + 3 * 3 * 8192 * 2) == 32 * 6 * 4_341_760
    assert s["max_batch"] == 2 + len(cfg["correct"]["other_lens"])
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "longgen32.json")) as f:
        traffic = json.load(f)
    assert traffic["clients"] == s["max_batch"] == traffic["block"]
