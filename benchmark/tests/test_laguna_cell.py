"""``laguna-xs2`` at tiny widths (``rehearse/configs/tiny-laguna.json``): its
reference against the program, the controls that must fail, the whole
``run.py --rehearse`` flow with the cell's new metric files, the new count,
and the cell's own file against the catalog row it was made from. The cases
``test_reference.py`` and ``test_run_rehearse.py`` would take as one more
parameter, in a file of their own: a PR that adds a configuration may add
files here and edit none."""

import json
import os

import jax
import numpy as np

from harness import opsbytes, serve
from test_reference import _cfg, _rms, _served
from test_run_rehearse import ROOT, _run

CELLS = "benchmark/tests/rehearse/cells_laguna.json"
CELL_FILE = os.path.join(ROOT, "benchmark", "configs", "laguna-xs2.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_program_agrees_with_the_laguna_reference_and_controls_do_not():
    cfg = _cfg("tiny-laguna")
    limit = cfg["correct"]["limit"]
    reference = serve.load_reference(cfg)
    assert reference.__file__.endswith("benchmark/references/laguna.py")
    params = reference.make_params(cfg, 3000000019)
    assert set(params) == {"embed", "lm_head", "final_norm", "mixers",
                           "window_mixers", "dense_mlps", "moe_mlps"}
    prompt = np.random.default_rng(1).integers(
        1, cfg["vocab_size"], 80).tolist()       # ten windows of 8
    toks, lps = _served(cfg, "tiny-laguna", params, prompt, 8)
    assert len(toks) == 8
    ref = reference.chosen_logprobs(cfg, params, prompt, toks)
    assert _rms(lps, ref) <= limit
    # kv_int8 rounds the cached K (rotated) and V of both kinds of layer
    for quant in ("bf16", "int8", "fp8", "kv_int8"):
        ctl = reference.chosen_logprobs(cfg, params, prompt, toks, quant)
        assert _rms(ctl, ref) > 3 * limit, quant


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "references",
                           "laguna.py")) as f:
        src = f.read()
    code = src.split('"""', 2)[2]            # past the module's docstring
    assert "rbg_tpu" not in code
    assert "base._rope" not in code and "base._moe" not in code
    assert "base._attention" not in code


def test_the_references_yarn_is_the_published_blend():
    """Frequencies that turn more than ``beta_fast`` times in the original
    context are kept, those that turn less than ``beta_slow`` times divided
    by the factor, at the published settings."""
    reference = serve.load_reference(_cfg("tiny-laguna"))
    inv = reference.yarn_inv_freq(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    plain = 500000.0 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(inv[:6], plain[:6], rtol=1e-12)
    np.testing.assert_allclose(inv[16:], plain[16:] / 64.0, rtol=1e-12)
    assert np.all(np.diff(inv) < 0)
    turns = 4096 * plain / (2 * np.pi)
    assert turns[5] > 64 > turns[6] and turns[15] > 1 > turns[16]


def test_laguna_weights_follow_the_seed_and_the_served_layout():
    cfg = _cfg("tiny-laguna")
    reference = serve.load_reference(cfg)
    a, b = reference.make_params(cfg, 7), reference.make_params(cfg, 7)
    c = reference.make_params(cfg, 2 ** 31 + 7)
    assert np.array_equal(a["window_mixers"]["wq"], b["window_mixers"]["wq"])
    assert not np.array_equal(a["mixers"]["wg"], c["mixers"]["wg"])
    # the layout is the program's own initialiser's, leaf for leaf
    from rbg_tpu.models import init_params
    own = jax.eval_shape(lambda: init_params(
        serve.model_config(cfg, "tiny-laguna-shapes"), jax.random.key(0)))
    assert jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), a) == \
        jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), own)
    # head counts by kind, a gate a head, the held experts, a whole router
    # (q, k, v held [out, in], as the program holds a window model's)
    assert a["mixers"]["wq"].shape == (2, 6 * 32, 128)
    assert a["window_mixers"]["wq"].shape == (6, 8 * 32, 128)
    assert a["window_mixers"]["wg"].shape == (6, 128, 8)
    assert a["moe_mlps"]["moe_up"].shape == (7, 8, 128, 48)
    assert a["moe_mlps"]["router"].shape == (7, 128, 16)


def test_the_laguna_cell_rehearses_with_its_metric_files():
    r = _run("--rehearse", "--workload", "laguna-xs2.closed", "--seed",
             "2147483659", "--seconds", "5", "--trace", "1", cells=CELLS)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    # device metrics read nothing on the CPU and are left out; the
    # counters' metrics read
    m = line["metrics"]
    assert set(m) == {"engine.tokens_per_step", "setup.compiles_in_window",
                      "kv.page_fill_share", "moe.experts_visited_share",
                      "moe.rows_per_visit", "kv.window_page_fill_share",
                      "kv.window_pages_released_per_step"}
    # prompts of 8-48 and answers of 8-24 tokens under a window of 8: a row
    # gives back about a page of 4 slots every 4 steps, and holds its
    # window's pages and the chunk's
    assert 0.1 < m["kv.window_pages_released_per_step"]["value"] < 3
    assert 10 < m["kv.window_page_fill_share"]["value"] <= 100
    assert 0 < m["kv.page_fill_share"]["value"] <= 100


def test_the_count_is_by_layer_kind_and_of_live_work_alone():
    with open(CELL_FILE) as f:
        cfg = json.load(f)
    count = opsbytes.models()["paged_attention_window_layers"]
    token, q48, q64 = 2 * 8 * 128 * 2, 2 * 48 * 128 * 2, 2 * 64 * 128 * 2
    # one decode row at 900 tokens: 5 full layers read 900, 15 window
    # layers the newest 512
    flops, nbytes = count(cfg, [(1, 900)])
    assert nbytes == 5 * (900 * token + q48) + 15 * (512 * token + q64)
    assert flops == 5 * 4 * 48 * 128 * 900 + 15 * 4 * 64 * 128 * 512
    # inside a window the two kinds differ by their heads alone
    flops, nbytes = count(cfg, [(1, 300)])
    assert nbytes == 5 * (300 * token + q48) + 15 * (300 * token + q64)
    assert flops == (5 * 48 + 15 * 64) * 4 * 128 * 300
    # a chunk of 64 queries that ends at 1000: each window query attends
    # 512, the row reads 512 + 63; a full query attends up to itself
    flops, nbytes = count(cfg, [(64, 1000)])
    pairs = 64 * 1000 - 64 * 63 // 2
    assert flops == 5 * 4 * 48 * 128 * pairs + 15 * 4 * 64 * 128 * 64 * 512
    assert nbytes == 5 * (1000 * token + 64 * q48) + 15 * (
        575 * token + 64 * q64)
    # a chunk from position 0: a window query at p attends p + 1 <= 512
    assert count(cfg, [(64, 64)])[0] == (5 * 48 + 15 * 64) * 4 * 128 * (
        64 * 65 // 2)
    # rows add up; the uncut 40 layers are ten periods
    one, two = count(cfg, [(1, 900)]), count(cfg, [(1, 900), (1, 900)])
    assert two == (2 * one[0], 2 * one[1])
    assert count(dict(cfg, num_hidden_layers=40), [(1, 900)]) == (
        2 * one[0], 2 * one[1])
    # under every layer counted as full with 48 heads the bytes are more
    assert opsbytes.paged_attention(cfg, [(1, 900)])[1] > one[1]


def test_cell_file_holds_the_catalog_and_its_preset_follows_its_keys():
    with open(CELL_FILE) as f:
        cfg = json.load(f)
    cut = {"num_hidden_layers", "vocab_size"}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Laguna-XS.2")
        assert cfg["source"] == row["source_url"]
        # every key as published but the ones cut, which ``published`` holds
        assert {k: cfg[k] for k in row["config"] if k not in cut} == {
            k: v for k, v in row["config"].items() if k not in cut}
        assert {k: cfg["published"][k] for k in cut} == {
            k: row["config"][k] for k in cut}
    assert set(cfg["reduced"]) == cut | {"experts_held"} == \
        set(cfg["published"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "laguna-xs2")
    assert set(entry["reduced"]) == set(cfg["reduced"])
    assert "v5e-16" in cfg["deployment"] and "8 chips" in cfg["deployment"]
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (20, 100352 // 8)
    m = serve.model_config(cfg, "laguna-cell-test")
    L = m.num_layers
    assert m.mixer_kinds == ("full", "window", "window", "window") * 5
    assert list(m.layer_types) == cfg["layer_types"][:L]
    kinds = {k: g for k, g, _ in m.param_groups}
    full, window = kinds["mixers"], kinds["window_mixers"]
    heads = cfg["num_attention_heads_per_layer"][:L]
    assert {h for h, k in zip(heads, m.mixer_kinds) if k == "full"} == {
        full.num_heads} == {cfg["num_attention_heads"]} == {48}
    assert {h for h, k in zip(heads, m.mixer_kinds) if k == "window"} == {
        window.num_heads} == {64}
    assert (m.num_kv_heads, m.head_dim_) == (8, 128)
    rope = cfg["rope_parameters"]
    y, w = rope["full_attention"], rope["sliding_attention"]
    assert (full.rope_scaling, full.rope_theta, full.rope_factor,
            full.rope_original_max, full.rope_beta_fast, full.rope_beta_slow,
            full.rope_attention_factor, full.partial_rotary_factor) == (
        y["rope_type"], y["rope_theta"], y["factor"],
        y["original_max_position_embeddings"], y["beta_fast"], y["beta_slow"],
        y["attention_factor"], y["partial_rotary_factor"])
    assert (window.rope_scaling, window.rope_theta,
            window.partial_rotary_factor) == (
        "", w["rope_theta"], w["partial_rotary_factor"])
    assert (window.sliding_window, full.sliding_window, m.sliding_window) == (
        cfg["sliding_window"], 0, 512)
    assert m.attn_gate == "head" and cfg["gating"] is True
    assert (m.num_experts, m.experts_per_token, m.experts_held) == (
        cfg["num_experts"], cfg["num_experts_per_tok"], (0, 32))
    assert (m.moe_f, m.moe_shared_f, m.moe_routed_scale,
            m.first_dense_layers, m.moe_scoring, m.moe_select_bias) == (
        cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"],
        cfg["moe_routed_scaling_factor"],
        cfg["mlp_layer_types"][:L].count("dense"), "sigmoid", True)
    assert cfg["mlp_layer_types"][0] == "dense"
    assert not m.tie_word_embeddings and m.dtype == "bfloat16"
    assert [(k, n) for k, _, n in m.param_groups] == [
        ("mixers", 5), ("dense_mlps", 1), ("window_mixers", 15),
        ("moe_mlps", 19)]
    # the bytes the file reckons: 5.60 GB of weights, 2.68 + 1.16 GB of pages
    assert m.num_params == 2_799_622_912
    assert "2,799,622,912" in cfg["arithmetic"]
    from rbg_tpu.engine.kvcache import PagedKVCache, window_pool_pages
    s = cfg["server"]
    pages = window_pool_pages(m, s["page_size"], s["max_batch"],
                              s["prefill_chunk"])
    assert f"{pages} pages" in cfg["arithmetic"]
    assert PagedKVCache.hbm_bytes(m, s["num_pages"], s["page_size"]) == \
        5 * 8192 * 16 * 2 * 8 * 128 * 2
    assert PagedKVCache.hbm_bytes(m, pages, s["page_size"], kind="window") \
        == 15 * pages * 65536
    assert s["max_batch"] == 2 + len(cfg["correct"]["other_lens"])
    # the check's sample reaches past a window, and on given-back pages
    c = cfg["correct"]
    assert c["first_len"] >= 768 and c["first_len"] // 2 > m.sliding_window
    assert max(c["other_lens"]) > 2 * m.sliding_window
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "longgen32.json")) as f:
        traffic = json.load(f)
    assert traffic["clients"] == s["max_batch"] == traffic["block"]


def test_what_benchmark_json_gains_keeps_the_files_form():
    """The driver refuses the file before any run for a `why` over 200
    characters (PR 46's first hand-in: 207 and 204), so hold every line the
    cell, its configuration and its metrics add to the rules of form."""
    import re
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
    cell = "laguna-xs2.longgen32"
    config = next(c for c in bench["configs"] if c["name"] == "laguna-xs2")
    work = next(w for w in bench["workloads"] if w["name"] == cell)
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert set(work) == {"name", "config", "traffic", "chips", "why"}
    for line in (config["why"], config["source"], work["why"]):
        assert 1 <= len(line) <= 200 and line.isprintable(), (len(line), line)
    assert (work["config"], work["traffic"], work["chips"]) == (
        "laguna-xs2", "longgen32", 1)
    assert config["file"] == os.path.relpath(CELL_FILE, ROOT)
    for word in [cell, config["name"], work["traffic"], *config["reduced"]]:
        assert name.fullmatch(word), word
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [cell]]
    assert [m["name"] for m in mine] == [
        "kernel.attn_window_roofline", "device.window_attn_share",
        "kv.window_page_fill_share", "kv.window_pages_released_per_step"]
    layers = {m["layer"] for m in bench["per_layer"] if m not in mine}
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert name.fullmatch(m["name"]) and m["layer"] in layers
        assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", m["unit"])
        assert m["moves"] == "out_tok_s" and m["better"] in ("lower", "higher")
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".json"))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
