"""``ouro-2.6b`` at tiny widths (``rehearse/configs/tiny-ouro.json``): its
reference against a few-line loop of this file's own and against the
program, the controls that must fail, the whole ``run.py --rehearse`` flow
with the cell's new metric files (and with every pass on pass 0's pages,
which has to end ``correct`` false), the new count, and the cell's own file
against the catalog row it was made from. The cases ``test_reference.py``
and ``test_run_rehearse.py`` would take as one more parameter, in a file of
their own: a PR that adds a configuration may add files here and edit
none."""

import json
import os
import re

import jax
import numpy as np

from harness import opsbytes, serve
from test_reference import _cfg, _rms, _served
from test_run_rehearse import ROOT, _run

CELLS = "benchmark/tests/rehearse/cells_ouro.json"
CELL_FILE = os.path.join(ROOT, "benchmark", "configs", "ouro-2.6b.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _loop(cfg, params, tokens):
    """The model's equations in a few lines of this file's own (float64
    numpy): log-probabilities ``[len(tokens), vocab]``."""
    z = {"/".join(str(p.key) for p in path): np.asarray(leaf, np.float64)
         for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}
    n, h, hd = len(tokens), cfg["num_attention_heads"], cfg["head_dim"]
    norm = lambda x, w: x / np.sqrt(
        (x * x).mean(-1, keepdims=True) + cfg["rms_norm_eps"]) * w
    ang = np.arange(n)[:, None] / cfg["rope_theta"] ** (
        np.arange(0, hd, 2) / hd)[None]
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    rope = lambda x: np.concatenate(
        [x[..., :hd // 2] * cos - x[..., hd // 2:] * sin,
         x[..., hd // 2:] * cos + x[..., :hd // 2] * sin], -1)
    x = z["embed"][np.asarray(tokens)]
    for _ in range(cfg["total_ut_steps"]):
        for l in range(cfg["num_hidden_layers"]):
            w = lambda name: z["blocks/" + name][l]
            a = norm(x, w("attn_norm"))
            q, k, v = (a @ w(m).T for m in ("wq", "wk", "wv"))
            q, k = (rope(y.reshape(n, h, hd)) for y in (q, k))
            s = np.einsum("thd,shd->hts", q, k) / np.sqrt(hd)
            s = np.where(np.tril(np.ones((n, n), bool))[None], s, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            o = np.einsum("hts,shd->thd", p / p.sum(-1, keepdims=True),
                          v.reshape(n, h, hd)).reshape(n, h * hd)
            x = x + norm(o @ w("wo"), w("attn_post_norm"))
            m = norm(x, w("mlp_norm"))
            g = m @ w("w_gate")
            x = x + norm((g / (1 + np.exp(-g)) * (m @ w("w_up")))
                         @ w("w_down"), w("mlp_post_norm"))
        x = norm(x, z["final_norm"])
    logits = x @ z["lm_head"]
    logits -= logits.max(-1, keepdims=True)
    return logits - np.log(np.exp(logits).sum(-1, keepdims=True))


def test_the_reference_is_the_equations_and_the_program_agrees_with_it():
    cfg = _cfg("tiny-ouro")
    limit = cfg["correct"]["limit"]
    reference = serve.load_reference(cfg)
    assert reference.__file__.endswith("benchmark/references/ouro.py")
    params = reference.make_params(cfg, 3000000019)
    assert set(params) == {"embed", "lm_head", "final_norm", "blocks",
                           "exit_gate"}
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, cfg["vocab_size"], 80).tolist()
    served = rng.integers(1, cfg["vocab_size"], 6).tolist()
    want = _loop(cfg, params, prompt + served)[79:85]
    ref = reference.chosen_logprobs(cfg, params, prompt, served)
    np.testing.assert_allclose(ref, want[np.arange(6), served], atol=2e-5)
    toks, lps = _served(cfg, "tiny-ouro", params, prompt, 8)
    ref = reference.chosen_logprobs(cfg, params, prompt, toks)
    assert len(toks) == 8 and _rms(lps, ref) <= limit
    # kv_int8 rounds every pass's cached K (rotated) and V
    for quant in ("bf16", "int8", "fp8", "kv_int8"):
        ctl = reference.chosen_logprobs(cfg, params, prompt, toks, quant)
        assert _rms(ctl, ref) > 3 * limit, quant
    # a threshold below 1 is refused by name, here as in the program
    for refuses in (lambda c: reference.chosen_logprobs(c, params, prompt,
                                                        toks),
                    lambda c: serve.model_config(
                        dict(c, preset=dict(c["preset"],
                                            early_exit_threshold=0.5)),
                        "tiny-ouro-exits")):
        try:
            refuses(dict(cfg, early_exit_threshold=0.5))
        except ValueError as e:
            assert "early_exit_threshold 0.5" in str(e)
        else:
            raise AssertionError("an early exit was not refused")


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "references", "ouro.py")) as f:
        code = f.read().split('"""', 2)[2]      # past the module's docstring
    assert "rbg_tpu" not in code and "exit_gate" in code


def test_the_files_assumed_scales_reach_the_weights():
    """``embed_init_scale`` and the two post norms' fills are the
    configuration file's own; a file without them gets the default
    module's (0.02, ones); nothing else moves with them."""
    cfg = _cfg("tiny-ouro")
    reference = serve.load_reference(cfg)
    plain = reference.make_params(cfg, 7)
    scaled = reference.make_params({**cfg, "assumed": {
        "embed_init_scale": 1.0, "attn_post_norm_init": 0.25,
        "mlp_post_norm_init": 0.5}}, 7)
    blocks = scaled["blocks"]
    assert np.all(np.asarray(plain["blocks"]["attn_post_norm"]) == 1.0)
    assert np.all(np.asarray(blocks["attn_post_norm"]) == 0.25)
    assert np.all(np.asarray(blocks["mlp_post_norm"]) == 0.5)
    assert np.all(np.asarray(blocks["attn_norm"]) == 1.0)
    np.testing.assert_allclose(np.asarray(scaled["embed"]),
                               np.asarray(plain["embed"]) * 50.0, rtol=1e-6)
    assert abs(float(np.std(np.asarray(scaled["embed"]))) - 1.0) < 0.02
    for name in ("wq", "wo", "w_down"):
        assert np.array_equal(blocks[name], plain["blocks"][name])
    assert np.array_equal(scaled["lm_head"], plain["lm_head"])


def test_ouro_weights_follow_the_seed_and_the_served_layout():
    cfg = _cfg("tiny-ouro")
    reference = serve.load_reference(cfg)
    a, b = reference.make_params(cfg, 7), reference.make_params(cfg, 7)
    c = reference.make_params(cfg, 2 ** 31 + 7)
    assert np.array_equal(a["blocks"]["wq"], b["blocks"]["wq"])
    assert not np.array_equal(a["blocks"]["wq"], c["blocks"]["wq"])
    from rbg_tpu.models import init_params
    own = jax.eval_shape(lambda: init_params(
        serve.model_config(cfg, "tiny-ouro-shapes"), jax.random.key(0)))
    assert jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), a) == \
        jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), own)


def test_the_ouro_cell_rehearses_with_its_metric_files():
    r = _run("--rehearse", "--workload", "ouro.closed", "--seed",
             "2147483659", "--seconds", "5", "--trace", "1", cells=CELLS)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    # device metrics read nothing on the CPU and are left out
    m = line["metrics"]
    assert set(m) == {"engine.tokens_per_step", "setup.compiles_in_window",
                      "kv.page_fill_share"}


def test_every_pass_on_pass_0s_pages_is_not_correct():
    r = _run("--rehearse", "--workload", "ouro-pass0.closed", "--seed", "11",
             "--seconds", "3", "--trace", "0", cells=CELLS)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["attempted"] > 0


def test_the_count_is_a_layers_times_layers_times_passes():
    with open(CELL_FILE) as f:
        cfg = json.load(f)
    count = opsbytes.models()["paged_attention_looped"]
    token, q = 2 * 16 * 128 * 2, 2 * 16 * 128 * 2      # K + V; q in, o out
    # one decode row at 900 tokens: 192 walks of 900 tokens of 8192 B
    flops, nbytes = count(cfg, [(1, 900)])
    assert nbytes == 192 * (900 * token + q) == 192 * 900 * 8192 + 192 * q
    assert flops == 192 * 4 * 16 * 128 * 900
    # a chunk of 256 queries that ends at 300: causal pairs, live rows alone
    flops, _ = count(cfg, [(256, 300)])
    assert flops == 192 * 4 * 16 * 128 * (256 * 300 - 256 * 255 // 2)
    one, two = count(cfg, [(1, 900)]), count(cfg, [(1, 900), (1, 900)])
    assert two == (2 * one[0], 2 * one[1])
    # four times a 48-layer model's that runs its layers once
    once = opsbytes.paged_attention(cfg, [(1, 900)])
    assert one == (4 * once[0], 4 * once[1])


def test_cell_file_holds_the_catalog_and_its_preset_follows_its_keys():
    with open(CELL_FILE) as f:
        cfg = json.load(f)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Ouro-2.6B")
        assert cfg["source"] == row["source_url"]
        assert {k: cfg[k] for k in row["config"]} == row["config"]
    assert cfg["reduced"] == {} == cfg["published"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "ouro-2.6b")
    assert entry["reduced"] == []
    m = serve.model_config(cfg, "ouro-cell-test")
    assert (m.num_layers, m.loop_steps, m.cache_layers) == (
        cfg["num_hidden_layers"], cfg["total_ut_steps"], 192)
    assert m.post_norms and m.exit_gate and not m.by_kind
    assert m.early_exit_threshold == cfg["early_exit_threshold"] == 1
    assert (m.num_heads, m.num_kv_heads, m.head_dim_) == (16, 16, 128)
    assert not m.tie_word_embeddings and m.dtype == "bfloat16"
    # the bytes the file reckons: 5.34 GB of weights, 8.05 GB of pages
    assert m.num_params == 2_667_974_657
    assert "2,667,974,657" in cfg["arithmetic"]
    from rbg_tpu.engine.kvcache import PagedKVCache
    s = cfg["server"]
    assert PagedKVCache.hbm_bytes(m, s["num_pages"], s["page_size"]) == \
        192 * s["num_pages"] * 16 * 2 * 16 * 128 * 2
    assert f"{192 * 16 * 2 * 16 * 128 * 2:,} B a page" in cfg["arithmetic"]
    assert s["max_batch"] == 2 + len(cfg["correct"]["other_lens"])
    assert s["max_batch"] * (s["max_seq_len"] // s["page_size"]) == \
        s["num_pages"]
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "longgen4.json")) as f:
        traffic = json.load(f)
    assert traffic["clients"] == s["max_batch"] == traffic["block"]
    assert traffic["prompt"]["max"] + traffic["output"]["max"] == \
        s["max_seq_len"]
    c = cfg["correct"]
    assert c["first_len"] + c["new_tokens"] <= s["max_seq_len"]
    assert c["first_len"] >= 3 * s["prefill_chunk"]


def test_what_benchmark_json_gains_keeps_the_files_form():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
    cell = "ouro.longgen4"
    config = next(c for c in bench["configs"] if c["name"] == "ouro-2.6b")
    work = next(w for w in bench["workloads"] if w["name"] == cell)
    assert bench["configs"][-1] is config and bench["workloads"][-1] is work
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert set(work) == {"name", "config", "traffic", "chips", "why"}
    for line in (config["why"], config["source"], work["why"]):
        assert 1 <= len(line) <= 200 and line.isprintable(), (len(line), line)
    assert (work["config"], work["traffic"], work["chips"]) == (
        "ouro-2.6b", "longgen4", 1)
    assert config["file"] == os.path.relpath(CELL_FILE, ROOT)
    for word in [cell, config["name"], work["traffic"]]:
        assert name.fullmatch(word), word
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [cell]]
    assert mine == bench["per_layer"][-2:]
    assert [m["name"] for m in mine] == [
        "kernel.attn_looped_roofline", "device.post_norm_share"]
    layers = {m["layer"] for m in bench["per_layer"] if m not in mine}
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert name.fullmatch(m["name"]) and m["layer"] in layers
        assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", m["unit"])
        assert m["moves"] == "out_tok_s" and m["better"] in ("lower", "higher")
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".json"))
    joined = {m["name"] for m in bench["per_layer"]
              if cell in m.get("workloads", ()) and m not in mine}
    assert joined == {"kernel.attn_busy_share", "device.mlp_share"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
