"""``kimi-linear-48b-a3b`` at tiny widths
(``rehearse/configs/tiny-kimi-linear.json``): its reference against the
program, the controls that must fail, the whole ``run.py --rehearse`` flow
with the cell's new metric files, and the cell's own file against the
catalog row it was made from. The cases ``test_reference.py`` and
``test_run_rehearse.py`` would take as one more parameter, in a file of
their own: a PR that adds a configuration may add files here and edit
none."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import serve
from test_reference import _cfg, _rms, _served
from test_run_rehearse import ROOT, _run

CELLS = "benchmark/tests/rehearse/cells_kimi.json"
CELL_FILE = os.path.join(ROOT, "benchmark", "configs",
                         "kimi-linear-48b-a3b.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_program_agrees_with_the_kimi_reference_and_controls_do_not():
    cfg = _cfg("tiny-kimi-linear")
    limit = cfg["correct"]["limit"]
    reference = serve.load_reference(cfg)
    assert reference.__file__.endswith("benchmark/references/kimi_linear.py")
    params = reference.make_params(cfg, 3000000019)
    assert set(params) == {"embed", "lm_head", "final_norm", "kda_mixers",
                           "mixers", "dense_mlps", "moe_mlps"}
    prompt = np.random.default_rng(1).integers(
        1, cfg["vocab_size"], 80).tolist()
    toks, lps = _served(cfg, "tiny-kimi-linear", params, prompt, 8)
    assert len(toks) == 8
    ref = reference.chosen_logprobs(cfg, params, prompt, toks)
    assert _rms(lps, ref) <= limit
    # kv_int8 rounds the latent cache AND the recurrent state
    for quant in ("bf16", "int8", "fp8", "kv_int8"):
        ctl = reference.chosen_logprobs(cfg, params, prompt, toks, quant)
        assert _rms(ctl, ref) > 3 * limit, quant


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "references",
                           "kimi_linear.py")) as f:
        src = f.read()
    code = src.split('"""', 2)[2]            # past the module's docstring
    assert "rbg_tpu" not in code
    assert "base._rope" not in code and "base._moe" not in code


def test_kimi_weights_follow_the_seed_and_the_served_layout():
    cfg = _cfg("tiny-kimi-linear")
    reference = serve.load_reference(cfg)
    a, b = reference.make_params(cfg, 7), reference.make_params(cfg, 7)
    c = reference.make_params(cfg, 2 ** 31 + 7)
    assert np.array_equal(a["kda_mixers"]["kda_qkv"],
                          b["kda_mixers"]["kda_qkv"])
    assert not np.array_equal(a["mixers"]["wq"], c["mixers"]["wq"])
    # the layout is the program's own initialiser's, leaf for leaf
    from rbg_tpu.models import init_params
    own = jax.eval_shape(lambda: init_params(
        serve.model_config(cfg, "tiny-kimi-shapes"), jax.random.key(0)))
    assert jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), a) == \
        jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), own)
    # half-layers in layer order; the held experts alone, a whole router
    assert a["dense_mlps"]["w_up"].shape == (1, 128, 320)
    assert a["moe_mlps"]["moe_up"].shape == (7, 8, 128, 48)
    assert a["moe_mlps"]["router"].shape == (7, 128, 16)
    lo, hi = cfg["assumed"]["kda_a_log_range"]
    a_log = np.asarray(a["kda_mixers"]["kda_a_log"])
    assert a_log.dtype == np.float32 and lo <= a_log.min() < a_log.max() <= hi


def test_the_assumed_draws_spread_the_decay_as_the_file_says():
    """One recurrent layer's decay at the published widths, from the
    file's ranges and the reference's weights: a token's ``exp(g)`` across
    channels. The file says 0.58..0.96 (5th..95th percentile), median
    0.86."""
    with open(CELL_FILE) as f:
        cfg = json.load(f)
    reference = serve.load_reference(cfg)
    z = reference.sizes(cfg)
    d, r, ch = z["d"], z["r"], z["kh"] * z["dk"]
    ks = jax.random.split(jax.random.key(3000000019 & 0x7FFFFFFF), 5)
    f_down = jax.random.normal(ks[0], (d, r)) * 0.02
    f_up = jax.random.normal(ks[1], (r, ch)) * 0.02
    a_log = jax.random.uniform(ks[2], (z["kh"],), jnp.float32, *z["a_log"])
    dt_bias = jax.random.uniform(ks[3], (ch,), jnp.float32, *z["dt_bias"])
    x = jax.random.normal(ks[4], (64, d))       # a normed input
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
        x @ f_down @ f_up + dt_bias).reshape(64, z["kh"], z["dk"])
    lo, mid, hi = np.percentile(np.exp(np.asarray(g)), [5, 50, 95])
    assert 0.55 < lo < 0.61 and 0.84 < mid < 0.88 and 0.95 < hi < 0.975


@pytest.mark.parametrize("trace", [0, 1])
def test_the_kimi_cell_rehearses_with_its_metric_files(trace):
    r = _run("--rehearse", "--workload", "kimi-linear.closed", "--seed",
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
        # 4 rows at most, 2 x (6 x 4 x 32 x 32 x 4 + tails) bytes a row
        assert 0 < m["kda.state_gb_per_step"]["value"] <= 4 * 224256e-9
        assert 0 < m["moe.experts_visited_share"]["value"] <= 100
    else:
        assert line["metrics"]["out_tok_s"]["value"] > 0


def test_cell_file_holds_the_catalog_and_its_preset_follows_its_keys():
    with open(CELL_FILE) as f:
        cfg = json.load(f)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
        assert cfg["source"] == row["source_url"]
        # every key as published: nothing of the catalog's config is cut
        assert {k: cfg[k] for k in row["config"]} == row["config"]
    assert set(cfg["reduced"]) == {"experts_held"} == set(cfg["published"])
    m = serve.model_config(cfg, "kimi-cell-test")
    lin = cfg["linear_attn_config"]
    assert list(m.kda_layers) == lin["kda_layers"] and m.num_layers == 27
    assert sorted(set(range(1, 28)) - set(m.kda_layers)) == \
        lin["full_attn_layers"]
    assert (m.kda_num_heads, m.kda_head_dim, m.kda_conv_kernel) == (
        lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"])
    assert (m.num_experts, m.experts_per_token, m.experts_held) == (
        cfg["num_experts"], cfg["num_experts_per_token"], (0, 16))
    assert (m.moe_f, m.moe_shared_f, m.moe_routed_scale, m.use_rope,
            m.q_lora_rank, m.first_dense_layers) == (
        cfg["moe_intermediate_size"],
        cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
        cfg["routed_scaling_factor"], not cfg["mla_use_nope"], 0,
        cfg["first_k_dense_replace"])
    assert [(k, n) for k, _, n in m.param_groups] == [
        ("kda_mixers", 20), ("dense_mlps", 1), ("moe_mlps", 26),
        ("mixers", 7)]
    # the bytes the file reckons: 4,956,653,952 parameters and the bias
    assert m.num_params == 4_956_653_952 + 26 * 256
    from rbg_tpu.engine.kvcache import PagedKVCache, StatePool
    s = cfg["server"]
    assert PagedKVCache.hbm_bytes(m, s["num_pages"], s["page_size"]) == \
        7 * 640 * 2 * 65536
    assert StatePool.hbm_bytes(m, s["max_batch"]) == 16 * 20 * (
        32 * 128 * 128 * 4 + 3 * 12288 * 2)
