"""What ``BENCHMARK.json`` names exists and is understood: fast, no server.

Every configuration loads, its reference module honours the contract, its
preset builds; every metric entry has a file with a known reader kind and
every roofline names a model some file defines. And the errors a
``model_config`` PR will meet first name what is wrong, where the README
says they are raised.
"""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from harness import opsbytes, serve, window

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REHEARSE = "benchmark/tests/rehearse"


def _load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


BENCH = _load("BENCHMARK.json")
CELLS = _load(f"{REHEARSE}/cells_by_files_alone.json")
# (name, file) of the benchmark's configurations and of the rehearsals'
CONFIGS = [(c["name"], c["file"]) for c in BENCH["configs"]] + [
    (c["name"], c["file"])
    for cells in (CELLS, _load(f"{REHEARSE}/cells.json"))
    for c in cells["configs"]]
# (group, metric name, directories searched, opsbytes directories)
METRICS = [(g, m["name"], [d], []) for g, d in (
    ("end_to_end", "benchmark/end_to_end_metrics"),
    ("per_layer", "benchmark/layer_metrics")) for m in BENCH[g]] + [
    ("per_layer", m["name"], CELLS["dirs"]["per_layer"]
     + ["benchmark/layer_metrics"], CELLS["dirs"]["opsbytes"])
    for m in CELLS["per_layer"]]


@pytest.mark.parametrize("name,path", CONFIGS, ids=[c[0] for c in CONFIGS])
def test_configuration_loads_with_its_reference_and_its_preset(name, path):
    cfg = _load(path)
    ref = serve.load_reference(cfg)
    assert callable(ref.make_params) and callable(ref.chosen_logprobs)
    assert ref.CONTROLS and all(isinstance(c, str) for c in ref.CONTROLS)
    mcfg = serve.model_config(cfg, name)
    assert mcfg.name == name and mcfg.vocab_size == cfg["vocab_size"]
    for field, value in cfg.get("preset", {}).items():
        assert getattr(mcfg, field) == value
    # what the harness itself reads of a configuration
    assert set(cfg["server"]) == {"page_size", "num_pages", "max_seq_len",
                                  "max_batch", "prefill_chunk"}
    assert {"limit", "limit_p75", "limit_request", "first_len", "tail_len",
            "other_lens", "new_tokens"} <= set(cfg["correct"])


def test_the_mixtral_preset_is_field_by_field_what_it_was():
    from rbg_tpu.models.config import ModelConfig
    got = serve.model_config(
        _load("benchmark/configs/mixtral-8x7b-v0.1.json"),
        "mixtral-8x7b-v0.1")
    want = ModelConfig(
        name="mixtral-8x7b-v0.1", vocab_size=32000, hidden_size=4096,
        intermediate_size=14336, num_layers=3, num_heads=32, num_kv_heads=8,
        head_dim=128, rope_theta=1000000.0, rms_norm_eps=1e-05,
        max_seq_len=32768, tie_word_embeddings=False, dtype="bfloat16",
        num_experts=8, experts_per_token=2, moe_intermediate_size=0,
        moe_shared_expert=False, moe_shared_expert_size=0, mla=False,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128)
    assert dataclasses.asdict(got) == dataclasses.asdict(want)
    for f in dataclasses.fields(ModelConfig):
        assert type(getattr(got, f.name)) is type(getattr(want, f.name)), f


def test_the_mixtral_file_names_no_reference_and_gets_the_default():
    cfg = _load("benchmark/configs/mixtral-8x7b-v0.1.json")
    assert "reference" not in cfg and "preset" not in cfg
    assert serve.reference_path(cfg) == os.path.join(
        ROOT, "benchmark/harness/reference.py")


def test_an_unknown_preset_field_is_refused_by_name():
    cfg = dict(_load(f"{REHEARSE}/configs/tiny-moe.json"),
               preset={"moe_shared_expert": True, "mamba_d_state": 256})
    with pytest.raises(ValueError, match="mamba_d_state") as e:
        serve.model_config(cfg, "x")
    assert "moe_shared_expert'" not in str(e.value).split("which has")[0]


def test_a_published_key_left_out_has_to_come_from_the_preset():
    cfg = _load(f"{REHEARSE}/configs/tiny-moe.json")
    del cfg["num_key_value_heads"]
    with pytest.raises(KeyError, match="num_key_value_heads"):
        serve.model_config(cfg, "x")
    assert serve.model_config(dict(cfg, preset={"num_kv_heads": 1}),
                              "x").num_kv_heads == 1


@pytest.mark.parametrize("body,match", [
    (None, "no such file"),
    ("CONTROLS = ('int8',)\ndef make_params(cfg, seed): pass\n",
     "chosen_logprobs"),
    ("CONTROLS = ('int8',)\ndef make_params(cfg, seed): pass\n"
     "def chosen_logprobs(cfg, params, prompt, served): pass\n", "quant"),
    ("def make_params(cfg, seed): pass\n"
     "def chosen_logprobs(cfg, params, prompt, served, quant=None): pass\n",
     "CONTROLS")], ids=["missing", "no-function", "no-quant", "no-controls"])
def test_a_reference_outside_the_contract_is_refused_by_name(tmp_path, body,
                                                             match):
    path = "benchmark/references/no_such_model.py"
    if body is not None:
        path = str(tmp_path / "reference_under_test.py")
        with open(path, "w") as f:
            f.write(body)
    with pytest.raises((FileNotFoundError, TypeError), match=match):
        serve.load_reference({"reference": path})


@pytest.mark.parametrize("group,name,dirs,ops_dirs", METRICS,
                         ids=[f"{m[1]}-{len(m[2])}" for m in METRICS])
def test_metric_entry_has_a_file_its_reader_and_its_model(group, name, dirs,
                                                          ops_dirs):
    path = next(p for p in (os.path.join(ROOT, d, name + ".json")
                            for d in dirs) if os.path.exists(p))
    models = opsbytes.models([os.path.join(ROOT, d) for d in ops_dirs])
    window.check_spec(_load(path), models)


def test_every_per_layer_entry_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells


def test_a_kernels_count_is_a_file():
    models = opsbytes.models([os.path.join(ROOT, REHEARSE, "opsbytes")])
    assert {"paged_attention", "latent_attention"} <= set(models)
    assert "latent_attention" not in opsbytes.MODELS      # the tests' own
    cfg = _load(f"{REHEARSE}/configs/tiny-latent-shared.json")
    flops, nbytes = models["latent_attention"](cfg, [(1, 100)])
    assert flops == 2 * 2 * 4 * (2 * 32 + 8) * 100
    assert nbytes == 2 * (100 * 40 * 4 + 4 * 72 * 4)
    # the reader finds it through the run's models, and refuses it without
    spec = _load(f"{REHEARSE}/layer_metrics/kernel.latent_attn_roofline.json")
    peak = opsbytes.peak_for("TPU v5 lite")
    rows = [(1, 4000)] * 4
    least = opsbytes.least_seconds(models["latent_attention"], cfg, rows,
                                   peak)
    ctx = {"trace": {"devices": [{"ops_total": {"_mla_decode_call.3":
                                                least * 5}}],
                     "busy_s": 1.0, "window_s": 2.0,
                     "steps": [[(0.0, 0.1, "decode_step", rows)]]},
           "cfg": cfg, "peak": peak, "models": models}
    assert window.read_metric(spec, ctx) == pytest.approx(20.0)
    with pytest.raises(ValueError, match="latent_attention"):
        window.read_metric(spec, dict(ctx, models=opsbytes.MODELS))


def test_a_model_defined_twice_is_refused(tmp_path):
    (tmp_path / "again.py").write_text(
        "def paged_attention(cfg, rows):\n    return 0, 0\n")
    with pytest.raises(ValueError, match="paged_attention"):
        opsbytes.models([str(tmp_path)])


# ---------------------------------------------------------------------------
# where a run stops: run.py before any server, the server before its engine
# ---------------------------------------------------------------------------


def _cells_with(tmp_path, config=None, metric=None):
    """A copy of the rehearsal's cells file with one thing wrong."""
    cells = json.loads(json.dumps(CELLS))
    if config is not None:
        cfg = dict(_load(cells["configs"][0]["file"]), **config)
        (tmp_path / "config.json").write_text(json.dumps(cfg))
        cells["configs"][0]["file"] = str(tmp_path / "config.json")
    if metric is not None:
        (tmp_path / "kernel.wrong.json").write_text(json.dumps(metric))
        cells["dirs"]["per_layer"].insert(0, str(tmp_path))
        cells["per_layer"].append({"name": "kernel.wrong", "unit": "%"})
    (tmp_path / "cells.json").write_text(json.dumps(cells))
    return str(tmp_path / "cells.json")


def _run(cells, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    cell = CELLS["workloads"][0]["name"]
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--benchmark", cells,
         "--rehearse", "--workload", cell, "--seed", "1", "--seconds", "2",
         "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    log = os.path.join(ROOT, ".bench_out", cell, "server0.log")
    return r, log


@pytest.mark.parametrize("wrong,trace,said", [
    ({"config": {"reference": "benchmark/references/no_such_model.py"}}, 0,
     "no_such_model.py"),
    ({"metric": {"kind": "trace_roofline", "ops": ["x"],
                 "model": "selective_scan"}}, 1, "selective_scan"),
    ({"metric": {"kind": "trace_by_magic"}}, 1, "trace_by_magic")],
    ids=["reference", "roofline-model", "reader-kind"])
def test_run_stops_before_any_server_starts(tmp_path, wrong, trace, said):
    cell = CELLS["workloads"][0]["name"]
    log = os.path.join(ROOT, ".bench_out", cell, "server0.log")
    if os.path.exists(log):
        os.remove(log)
    r, _ = _run(_cells_with(tmp_path, **wrong), trace)
    assert r.returncode != 0 and '"correct"' not in r.stdout
    assert said in r.stderr, r.stderr[-2000:]
    assert not os.path.exists(log)          # no server was started


def test_an_unknown_preset_field_stops_the_server_before_its_engine(tmp_path):
    preset = dict(_load(CELLS["configs"][0]["file"])["preset"],
                  mamba_d_state=256)
    r, log = _run(_cells_with(tmp_path, config={"preset": preset}), 0)
    assert r.returncode != 0 and '"correct"' not in r.stdout
    assert "mamba_d_state" in r.stderr, r.stderr[-2000:]
    with open(log) as f:
        said = f.read()
    assert "mamba_d_state" in said and "bench: weights from seed" not in said
