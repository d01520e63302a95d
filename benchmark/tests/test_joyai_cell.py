"""``joyai-llm-flash`` at tiny widths (``rehearse/configs/tiny-joyai.json``):
its reference against the program, the controls that must fail, and the
whole ``run.py --rehearse`` flow with the cell's new metric files. The
cases ``test_reference.py`` and ``test_run_rehearse.py`` would take as one
more parameter, in a file of their own: a PR that adds a configuration may
add files here and edit none."""

import json
import os

import numpy as np
import pytest

from harness import serve
from test_reference import _cfg, _rms, _served
from test_run_rehearse import ROOT, _run

CELLS = "benchmark/tests/rehearse/cells_joyai.json"


def test_program_agrees_with_the_joyai_reference_and_controls_do_not():
    cfg = _cfg("tiny-joyai")
    limit = cfg["correct"]["limit"]
    reference = serve.load_reference(cfg)
    assert reference.__file__.endswith("benchmark/references/joyai_flash.py")
    params = reference.make_params(cfg, 3000000019)
    assert set(params) == {"embed", "lm_head", "final_norm", "dense_blocks",
                           "blocks"}
    prompt = np.random.default_rng(1).integers(
        1, cfg["vocab_size"], 80).tolist()
    toks, lps = _served(cfg, "tiny-joyai", params, prompt, 8)
    assert len(toks) == 8
    ref = reference.chosen_logprobs(cfg, params, prompt, toks)
    assert _rms(lps, ref) <= limit
    for quant in ("bf16", "int8", "fp8"):
        ctl = reference.chosen_logprobs(cfg, params, prompt, toks, quant)
        assert _rms(ctl, ref) > 3 * limit, quant


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "references",
                           "joyai_flash.py")) as f:
        src = f.read()
    code = src.split('"""', 2)[2]            # past the module's docstring
    assert "rbg_tpu" not in code
    assert "base._rope" not in code and "base._moe" not in code


def test_joyai_weights_follow_the_seed_and_the_served_layout():
    cfg = _cfg("tiny-joyai")
    reference = serve.load_reference(cfg)
    a, b = reference.make_params(cfg, 7), reference.make_params(cfg, 7)
    c = reference.make_params(cfg, 2 ** 31 + 7)
    assert np.array_equal(a["blocks"]["moe_up"], b["blocks"]["moe_up"])
    assert not np.array_equal(a["blocks"]["wq_a"], c["blocks"]["wq_a"])
    assert a["blocks"]["router_bias"].dtype == np.float32
    assert a["dense_blocks"]["w_up"].shape == (1, 128, 320)
    s = cfg["assumed"]["e_score_correction_bias_scale"]
    assert abs(float(np.std(a["blocks"]["router_bias"])) - s) < 0.4 * s


@pytest.mark.parametrize("trace", [0, 1])
def test_the_joyai_cell_rehearses_with_its_metric_files(trace):
    r = _run("--rehearse", "--workload", "joyai.closed", "--seed",
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
                          "moe.experts_visited_share", "moe.rows_per_visit"}
        assert 0 < m["moe.experts_visited_share"]["value"] <= 100
        assert 1 <= m["moe.rows_per_visit"]["value"] <= 4    # max_batch 4
    else:
        assert line["metrics"]["out_tok_s"]["value"] > 0
