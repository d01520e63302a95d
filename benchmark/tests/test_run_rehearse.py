"""``run.py --rehearse`` end to end on the CPU: the flow the chip runs."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = "benchmark/tests/rehearse/cells.json"


BY_FILES_ALONE = "benchmark/tests/rehearse/cells_by_files_alone.json"


def _run(*extra, timeout=600, cells=CELLS):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # four server processes, a device each
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--benchmark", cells, *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("cell,trace", [
    ("dense.open", 0), ("moe.closed", 0), ("moe.docs", 1),
    ("dense.sessions_x4", 1)])
def test_rehearsal_prints_the_contracts_last_line(cell, trace):
    r = _run("--rehearse", "--workload", cell, "--seed", "2147483659",
             "--seconds", "5", "--trace", str(trace))
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    # a CPU line carries no device metric
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert not {"kernel.attn_roofline", "device.idle_share",
                "kernel.attn_busy_share", "device.peak_hbm_gb"} \
        & set(line["metrics"])
    if trace:
        assert line["metrics"]["setup.compiles_in_window"]["value"] >= 0
        assert "engine.tokens_per_step" in line["metrics"]
    else:
        assert {"ttft_p50_ms", "itl_p50_ms", "out_tok_s", "setup_s"} \
            <= set(line["metrics"])
        for m in line["metrics"].values():
            assert m["value"] > 0
    if cell == "moe.docs":
        assert line["metrics"]["kv.cached_prompt_share"]["value"] > 30
    if cell == "dense.sessions_x4":
        assert line["device"]["count"] == 4
        assert "router.affinity_hit_share" in line["metrics"]


@pytest.mark.parametrize("trace", [0, 1])
def test_a_block_the_default_reference_lacks_is_added_by_files_alone(trace):
    """``tiny-latent-shared``: latent attention and a shared expert beside
    routed experts of their own width, reached through the file's
    ``preset``; its reference module, its kernel count and the roofline
    metric that names it are files under ``rehearse/``. No file of the
    harness knows any of them."""
    r = _run("--rehearse", "--workload", "latent-shared.closed", "--seed",
             "2147483659", "--seconds", "5", "--trace", str(trace),
             cells=BY_FILES_ALONE)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    if trace:
        # device metrics read nothing on the CPU and are left out
        assert set(line["metrics"]) == {"engine.tokens_per_step",
                                        "setup.compiles_in_window"}
    else:
        assert line["metrics"]["out_tok_s"]["value"] > 0
    with open(os.path.join(ROOT, ".bench_out", "latent-shared.closed",
                           "server0.log")) as f:
        assert "bench: weights from seed 2147483659" in f.read()


def test_a_token_altered_where_it_is_produced_is_not_correct():
    """The whole flow with the served path broken underneath: the
    configuration's reference module (``rehearse/references/
    altered_token.py``) alters every fifth token the engine emits, in the
    server process the run times. The run ends, and says ``correct``
    false."""
    r = _run("--rehearse", "--workload", "altered.closed", "--seed", "11",
             "--seconds", "3", "--trace", "0",
             cells="benchmark/tests/rehearse/cells_broken.json")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["attempted"] > 0
    # what was compared stands beside its limit on standard error too
    assert "correct: over 30 positions median" in r.stderr.splitlines()[-1]


def test_without_a_tpu_and_without_rehearse_there_is_no_result():
    r = _run("--workload", "dense.open", "--seed", "1", "--seconds", "2",
             "--trace", "0", timeout=300)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
