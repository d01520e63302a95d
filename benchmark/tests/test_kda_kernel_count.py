"""The count of the KDA decode kernel (``opsbytes/kda.py::kda_decode_step``)
and the two metric files that read the kernel's operations
(``kernel.kda_step_busy_share``, ``kernel.kda_step_roofline``), in a file
of their own: a PR that adds a count and its metrics adds files here and
edits none."""

import json
import os

import pytest

from harness import opsbytes, serve, window
from test_run_rehearse import ROOT

CELL_FILE = os.path.join(ROOT, "benchmark", "configs",
                         "kimi-linear-48b-a3b.json")
METRICS = ("kernel.kda_step_busy_share", "kernel.kda_step_roofline")
VECTORS = (3 * 32 * 128 + 2 * 32 * 128 + 32) * 4    # q, k, g; v, o; b


@pytest.fixture(scope="module")
def cfg():
    with open(CELL_FILE) as f:
        return json.load(f)


def _spec(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("rows", [1, 5, 16])
def test_a_step_of_single_tokens_moves_each_live_rows_state_once_each_way(
        cfg, rows):
    step = [(1, 300 + 7 * r) for r in range(rows)]
    flops, nbytes = opsbytes.MODELS["kda_decode_step"](cfg, step)
    state = 20 * rows * 32 * 128 * 128 * 4 * 2
    assert nbytes == state + 20 * rows * VECTORS
    assert flops == 20 * rows * 8 * 32 * 128 * 128
    # bytes bound it: 1.64 ms of state (1.67 in all) a step of 16 rows at
    # the chip's 819 GB/s
    peak = opsbytes.peak_for("TPU v5 lite")
    least = opsbytes.least_seconds(opsbytes.MODELS["kda_decode_step"], cfg,
                                   step, peak)
    assert least == nbytes / 819e9
    assert rows != 16 or 1.63e-3 < state / 819e9 < 1.65e-3 < least < 1.68e-3


@pytest.mark.parametrize("step", [[(1, 40), (64, 64)], [(2, 9)], []],
                         ids=["a chunk beside a decode row", "two tokens",
                              "no live row"])
def test_a_step_with_a_longer_row_counts_nothing(cfg, step):
    assert opsbytes.MODELS["kda_decode_step"](cfg, step) == (0, 0)


def test_the_states_bytes_are_the_wire_counters_less_the_tails(cfg):
    """``state_bytes_moved`` adds ``StatePool.row_bytes`` a row a step: the
    state and the convolution's tails, read once and written once."""
    from rbg_tpu.engine.kvcache import StatePool
    mcfg = serve.model_config(cfg, "kimi-count")
    pool = StatePool(mcfg, 2)
    tails = 2 * pool.arrays["conv"].nbytes // pool.slots
    _, nbytes = opsbytes.MODELS["kda_decode_step"](cfg, [(1, 10)] * 3)
    assert nbytes - 3 * 20 * VECTORS == 3 * (pool.row_bytes - tails)


def test_layers_are_the_recurrent_ones_among_those_served(cfg):
    cut = dict(cfg, num_hidden_layers=8)        # layers 1-3, 5-7 recur
    _, nbytes = opsbytes.MODELS["kda_decode_step"](cut, [(1, 10)])
    assert nbytes == 6 * (2 * 32 * 128 * 128 * 4 + VECTORS)


@pytest.mark.parametrize("name", METRICS)
def test_the_metric_files_are_accepted_and_listed_for_the_kimi_cell(name):
    spec = _spec(name)
    window.check_spec(spec, opsbytes.MODELS)
    assert spec["ops"] == ["_kda_decode_call"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert entry["workloads"] == ["kimi-linear.longgen16"]
    assert (entry["layer"], entry["moves"]) == ("kernels", "out_tok_s")


def _ctx(cfg, ops, steps):
    return {"trace": {"devices": [{"ops_total": ops, "ops_self": {}}],
                      "busy_s": 2.0, "window_s": 2.5, "steps": [steps]},
            "cfg": cfg, "peak": opsbytes.peak_for("TPU v5 lite")}


def test_the_roofline_reads_the_kernels_time_against_the_single_token_steps(
        cfg):
    rows = [(1, 500)] * 16
    decode = (0.0, 0.02, "decode_step", rows)
    unified = (0.02, 0.08, "unified_step", rows[:15] + [(64, 64)])
    least = opsbytes.least_seconds(opsbytes.MODELS["kda_decode_step"], cfg,
                                   rows, opsbytes.peak_for("TPU v5 lite"))
    # the kernel's calls carry a suffix; a step that holds a chunk adds no work
    ctx = _ctx(cfg, {"_kda_decode_call.9": 3 * least, "fusion.540": 1.0,
                     "_mla_decode_call.4": 0.3},
               [decode, unified, decode])
    assert window.read_metric(_spec(METRICS[1]), ctx) == pytest.approx(
        100 * 2 / 3)
    assert window.read_metric(_spec(METRICS[0]), ctx) == pytest.approx(
        100 * 3 * least / 2.0)
    with pytest.raises(ValueError, match="above 100"):
        window.read_metric(_spec(METRICS[1]), _ctx(
            cfg, {"_kda_decode_call.9": least / 2}, [decode]))


def test_a_trace_without_the_kernel_reads_nothing(cfg):
    """The parent's program has no such operation: the line leaves both
    metrics out and nothing raises."""
    ctx = _ctx(cfg, {"fusion.540": 1.0, "_mla_decode_call.4": 0.3},
               [(0.0, 0.02, "decode_step", [(1, 500)] * 16)])
    assert window.read_metric(_spec(METRICS[1]), ctx) is None
    assert not window.read_metric(_spec(METRICS[0]), ctx)
