"""``device.copy_share``: the device time of the whole-array copies the
compiler put into the step programs (``copy``, ``copy-start``,
``copy-done``), over busy time. In a file of its own: a PR that adds a
metric adds files here and edits none."""

import json
import os
import re

import pytest

from harness import trace_reduce, window
from test_run_rehearse import ROOT

NAME = "device.copy_share"


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           NAME + ".json")) as f:
        return json.load(f)


def _hlo(name, shape, op):
    """A device event's name as the profiler records it."""
    return f"%{name} = {shape}{{2,1,0:T(8,128)(2,1)}} {op}(%p.1)"


# an instruction as ``trace_reduce.op_name`` makes it, and whether it is a copy
NAMES = [
    ("copy-done bf16[1,9216,2304]", True),
    ("copy-done.1 bf16[1,2304,9216]", True),
    ("copy-start.1 bf16[1,2304,9216]", True),
    ("copy.44 bf16[1,4096,4096]", True),
    ("copy", True),
    ("_kda_decode_call.7 f32[20,16,32,128,128]", False),
    ("dynamic-update-slice.8 bf16[20,16,36864]", False),
    ("fusion.521 bf16[20,16,18432]", False),
    ("copy_fusion.3 bf16[8,128]", False),
    ("constant_dynamic-slice_fusion bf16[8,128]", False),
    ("bitcast_copy_fusion.2 bf16[8,128]", False),
    ("copy-done_fusion bf16[8,128]", False),
]


@pytest.mark.parametrize("name,is_copy", NAMES, ids=[n for n, _ in NAMES])
def test_the_pattern_finds_copies_and_no_fusion_that_holds_the_word(
        spec, name, is_copy):
    assert spec["kind"] == "trace_op_share"
    assert any(re.search(p, name) for p in spec["ops"]) == is_copy


def test_op_name_makes_the_names_the_pattern_reads():
    assert trace_reduce.op_name(_hlo(
        "copy-done", "bf16[1,9216,2304]", "copy-done")) == NAMES[0][0]
    # a copy-start's result is a tuple: the name takes its first shape
    assert trace_reduce.op_name(
        "%copy-start.1 = (bf16[1,2304,9216]{2,1,0:T(8,128)(2,1)S(1)}, "
        "bf16[1,2304,9216]{2,1,0}, u32[]{:S(2)}) copy-start(%p.2)"
    ) == NAMES[2][0]
    assert trace_reduce.op_name(_hlo(
        "copy_fusion.3", "bf16[8,128]", "fusion")) == NAMES[8][0]


def test_the_share_is_the_copies_seconds_over_busy_time(spec):
    ops = {"copy-done bf16[1,9216,2304]": 0.24,
           "copy-done.1 bf16[1,2304,9216]": 0.13,
           "copy-start.1 bf16[1,2304,9216]": 0.01,
           "copy.44 bf16[1,4096,4096]": 0.02,
           "copy_fusion.3 bf16[8,128]": 0.5,
           "_kda_decode_call.7 f32[20,16,32,128,128]": 0.44}
    ctx = {"trace": {"devices": [{"ops_total": ops}], "busy_s": 4.0}}
    assert window.read_metric(spec, ctx) == pytest.approx(10.0)
    # a program without a copy reads 0, a run without a trace nothing
    ctx["trace"]["devices"][0]["ops_total"] = {"fusion.1 f32[8]": 1.0}
    assert window.read_metric(spec, ctx) == 0.0
    assert window.read_metric(spec, {"trace": None}) is None


def test_the_benchmark_lists_it_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = (m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "lower",
                     "source": "device_trace", "layer": "device",
                     "moves": "out_tok_s"}
