"""``engine.chunk_row_share``: of the rows of the unified steps that
dispatched, the share with more than one query token (a prompt's chunk),
from two wire counters the engine adds at pack time. It says how often a
recurrent layer's walk over chunk rows engages
(``rbg_tpu/models/llama.py::_kda_packed``). In a file of its own: a PR that
adds a metric adds files here and edits none."""

import json
import os

import pytest

from harness import window
from test_run_rehearse import ROOT

NAME = "engine.chunk_row_share"


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           NAME + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("rows,chunk_rows,want", [
    (32 * 357, 357, 100.0 / 32),        # one chunk row a step of 32
    (16 * 50, 75, 9.375), (40, 0, 0.0), (8, 8, 100.0)])
def test_the_share_is_chunk_rows_over_rows_in_percent(spec, rows, chunk_rows,
                                                      want):
    assert spec["kind"] == "counter_ratio"
    ctx = {"scalars": {"unified_rows": rows,
                       "unified_chunk_rows": chunk_rows}}
    assert window.read_metric(spec, ctx) == pytest.approx(want)


@pytest.mark.parametrize("scalars", [
    {}, {"unified_steps_run": 12},                  # a server without them
    {"unified_rows": 0, "unified_chunk_rows": 0}],  # no unified step ran
    ids=["no counter", "an older server", "no unified step"])
def test_without_the_counters_or_a_unified_step_it_reads_nothing(spec,
                                                                 scalars):
    assert window.read_metric(spec, {"scalars": scalars}) is None


def test_the_engine_counts_rows_and_chunk_rows_at_pack_time():
    """Two prompts of 20 tokens in chunks of 8 beside nothing else: every
    unified step's rows are the prefilling ones, and a row counts as a
    chunk row where its chunk is longer than a token."""
    from rbg_tpu.engine import Engine, EngineConfig, SamplingParams
    eng = Engine(EngineConfig(model="tiny", page_size=8, num_pages=64,
                              max_seq_len=128, max_batch=4, prefill_chunk=8,
                              enable_radix_cache=False))
    for n in (20, 17):
        eng.add_request(list(range(1, n + 1)),
                        SamplingParams(max_new_tokens=4, temperature=0.0))
    while eng.has_work():
        eng.step()
    m = eng.metrics
    # chunks of 8, 8, 4 and of 8, 8, 1: five chunk rows in three steps of
    # two rows; the last chunk of one token is no chunk row
    assert m["unified_steps_run"] >= 3
    assert m["unified_rows"] >= 6 and m["unified_chunk_rows"] == 5
    assert m["unified_chunk_rows"] <= m["unified_rows"]


def test_the_benchmark_lists_it_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = (m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "lower",
                     "source": "program_counter", "layer": "engine step",
                     "moves": "out_tok_s"}
