"""Reduction of client records and the reader kinds."""

import math

import pytest

from harness import opsbytes, window


def _rec(due, ok=True, times=(), counts=(), code=None, sent=None, cls="x",
         error=None):
    return {"due": due, "ok": ok, "times": list(times),
            "counts": list(counts), "code": code,
            "sent": due if sent is None else sent, "class": cls,
            "prompt_len": 10, "want": sum(counts), "error": error,
            "stream": "s"}


def test_refused_request_is_failed_and_ranks_last():
    records = [_rec(1.0, times=[1.5, 1.6], counts=[1, 1]),
               _rec(2.0, times=[2.2, 2.3], counts=[1, 1]),
               _rec(3.0, ok=False, code="overloaded",
                    error="queue full")]
    scalars, series = window.client_readings(records, 10.0)
    assert scalars["client.attempted"] == 3
    assert scalars["client.failed"] == 1 and scalars["client.refused"] == 1
    assert sorted(series["client.ttft_ms"])[-1] == math.inf
    ctx = {"series": series, "cap_ms": 99000.0}
    spec = {"kind": "client_percentile", "series": "client.ttft_ms", "p": 50}
    assert window.read_metric(spec, ctx) == pytest.approx(500.0)
    spec["p"] = 90      # lands on the failure: the cap stands in, not a drop
    assert window.read_metric(spec, ctx) == 99000.0


def test_latency_belongs_to_due_time_and_tokens_to_receive_time():
    records = [_rec(-1.0, times=[0.5, 0.6], counts=[1, 2]),   # due in ramp
               _rec(9.0, times=[9.5, 10.5], counts=[1, 1])]   # ends outside
    scalars, series = window.client_readings(records, 10.0)
    assert scalars["client.attempted"] == 1
    assert scalars["client.tokens_in_window"] == 3 + 1
    assert series["client.ttft_ms"] == [pytest.approx(500.0)]
    # a frame of two tokens is two gaps of half its wait
    _, s2 = window.client_readings([_rec(0.0, times=[0.5, 0.6],
                                         counts=[1, 2])], 10.0)
    assert s2["client.itl_ms"] == [pytest.approx(50.0)] * 2


def test_truncated_request_is_failed():
    r = _rec(1.0, ok=False, times=[1.2], counts=[1],
             error="truncated: 1 of 4 tokens")
    scalars, series = window.client_readings([r], 5.0)
    assert scalars["client.failed"] == 1
    assert series["client.ttft_ms"] == [math.inf]


def test_counter_readers():
    ctx = {"scalars": {"prefill_tokens": 90, "decode_tokens": 10,
                       "steps": 20, "radix_hit_tokens": 0,
                       "open.compile.programs": 140, "compile.programs": 0,
                       "client.tokens_in_window": 500},
           "per_server": [{"joins": 3}, {"joins": 1}], "seconds": 10.0,
           "series": {"samples.waiting": [0, 2, 4]}}
    rd = window.read_metric
    assert rd({"kind": "counter_ratio", "num": ["prefill_tokens",
                                                "decode_tokens"],
               "den": ["steps"]}, ctx) == 5.0
    assert rd({"kind": "counter_ratio", "num": ["radix_hit_tokens"],
               "den": ["radix_hit_tokens", "prefill_tokens"],
               "scale": 100}, ctx) == 0.0
    assert rd({"kind": "counter_at_open", "counter": "compile.programs"},
              ctx) == 140
    assert rd({"kind": "counter_delta", "counter": "compile.programs"},
              ctx) == 0
    assert rd({"kind": "per_second", "counter": "client.tokens_in_window"},
              ctx) == 50.0
    assert rd({"kind": "server_max_over_mean", "counter": "joins"},
              ctx) == 1.5
    assert rd({"kind": "series_mean", "series": "samples.waiting"}, ctx) == 2
    # a reader that finds nothing returns nothing
    assert rd({"kind": "counter_delta", "counter": "absent"}, ctx) is None
    assert rd({"kind": "trace_idle"}, dict(ctx, trace=None)) is None
    with pytest.raises(ValueError):
        rd({"kind": "no_such_reader"}, ctx)


CFG = {"num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
       "hidden_size": 4096, "num_hidden_layers": 16}


def test_opsbytes_counts_live_work_only():
    flops, nbytes = opsbytes.paged_attention(CFG, [(1, 1000)])
    assert flops == 16 * 4 * 32 * 128 * 1000
    assert nbytes == 16 * (1000 * 2 * 8 * 128 * 2 + 2 * 32 * 128 * 2)
    # a 256-token chunk ending at 256: the causal triangle, not the square
    flops2, _ = opsbytes.paged_attention(CFG, [(256, 256)])
    assert flops2 == 16 * 4 * 32 * 128 * (256 * 257 // 2)


def test_peaks_table():
    peak = opsbytes.peak_for("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        opsbytes.peak_for("a device nobody listed")


def _trace_ctx(kernel_seconds, rows):
    return {"trace": {"devices": [{"ops_total": {"_decode_call.3":
                                                 kernel_seconds},
                                   "ops_self": {}}],
                      "busy_s": 1.0, "window_s": 2.0,
                      "steps": [[(0.0, 0.1, "decode_step", rows)]]},
            "cfg": CFG, "peak": opsbytes.peak_for("TPU v5 lite")}


def test_roofline_share_and_its_refusal_above_100():
    spec = {"kind": "trace_roofline", "ops": ["_decode_call"],
            "model": "paged_attention"}
    rows = [(1, 4000)] * 8
    least = opsbytes.least_seconds(opsbytes.paged_attention, CFG, rows,
                                   opsbytes.peak_for("TPU v5 lite"))
    share = window.read_metric(spec, _trace_ctx(least * 4, rows))
    assert share == pytest.approx(25.0)
    with pytest.raises(ValueError, match="above 100"):
        window.read_metric(spec, _trace_ctx(least / 2, rows))
    ctx = _trace_ctx(0.25, rows)
    assert window.read_metric({"kind": "trace_op_share",
                               "ops": ["_decode_call"]}, ctx) == 25.0
    assert window.read_metric({"kind": "trace_idle"}, ctx) == 50.0


def test_silences_are_the_stretches_with_no_token_from_anyone():
    records = [_rec(0.0, times=[1.5, 2.0, 9.0], counts=[1, 1, 1]),
               _rec(0.0, times=[2.5, 11.0], counts=[1, 1])]
    got = window.silences(records, 10.0)
    assert got[0] == (pytest.approx(6.5), 2.5)      # 2.5 s .. 9.0 s
    assert got[1] == (pytest.approx(1.5), 0.0)      # before the first token
    assert sum(d for d, _ in got) == pytest.approx(10.0)
