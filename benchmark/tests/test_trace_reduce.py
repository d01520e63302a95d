"""The trace reduction on a hand-built event list."""

import pytest

from harness import trace_reduce

US = 1000


def _events():
    # device: a while loop [0, 100us) holding two kernels, a gap, a fusion
    return [(0, 100 * US, "while.1"),
            (10 * US, 40 * US, "_block_ragged_call.9"),
            (50 * US, 90 * US, "_block_ragged_call.9"),
            (300 * US, 400 * US, "fusion.7"),
            (400 * US, 450 * US, "fusion.8")]


def test_busy_union_and_per_op_time():
    r = trace_reduce.reduce_events(_events(), [])
    assert r["busy_s"] == pytest.approx(250e-6)
    assert r["ops_total"]["_block_ragged_call.9"] == pytest.approx(70e-6)
    assert r["ops_total"]["while.1"] == pytest.approx(100e-6)
    assert r["ops_self"]["while.1"] == pytest.approx(30e-6)
    assert sum(r["ops_self"].values()) == pytest.approx(r["busy_s"])
    assert r["first_ns"] == 0 and r["last_ns"] == 450 * US


def test_gap_is_named_by_the_innermost_host_events():
    host = [(0, 500 * US, "service.loop"),
            (90 * US, 310 * US, "bench.unified_step"),
            (120 * US, 280 * US, "$engine.py:1493 _build_decode_state"),
            (410 * US, 420 * US, "elsewhere")]
    r = trace_reduce.reduce_events(_events(), host)
    assert r["gaps"] == [("$engine.py:1493 _build_decode_state < "
                          "bench.unified_step", pytest.approx(200e-6))]
    r0 = trace_reduce.reduce_events(_events(), [])
    assert r0["gaps"][0][0] == "unattributed"


def test_idle_share_from_busy_and_window():
    from harness import window
    r = trace_reduce.reduce_events(_events(), [])
    ctx = {"trace": {"devices": [r], "busy_s": r["busy_s"],
                     "window_s": 500e-6}}
    assert window.read_metric({"kind": "trace_idle"}, ctx) == \
        pytest.approx(50.0)


def test_merge():
    assert trace_reduce.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [[0, 3], [5, 8]]


def test_op_name_keeps_the_instruction_and_its_first_shape():
    full = ("%_block_ragged_call.9 = bf16[32,8,32,128]{3,2,1,0:T(8,128)"
            "(2,1)S(1)} custom-call(bf16[32,8,32,128] %x), custom_call_target"
            "=\"tpu_custom_call\"")
    assert trace_reduce.op_name(full) == \
        "_block_ragged_call.9 bf16[32,8,32,128]"
    assert trace_reduce.op_name("%fusion.40 = (bf16[14336]{0}, bf16[2]) "
                                "fusion(...)") == "fusion.40 bf16[14336]"
    assert trace_reduce.op_name("while.1") == "while.1"
