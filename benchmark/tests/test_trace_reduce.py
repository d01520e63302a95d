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


def test_the_traced_stretch_is_never_shorter_than_the_devices_span():
    from harness import window
    r = trace_reduce.reduce_events(_events(), [])       # spans 450 us
    assert window.traced_stretch(500e-6, [r]) == 500e-6
    # the profiler recorded past the host's stamps: busy stays within
    assert window.traced_stretch(400e-6, [r]) == pytest.approx(450e-6)
    assert window.traced_stretch(1.0, []) == 1.0
    empty = trace_reduce.reduce_events([], [])
    assert window.traced_stretch(1.0, [empty]) == 1.0


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


def _scoped():
    # the same device line with the path the profiler recorded for each
    # operation; the ``while`` has none, as on the chip
    paths = ["", "jit(step)/while/body/attention/pallas_call:",
             "jit(step)/while/body/attention/pallas_call:",
             "jit(step)/while/body/moe/dot_general:",
             "jit(step)/sampler/jit(argsort)/sort:"]
    return [ev + (p,) for ev, p in zip(_events(), paths)]


def test_self_time_by_scope_sums_to_busy():
    r = trace_reduce.reduce_events(_scoped(), [])
    sc = r["scopes_self"]
    assert sc[""] == pytest.approx(30e-6)           # the loop's own time
    assert sc["jit(step)/while/body/attention/pallas_call:"] == \
        pytest.approx(70e-6)
    assert sum(sc.values()) == pytest.approx(r["busy_s"])
    # names are reduced as before beside them
    assert r["ops_self"] == trace_reduce.reduce_events(_events(),
                                                       [])["ops_self"]
    # events without a scope (a trace of before) all fall under ""
    assert trace_reduce.reduce_events(_events(), [])["scopes_self"] == \
        {"": pytest.approx(r["busy_s"])}


def test_scope_share_reads_whole_path_components():
    from harness import window
    r = trace_reduce.reduce_events(_scoped(), [])
    ctx = {"trace": {"devices": [r], "busy_s": r["busy_s"],
                     "window_s": 500e-6}}

    def share(*scopes):
        return window.read_metric({"kind": "trace_scope_share",
                                   "scopes": list(scopes)}, ctx)

    assert share("(^|/)attention(/|$)") == pytest.approx(100 * 70 / 250)
    assert share("(^|/)moe(/|$)") == pytest.approx(100 * 100 / 250)
    assert share("(^|/)sampler(/|$)") == pytest.approx(100 * 50 / 250)
    assert share("(^|/)moe(/|$)", "(^|/)sampler(/|$)") == \
        pytest.approx(100 * 150 / 250)
    # a block the program does not have reads nothing, never 0; neither
    # does a trace whose operations carry no path
    assert share("(^|/)mixer(/|$)") is None
    old = {"trace": {"devices": [trace_reduce.reduce_events(_events(), [])],
                     "busy_s": 1.0, "window_s": 2.0}}
    assert window.read_metric({"kind": "trace_scope_share",
                               "scopes": ["moe"]}, old) is None


def test_the_profile_file_is_read_with_its_metadatas_scope(tmp_path):
    """A two-plane profile written with the reader's own message classes:
    names, clocks and the ``tf_op`` of an event's metadata come back."""
    from harness import xplane
    space = xplane._space_class()()
    dev = space.planes.add(name="/device:TPU:0")
    for key, name in ((1, "device_offset_ps"), (2, "tf_op")):
        dev.stat_metadata.add(key=key).value.name = name
    md = dev.event_metadata.add(key=7).value
    md.name = "%fusion.3 = bf16[8,4096]{1,0} fusion(...)"
    md.stats.add(metadata_id=2, str_value="jit(f)/while/body/moe/dot:")
    dev.event_metadata.add(key=8).value.name = "%while.1 = (s32[]) while()"
    ops = dev.lines.add(name="XLA Ops", timestamp_ns=1000)
    ops.events.add(metadata_id=8, offset_ps=0, duration_ps=90_000_000)
    ops.events.add(metadata_id=7, offset_ps=5_000_000, duration_ps=40_000_000)
    host = space.planes.add(name="/host:CPU")
    host.event_metadata.add(key=1).value.name = "engine.sync"
    host.lines.add(name="python", timestamp_ns=900).events.add(
        metadata_id=1, offset_ps=0, duration_ps=500_000_000)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())

    planes = xplane.read_planes(str(path))
    assert planes[0]["lines"][0]["events"] == [
        (1000, 91000, "%while.1 = (s32[]) while()", ""),
        (6000, 46000, "%fusion.3 = bf16[8,4096]{1,0} fusion(...)",
         "jit(f)/while/body/moe/dot:")]
    devices, host_events, structure = trace_reduce.read_xplane(str(path))
    assert devices["/device:TPU:0"][1] == (
        6000, 46000, "fusion.3 bf16[8,4096]", "jit(f)/while/body/moe/dot:")
    assert host_events == [(900, 500900, "engine.sync")]
    assert structure[0]["lines"] == [{"name": "XLA Ops", "events": 2}]
    r = trace_reduce.reduce_events(devices["/device:TPU:0"], host_events)
    assert r["scopes_self"] == {"": pytest.approx(50e-6),
                                "jit(f)/while/body/moe/dot:":
                                pytest.approx(40e-6)}
