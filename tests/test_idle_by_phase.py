"""``scripts/idle_by_phase.py``: the device's idle gaps put down to the
loop thread's innermost ``engine.*`` / ``service.*`` annotation, on a
hand-built plane list (the shape ``benchmark/harness/xplane.read_planes``
returns)."""

import importlib.util
import os

import pytest

from rbg_tpu.obs import names

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def script():
    spec = importlib.util.spec_from_file_location(
        "idle_by_phase", os.path.join(ROOT, "scripts", "idle_by_phase.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def us(*events):
    """Events given in microseconds, as the reader's nanoseconds."""
    return [(s * 1000, e * 1000, name, "") for s, e, name in events]


def planes():
    """Two steps of a loop thread over a device that runs 100-300, 400-500
    and 900-1000 us: idle 300-400 (the first step's dispatch: its call,
    then its book) and 500-900 (deliver, intake, an idle wait, then the
    second step's pack: a window release inside its rows, and its fill)."""
    loop = us(
        (0, 450, "engine.step#step_num=0#"),
        (10, 290, names.SPAN_ENGINE_PACK),
        (10, 290, names.SPAN_ENGINE_PACK_ROWS),
        (290, 430, names.SPAN_ENGINE_DISPATCH),
        (290, 360, names.SPAN_ENGINE_DISPATCH_CALL),
        (360, 430, names.SPAN_ENGINE_DISPATCH_BOOK),
        (450, 560, names.SPAN_SERVICE_DELIVER),
        (560, 600, names.SPAN_SERVICE_INTAKE),
        (600, 700, names.SPAN_SERVICE_IDLE),
        (710, 1100, "engine.step#step_num=1#"),
        (720, 880, names.SPAN_ENGINE_PACK),
        (720, 800, names.SPAN_ENGINE_PACK_ROWS),
        (730, 790, names.SPAN_KV_WINDOW_RELEASE),
        (800, 880, names.SPAN_ENGINE_PACK_FILL),
        (880, 1000, names.SPAN_ENGINE_DISPATCH),
        (880, 1000, names.SPAN_ENGINE_DISPATCH_CALL),
        # Python frames of the tracer on the same line: not annotations.
        (300, 350, "$engine.py:1800 _unified_step"),
    )
    relay = us((280, 420, "server.relay_send"), (300, 390, "sendall"))
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": us(
                (100, 300, "%fusion.1", ), (150, 200, "%inner"),
                (400, 500, "%fusion.2"), (900, 1000, "%fusion.3"))},
            {"name": "Steps", "events": us((0, 1000, "7"))}]},
        {"name": "/host:CPU", "lines": [
            {"name": "relay", "events": relay},
            {"name": "loop", "events": loop}]},
    ]


def test_innermost_segments_of_nested_events(script):
    segs = script.innermost_segments(
        [(0, 100, "a"), (10, 40, "b"), (20, 30, "c"), (60, 70, "b"),
         (120, 130, "d")])
    assert segs == [(0, 10, "a"), (10, 20, "b"), (20, 30, "c"),
                    (30, 40, "b"), (40, 60, "a"), (60, 70, "b"),
                    (70, 100, "a"), (120, 130, "d")]


def test_the_loop_threads_line_is_the_one_that_holds_engine_step(script):
    loop = script.loop_line(planes())
    assert {n for _, _, n in loop} >= {"engine.step", "service.idle"}
    # Names are stripped of their attributes; no frame, no other thread's
    # event, and no span outside the two families.
    assert all(n.startswith(("engine.", "service.")) for _, _, n in loop)


def test_idle_seconds_go_to_the_innermost_loop_thread_span(script):
    (dev,) = script.attribute(planes())
    assert dev["plane"] == "/device:TPU:0" and dev["steps"] == 2
    assert dev["stretch_s"] == pytest.approx(900e-6)
    assert dev["idle_s"] == pytest.approx(500e-6) and dev["outside_s"] == 0
    got = {n: round(s * 1e6) for n, s in dev["by_span"]}
    assert got == {
        names.SPAN_ENGINE_DISPATCH_CALL: 60 + 20,   # 300-360, 880-900
        names.SPAN_ENGINE_DISPATCH_BOOK: 40,        # 360-400
        names.SPAN_SERVICE_DELIVER: 60,             # 500-560
        names.SPAN_SERVICE_INTAKE: 40,
        names.SPAN_SERVICE_IDLE: 100,
        "engine.step": 10,                          # 710-720, before the pack
        names.SPAN_ENGINE_PACK_ROWS: 80,            # the release inside it too
        names.SPAN_ENGINE_PACK_FILL: 80,
        "unattributed": 10}                         # 700-710, between turns
    assert sum(got.values()) == 500
    assert [s for _, s in dev["by_span"]] == sorted(
        (s for _, s in dev["by_span"]), reverse=True)
    text = script.render(dev)
    assert "named" in text and "98.00 %" in text
    assert "steps 2: 0.450 ms a step, of them idle 0.250 ms" in text


def test_idle_outside_the_loop_threads_trace_is_left_out(script):
    """The profiler records the device while it is being stopped, the
    host no longer: what the device idles there says nothing of a span."""
    cut = planes()
    host = cut[1]["lines"][1]
    host["events"] = [ev for ev in host["events"] if ev[1] <= 600_000]
    (dev,) = script.attribute(cut)
    # The loop thread's last span ends at 600 us: the stretch is 100-600,
    # its idle time 300-400 and 500-600; 600-900 lies outside.
    assert dev["stretch_s"] == pytest.approx(500e-6)
    assert dev["idle_s"] == pytest.approx(200e-6)
    assert dev["outside_s"] == pytest.approx(300e-6)
    assert dev["steps"] == 1
    got = {n: round(s * 1e6) for n, s in dev["by_span"]}
    assert got == {names.SPAN_ENGINE_DISPATCH_CALL: 60,
                   names.SPAN_ENGINE_DISPATCH_BOOK: 40,
                   names.SPAN_SERVICE_DELIVER: 60,
                   names.SPAN_SERVICE_INTAKE: 40}
    assert "outside it idle 0.0003 s" in script.render(dev)


def test_a_profile_without_the_loop_threads_line_is_all_unattributed(script):
    only_device = [p for p in planes() if p["name"].startswith("/device")]
    (dev,) = script.attribute(only_device)
    assert dev["by_span"] == [("unattributed", pytest.approx(500e-6))]
    assert dev["steps"] == 0
