"""The step timeline (docs/observability.md "Step timeline"): the phase
clocks and counts the engine and the service loop keep, the ring of step
records behind the ``traces`` op, the late-step records and what they say
of a turn made late on purpose, the relay's clocks, the profiler
annotations' names, and the benchmark's metric files that read the clocks
off the wire."""

import collections
import glob
import json
import math
import os
import re
import resource
import socket
import sys
import time

import jax
import pytest

from rbg_tpu.engine import Engine, EngineConfig, SamplingParams
from rbg_tpu.engine import engine as engine_mod
from rbg_tpu.engine import service as service_mod
from rbg_tpu.engine.protocol import recv_msg, request_once, send_msg
from rbg_tpu.engine.service import EngineService
from rbg_tpu.models import get_config, init_params
from rbg_tpu.obs import names, trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# What engine.pack and engine.dispatch are made of, by the kind of step
# (a decode step has no ``sample``), and the starved-time clocks.
SUB_CLOCKS = {
    "unified": ("t_unified_rows_s", "t_unified_fill_s", "t_unified_upload_s",
                "t_unified_call_s", "t_unified_book_s", "t_unified_sample_s"),
    "decode": ("t_decode_rows_s", "t_decode_fill_s", "t_decode_upload_s",
               "t_decode_call_s", "t_decode_book_s")}
STARVED_SPLIT = ("t_starved_between_s", "t_starved_pack_s",
                 "t_starved_dispatch_s")
STARVED_CLOCKS = ("t_starved_s", "t_starved_max_s") + STARVED_SPLIT
ENGINE_CLOCKS = (("t_step_s", "t_admit_s", "t_pack_s", "t_dispatch_s",
                  "t_sync_s", "t_emit_s", "t_unified_s", "t_decode_s")
                 + SUB_CLOCKS["unified"] + SUB_CLOCKS["decode"]
                 + STARVED_CLOCKS)
PHASE_CLOCKS = ("t_admit_s", "t_pack_s", "t_dispatch_s", "t_sync_s",
                "t_emit_s")
LOOP_CLOCKS = ("t_loop_s", "t_intake_s", "t_deliver_s", "t_idle_s",
               "queue_wait_s", "ttft_s", "t_host_s", "t_host_off_s",
               "t_late_s", "gc_pause_s", "t_relay_send_s", "relay_lag_s")
COUNTS = ("steps", "steps_run", "unified_steps_run", "decode_steps_run",
          "queue_waited", "first_tokens", "kv_live_token_steps",
          "kv_held_slot_steps", "late_steps", "device_waited_steps",
          "gc_collections", "relay_frames", "relay_tokens", "uploads")
# Nothing in a service driven in-process has to move these: no idle turn,
# no collection, and no relay (a connection thread of the server); and a
# device that was never seen finished early starved for nothing.
MAY_STAY_ZERO = ("t_idle_s", "gc_pause_s", "gc_collections",
                 "t_relay_send_s", "relay_lag_s", "relay_frames",
                 "relay_tokens") + STARVED_CLOCKS

# The eleven metric files this timeline brought to the benchmark, and the
# eight that put a late step down to a cause.
NEW_METRICS = (
    "engine.decode_step_ms", "engine.unified_step_ms",
    "engine.prefill_time_share", "engine.host_ms_per_step",
    "engine.sync_wait_share", "service.queue_wait_mean_ms",
    "service.ttft_server_mean_ms", "service.loop_idle_share",
    "kv.page_fill_share", "setup.compile_s", "setup.warmup_s",
    "engine.late_time_share", "engine.late_step_share",
    "engine.host_offcpu_share", "engine.device_waited_share",
    "relay.lag_mean_ms", "relay.tokens_per_frame", "engine.gc_pause_share",
    "relay.send_mean_ms")
# The seventeen that say what the loop thread does inside engine.pack and
# engine.dispatch, and how long the device starved for it.
NEW_METRICS += tuple(
    f"engine.{clock[2:-2]}_ms" for kind in ("unified", "decode")
    for clock in SUB_CLOCKS[kind]) + (
    "engine.uploads_per_step", "engine.starved_ms_per_step",
    "engine.starved_max_ms_per_step", "engine.starved_between_ms",
    "engine.starved_in_pack_ms", "engine.starved_in_dispatch_ms")

# Fields of a late-step record (``Engine.note_late``).
LATE_FIELDS = ("t0", "t_end", "step_num", "kind", "phase", "phase_wall_s",
               "cpu_s", "nvcsw", "nivcsw", "majflt", "minflt",
               "gc_collections", "gc_pause_s", "compiles", "relay_frames",
               "watchdog_late_s", "stack")


@pytest.fixture(scope="module")
def params():
    return init_params(get_config("tiny"), jax.random.key(0))


def engine_config(**kw):
    base = dict(model="tiny", page_size=8, num_pages=64, max_batch=2,
                max_seq_len=128, prefill_chunk=16, use_pallas="never",
                enable_radix_cache=False, decode_buckets=(2,))
    base.update(kw)
    return EngineConfig(**base)


@pytest.fixture
def svc(params):
    s = EngineService(engine_config(), params=params)
    try:
        yield s
    finally:
        s.stop()


def wait_for_turn(svc):
    """Let the loop thread finish the turn that completed a request (the
    caller is woken from inside ``service.deliver``)."""
    seen = svc.timeline["t_loop_s"]
    deadline = time.monotonic() + 5.0
    while svc.timeline["t_loop_s"] == seen and time.monotonic() < deadline:
        time.sleep(0.002)


# ---- (1) clocks --------------------------------------------------------


@pytest.fixture(scope="module")
def three_snapshots(params):
    s = EngineService(engine_config(), params=params)
    try:
        seen = []
        for n in (3, 6, 4):
            s.submit([1, 2, 3, 4, 5], SamplingParams(max_new_tokens=n))
            wait_for_turn(s)
            seen.append(s.stats())
        return seen
    finally:
        s.stop()


@pytest.mark.parametrize("name", ENGINE_CLOCKS + LOOP_CLOCKS + COUNTS)
def test_clock_or_count_is_present_monotone_and_never_negative(
        three_snapshots, name):
    vals = [s[name] for s in three_snapshots]
    assert all(v >= 0 for v in vals), (name, vals)
    assert vals == sorted(vals), (name, vals)
    assert vals[-1] > 0 or name in MAY_STAY_ZERO, (name, vals)


def test_phases_fit_inside_the_step(three_snapshots):
    last = three_snapshots[-1]
    # The phase clocks never overlap, so they sum to no more than the
    # steps' wall time; steps by kind are some of all steps.
    assert sum(last[c] for c in PHASE_CLOCKS) <= last["t_step_s"] + 1e-9
    assert last["t_unified_s"] + last["t_decode_s"] <= last["t_step_s"] + 1e-9


def test_loop_phases_tile_the_loop_threads_wall_time(svc):
    svc.submit([9, 8, 7], SamplingParams(max_new_tokens=5))
    time.sleep(0.05)                       # a few idle turns
    wait_for_turn(svc)
    st = svc.stats()
    parts = (st["t_intake_s"] + st["t_step_s"] + st["t_deliver_s"]
             + st["t_idle_s"])
    assert st["t_idle_s"] > 0
    assert parts <= st["t_loop_s"] + 1e-6
    # What the four parts leave out is a few clock reads a turn.
    assert st["t_loop_s"] - parts < 0.05 * st["t_loop_s"] + 0.01


# ---- (2) counts --------------------------------------------------------


def test_steps_run_counts_only_steps_that_dispatched(params):
    eng = Engine(engine_config(), params=params)
    eng.step()                             # nothing to run
    eng.step()
    m = eng.metrics
    assert (m["steps"], m["steps_run"], m["unified_steps"]) == (2, 0, 0)
    assert len(eng.step_ring) == 0 and m["t_step_s"] > 0

    rid = eng.add_request(list(range(1, 21)), SamplingParams(max_new_tokens=4))
    turns = unified = 0
    while eng.has_work():
        was = m["unified_steps"]
        eng.step()
        turns += 1
        unified += m["unified_steps"] - was
    assert rid not in eng.requests
    # `steps` and `unified_steps` read as they always did: every turn of
    # step(), and every turn that took the unified path.
    assert m["steps"] == 2 + turns and m["unified_steps"] == unified == 2
    # 20 prompt tokens in chunks of 16: two unified steps; the first
    # token comes with the second, the other three from decode steps.
    assert m["unified_steps_run"] == 2
    assert m["decode_steps_run"] >= 3
    assert m["steps_run"] == m["unified_steps_run"] + m["decode_steps_run"]
    assert m["steps_run"] <= turns
    assert [r[6] for r in eng.step_ring][:2] == ["unified", "unified"]
    assert {r[6] for r in eng.step_ring} == {"unified", "decode"}
    # Rows, query tokens and buckets of the first step: one row, 16 tokens.
    first = eng.step_ring[0]
    assert first[7:10] == (1, 16, (2, 16)) and first[10] == 0


def test_split_path_steps_are_prefill_and_decode(params):
    eng = Engine(engine_config(ragged="off"), params=params)
    eng.add_request(list(range(1, 21)), SamplingParams(max_new_tokens=3))
    while eng.has_work():
        eng.step()
    m = eng.metrics
    kinds = [r[6] for r in eng.step_ring]
    assert kinds[:2] == ["prefill", "prefill"] and "decode" in kinds
    assert m["unified_steps_run"] == 0
    assert m["steps_run"] == len(kinds) > m["decode_steps_run"] > 0


# ---- (3) queue wait ----------------------------------------------------


def test_queue_wait_shows_a_delay_before_admission(svc):
    delay = 0.08
    real_pump = svc._pump
    svc._pump = lambda: (time.sleep(delay), real_pump())   # loop thread,
    try:                                   # between submit and the batch row
        svc.submit([5, 6, 7, 8], SamplingParams(max_new_tokens=2))
    finally:
        svc._pump = real_pump
    wait_for_turn(svc)
    st = svc.stats()
    assert st["queue_waited"] == 1 and st["first_tokens"] == 1
    assert st["queue_wait_s"] / st["queue_waited"] >= delay
    # The first token came after the wait, and the server's own TTFT
    # holds both.
    assert st["ttft_s"] / st["first_tokens"] > st["queue_wait_s"]


# ---- (3b) a late step and its cause -------------------------------------

PAUSE_S = 0.12        # over LATE_STEP_S by three watchdog periods


REQUEST = ([7, 7, 3, 1, 2], SamplingParams(max_new_tokens=4))


def warmed(params):
    """A service whose programs for ``REQUEST`` are compiled."""
    s = EngineService(engine_config(), params=params)
    for _ in range(2):
        s.submit(*REQUEST)
    wait_for_turn(s)
    return s


def once(fn, inner):
    """``inner`` with ``fn()`` before its first call only."""
    state = {"done": False}

    def slow_once(*a, **kw):
        if not state["done"]:
            state["done"] = True
            fn()
        return inner(*a, **kw)
    return slow_once


def spin(took=None):
    """Burn ``PAUSE_S`` of this thread's CPU time; ``took`` is given the
    wall seconds that needed."""
    t0 = time.monotonic()
    end = time.thread_time() + PAUSE_S
    while time.thread_time() < end:
        pass
    if took is not None:
        took.append(time.monotonic() - t0)


def late_turn(params, where, pause):
    """Serve ``REQUEST`` on a warmed service with one phase made slow
    once: (stats before, stats after, the new late-step records)."""
    s = warmed(params)
    try:
        before = s.stats()
        obj, attr = {"service.intake": (s, "_pump"),
                     "engine.admit": (s.engine, "_admit")}[where]
        real = getattr(obj, attr)
        setattr(obj, attr, once(pause, real))
        try:
            s.submit(*REQUEST)
        finally:
            setattr(obj, attr, real)
        wait_for_turn(s)
        after = s.stats()
        new = list(s.engine.late_ring)[before["late_steps"]:]
        return before, after, [dict(zip(LATE_FIELDS, r)) for r in new]
    finally:
        s.stop()


def cpu_tick_s():
    """The step of the thread CPU clock ``getrusage`` reads on this host
    (a sandboxed kernel's is 10 ms), found by spinning over one."""
    def read():
        ru = resource.getrusage(resource.RUSAGE_THREAD)
        return ru.ru_utime + ru.ru_stime
    first = read()
    while read() == first:
        pass
    a = read()
    while read() == a:
        pass
    return read() - a


def cpu_far_under_pause():
    """Most CPU seconds a record of a turn that slept ``PAUSE_S`` may
    show: a clock of coarse ticks gives a reading of a few ordinary turns
    a whole tick or two."""
    return PAUSE_S / 4 + 2 * cpu_tick_s()


@pytest.mark.parametrize("where", ["service.intake", "engine.admit"])
def test_a_turn_that_slept_is_late_off_the_cpu_and_its_stack_says_where(
        params, where):
    cpu_bound = cpu_far_under_pause()
    for attempt in range(3):            # a loaded machine may pause a turn
        before, after, new = late_turn(params, where,
                                       lambda: time.sleep(PAUSE_S))
        if (len(new) == 1 and new[0]["cpu_s"] <= cpu_bound
                and new[0]["watchdog_late_s"] < PAUSE_S / 2):
            break
    assert after["late_steps"] - before["late_steps"] == 1 == len(new)
    rec = new[0]
    assert rec["phase"] == where
    assert rec["phase_wall_s"] >= PAUSE_S and rec["cpu_s"] <= cpu_bound
    assert rec["t_end"] - rec["t0"] >= rec["phase_wall_s"]
    assert rec["compiles"] == 0 and rec["gc_pause_s"] < PAUSE_S / 2
    # The watchdog took the loop thread's stack inside the sleep, and
    # was itself on time: the thread stood, not the process.
    assert any(f.endswith(" slow_once") for f in rec["stack"]), rec["stack"]
    assert all(re.fullmatch(r"\S+\.py:\d+ \S+", f) for f in rec["stack"])
    assert len(rec["stack"]) <= service_mod.STACK_FRAMES
    assert rec["watchdog_late_s"] < PAUSE_S / 2
    off = after["t_host_off_s"] - before["t_host_off_s"]
    # All of the sleep but what earlier turns' CPU readings left owing.
    assert PAUSE_S - service_mod._CPU_DEBT_S - 0.01 <= off <= PAUSE_S + 0.1
    # The clock it is a share of covers the same turns, the sleep's too.
    assert after["t_host_s"] - before["t_host_s"] >= off
    late = after["t_late_s"] - before["t_late_s"]
    assert PAUSE_S <= late <= rec["t_end"] - rec["t0"]


def test_a_turn_that_spun_is_late_on_the_cpu(params):
    # Six busy test workers share this machine's cores. A spin that the
    # machine kept off the CPU for a quarter of its own length, by the
    # spin's own two clocks, or a run in which it made another turn late
    # too, measures the machine and not the clocks under test: such an
    # attempt is made again, and the last one is held to the same numbers.
    for attempt in range(5):
        took = []
        before, after, new = late_turn(params, "service.intake",
                                       lambda: spin(took))
        if len(new) == 1 and took[0] - PAUSE_S < PAUSE_S / 4:
            break
    assert after["late_steps"] - before["late_steps"] == 1 == len(new)
    rec = new[0]
    assert rec["phase"] == "service.intake"
    assert rec["cpu_s"] >= PAUSE_S * 0.99
    assert any(f.endswith(" spin") for f in rec["stack"]), rec["stack"]
    # Off the CPU only as long as other threads held the interpreter.
    assert after["t_host_off_s"] - before["t_host_off_s"] < PAUSE_S / 2


class SlowWindow:
    """A pending window's token array that takes its time to arrive."""

    def __init__(self, arr):
        self.arr = arr

    def is_ready(self):
        return False

    def __array__(self, *a, **kw):
        time.sleep(PAUSE_S)
        return jax.device_get(self.arr)


def test_a_sync_wait_that_stalled_is_late_and_named_sync(params, monkeypatch):
    monkeypatch.setattr(service_mod, "SYNC_STALL_S", 0.05)
    s = warmed(params)
    try:
        before = s.stats()
        real = s.engine._emit_pending
        state = {"done": False}

        def slow_fetch(pending):
            if not state["done"]:
                state["done"] = True
                pending = pending._replace(toks=SlowWindow(pending.toks))
            return real(pending)

        s.engine._emit_pending = slow_fetch
        s.submit(*REQUEST)
        wait_for_turn(s)
        after = s.stats()
        new = [dict(zip(LATE_FIELDS, r)) for r in
               list(s.engine.late_ring)[before["late_steps"]:]]
        assert after["late_steps"] - before["late_steps"] == len(new)
        # With the limit this low a loaded machine's ordinary sync wait
        # may pass it too: the stalled fetch is the one that long.
        stalled = [r for r in new if r["phase"] == "engine.sync"
                   and r["phase_wall_s"] >= PAUSE_S]
        assert len(stalled) == 1, new
        rec = stalled[0]
        assert rec["cpu_s"] <= cpu_far_under_pause()
        assert any(f.endswith(" __array__") for f in rec["stack"]), rec
        assert after["t_late_s"] - before["t_late_s"] >= PAUSE_S
        # Waiting for the device is not time the host wanted to run.
        assert after["t_host_off_s"] - before["t_host_off_s"] < PAUSE_S / 2
    finally:
        s.stop()


def test_a_turn_in_which_the_watchdog_woke_late_is_late_whatever_its_host_part(
        params):
    """The process stood while the loop thread waited in sync: its host
    part is short, and the watchdog's own lateness is what says so."""
    s = warmed(params)
    try:
        before = s.stats()
        real_admit = s.engine._admit
        s.engine._admit = lambda: (time.sleep(0.003), real_admit())
        p = s.submit_async([7, 7, 3, 1, 2], SamplingParams(max_new_tokens=100))
        s._wd_stop.wait = once(lambda: time.sleep(PAUSE_S), s._wd_stop.wait)
        s.wait(p, 60.0)
        wait_for_turn(s)
        after = s.stats()
        new = [dict(zip(LATE_FIELDS, r)) for r in
               list(s.engine.late_ring)[before["late_steps"]:]]
        # The loop thread went on turning while the watchdog overslept
        # (a process that stands holds both): the first turn that finds
        # it overdue by LATE_STEP_S takes that sleep, and no later one.
        assert len(new) == 1 == after["late_steps"] - before["late_steps"]
        rec = new[0]
        assert rec["watchdog_late_s"] > service_mod.LATE_STEP_S
        assert rec["phase"] == "engine.sync" and rec["kind"] == "decode"
        assert rec["t_end"] - rec["t0"] < service_mod.LATE_STEP_S
        assert (after["t_late_s"] - before["t_late_s"]
                == pytest.approx(rec["watchdog_late_s"]))
    finally:
        s.stop()


def test_an_ordinary_turn_of_a_warmed_service_records_none(params):
    s = warmed(params)
    try:
        for _ in range(3):              # a loaded machine may pause a turn
            before = s.stats()["late_steps"]
            s.submit(*REQUEST)
            wait_for_turn(s)
            if s.stats()["late_steps"] == before:
                return
        pytest.fail(f"every request had a late step: {list(s.engine.late_ring)[-3:]}")
    finally:
        s.stop()


def test_a_first_step_that_compiles_is_late_and_says_so(svc):
    svc.submit(*REQUEST)
    wait_for_turn(svc)
    rec = dict(zip(LATE_FIELDS, svc.engine.late_ring[0]))
    assert rec["compiles"] > 0 and rec["phase"] == "engine.dispatch"
    assert rec["kind"] == "unified" and rec["step_num"] == 0
    assert svc.stats()["late_steps"] == len(svc.engine.late_ring) >= 1


def test_late_ring_is_bounded_and_stop_joins_the_watchdog(params):
    s = EngineService(engine_config(), params=params)
    assert s.engine.late_ring.maxlen == engine_mod.LATE_RING == 64
    assert s._watchdog.is_alive() and s._watchdog.daemon
    s.stop()
    assert not s._watchdog.is_alive() and not s._thread.is_alive()


# ---- (3c) the steps the device waited for --------------------------------


class FakeWindow:
    """The pending window's token array with a readiness of our choosing
    (a value, or what a call says each time it is asked)."""

    def __init__(self, arr, ready):
        self.arr, self.ready = arr, ready

    def is_ready(self):
        return self.ready() if callable(self.ready) else self.ready

    def __array__(self, *a, **kw):
        return jax.device_get(self.arr)


@pytest.mark.parametrize("ready", [True, False])
def test_device_waited_counts_a_dispatch_with_nothing_left_running(
        params, ready):
    eng = Engine(engine_config(), params=params)
    eng.add_request([3, 1, 4, 1, 5], SamplingParams(max_new_tokens=40))
    m = eng.metrics
    while m["decode_steps_run"] < 3:
        eng.step()
    # The first step of all found nothing pending, so the device waited for
    # it; every later one was dispatched with the step before it unread,
    # the decode step after the prompt's unified step too.
    assert m["device_waited_steps"] >= 1
    assert m["lagged_steps"] == m["steps_run"] - 1
    eng._pending = eng._pending._replace(
        toks=FakeWindow(eng._pending.toks, ready))
    was, steps = m["device_waited_steps"], m["steps_run"]
    eng.step()
    assert m["steps_run"] == steps + 1
    assert m["device_waited_steps"] - was == (1 if ready else 0)
    assert m["device_waited_steps"] <= m["steps_run"]


# ---- (3d) what pack and dispatch are made of ---------------------------


def run_steps(eng, until):
    """Step ``eng`` until ``until()``: per step that dispatched, (kind,
    what each counter moved by)."""
    m = eng.metrics
    steps = []
    while not until():
        was = dict(m)
        eng.step()
        if eng._dispatched is not None:
            steps.append((eng._dispatched[0],
                          {k: v - was[k] for k, v in m.items()
                           if isinstance(v, (int, float))}))
    return steps


@pytest.fixture(scope="module")
def mixed_run(params):
    """Unified and decode steps of one engine: a prompt of two chunks,
    its decode steps, and a second prompt that joins them."""
    eng = Engine(engine_config(), params=params)
    eng.add_request(list(range(1, 21)), SamplingParams(max_new_tokens=24))
    m = eng.metrics
    steps = run_steps(eng, lambda: m["decode_steps_run"] >= 3)
    eng.add_request(list(range(30, 50)), SamplingParams(max_new_tokens=6))
    steps += run_steps(eng, lambda: not eng.has_work())
    return eng, steps


@pytest.mark.parametrize("kind", ["unified", "decode"])
def test_sub_phase_clocks_tile_pack_and_dispatch(mixed_run, kind):
    eng, steps = mixed_run
    mine = [d for k, d in steps if k == kind]
    assert len(mine) >= 3
    other = "decode" if kind == "unified" else "unified"
    for d in mine:
        subs = sum(d[c] for c in SUB_CLOCKS[kind])
        assert subs == pytest.approx(d["t_pack_s"] + d["t_dispatch_s"],
                                     abs=1e-9)
        # A step's pack and dispatch are its own kind's sub-phases alone,
        # and each of the two phases had one open throughout.
        assert all(d[c] == 0 for c in SUB_CLOCKS[other])
        assert d[f"t_{kind}_rows_s"] > 0 and d[f"t_{kind}_call_s"] > 0
        assert d[f"t_{kind}_book_s"] > 0
        assert (d["t_unified_sample_s"] > 0) == (kind == "unified")
    # The state's build and the pack's lines were filled and put.
    assert sum(d[f"t_{kind}_fill_s"] for d in mine) > 0
    assert sum(d[f"t_{kind}_upload_s"] for d in mine) > 0


def test_the_eleven_sub_phase_clocks_sum_to_the_two_phases(mixed_run):
    m = mixed_run[0].metrics
    eleven = SUB_CLOCKS["unified"] + SUB_CLOCKS["decode"]
    assert len(eleven) == 11
    assert sum(m[c] for c in eleven) == pytest.approx(
        m["t_pack_s"] + m["t_dispatch_s"], abs=1e-9)


def test_split_path_steps_get_no_marks(params):
    eng = Engine(engine_config(ragged="off"), params=params)
    eng.add_request(list(range(1, 21)), SamplingParams(max_new_tokens=3))
    while eng.has_work():
        eng.step()
    m = eng.metrics
    # The prompt's chunks ran on the split path: their time stays in the
    # two phases' clocks; the fused windows after them are marked.
    assert all(m[c] == 0 for c in SUB_CLOCKS["unified"])
    assert 0 < sum(m[c] for c in SUB_CLOCKS["decode"]) \
        < m["t_pack_s"] + m["t_dispatch_s"]


# ---- (3e) how long the device starved ------------------------------------

NAP_S = 0.02


def engine_before_a_step_of(params, kind):
    """An engine with a step in flight whose next step is of ``kind``."""
    eng = Engine(engine_config(), params=params)
    eng.add_request([3, 1, 4, 1, 5], SamplingParams(max_new_tokens=40))
    while eng.metrics["decode_steps_run"] < 3:
        eng.step()
    if kind == "unified":
        eng.add_request([2, 7, 1, 8, 2, 8], SamplingParams(max_new_tokens=4))
    return eng


def starved_by_one_step(eng, kind, ready):
    """What the starved clocks moved by over one step taken ``NAP_S`` after
    the loop last looked at the step in flight (as it does at the end of
    ``service.deliver``), whose tokens are ready as ``ready()`` says."""
    m = eng.metrics
    eng._pending = eng._pending._replace(
        toks=FakeWindow(eng._pending.toks, ready))
    was = {c: m[c] for c in STARVED_CLOCKS}
    eng.probe(time.monotonic())
    time.sleep(NAP_S)
    eng.step()
    assert eng.step_ring[-1][6] == kind
    return {c: m[c] - was[c] for c in STARVED_CLOCKS}


@pytest.mark.parametrize("kind", ["unified", "decode"])
def test_a_step_in_flight_seen_finished_starves_the_device_until_the_next(
        params, kind):
    eng = engine_before_a_step_of(params, kind)
    d = starved_by_one_step(eng, kind, True)
    # Seen finished before the nap: all of the nap is the device's idle
    # time, and it passed before the pack began.
    assert d["t_starved_s"] >= NAP_S and d["t_starved_between_s"] >= NAP_S
    assert d["t_starved_pack_s"] > 0 and d["t_starved_dispatch_s"] > 0
    assert sum(d[c] for c in STARVED_SPLIT) == pytest.approx(
        d["t_starved_s"], abs=1e-9)
    assert d["t_starved_s"] <= d["t_starved_max_s"] + 1e-9


@pytest.mark.parametrize("kind", ["unified", "decode"])
def test_a_step_still_running_at_the_next_call_starves_nothing(params, kind):
    eng = engine_before_a_step_of(params, kind)
    d = starved_by_one_step(eng, kind, False)
    # The probe at the call's return found it running: the program was
    # queued behind it, whatever the host took.
    assert all(v == 0 for v in d.values()), d


@pytest.mark.parametrize("kind", ["unified", "decode"])
def test_a_step_that_finished_between_two_probes_moves_the_upper_bound_alone(
        params, kind):
    """Running at the entry of engine.dispatch, finished when the program's
    call returned: the device idled for some of the call or none of it."""
    eng = engine_before_a_step_of(params, kind)
    done = []
    real = eng._put_pools
    eng._put_pools = lambda *a, **kw: (done.append(1), real(*a, **kw))[1]
    t_was = eng.metrics[f"t_{kind}_call_s"]
    d = starved_by_one_step(eng, kind, lambda: bool(done))
    assert d["t_starved_s"] == 0 and all(d[c] == 0 for c in STARVED_SPLIT)
    # One probe interval: no more than the call sub-phase.
    assert 0 < d["t_starved_max_s"] <= (eng.metrics[f"t_{kind}_call_s"]
                                        - t_was) + 1e-9


@pytest.mark.parametrize("kind", ["unified", "decode"])
def test_starved_parts_sum_to_the_lower_bound_under_the_upper(mixed_run,
                                                              kind):
    eng, steps = mixed_run
    for k, d in steps:
        if k == kind:
            assert all(d[c] >= 0 for c in STARVED_CLOCKS), d
            assert sum(d[c] for c in STARVED_SPLIT) == pytest.approx(
                d["t_starved_s"], abs=1e-9)
            assert d["t_starved_s"] <= d["t_starved_max_s"] + 1e-9


def test_with_nothing_in_flight_both_bounds_run_from_the_read_that_emptied(
        params):
    eng = Engine(engine_config(), params=params)
    m = eng.metrics
    eng.add_request([3, 1, 4], SamplingParams(max_new_tokens=3))
    while eng.has_work():
        eng.step()
    # The first step of all had nothing before it: no starved time. The
    # request's last read emptied the pipeline, and the next request's
    # first step comes a nap after it.
    assert eng._pending is None
    was = {c: m[c] for c in STARVED_CLOCKS}
    time.sleep(NAP_S)
    eng.add_request([2, 7, 1], SamplingParams(max_new_tokens=2))
    eng.step()
    d = {c: m[c] - was[c] for c in STARVED_CLOCKS}
    assert d["t_starved_s"] == d["t_starved_max_s"] >= NAP_S
    assert d["t_starved_between_s"] >= NAP_S


def test_a_service_that_idled_adds_none_of_the_idle(params):
    idle_s = 0.6
    s = warmed(params)
    try:
        before = s.stats()
        time.sleep(idle_s)
        s.submit(*REQUEST)
        wait_for_turn(s)
        after = s.stats()
        assert after["t_idle_s"] - before["t_idle_s"] > idle_s / 2
        assert after["steps_run"] > before["steps_run"]
        d = {c: after[c] - before[c] for c in STARVED_CLOCKS}
        assert 0 <= d["t_starved_s"] <= d["t_starved_max_s"] < idle_s / 2, d
        assert sum(d[c] for c in STARVED_SPLIT) == pytest.approx(
            d["t_starved_s"], abs=1e-9)
    finally:
        s.stop()


# ---- (3f) the puts a step makes -------------------------------------------


class CountingJnp:
    """``jax.numpy`` with its ``asarray`` counted."""

    def __init__(self, real):
        self.real, self.puts = real, 0

    def __getattr__(self, name):
        return getattr(self.real, name)

    def asarray(self, *a, **kw):
        self.puts += 1
        return self.real.asarray(*a, **kw)


@pytest.mark.parametrize("kind", ["unified", "decode"])
def test_uploads_counts_every_put_of_a_step_and_the_helper_makes_them_all(
        params, kind, monkeypatch):
    eng = engine_before_a_step_of(params, kind)
    m = eng.metrics
    counted = CountingJnp(engine_mod.jnp)
    monkeypatch.setattr(engine_mod, "jnp", counted)
    helper = []
    real = eng._put
    eng._put = lambda a: (helper.append(a.shape), real(a))[1]
    per_step = []
    for _ in range(8 if kind == "decode" else 1):
        was, puts, made = m["uploads"], counted.puts, len(helper)
        eng.step()
        assert eng.step_ring[-1][6] == kind
        # No put of the step went around the helper, the sampler's key
        # lines included.
        assert m["uploads"] - was == len(helper) - made == counted.puts - puts
        per_step.append(m["uploads"] - was)
    if kind == "unified":
        # The seven packed lines, the head's rows, the key positions, the
        # three lines of the rows' keys and the four sampling lines.
        assert per_step == [16]
    else:
        # A batch that did not change puts its page table when a row took
        # a page, and nothing else.
        assert set(per_step) == {0, 1} and per_step.count(0) >= 4


# ---- (4) the ring ------------------------------------------------------


def test_ring_is_bounded_and_ordered(params):
    eng = Engine(engine_config(), params=params)
    assert eng.step_ring.maxlen == engine_mod.STEP_RING
    eng.step_ring = collections.deque(maxlen=6)
    eng.add_request([3, 1, 4, 1, 5], SamplingParams(max_new_tokens=12))
    while eng.has_work():
        eng.step()
    ring = list(eng.step_ring)
    assert len(ring) == 6 and eng.metrics["steps_run"] > 6
    for rec in ring:
        stamps = rec[:6]
        assert list(stamps) == sorted(stamps), rec
    assert [r[0] for r in ring] == sorted(r[0] for r in ring)
    nums = [r[10] for r in ring]
    assert nums == list(range(nums[0], nums[0] + 6))
    assert nums[-1] == eng.metrics["steps_run"] - 1

    everything = eng.steps_since(0.0)
    assert [tuple(r) for r in everything["steps"]] == ring
    assert everything["steps_dropped"] == eng.metrics["steps_run"] - 6
    # A cursor inside the ring: only later records, nothing missed.
    later = eng.steps_since(ring[2][0])
    assert [r[10] for r in later["steps"]] == nums[3:]
    assert later["steps_dropped"] == 0
    assert eng.steps_since(ring[-1][0]) == {
        "steps": [], "steps_dropped": 0, "late_steps": []}


# ---- (5) cache held against cache used ----------------------------------


def test_live_tokens_never_exceed_held_slots(svc):
    ps = [svc.submit_async(list(range(1, n)), SamplingParams(max_new_tokens=9))
          for n in (8, 30)]
    for p in ps:
        svc.wait(p, 60.0)
    m = svc.engine.metrics
    assert 0 < m["kv_live_token_steps"] <= m["kv_held_slot_steps"]
    # Pages are reserved for the prompt and one token, then one at a time:
    # what is held is never a page and a prompt's chunk more than is used.
    assert m["kv_held_slot_steps"] < 2 * m["kv_live_token_steps"]


# ---- (5b) experts visited against expert slots --------------------------


@pytest.mark.parametrize("ep", [1, 2])
def test_decode_steps_count_the_experts_they_visited(ep):
    """A decode step of an expert model reads hit experts only and says
    how many (``llama._moe_mlp_hit``); a unified step is dense by shape,
    every step is where a mesh axis shards the experts, and a dense model
    has none: these move neither counter."""
    from rbg_tpu.parallel import make_mesh
    mcfg = get_config("tiny-moe")
    slots = mcfg.num_experts * mcfg.num_layers
    eng = Engine(engine_config(model="tiny-moe", max_batch=1,
                               decode_buckets=(1,)),
                 params=init_params(mcfg, jax.random.key(0)),
                 mesh=make_mesh(dp=1, sp=1, ep=ep, tp=1) if ep > 1 else None)
    eng.add_request([3, 1, 4, 1, 5], SamplingParams(max_new_tokens=6))
    m = eng.metrics
    while not m["decode_steps_run"]:
        eng.step()                         # the prompt's unified step(s)
        assert (m["moe_expert_slots"], m["moe_experts_visited"]) == (0, 0)
    while eng.running or eng.waiting:
        eng.step()
    if ep > 1:
        assert (m["moe_expert_slots"], m["moe_experts_visited"]) == (0, 0)
        return
    # The lagged fetch counts a step when its tokens are read; the drain
    # that ends the request reads the last one.
    steps = m["decode_steps_run"]
    assert m["moe_expert_slots"] == steps * slots
    # One live row of top-k visits k experts a layer, never more.
    assert m["moe_experts_visited"] == (
        steps * mcfg.num_layers * mcfg.experts_per_token)
    assert 0 < m["moe_experts_visited"] <= m["moe_expert_slots"]


def test_a_dense_model_reports_no_experts(svc):
    svc.submit([1, 2, 3, 4, 5], SamplingParams(max_new_tokens=4))
    st = svc.stats()
    assert st["decode_steps_run"] > 0
    assert (st["moe_expert_slots"], st["moe_experts_visited"]) == (0, 0)


# ---- (6) annotation names ----------------------------------------------


def test_every_phase_annotation_is_cataloged():
    phases = set(engine_mod._Phase.SPANS)
    assert len(phases) == len(engine_mod._Phase.CLOCKS) == 5
    loop = {names.SPAN_SERVICE_INTAKE, names.SPAN_SERVICE_DELIVER,
            names.SPAN_SERVICE_IDLE, names.SPAN_ENGINE_STEP,
            names.SPAN_ENGINE_LATE_STEP, names.SPAN_SERVER_RELAY_SEND}
    assert phases | loop <= names.SPANS
    subs = engine_mod._Phase.SUB_SPANS
    assert subs == (names.SPAN_ENGINE_PACK_ROWS, names.SPAN_ENGINE_PACK_FILL,
                    names.SPAN_ENGINE_PACK_UPLOAD,
                    names.SPAN_ENGINE_DISPATCH_CALL,
                    names.SPAN_ENGINE_DISPATCH_BOOK,
                    names.SPAN_ENGINE_DISPATCH_SAMPLE)
    # Each sub-phase is named under its phase, and has a clock a kind of
    # step that runs it.
    assert [s.rsplit(".", 1)[0] for s in subs] == (
        [names.SPAN_ENGINE_PACK] * 3 + [names.SPAN_ENGINE_DISPATCH] * 3)
    assert engine_mod._Phase.SUB_CLOCKS == SUB_CLOCKS
    for kind, clocks in SUB_CLOCKS.items():
        assert [c[len(f"t_{kind}_"):-2] for c in clocks] == [
            s.rsplit(".", 1)[1] for s in subs[:len(clocks)]]
    assert {n for n in names.SPANS
            if n.startswith("engine.") and n != names.SPAN_ENGINE_OP} \
        == phases | set(subs) | {names.SPAN_ENGINE_STEP,
                                 names.SPAN_ENGINE_LATE_STEP}
    # The host phases a late-step record weighs: all but sync and idle.
    assert service_mod._LATE_PHASES == service_mod._HOST_PHASES + (
        names.SPAN_ENGINE_SYNC,)
    assert not set(subs) & set(service_mod._LATE_PHASES)
    assert set(service_mod._HOST_PHASES) == (phases | loop) - {
        names.SPAN_ENGINE_SYNC, names.SPAN_SERVICE_IDLE,
        names.SPAN_ENGINE_STEP, names.SPAN_ENGINE_LATE_STEP,
        names.SPAN_SERVER_RELAY_SEND}


def test_every_cataloged_span_is_entered_somewhere():
    """The lint's other direction: a name in ``SPANS`` that no call site
    of the program passes to the tracer is as wrong as one entered and
    not cataloged."""
    consts = {k for k, v in vars(names).items()
              if k.startswith("SPAN_") and v in names.SPANS}
    assert len(consts) == len(names.SPANS)
    used = set()
    for path in glob.glob(os.path.join(ROOT, "rbg_tpu", "**", "*.py"),
                          recursive=True):
        if not path.endswith(os.path.join("obs", "names.py")):
            used |= set(re.findall(r"\bSPAN_[A-Z_]+\b", open(path).read()))
    assert consts <= used, sorted(consts - used)


def test_strict_mode_rejects_an_uncataloged_annotation():
    was = trace._CFG.strict
    trace.configure(strict=True)
    try:
        with trace.annotation(names.SPAN_ENGINE_PACK):
            pass
        with trace.annotation(names.SPAN_ENGINE_STEP, step_num=3) as ann:
            ann.set_metadata(kind="decode", rows=2)
        with pytest.raises(ValueError, match="not cataloged"):
            trace.annotation("engine.pak")  # lint: allow[span-name-registry] strict-mode negative test needs an uncataloged literal
    finally:
        trace.configure(strict=was)


def test_strict_mode_serves_a_request(params):
    """Every annotation the serving loop makes passes the strict check."""
    was = trace._CFG.strict
    trace.configure(strict=True)
    s = EngineService(engine_config(), params=params)
    try:
        toks, _ = s.submit([2, 4, 6], SamplingParams(max_new_tokens=3))
        assert len(toks) == 3
    finally:
        s.stop()
        trace.configure(strict=was)


def test_span_lint_checks_annotation_call_sites(tmp_path):
    from rbg_tpu.analysis.core import run_lint
    from rbg_tpu.analysis.rules import make_rules
    src = tmp_path / "phases.py"
    src.write_text(
        "from rbg_tpu.obs import names, trace\n"
        "def good():\n"
        "    with trace.annotation(names.SPAN_ENGINE_SYNC):\n"
        "        pass\n"
        "def bad():\n"
        "    with trace.annotation('engine.sink'):\n"
        "        pass\n")
    found = [f for f in run_lint([str(src)],
                                 make_rules(["span-name-registry"]),
                                 skip_fixture_dirs=False)
             if f.rule == "span-name-registry"]
    assert [f.line for f in found] == [6], [f.render() for f in found]


# ---- (4, 7, 8) over the wire -------------------------------------------


@pytest.fixture(scope="module")
def wire():
    """A served tiny engine: the ``metrics`` reply before and after some
    traffic, as the benchmark reads a window, and the server itself."""
    from conftest import SpawnedEngineServer
    srv = SpawnedEngineServer(
        "--model", "tiny", "--page-size", "8", "--num-pages", "128",
        "--max-seq-len", "256", "--max-batch", "2", "--prefill-chunk", "16",
        "--use-pallas", "never")
    with srv:
        def ask(obj, timeout=120):
            reply, _, _ = request_once(srv.addr, obj, timeout=timeout)
            assert reply is not None and "error" not in reply, reply
            return reply

        ask({"op": "warmup"}, 600)
        t_mid = time.monotonic()
        before = ask({"op": "metrics"})["metrics"]
        for i in range(3):
            ask({"op": "generate", "prompt": list(range(1, 20 + i)),
                 "max_new_tokens": 6})
        # A shape the warm-up never met: a program compiled after the
        # first snapshot (the embedding program of a 3-row batch).
        ask({"op": "embed", "prompts": [[1, 2, 3]] * 3})
        # One request through the relay, so that its clocks have moved.
        streamed = stream(srv.addr, {"op": "generate", "stream": True,
                                     "prompt": list(range(1, 24)),
                                     "max_new_tokens": 9})
        after = ask({"op": "metrics"})["metrics"]
        yield {"ask": ask, "before": before, "after": after, "t_mid": t_mid,
               "addr": srv.addr, "streamed": streamed}


def stream(addr, obj):
    """The token frames of one streamed request, as the relay sent them."""
    host, port = addr.rsplit(":", 1)
    frames = []
    with socket.create_connection((host, int(port)), timeout=120) as sock:
        send_msg(sock, obj)
        while True:
            frame, _, _ = recv_msg(sock)
            assert frame is not None and "error" not in frame, frame
            if frame["done"]:
                return frames
            frames.append(frame["tokens"])


def test_traces_op_returns_the_steps_after_the_cursor(wire):
    ask = wire["ask"]
    plain = ask({"op": "traces"})
    assert "steps" not in plain and "recent" in plain
    every = ask({"op": "traces", "steps_since": 0})
    steps = every["steps"]
    assert every["steps_dropped"] == 0
    assert len(steps) == wire["after"]["steps_run"] > 6
    assert all(len(r) == 11 and r[6] in ("unified", "decode") for r in steps)
    # The traffic's steps lie after the warm-up's; a cursor between the
    # two gets exactly those.
    late = ask({"op": "traces", "steps_since": wire["t_mid"]})["steps"]
    assert 0 < len(late) < len(steps)
    assert late == steps[-len(late):]
    assert all(r[0] > wire["t_mid"] for r in late)
    assert len(late) == (wire["after"]["steps_run"]
                         - wire["before"]["steps_run"])
    assert ask({"op": "traces", "steps_since": steps[-1][0]})["steps"] == []


def test_traces_op_returns_the_late_steps_by_the_same_cursor(wire):
    ask = wire["ask"]
    assert "late_steps" not in ask({"op": "traces"})
    late = ask({"op": "traces", "steps_since": 0})["late_steps"]
    # The warm-up's first steps compiled their programs: late, each.
    assert len(late) == min(64, wire["after"]["late_steps"]) > 0
    assert all(len(r) == len(LATE_FIELDS) for r in late)
    recs = [dict(zip(LATE_FIELDS, r)) for r in late]
    assert any(r["compiles"] > 0 for r in recs)
    assert [r["t0"] for r in recs] == sorted(r["t0"] for r in recs)
    assert all(r["t_end"] - r["t0"] > service_mod.LATE_STEP_S for r in recs)
    cursor = recs[len(recs) // 2]["t0"]
    later = ask({"op": "traces", "steps_since": cursor})["late_steps"]
    assert later == late[len(recs) // 2 + 1:]


def test_a_streamed_request_moves_the_relays_clocks(wire):
    frames = wire["streamed"]
    assert sum(len(f) for f in frames) == 9 and all(frames)
    poll = 0.005
    lags = []
    for _ in range(3):                  # a loaded machine may oversleep a poll
        was = wire["ask"]({"op": "metrics"})["metrics"]
        frames = stream(wire["addr"], {"op": "generate", "stream": True,
                                       "prompt": [5, 4, 3, 2, 1],
                                       "max_new_tokens": 12})
        now = wire["ask"]({"op": "metrics"})["metrics"]
        sent = now["relay_frames"] - was["relay_frames"]
        assert sent == len(frames) > 0
        assert now["relay_tokens"] - was["relay_tokens"] == 12
        assert now["t_relay_send_s"] > was["t_relay_send_s"]
        lags.append((now["relay_lag_s"] - was["relay_lag_s"]) / sent)
    assert 0 < min(lags) <= 3 * poll, lags


def window_ctx(before, after):
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        from harness import window
    finally:
        sys.path.remove(os.path.join(ROOT, "benchmark"))
    o, c = window.flatten(before), window.flatten(after)
    scalars = {k: c[k] - o.get(k, 0) for k in c}
    scalars.update({"open." + k: v for k, v in o.items()})
    return window, {"scalars": scalars, "series": {}, "per_server": [],
                    "seconds": 1.0, "trace": None}


@pytest.mark.parametrize("metric", NEW_METRICS + ("sampler.sort_step_share",))
def test_metric_file_reads_a_finite_value_off_the_wire(wire, metric):
    """A misspelt counter fails here, on the CPU, and not on the chip."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = [m for m in bench["per_layer"] if m["name"] == metric]
    assert len(entry) == 1 and entry[0]["source"] == "program_counter"
    spec = json.load(open(os.path.join(
        ROOT, "benchmark", "layer_metrics", metric + ".json")))
    window, ctx = window_ctx(wire["before"], wire["after"])
    value = window.read_metric(spec, ctx)
    assert value is not None and math.isfinite(value) and value >= 0, value
    if entry[0]["unit"] == "%":
        assert value <= 100.0 + 1e-9, value
    # Every name the file reads is a numeric leaf of the reply.
    for key in ("num", "den"):
        for name in spec.get(key, []):
            assert name in ctx["scalars"], name


def test_the_new_metric_files_are_the_eleven_and_the_eight():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    listed = [m["name"] for m in bench["per_layer"]]
    assert set(NEW_METRICS) <= set(listed)
    on_disk = {os.path.basename(p)[:-5] for p in glob.glob(os.path.join(
        ROOT, "benchmark", "layer_metrics", "*.json"))}
    assert on_disk == set(listed)


def test_compile_recent_names_a_program_compiled_in_the_window(wire):
    before, after = wire["before"]["compile"], wire["after"]["compile"]
    assert after["programs"] > before["programs"]
    assert 0 < len(after["recent"]) <= 64
    new = [r for r in after["recent"] if r[0] > wire["t_mid"]]
    assert new, after["recent"][-3:]
    assert all(isinstance(r[1], str) and r[2] >= 0 for r in new)
    assert any("rbg_embed_pooled" in r[1] for r in new), new


def test_warmup_times_are_on_the_wire(wire):
    m = wire["after"]
    assert m["warmup_s"] > 0
    assert set(m["warmup"]) == {"ragged_s", "waves_s", "decode_s",
                                "join_windows_s", "samplers_s", "place_s"}
    assert sum(m["warmup"].values()) <= m["warmup_s"] + 0.01
