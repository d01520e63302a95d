"""``utils/pystack.py``: a call whose frames all live in one chunk of the
interpreter's frame stack, and the two threads of a service that trace
step programs running under it."""

import sys
import threading

import jax
import pytest

from rbg_tpu.engine import EngineConfig
from rbg_tpu.engine.service import EngineService
from rbg_tpu.models import get_config, init_params
from rbg_tpu.utils import pystack


@pytest.mark.parametrize("args,kwargs,want", [
    ((), {}, 0), ((3,), {}, 3), ((3, 4), {}, 7), ((3,), {"b": 5}, 8),
    ((), {"a": 1, "b": 2}, 3)])
def test_it_calls_through_with_arguments_and_returns_the_value(args, kwargs,
                                                               want):
    assert pystack.on_roomy_stack(lambda a=0, b=0: a + b, *args,
                                  **kwargs) == want


def test_it_lets_an_exception_through():
    with pytest.raises(KeyError, match="gone"):
        pystack.on_roomy_stack({}.__getitem__, "gone")


def _frames_above():
    f, out = sys._getframe(1), []
    while f is not None:
        out.append(f.f_code)
        f = f.f_back
    return out


def _roomy(codes):
    return [c for c in codes if c.co_filename == pystack.__file__
            and c.co_stacksize > 1 << 17]


@pytest.mark.skipif(sys.implementation.name != "cpython",
                    reason="the frame stack's chunks are CPython's")
def test_the_frame_above_claims_over_a_mebibyte_so_its_chunk_is_two():
    # 8 bytes a slot: a little over 2 ** 20 bytes, which the interpreter
    # rounds up to a chunk of 2 ** 21.
    frame, = _roomy(pystack.on_roomy_stack(_frames_above))
    assert 1 << 20 < 8 * frame.co_stacksize < (1 << 20) + (1 << 12)
    assert not _roomy(_frames_above())


@pytest.mark.parametrize("depth", [10, 900, 4000])
def test_frames_thousands_deep_run_under_it(depth):
    def down(n):
        return 0 if n == 0 else 1 + down(n - 1)

    was = sys.getrecursionlimit()
    sys.setrecursionlimit(depth + 200)
    try:
        assert pystack.on_roomy_stack(down, depth) == depth
    finally:
        sys.setrecursionlimit(was)


def test_it_is_one_chunk_a_thread_and_threads_do_not_share_it():
    got = []
    threads = [threading.Thread(target=lambda: got.append(
        len(_roomy(pystack.on_roomy_stack(_frames_above)))))
        for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert got == [1, 1, 1, 1]


@pytest.fixture(scope="module")
def svc():
    s = EngineService(
        EngineConfig(model="tiny", page_size=8, num_pages=64, max_batch=2,
                     max_seq_len=128, prefill_chunk=16, use_pallas="never",
                     enable_radix_cache=False, decode_buckets=(2,)),
        params=init_params(get_config("tiny"), jax.random.key(0)))
    yield s
    s.stop()


def test_the_serving_loop_runs_on_a_roomy_stack(svc):
    f, codes = sys._current_frames()[svc._thread.ident], []
    while f is not None:
        codes.append(f.f_code)
        f = f.f_back
    assert [c.co_name for c in codes].count("_loop") == 1
    assert len(_roomy(codes)) == 1


def test_the_warm_up_runs_on_a_roomy_stack(svc, monkeypatch):
    seen = {}

    def warmers(input_len, out_len):
        seen["args"] = (input_len, out_len)
        seen["roomy"] = len(_roomy(_frames_above()))
        return 1.5

    monkeypatch.setattr(svc, "_warmup", warmers)
    assert svc.warmup(8, out_len=3) == 1.5
    assert seen == {"args": (8, 3), "roomy": 1}
