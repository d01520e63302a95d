"""Two classes of page in one cache manager (``tiny-laguna``: full attention
beside window layers of 8 tokens over pages of 4 slots): the window class is
sized by the rows' windows, its pages are given back as a row moves and never
read again, what is served across a window's edge is the reference's, and a
finished, cancelled or preempted request returns both classes in full. A
file of its own, because a file is one worker's under ``--dist loadfile``;
the model's other mechanisms are ``test_laguna.py``'s."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rbg_tpu.engine import Engine, EngineConfig, SamplingParams
from rbg_tpu.engine.kvcache import (PagedKVCache, window_pages_per_row,
                                    window_pool_pages)
from rbg_tpu.models import get_config, init_params

from model_contract import (Case, drive, engine, error, load, prompts, read,
                            serve)

CFG = get_config("tiny-laguna")
PARAMS = init_params(CFG, jax.random.key(0))
CASE = Case(tiny="tiny-laguna", controls=())
W = CFG.sliding_window


def test_the_window_class_is_sized_by_the_rows_windows():
    assert window_pages_per_row(CFG, 4, 16) == 7        # 7 + 16 slots, + 1
    assert window_pool_pages(CFG, 4, 4, 16) == 29
    cell = read("configs", "laguna-xs2.json")
    from harness import serve as harness
    m, s = harness.model_config(cell, "laguna-cell"), cell["server"]
    pages = window_pool_pages(m, s["page_size"], s["max_batch"],
                              s["prefill_chunk"])
    assert pages == 32 * 37 + 1      # prefill_chunk 64
    full = PagedKVCache.hbm_bytes(m, s["num_pages"], s["page_size"])
    window = PagedKVCache.hbm_bytes(m, pages, s["page_size"], kind="window")
    assert (full, window) == (5 * 8192 * 65536, 15 * 1185 * 65536)
    one_class = 20 * 32 * 4096 * 4096
    assert full + window < 0.40 * one_class
    eng = Engine(EngineConfig(model="tiny-laguna", page_size=4, num_pages=64,
                              max_seq_len=128, max_batch=4, prefill_chunk=16))
    assert eng.cache.k_pages.shape == (2, 64, 4, 2, 32)
    assert eng.cache.window_k.shape == (6, 29, 4, 2, 32)
    assert eng.window_allocator.num_pages == 29


KW = dict(page_size=4, num_pages=128, max_seq_len=128, max_batch=4,
          prefill_chunk=16)


@pytest.mark.parametrize("ragged,use_pallas", [
    ("auto", "auto"), ("off", "auto"), ("auto", "always"),
    ("off", "always")], ids=["packed", "rows", "packed-kernels",
                             "rows-kernels"])
def test_served_logits_agree_with_the_reference_around_a_windows_edge(
        interpreted, ragged, use_pallas):
    """Prompts of W - 1, W, W + 1 and 3 W tokens side by side, then decode
    steps across the edge: what is served is the reference's, whatever the
    window has dropped."""
    bench = load(CASE)
    asked = prompts(bench.cfg, (W - 1, W, W + 1, 3 * W), seed=11)
    eng = engine(bench, ragged=ragged, use_pallas=use_pallas)
    for prompt, got in zip(asked, serve(eng, asked, 2 * W + 3)):
        assert error(bench, prompt, got) <= bench.cfg["correct"]["limit"]
    assert eng.metrics["kv_window_pages_released"] > 0


@pytest.mark.parametrize("use_pallas", ["auto", "always"])
def test_no_page_given_back_is_read_again(interpreted, use_pallas):
    """After every step each FREE page of the window class (all but the
    null page) is overwritten with large numbers: a later step that read a
    page it gave back, or a slot it had not yet written, would serve
    nonsense; it serves the reference's logits, and no row ever holds
    more pages than its window and a chunk span."""
    bench = load(CASE)
    eng = engine(bench, use_pallas=use_pallas)
    asked = prompts(bench.cfg, (50, 9, 27), seed=17)
    held = []

    def poison(eng, out):
        free = jnp.asarray(eng.window_allocator._free, jnp.int32)
        eng.cache = dataclasses.replace(
            eng.cache, window_k=eng.cache.window_k.at[:, free].set(1e4),
            window_v=eng.cache.window_v.at[:, free].set(-1e4))
        held.extend(len(r.window_pages) for r in eng.running)

    ids = [eng.add_request(p, SamplingParams(max_new_tokens=20,
                                             logprobs=True)) for p in asked]
    for prompt, got in zip(asked, drive(eng, ids, poison)):
        assert error(bench, prompt, got) <= bench.cfg["correct"]["limit"]
    assert max(held) <= window_pages_per_row(CFG, 4, 16)
    assert eng.metrics["kv_window_pages_released"] >= 20
    assert eng.window_allocator.free_pages == \
        eng.window_allocator.num_pages - 1


def test_finish_cancel_and_preemption_return_both_classes_in_full():
    eng = Engine(EngineConfig(model="tiny-laguna", **KW), params=PARAMS)
    full0, win0 = eng.allocator.free_pages, eng.window_allocator.free_pages
    rng = np.random.default_rng(3)
    ids = [eng.add_request(rng.integers(1, 256, n).tolist(),
                           SamplingParams(max_new_tokens=30))
           for n in (40, 21, 33)]
    for _ in range(6):
        eng.step()
    reqs = [eng.requests[i] for i in ids]
    assert all(r.pages and r.window_pages for r in reqs)
    assert eng.window_allocator.free_pages < win0
    eng._drain_decode()
    eng._preempt(reqs[0])
    assert reqs[0].window_pages == [] and reqs[0].window_lo == 0
    assert eng.cancel_request(ids[1])
    assert reqs[1].window_pages == []
    while eng.has_work():
        eng.step()
    assert eng.metrics["preemptions"] == 1
    assert (eng.allocator.free_pages, eng.window_allocator.free_pages) == (
        full0, win0)


