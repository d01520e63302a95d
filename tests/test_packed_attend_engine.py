"""A packed step's split attend through the engine
(``models/llama.py::_pool_attention``; the layer alone is held to the
unsplit path in ``tests/test_packed_attend.py``): a decoding request reads
the same tokens whether or not a prompt is prefilled beside it in unified
steps.
"""

import dataclasses

import jax
import numpy as np
import pytest

from rbg_tpu.engine import Engine, EngineConfig, SamplingParams
from rbg_tpu.models import config as presets
from rbg_tpu.models import get_config
from rbg_tpu.ops.pallas import page_walk


@pytest.mark.parametrize("model,use_pallas", [
    ("tiny", "never"), ("tiny", "always"), ("tiny-laguna", "never"),
    ("tiny-laguna", "always"), ("tiny-mla", "always")])
def test_a_decoding_row_reads_the_same_beside_a_prefill(interpreted, model,
                                                        use_pallas):
    """Through the engine: a request's greedy tokens are the same whether
    its decode steps run alone or, while a late prompt is prefilled in
    chunks beside it and four more rows decode, inside unified steps (its
    token attended by the decode step's walk beside the ragged one): by
    the XLA forms and by the kernels."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 256, n).tolist() for n in (21, 5, 9, 12, 7)]
    # three chunks, the last of ONE token: a one-token row like any other
    late = rng.integers(1, 256, 33).tolist()
    greedy = SamplingParams(max_new_tokens=7, temperature=0.0)

    def tokens(beside):
        eng = Engine(EngineConfig(
            model=model, page_size=8, num_pages=96, max_seq_len=128,
            max_batch=8, prefill_chunk=16, enable_radix_cache=False,
            use_pallas=use_pallas))
        ids = [eng.add_request(p, greedy) for p in prompts]
        out, before = [], dict(eng.metrics)
        while eng.has_work():
            for ev in eng.step():
                if ev.request_id == ids[0]:
                    out.append(ev.token)
            if beside and len(out) == 2 and len(eng.requests) == len(ids):
                before = dict(eng.metrics)
                eng.add_request(late, greedy)
        return out, {k: eng.metrics[k] - before[k] for k in (
            "unified_rows", "unified_chunk_rows", "unified_steps_run")}

    alone, _ = tokens(False)
    assert len(alone) == 7
    beside, since = tokens(True)
    # the late prompt's three chunks rode with the five decoding rows: six
    # rows a step (the bucket of 8), five of them of one token and in the
    # third step all six
    assert since["unified_steps_run"] >= 3
    assert since["unified_rows"] >= 3 * 6
    assert since["unified_chunk_rows"] == 2
    assert beside == alone


def _whole_tiles():
    """bf16, 8 KV heads of 128, two full and two window layers: both
    classes of page."""
    base = get_config("tiny-laguna")
    return dataclasses.replace(
        base, num_layers=4, num_heads=8, num_kv_heads=8, head_dim=128,
        dtype="bfloat16",
        window_layer={**dict(base.window_layer), "num_heads": 16},
        layer_types=("full_attention", "sliding_attention") * 2), 4


def _packed():
    """LFM2's pool: bf16, 8 heads of 64 held two to a lane tile,
    ``[NP, 16, 4, 128]``, in the two attention layers of the hybrid."""
    return dataclasses.replace(get_config("tiny-lfm2"), num_heads=8,
                               num_kv_heads=8, dtype="bfloat16"), 2


def _latent():
    """bf16 latents a lane tile wide beside the rotary key's pool,
    ``[NP, 16, 1, 128]`` each."""
    return dataclasses.replace(get_config("tiny-mla"), kv_lora_rank=128,
                               dtype="bfloat16"), 2


@pytest.mark.parametrize("preset", [_whole_tiles, _packed, _latent])
def test_kernel_copies_and_the_pipeline_serve_the_same_tokens(
        interpreted, monkeypatch, preset):
    """Through the engine, at pools ``page_walk.kernel_copies`` takes (the
    tiny presets' own fall on the pipeline's side): decode steps and the
    one-token rows of unified steps walk with the kernel's own copies in
    every attention layer of every class of page (the gauge says so), and
    the served tokens are those of the same engine with the predicate
    turned off, the pipeline's."""
    mcfg, walks = preset()
    monkeypatch.setitem(presets._PRESETS, "copies",
                        dataclasses.replace(mcfg, name="copies"))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 256, n).tolist() for n in (37, 5, 18)]
    late = rng.integers(1, 256, 33).tolist()
    greedy = SamplingParams(max_new_tokens=6, temperature=0.0)

    def served():
        jax.clear_caches()      # ``_decode_call`` is traced once a shape
        eng = Engine(EngineConfig(
            model="copies", page_size=16, num_pages=64, max_seq_len=128,
            max_batch=4, prefill_chunk=16, enable_radix_cache=False,
            use_pallas="always"))
        ids = [eng.add_request(p, greedy) for p in prompts]
        out = {}
        while eng.has_work():
            for ev in eng.step():
                out.setdefault(ev.request_id, []).append(ev.token)
            if len(out.get(ids[0], ())) == 2 and len(eng.requests) == 3:
                eng.add_request(late, greedy)       # unified steps follow
        assert eng.metrics["unified_steps_run"] >= 3
        return eng.metrics["decode_walk_kernel_copies"], list(out.values())

    copies, tokens = served()
    assert copies == walks
    monkeypatch.setattr(page_walk, "kernel_copies", lambda pools: False)
    piped, tokens_piped = served()
    jax.clear_caches()
    assert piped == 0
    assert tokens == tokens_piped and len(tokens) == 4
