"""A packed step's split attend through the engine
(``models/llama.py::_pool_attention``; the layer alone is held to the
unsplit path in ``tests/test_packed_attend.py``): a decoding request reads
the same tokens whether or not a prompt is prefilled beside it in unified
steps.
"""

import numpy as np
import pytest

from rbg_tpu.engine import Engine, EngineConfig, SamplingParams


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
