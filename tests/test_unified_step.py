"""Ragged unified prefill/decode step + continuous batching: bit-identity
against the split paths (pure prefill, pure decode, mixed joins; greedy and
seeded-sampled), join accounting, window shortening, and the prefill-chunk
boundary / pending-window seq_len invariant."""

import jax
import numpy as np
import pytest

from rbg_tpu.engine import Engine, EngineConfig, SamplingParams
from rbg_tpu.models import get_config, init_params
from rbg_tpu.models.llama import prefill_and_decode_greedy


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    return cfg, params


def make_engine(params, ragged="auto", **kw):
    defaults = dict(model="tiny", page_size=8, num_pages=64, max_batch=4,
                    max_seq_len=128, prefill_chunk=16,
                    enable_radix_cache=False, use_pallas="never",
                    multi_step=4)
    defaults.update(kw)
    return Engine(EngineConfig(ragged=ragged, **defaults), params=params)


def drain(eng, outputs, ids):
    while eng.has_work():
        for ev in eng.step():
            if ev.request_id in outputs:
                outputs[ev.request_id].append(ev.token)
    return [outputs[i] for i in ids]


def run_batch(params, ragged, prompts, sps, stagger_after=None, **kw):
    """Drive a batch to completion; ``stagger_after`` splits the adds
    around a few steps so late rows JOIN a decoding batch."""
    eng = make_engine(params, ragged=ragged, **kw)
    cut = stagger_after if stagger_after is not None else len(prompts)
    ids = [eng.add_request(p, s) for p, s in zip(prompts[:cut], sps[:cut])]
    outputs = {i: [] for i in ids}
    if stagger_after is not None:
        for _ in range(3):
            for ev in eng.step():
                outputs[ev.request_id].append(ev.token)
        for p, s in zip(prompts[cut:], sps[cut:]):
            i = eng.add_request(p, s)
            ids.append(i)
            outputs[i] = []
    return drain(eng, outputs, ids), eng


def _prompts(cfg, sizes, seed=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, size=n).tolist() for n in sizes]


def test_pure_prefill_bit_identity(tiny_setup):
    """max_new_tokens=1: the run is all prefill — the packed ragged
    dispatch must reproduce the split prefill path exactly."""
    cfg, params = tiny_setup
    prompts = _prompts(cfg, (4, 23, 9, 17))
    sps = [SamplingParams(max_new_tokens=1)] * 4
    got, eng = run_batch(params, "auto", prompts, sps)
    ref, _ = run_batch(params, "off", prompts, sps)
    assert got == ref
    assert eng.metrics["unified_steps"] > 0


@pytest.mark.slow
def test_pure_decode_keeps_fused_scan(tiny_setup):
    """Once every row is decoding, the engine must return to the fused
    multi-step scan (unified steps only cover the prefill-mixed phase) —
    and the output still matches the dense reference."""
    cfg, params = tiny_setup
    prompt = [5, 9, 13, 2, 7, 11, 3, 1, 8, 4]
    out = prefill_and_decode_greedy(
        params, cfg, np.asarray([prompt], np.int32), 8)
    expect = [int(t) for t in np.asarray(out)[0]]
    eng = make_engine(params, ragged="auto")
    got = eng.generate([prompt], SamplingParams(max_new_tokens=8))[0]
    assert got == expect
    # one chunk of prefill → exactly one unified step; the rest decoded
    # in fused windows
    assert eng.metrics["unified_steps"] == 1
    assert eng.metrics["decode_tokens"] > 4


@pytest.mark.slow
def test_mixed_join_bit_identity_greedy(tiny_setup):
    """Rows joining a decoding batch mid-stream (continuous admission)
    produce bit-identical streams to the split path for every row."""
    cfg, params = tiny_setup
    prompts = _prompts(cfg, (4, 23, 9, 17))
    sps = [SamplingParams(max_new_tokens=6)] * 4
    got, eng = run_batch(params, "auto", prompts, sps, stagger_after=2)
    ref, _ = run_batch(params, "off", prompts, sps, stagger_after=2)
    assert got == ref
    assert eng.metrics["unified_steps"] >= 2  # initial prefill + the join
    assert eng.metrics["joins"] == 4


@pytest.mark.slow
def test_mixed_join_bit_identity_sampled(tiny_setup):
    """Seeded sampling + penalties + logprobs across a mid-decode join:
    per-row keys are position-keyed, so the ragged path must replay the
    identical random stream."""
    cfg, params = tiny_setup
    prompts = _prompts(cfg, (4, 23, 9, 17), seed=3)
    sps = [SamplingParams(max_new_tokens=8, temperature=0.8, top_k=20,
                          seed=i, logprobs=True,
                          repetition_penalty=1.2 if i % 2 else 1.0)
           for i in range(4)]
    got, _ = run_batch(params, "auto", prompts, sps, stagger_after=2)
    ref, _ = run_batch(params, "off", prompts, sps, stagger_after=2)
    assert got == ref


@pytest.mark.slow
def test_mixed_join_bit_identity_int8_pool(tiny_setup):
    cfg, params = tiny_setup
    prompts = _prompts(cfg, (4, 23, 9), seed=5)
    sps = [SamplingParams(max_new_tokens=6)] * 3
    got, _ = run_batch(params, "auto", prompts, sps, stagger_after=1,
                       kv_dtype="int8")
    ref, _ = run_batch(params, "off", prompts, sps, stagger_after=1,
                       kv_dtype="int8")
    assert got == ref


@pytest.mark.slow
def test_grammar_row_joins_mid_decode(tiny_setup):
    """A regex-constrained row joining plain decoding rows rides the
    unified step on host-side masks — identical to the split path."""
    from rbg_tpu.engine.tokenizer import ByteTokenizer
    cfg, params = tiny_setup
    tok = ByteTokenizer()

    def run(ragged):
        eng = make_engine(params, ragged=ragged)
        eng.enable_json_grammar(tok)
        plain = eng.add_request(
            _prompts(cfg, (12,), seed=7)[0],
            SamplingParams(max_new_tokens=10))
        outputs = {plain: []}
        for _ in range(2):
            for ev in eng.step():
                outputs[ev.request_id].append(ev.token)
        gr = eng.add_request(
            tok.encode("p:", add_bos=False),
            SamplingParams(max_new_tokens=8, temperature=0.7, seed=1,
                           regex="[ab]{8}", stop_token=tok.eos_id))
        outputs[gr] = []
        return drain(eng, outputs, [plain, gr])

    assert run("auto") == run("off")


@pytest.mark.slow
def test_preemption_under_page_pressure_ragged(tiny_setup):
    """Page exhaustion mid-mix preempts the youngest and still completes
    every stream — identically to the split path."""
    cfg, params = tiny_setup
    prompts = _prompts(cfg, (20, 22, 24), seed=9)
    sps = [SamplingParams(max_new_tokens=12)] * 3
    got, eng = run_batch(params, "auto", prompts, sps, num_pages=16,
                        max_batch=3)
    ref, _ = run_batch(params, "off", prompts, sps, num_pages=16,
                       max_batch=3)
    assert got == ref
    assert all(len(o) == 12 for o in got)


@pytest.mark.slow
def test_seq_len_accounting_after_pending_drain(tiny_setup):
    """Regression for the prefill-chunk boundary invariant (the seq_len
    double-count the runtime-LoRA drain comment protects): a join forces
    a unified step while a fused window's tokens are still PENDING — the
    read must reconcile seq_len with the emitted stream, and after any
    step every running row satisfies seq_len == total_len - 1 (last_token
    not yet written) plus its tokens in flight, which seq_len counts from
    the dispatch on."""
    cfg, params = tiny_setup
    eng = make_engine(params, ragged="auto", multi_step=4)
    first = eng.add_request(_prompts(cfg, (10,), seed=11)[0],
                            SamplingParams(max_new_tokens=20))
    outputs = {first: []}
    # prefill + a couple of fused windows so a pending emission lag exists
    for _ in range(3):
        for ev in eng.step():
            outputs[ev.request_id].append(ev.token)
    assert eng._dec is not None and eng._pending is not None
    joiner = eng.add_request(_prompts(cfg, (21,), seed=12)[0],
                             SamplingParams(max_new_tokens=20))
    outputs[joiner] = []
    for ev in eng.step():                  # unified: reads the window after
        outputs[ev.request_id].append(ev.token)    # its own dispatch
    assert eng._dec is None                # window consumed, not discarded
    assert len(outputs[first]) > 1
    inflight = eng._pending_counts()
    for r in eng.running:
        if r.state == "running":
            assert r.seq_len == r.total_len - 1 + inflight.get(id(r), 0)
    got = drain(eng, outputs, [first, joiner])
    # no token lost or duplicated across the drain: full streams, and
    # identical to the split path end to end
    assert [len(o) for o in got] == [20, 20]

    def split_run():
        eng2 = make_engine(params, ragged="off", multi_step=4)
        a = eng2.add_request(_prompts(cfg, (10,), seed=11)[0],
                             SamplingParams(max_new_tokens=20))
        outs = {a: []}
        for _ in range(3):
            for ev in eng2.step():
                outs[ev.request_id].append(ev.token)
        b = eng2.add_request(_prompts(cfg, (21,), seed=12)[0],
                             SamplingParams(max_new_tokens=20))
        outs[b] = []
        return drain(eng2, outs, [a, b])

    assert got == split_run()


@pytest.mark.slow
def test_join_accounting_metrics(tiny_setup):
    """Admissions record joins and (with free capacity) zero excess wait;
    page-blocked queueing counts as availability wait, not excess."""
    cfg, params = tiny_setup
    eng = make_engine(params, ragged="auto", num_pages=16, max_batch=4)
    sps = SamplingParams(max_new_tokens=8)
    for p in _prompts(cfg, (20, 22, 24, 26), seed=13):
        eng.add_request(p, sps)
    while eng.has_work():
        eng.step()
    m = eng.metrics
    assert m["joins"] >= 4            # preempted rows re-join
    assert m["join_excess_steps_max"] <= 1
    assert len(eng.last_join_waits) == m["joins"]


def test_decode_window_shortens_for_joins(tiny_setup):
    cfg, params = tiny_setup
    eng = make_engine(params, ragged="auto", multi_step=8, max_batch=4)
    rid = eng.add_request(_prompts(cfg, (8,), seed=15)[0],
                          SamplingParams(max_new_tokens=4))
    assert eng._decode_window() == 1          # queued request, free slot
    eng.step()                                # admit + prefill it
    assert eng._decode_window() == 8          # no waiting work
    eng.join_hint = True
    assert eng._decode_window() == 1          # free slot + hinted join
    eng.join_hint = False
    eng.cancel_request(rid)

    off = make_engine(params, ragged="off", multi_step=8)
    off.join_hint = True
    assert off._decode_window() == 8          # baseline keeps full windows


def test_service_publishes_join_and_occupancy_metrics(tiny_setup):
    from rbg_tpu.engine.service import EngineService
    from rbg_tpu.obs import names
    from rbg_tpu.obs.metrics import REGISTRY

    _, params = tiny_setup
    svc = EngineService(
        EngineConfig(model="tiny", page_size=8, num_pages=64, max_batch=2,
                     max_seq_len=128, prefill_chunk=16, use_pallas="never",
                     enable_radix_cache=False, decode_buckets=(2,)),
        params=params)
    try:
        svc.submit([1, 2, 3, 4], SamplingParams(max_new_tokens=4))
        assert svc.engine.metrics["joins"] >= 1
        assert not svc.engine.last_join_waits    # drained by the loop
        assert REGISTRY.quantile(names.SERVING_JOIN_LATENCY_SECONDS, 0.5,
                                 service="engineservice") is not None
        assert REGISTRY.quantile(names.SERVING_BATCH_OCCUPANCY, 0.5,
                                 service="engineservice") is not None
    finally:
        svc.stop()


# ---- MLA rides the unified step (round 16) ----


@pytest.fixture(scope="module")
def tiny_mla_setup():
    cfg = get_config("tiny-mla")
    params = init_params(cfg, jax.random.key(2))
    return cfg, params


def run_mla_batch(params, ragged, prompts, sps, stagger_after=None, **kw):
    return run_batch(params, ragged, prompts, sps,
                     stagger_after=stagger_after, model="tiny-mla", **kw)


def test_mla_unified_step_bit_identity(tiny_mla_setup):
    """MLA models join the unified prefill/decode step (the mcfg.mla
    exclusion fell in round 16): packed ragged latent attention must
    reproduce the phase-split MLA path exactly."""
    cfg, params = tiny_mla_setup
    prompts = _prompts(cfg, (4, 23, 9), seed=7)
    sps = [SamplingParams(max_new_tokens=4)] * 3
    got, eng = run_mla_batch(params, "auto", prompts, sps)
    ref, off = run_mla_batch(params, "off", prompts, sps)
    assert got == ref
    assert eng.metrics["unified_steps"] > 0
    assert off.metrics["unified_steps"] == 0


@pytest.mark.slow
def test_mla_unified_step_staggered_joins(tiny_mla_setup):
    """Late MLA rows joining a decoding batch mid-stream — the ragged
    pack carries a decode row and a prefill chunk through the latent
    kernel in one dispatch — stay bit-identical to phase-split."""
    cfg, params = tiny_mla_setup
    prompts = _prompts(cfg, (4, 23, 9, 17), seed=8)
    sps = [SamplingParams(max_new_tokens=6, temperature=0.8, top_k=20,
                          seed=i, logprobs=bool(i % 2)) for i in range(4)]
    got, eng = run_mla_batch(params, "auto", prompts, sps, stagger_after=2)
    ref, _ = run_mla_batch(params, "off", prompts, sps, stagger_after=2)
    assert got == ref
    assert eng.metrics["unified_steps"] >= 2
    assert eng.metrics["joins"] == 4


@pytest.mark.slow
def test_mla_unified_step_int8_latent_pool(tiny_mla_setup):
    """int8 latent pools through the ragged MLA path (scatter detour's
    _q reference on CPU) — identical to the phase-split int8 path."""
    cfg, params = tiny_mla_setup
    prompts = _prompts(cfg, (4, 23, 9), seed=9)
    sps = [SamplingParams(max_new_tokens=4)] * 3
    got, _ = run_mla_batch(params, "auto", prompts, sps, stagger_after=1,
                           kv_dtype="int8")
    ref, _ = run_mla_batch(params, "off", prompts, sps, stagger_after=1,
                           kv_dtype="int8")
    assert got == ref


# ---- the lagged read: a step's tokens are read after the next dispatch ------

LAGGED_MODELS = {"gqa": "tiny", "latent": "tiny-mla",
                 "recurrent": "tiny-kimi-linear", "window": "tiny-laguna"}


@pytest.fixture(scope="module", params=sorted(LAGGED_MODELS))
def lag(request):
    """One engine a kind of model, for every case below: each leaves it
    idle, with every page, window page and state slot back."""
    from rbg_tpu.engine.tokenizer import ByteTokenizer
    eng = Engine(EngineConfig(
        model=LAGGED_MODELS[request.param], vocab_size=512, page_size=4,
        num_pages=128, max_batch=4, max_seq_len=160, prefill_chunk=16,
        enable_radix_cache=False, use_pallas="never"))
    eng.enable_json_grammar(ByteTokenizer())
    return eng


def _tokens(n, seed):
    return np.random.RandomState(seed).randint(1, 200, size=n).tolist()


def _free(eng):
    return (eng.allocator.free_pages,
            eng.window_allocator.free_pages if eng.window_allocator else 0,
            eng.state.held if eng.state is not None else 0)


def _play(eng, script, steps=400):
    """Run ``script``, ``{step: [(prompt, SamplingParams), ...]}``, to the
    end: every request's ``(tokens, logprobs, finished flags)`` in the
    order of admission, and per step the ids whose events it returned."""
    free = _free(eng)
    ids, out, per_step = [], {}, []
    for step in range(steps):
        for prompt, sp in script.get(step, ()):
            ids.append(eng.add_request(prompt, sp))
        if step > max(script) and not eng.has_work():
            break
        events = eng.step()
        per_step.append([ev.request_id for ev in events])
        for ev in events:
            toks, lps, fin = out.setdefault(ev.request_id, ([], [], []))
            toks.append(ev.token)
            lps.append(ev.logprob)
            fin.append(ev.finished)
    assert not eng.has_work() and eng._pending is None
    assert _free(eng) == free           # everything back, exactly once
    return [out[i] for i in ids], ids, per_step


def _packs(eng, monkeypatch):
    """Spy on the unified step's pack: per pack, the ids of its rows."""
    seen = []
    real = eng._pack_unified

    def pack(events):
        packed = real(events)
        seen.append(None if packed is None else
                    [(r.id, end > start) for r, start, end in packed[0]])
        return packed

    monkeypatch.setattr(eng, "_pack_unified", pack)
    return seen


def _mixed_script(extra):
    """A decoding row, a prompt of three chunks joining it, a third of two
    and one that stays to the end (``extra``: its sampling)."""
    lp = dict(logprobs=True)
    return {0: [(_tokens(10, 1), SamplingParams(max_new_tokens=14, **lp)),
                (_tokens(7, 2), SamplingParams(max_new_tokens=40, **extra))],
            4: [(_tokens(37, 3), SamplingParams(
                max_new_tokens=9, temperature=0.8, seed=7, **lp))],
            6: [(_tokens(21, 4), SamplingParams(max_new_tokens=6, **lp))]}


def test_lagged_run_gives_the_synchronous_orders_tokens_and_logprobs(lag):
    """(a) The same admissions twice: read one step late, and in the
    synchronous order, which a penalty row in the batch forces (its counts
    are built on the host from the tokens it has seen)."""
    m = lag.metrics
    was = m["lagged_steps"], m["steps_run"], m["unified_steps_run"]
    lagged, ids, _ = _play(lag, _mixed_script({}))
    mid = m["lagged_steps"], m["steps_run"], m["unified_steps_run"]
    synced, ids2, _ = _play(lag, _mixed_script(
        dict(presence_penalty=1e-6)))
    end = m["lagged_steps"], m["steps_run"], m["unified_steps_run"]
    for n in (0, 2, 3):                  # the rows without the penalty
        assert lagged[n][0] == synced[n][0]
        np.testing.assert_allclose(lagged[n][1], synced[n][1], atol=1e-5)
        assert lagged[n][2] == synced[n][2]
    assert [len(t) for t, _, _ in lagged] == [14, 40, 9, 6]
    # Unified steps ran in both; only the first run's were lagged.
    assert mid[2] - was[2] >= 5 and end[2] - mid[2] >= 5
    unlagged = (end[1] - mid[1]) - (end[0] - mid[0])
    assert unlagged >= end[2] - mid[2]
    assert (mid[1] - was[1]) - (mid[0] - was[0]) < unlagged


def test_a_stop_token_at_step_n_drops_what_step_n_plus_1_computed(
        lag, monkeypatch):
    """(b) The row rides the step after its stop token, which was
    dispatched before the token was read; that step's token is dropped, and
    the row's pages, window pages and state slot come back exactly once
    (``_play`` checks the pools)."""
    sp = dict(max_new_tokens=12)
    joiner = (_tokens(90, 6), SamplingParams(max_new_tokens=2))
    free_run, _, _ = _play(lag, {0: [(_tokens(9, 5), SamplingParams(**sp))],
                                 1: [joiner]})
    whole = free_run[0][0]
    k = next(i for i, t in enumerate(whole) if i >= 2 and
             t not in whole[:i])
    packs = _packs(lag, monkeypatch)
    got, ids, per_step = _play(
        lag, {0: [(_tokens(9, 5),
                   SamplingParams(stop_token=whole[k], **sp))],
              1: [joiner]})
    toks, _, fin = got[0]
    assert toks == whole[:k + 1] and fin == [False] * k + [True]
    # Token k was computed by the row's step k (0: its prompt's), all of
    # them unified while the joiner's six chunks last, and read in the
    # call of step k + 1, which had packed the row once more.
    rode = [n for n, rows in enumerate(packs) if rows and (ids[0], False)
            in rows]
    assert rode == list(range(1, k + 2))
    assert ids[0] in per_step[k + 1] and ids[0] not in per_step[k + 2]
    assert lag.metrics["preemptions"] == 0


def test_a_row_at_its_length_is_not_packed_again(lag, monkeypatch):
    """(c) ``max_new_tokens`` is judged by the tokens in flight: the row is
    known to end without its last token being read."""
    packs = _packs(lag, monkeypatch)
    got, ids, per_step = _play(
        lag, {0: [(_tokens(5, 8), SamplingParams(max_new_tokens=3)),
                  (_tokens(100, 9), SamplingParams(max_new_tokens=2))]})
    assert len(got[0][0]) == 3 and got[0][2] == [False, False, True]
    rode = [n for n, rows in enumerate(packs) if rows and
            any(rid == ids[0] for rid, _ in rows)]
    assert rode == [0, 1, 2]            # its prompt, then two decode steps
    assert len(packs) >= 7              # while the other's chunks went on
    # Each token is read in the call after the step that computed it.
    assert [n for n, evs in enumerate(per_step) if ids[0] in evs] == [1, 2, 3]


def test_preemption_reads_the_pending_step_before_it_releases_a_page(
        lag, monkeypatch):
    """(d) Page pressure inside a run of chunks: the tokens in flight are
    emitted before the victim's pages go back, and every stream is what it
    is without the pressure."""
    script = {0: [(_tokens(6, 10), SamplingParams(max_new_tokens=12)),
                  (_tokens(6, 11), SamplingParams(max_new_tokens=12))],
              1: [(_tokens(60, 12), SamplingParams(max_new_tokens=3))]}
    free_run, _, _ = _play(lag, script)
    # 2 + 2 pages for the two rows, 16 for the prompt, none to grow into.
    held = lag.allocator.alloc(lag.allocator.free_pages - 20)
    unread_at_preempt, log = [], []
    real, real_emit = lag._preempt, lag._emit_pending

    def preempt(req):
        unread_at_preempt.append(lag._pending)
        log.append("preempt")
        real(req)

    def emit(unread):
        log.append("emit")
        return real_emit(unread)

    monkeypatch.setattr(lag, "_preempt", preempt)
    monkeypatch.setattr(lag, "_emit_pending", emit)
    before = lag.metrics["preemptions"]
    try:
        ids, out = [], {}
        for step in range(400):
            for prompt, sp in script.get(step, ()):
                ids.append(lag.add_request(prompt, sp))
            if step > 1 and not lag.has_work():
                break
            log.append("step")
            for ev in lag.step():
                out.setdefault(ev.request_id, []).append(ev.token)
    finally:
        lag.allocator.release(held)
    assert lag.metrics["preemptions"] > before
    assert unread_at_preempt and all(u is None for u in unread_at_preempt)
    # The step that preempted first read the step before it, in its pack.
    assert "step emit preempt" in " ".join(log)
    assert [out[i] for i in ids] == [t for t, _, _ in free_run]


def test_lagged_steps_counts_a_run_of_chunks_and_both_its_ends(lag):
    """(e) decode, decode, three chunks, decode, decode: all but the first
    step of all were dispatched with the step before them unread."""
    m = lag.metrics
    lag.add_request(_tokens(8, 13), SamplingParams(max_new_tokens=30))
    for _ in range(3):
        lag.step()
    was = m["lagged_steps"], m["steps_run"]
    ring0 = len(lag.step_ring)
    lag.add_request(_tokens(40, 14), SamplingParams(max_new_tokens=30))
    for _ in range(5):
        lag.step()
    kinds = [r[6] for r in list(lag.step_ring)[ring0 - 2:]]
    assert kinds == ["decode", "decode", "unified", "unified", "unified",
                     "decode", "decode"]
    assert m["lagged_steps"] - was[0] == m["steps_run"] - was[1] == 5
    for rid in list(lag.requests):
        lag.cancel_request(rid)
    assert lag.step() == [] and lag._pending is None


def test_lagged_steps_reads_zero_for_rows_that_need_the_host(lag):
    """(e) A grammar row without a device table (the pushdown JSON
    grammar) needs the host between steps: while the batch holds one the
    order stays synchronous, by what the rows are, and a token is emitted
    by the call that computed it."""
    m = lag.metrics
    plain = lag.add_request(_tokens(8, 15), SamplingParams(max_new_tokens=9))
    for _ in range(3):
        lag.step()
    assert lag._pending is not None
    was = m["lagged_steps"], m["unified_steps_run"]
    grammar = lag.add_request(_tokens(40, 16), SamplingParams(
        max_new_tokens=1, json_mode=True, temperature=0.7, seed=3))
    assert not lag._row_fusable(lag.requests[grammar])
    calls = [[ev.request_id for ev in lag.step()] for _ in range(3)]
    # The first call also read the step before it, ahead of its dispatch.
    assert calls == [[plain, plain], [plain], [plain, grammar]]
    assert m["unified_steps_run"] - was[1] == 3
    assert m["lagged_steps"] == was[0] and lag._pending is None
    while lag.has_work():
        lag.step()
    assert m["lagged_steps"] > was[0]       # the plain row alone chains again
    if lag.mcfg.unbuilt_for:
        return      # its decode steps have no host-synced form to ride
    sp = SamplingParams(max_new_tokens=6, json_mode=True, temperature=0.7,
                        seed=3)
    was = m["lagged_steps"], m["steps_run"], m["unified_steps_run"]
    got, _, _ = _play(lag, {0: [(_tokens(5, 15), sp)],
                            2: [(_tokens(30, 16), sp)]})
    assert [len(t) for t, _, _ in got] == [6, 6]
    assert m["unified_steps_run"] - was[2] >= 3
    assert m["steps_run"] - was[1] >= 8
    assert m["lagged_steps"] == was[0]


def test_no_host_read_between_a_unified_steps_pack_and_its_dispatch(
        lag, monkeypatch):
    """(f) The read of step N comes after step N + 1 is on the device."""
    log = []
    real_get, real_pack = jax.device_get, lag._pack_unified
    real_fn, real_emit = lag._get_ragged_fn, lag._emit_pending

    def device_get(x):
        log.append("read")
        return real_get(x)

    def pack(events):
        log.append("pack")
        return real_pack(events)

    def get_fn(R, T):
        log.append("dispatch")
        return real_fn(R, T)

    def emit(unread):
        log.append("emit")
        return real_emit(unread)

    monkeypatch.setattr(jax, "device_get", device_get)
    monkeypatch.setattr(lag, "_pack_unified", pack)
    monkeypatch.setattr(lag, "_get_ragged_fn", get_fn)
    monkeypatch.setattr(lag, "_emit_pending", emit)
    _play(lag, {0: [(_tokens(8, 17), SamplingParams(max_new_tokens=8))],
                3: [(_tokens(50, 18), SamplingParams(max_new_tokens=4,
                                                     logprobs=True))]})
    text = " ".join(log)
    assert text.count("pack dispatch") == log.count("pack") >= 5
    # Every read is an emit's, and a unified step's follows its dispatch.
    assert log.count("read") == log.count("emit")
    assert "pack read" not in text and "pack emit" not in text
    assert text.count("dispatch emit read") >= 4


def test_a_row_leaving_the_decode_batch_forces_no_read(lag):
    """The decode state is built anew off the unread step when a row at
    its length leaves the batch, as it is after a unified step."""
    m = lag.metrics
    was = m["lagged_steps"], m["steps_run"], m["decode_steps_run"]
    got, _, _ = _play(lag, {0: [
        (_tokens(6, 19), SamplingParams(max_new_tokens=5)),
        (_tokens(7, 20), SamplingParams(max_new_tokens=14))]})
    assert [len(t) for t, _, _ in got] == [5, 14]
    assert m["decode_steps_run"] - was[2] == 13
    assert m["lagged_steps"] - was[0] == m["steps_run"] - was[1] - 1 == 13
