"""Sampling surface: top-p/min-p masking, penalties, per-request seeds,
logprobs — unit math on the sampler plus engine-level behavior.

Reference context: the reference orchestrates engines (SGLang/vLLM) whose
request API carries these fields; the TPU engine implements them natively
(rbg_tpu/engine/sampler.py) with per-row PRNG streams and optional
penalty state threaded through the fused decode scan."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rbg_tpu.engine import Engine, EngineConfig, SamplingParams
from rbg_tpu.engine.sampler import apply_penalties, row_keys, sample, step_keys


def _keys(n, seed=0):
    return row_keys([None] * n, jax.random.key(seed), list(range(n)))


def _arr(x, dt=jnp.float32):
    return jnp.asarray(x, dt)


# ---- sampler unit math ----


def test_top_p_masks_tail():
    # Row distribution: probs ~ [0.6, 0.3, 0.05, 0.05]; top_p=0.8 keeps
    # {0, 1} only (exclusive cumulative 0.0, 0.6 < 0.8; 0.9 for idx 2).
    logits = jnp.log(jnp.asarray([[0.6, 0.3, 0.05, 0.05]]))
    logits = jnp.tile(logits, (64, 1))
    toks, _ = sample(logits, _keys(64), _arr([1.0] * 64),
                     jnp.zeros(64, jnp.int32), _arr([0.8] * 64),
                     _arr([0.0] * 64))
    assert set(np.asarray(toks).tolist()) <= {0, 1}


def test_top_p_one_is_disabled():
    logits = jnp.tile(jnp.log(jnp.asarray([[0.25, 0.25, 0.25, 0.25]])),
                      (256, 1))
    toks, _ = sample(logits, _keys(256), _arr([1.0] * 256),
                     jnp.zeros(256, jnp.int32), _arr([1.0] * 256),
                     _arr([0.0] * 256))
    assert set(np.asarray(toks).tolist()) == {0, 1, 2, 3}


def test_min_p_masks_below_ratio():
    # max prob 0.5; min_p=0.3 keeps probs >= 0.15 → {0 (0.5), 1 (0.3)}.
    logits = jnp.tile(jnp.log(jnp.asarray([[0.5, 0.3, 0.12, 0.08]])),
                      (64, 1))
    toks, _ = sample(logits, _keys(64), _arr([1.0] * 64),
                     jnp.zeros(64, jnp.int32), _arr([1.0] * 64),
                     _arr([0.3] * 64))
    assert set(np.asarray(toks).tolist()) <= {0, 1}


def test_per_row_params_mix():
    # Row 0 greedy, row 1 top-k=1 (== greedy), row 2 top-p over a peaked
    # distribution — each row honors ITS params inside one batch.
    logits = jnp.asarray([[5.0, 0.0, 0.0], [0.0, 5.0, 0.0],
                          [0.0, 0.0, 5.0]])
    toks, _ = sample(logits, _keys(3), _arr([0.0, 1.0, 1.0]),
                     jnp.asarray([0, 1, 0], jnp.int32),
                     _arr([1.0, 1.0, 0.5]), _arr([0.0] * 3))
    got = np.asarray(toks).tolist()
    assert got[0] == 0 and got[1] == 1 and got[2] == 2


def test_seeded_rows_reproduce():
    logits = jnp.tile(jnp.asarray([[1.0, 1.1, 0.9, 1.05]]), (4, 1))
    keys = row_keys([7, 7, None, None], jax.random.key(3), [0, 1, 2, 3])
    keys = step_keys(keys, jnp.asarray([5, 5, 5, 5], jnp.int32))
    toks, _ = sample(logits, keys, _arr([1.0] * 4),
                     jnp.zeros(4, jnp.int32), _arr([1.0] * 4),
                     _arr([0.0] * 4))
    got = np.asarray(toks)
    assert got[0] == got[1]  # same seed, same position → same sample


def test_apply_penalties_math():
    logits = jnp.asarray([[2.0, -2.0, 1.0, 0.5]])
    pmask = jnp.asarray([[True, True, False, False]])
    counts = jnp.asarray([[0, 0, 3, 0]], jnp.int32)
    out = apply_penalties(logits, pmask, counts,
                          rep=_arr([2.0]), pres=_arr([0.5]),
                          freq=_arr([0.1]))
    out = np.asarray(out)[0]
    # token 0: prompt-seen, positive → 2.0/2 = 1.0
    assert out[0] == pytest.approx(1.0)
    # token 1: prompt-seen, negative → -2.0*2 = -4.0
    assert out[1] == pytest.approx(-4.0)
    # token 2: output-seen ×3 → 1.0/2 (rep) - 0.5 (pres) - 0.3 (freq)
    assert out[2] == pytest.approx(1.0 / 2 - 0.5 - 0.3)
    # token 3: unseen → untouched
    assert out[3] == pytest.approx(0.5)


def test_logprobs_returned_and_normalized():
    logits = jnp.asarray([[0.0, jnp.log(3.0)]])  # probs = [0.25, 0.75]
    toks, lps = sample(logits, _keys(1), _arr([0.0]),
                       jnp.zeros(1, jnp.int32), _arr([1.0]), _arr([0.0]),
                       want_logprobs=True)
    assert int(toks[0]) == 1
    assert float(lps[0]) == pytest.approx(np.log(0.75), abs=1e-5)


# ---- every stage runs only when a row asks for it, and changes no token ----


def _straight_line_sample(logits, keys, temperature, top_k, top_p, min_p, *,
                          prompt_mask=None, out_counts=None, rep=None,
                          pres=None, freq=None, want_logprobs=False):
    """The oracle: ``sample`` as it stood before its stages ran under
    ``lax.cond``: every batch pays the divide, both sorts, the noise and
    the second argmax, and a ``where`` picks the greedy rows' tokens."""
    from rbg_tpu.engine.sampler import _mask_top_k, _mask_top_p_min_p
    if prompt_mask is not None:
        logits = apply_penalties(logits, prompt_mask, out_counts, rep, pres,
                                 freq)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    scaled = _mask_top_k(scaled, top_k)
    scaled = _mask_top_p_min_p(scaled, top_p, min_p)
    noise = jax.vmap(lambda k, row: jax.random.gumbel(k, row.shape,
                                                      row.dtype))(keys, scaled)
    sampled = jnp.argmax(scaled + noise, axis=-1).astype(jnp.int32)
    toks = jnp.where(temperature > 0, sampled, greedy)
    lps = None
    if want_logprobs:
        full = jax.nn.log_softmax(logits, axis=-1)
        lps = jnp.take_along_axis(full, toks[:, None], axis=-1)[:, 0]
    return toks, lps


# temperature, top_k, top_p, min_p of the batch's eight rows
_B = 8
MIXES = {
    "greedy": ([0.0] * 8, [0] * 8, [1.0] * 8, [0.0] * 8),
    "sampled": ([0.7, 1.0, 1.3, 0.2, 0.9, 1.0, 2.0, 0.5],
                [0] * 8, [1.0] * 8, [0.0] * 8),
    "top_k": ([0.8] * 8, [1, 5, 40, 3, 0, 7, 0, 2], [1.0] * 8, [0.0] * 8),
    "top_p": ([1.0] * 8, [0] * 8,
              [0.9, 0.5, 1.0, 0.1, 0.95, 0.7, 1.0, 0.3], [0.0] * 8),
    "min_p": ([1.2] * 8, [0] * 8, [1.0] * 8,
              [0.05, 0.0, 0.2, 0.5, 0.0, 0.1, 0.3, 0.01]),
    "mixed": ([0.0, 0.9, 1.0, 0.7, 0.0, 1.1, 0.8, 1.5],
              [0, 0, 5, 0, 0, 20, 3, 0],
              [1.0, 1.0, 1.0, 0.8, 1.0, 0.9, 1.0, 1.0],
              [0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.1, 0.2]),
    # Greedy rows that set filters ask for no sort; one row samples plainly.
    "filters_on_greedy_rows": ([0.0, 0.0, 0.0, 1.0, 0.0, 0.0, 0.0, 0.0],
                               [5, 0, 3, 0, 1, 0, 0, 9],
                               [0.5, 0.9, 1.0, 1.0, 1.0, 0.2, 1.0, 1.0],
                               [0.0, 0.1, 0.0, 0.0, 0.0, 0.0, 0.3, 0.0]),
}


def _ulps_apart(a, b):
    """Largest distance between two float32 arrays of one sign pattern,
    counted in representable values."""
    a, b = (np.asarray(x).view(np.int32).astype(np.int64) for x in (a, b))
    return int(np.abs(a - b).max())


@pytest.mark.parametrize("where", ["jit", "scan"])
@pytest.mark.parametrize("pen,lp", [(False, False), (True, False),
                                    (False, True), (True, True)])
@pytest.mark.parametrize("mix", sorted(MIXES))
def test_gated_sampler_is_the_straight_line_sampler_to_the_bit(mix, pen, lp,
                                                               where):
    V, T = 384, 3
    rng = np.random.default_rng(sorted(MIXES).index(mix))
    logits = jnp.asarray(rng.normal(0, 3, (T, _B, V)), jnp.float32)
    temps, ks, tps, mps = MIXES[mix]
    params = (_arr(temps), jnp.asarray(ks, jnp.int32), _arr(tps), _arr(mps))
    keys = row_keys([None, 11] * (_B // 2), jax.random.key(5),
                    list(range(_B)))
    pkw = {}
    if pen:
        pkw = dict(
            prompt_mask=jnp.asarray(rng.random((_B, V)) < 0.1),
            out_counts=jnp.asarray(rng.integers(0, 3, (_B, V)), jnp.int32),
            rep=_arr(rng.uniform(1.0, 1.5, _B)),
            pres=_arr(rng.uniform(0.0, 0.5, _B)),
            freq=_arr(rng.uniform(0.0, 0.3, _B)))

    def run(fn):
        def one(lg, t):
            pos = jnp.full(_B, 17, jnp.int32) + t
            return fn(lg, step_keys(keys, pos), *params,
                      want_logprobs=lp, **pkw)

        if where == "jit":
            return jax.jit(one)(logits[0], 0)
        # As the fused decode window runs it: the sampler inside a scan.
        return jax.jit(lambda: jax.lax.scan(
            lambda c, xs: (c, one(*xs)), 0,
            (logits, jnp.arange(T, dtype=jnp.int32)))[1])()

    toks, lps = run(sample)
    want_toks, want_lps = run(_straight_line_sample)
    assert np.array_equal(np.asarray(toks), np.asarray(want_toks))
    if lp:
        # The gates sit between nothing and the logprob: it is the same
        # expression of the same penalised logits and the same token. Where
        # XLA fuses ``apply_penalties`` into the log-softmax's reduction in
        # one program and reads it from memory in the other, the CPU's
        # sums round apart in the last places (as either does from the
        # eager result); with no penalty to fuse they are the same bits.
        assert _ulps_apart(lps, want_lps) <= (16 if pen else 0)
    else:
        assert lps is None and want_lps is None
    if sum(t > 0 for t in temps) > 1 and not pen:
        # A mix whose sampling rows all drew the greedy token tests nothing.
        sampling = np.asarray(temps) > 0
        lg = logits if where == "scan" else logits[:1]
        greedy = np.asarray(jnp.argmax(lg, -1))[:, sampling]
        assert (np.asarray(toks).reshape(-1, _B)[:, sampling] != greedy).any()


def _sorts(lowered):
    """(sorts in a lowered program, those of them that run whatever the
    batch holds): a sort is guarded when it sits in a branch of a
    ``lax.cond`` (``stablehlo.case``), itself or through its callers."""
    module = lowered.compiler_ir("stablehlo")
    open_sorts, open_calls, total = {}, {}, 0

    def walk(op, fn, guarded):
        nonlocal total
        for region in op.regions:
            for block in region:
                for child in block:
                    o = child.operation
                    if o.name == "stablehlo.sort":
                        total += 1
                        open_sorts[fn] += not guarded
                    if o.name == "func.call" and not guarded:
                        open_calls[fn].append(
                            str(o.attributes["callee"]).lstrip("@"))
                    walk(o, fn, guarded
                         or o.name in ("stablehlo.case", "stablehlo.if"))

    for f in module.body.operations:
        fn = str(f.attributes["sym_name"]).strip('"')
        open_sorts[fn], open_calls[fn] = 0, []
        walk(f.operation, fn, False)
    seen, todo = set(), ["main"]
    while todo:
        fn = todo.pop()
        if fn not in seen:
            seen.add(fn)
            todo += open_calls[fn]
    return total, sum(open_sorts[fn] for fn in seen)


def _lower_step_program(eng, program):
    S = jax.ShapeDtypeStruct
    cfg, pool = eng.cfg, eng.cache
    B, P, V = cfg.max_batch, cfg.max_pages_per_seq, eng.mcfg.vocab_size
    i32 = jnp.int32
    vec, table = S((B,), i32), S((B, P), i32)
    temps, ks, tps, mps, seeds, rids, _, _, _ = eng._sampling_rows([], B)
    tail = (row_keys(seeds, eng._sample_base, rids), jnp.asarray(temps),
            jnp.asarray(ks), jnp.asarray(tps), jnp.asarray(mps))
    if program == "rbg_fused_decode":
        return eng._get_decode_fn(B, False, False).lower(
            eng.params, vec, vec, vec, table, S((B, 1), bool), vec,
            pool.k_pages, pool.v_pages, None, None, *tail)
    if program == "rbg_spec_verify":
        T = cfg.spec_k + 1
        return eng._get_spec_fn(B, False).lower(
            eng.params, S((B, T), i32), S((B, T), i32), S((B, T), bool), vec,
            table, pool.k_pages, pool.v_pages, None, None, *tail)
    return eng._get_sampler(False, False).lower(S((B, V), jnp.float32), *tail)


@pytest.mark.parametrize("program", ["rbg_fused_decode", "rbg_spec_verify",
                                     "rbg_sampler"])
def test_every_sort_of_a_step_program_sits_in_a_conditional(program):
    """A batch of greedy rows must not pay for a sort: in the decode scan,
    in the host-path sampler, and in the speculative verify, whose ``vmap``
    over the draft positions would turn a predicate that read the logits
    into a select that runs both branches."""
    total, unguarded = _sorts(_lower_step_program(_engine(multi_step=4),
                                                  program))
    assert total >= 1 and unguarded == 0


def test_the_straight_line_sampler_sorts_whatever_the_batch_holds():
    """The control of the test above: the walk does see a sort that no
    conditional guards."""
    S = jax.ShapeDtypeStruct
    vec = S((_B,), jnp.float32)
    lowered = jax.jit(_straight_line_sample).lower(
        S((_B, 64), jnp.float32), _keys(_B), vec, S((_B,), jnp.int32), vec,
        vec)
    total, unguarded = _sorts(lowered)
    assert unguarded >= 1


# ---- engine behavior ----


def _engine(**kw):
    cfg = EngineConfig(model="tiny", page_size=8, num_pages=96,
                       max_seq_len=128, use_pallas="never", **kw)
    return Engine(cfg)


@pytest.mark.slow
def test_engine_seed_reproducible_across_instances():
    sp = SamplingParams(max_new_tokens=8, temperature=1.0, top_p=0.9, seed=42)
    a = _engine().generate([[1, 2, 3, 4]], sp)[0]
    b = _engine().generate([[1, 2, 3, 4]], sp)[0]
    assert a == b


@pytest.mark.slow
def test_engine_presence_penalty_forces_distinct_tokens():
    # Greedy + overwhelming presence penalty → no output token repeats
    # (the in-scan count update must apply within a multi-step window too).
    sp = SamplingParams(max_new_tokens=12, temperature=0.0,
                        presence_penalty=1e9)
    for ms in (1, 4):
        out = _engine(multi_step=ms).generate([[1, 2, 3, 4]], sp)[0]
        assert len(out) == len(set(out)), (ms, out)


def test_engine_repetition_penalty_blocks_prompt_echo():
    # Repetition penalty so extreme every prompt token is suppressed —
    # output must avoid the prompt tokens entirely (logits stay positive
    # pre-division for the argmax winner on random init, so a huge divisor
    # pushes prompt tokens below every unseen token).
    prompt = [9, 9, 9, 9, 9, 9]
    sp = SamplingParams(max_new_tokens=8, temperature=0.0,
                        repetition_penalty=1e6, presence_penalty=1e9)
    out = _engine().generate([prompt], sp)[0]
    assert 9 not in out


def test_engine_logprobs_events_all_steps():
    eng = _engine(multi_step=2)
    rid = eng.add_request([1, 2, 3, 4],
                          SamplingParams(max_new_tokens=6, logprobs=True))
    lps = []
    while eng.has_work():
        for ev in eng.step():
            if ev.request_id == rid:
                lps.append(ev.logprob)
    assert len(lps) == 6
    assert all(lp is not None and lp <= 0.0 for lp in lps)


def test_engine_mixed_batch_logprobs_only_where_requested():
    eng = _engine()
    r1 = eng.add_request([1, 2, 3], SamplingParams(max_new_tokens=4,
                                                   logprobs=True))
    r2 = eng.add_request([4, 5, 6], SamplingParams(max_new_tokens=4))
    got = {r1: [], r2: []}
    while eng.has_work():
        for ev in eng.step():
            got[ev.request_id].append(ev.logprob)
    assert all(lp is not None for lp in got[r1])
    assert all(lp is None for lp in got[r2])


def test_sampling_params_validation():
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0).validate()
    with pytest.raises(ValueError):
        SamplingParams(min_p=1.0).validate()
    with pytest.raises(ValueError):
        SamplingParams(temperature=-0.1).validate()
    with pytest.raises(ValueError):
        SamplingParams(repetition_penalty=0.0).validate()
    with pytest.raises(ValueError):
        SamplingParams.from_wire({"top_p": 2.0})


def test_from_wire_roundtrip_defaults():
    sp = SamplingParams.from_wire({}, default_max_tokens=9, stop_token=3)
    assert sp.max_new_tokens == 9 and sp.stop_token == 3
    assert not sp.needs_penalties() and not sp.logprobs
    sp2 = SamplingParams.from_wire(
        {"temperature": 0.7, "top_p": 0.9, "seed": 5, "logprobs": True,
         "presence_penalty": 0.2, "stop_token": 11}, stop_token=3)
    assert sp2.stop_token == 11 and sp2.seed == 5
    assert sp2.needs_penalties() and sp2.logprobs


def test_greedy_unchanged_by_sampling_machinery():
    # The default path (no penalties, no logprobs) must produce the same
    # greedy continuation as before the sampling surface grew.
    out1 = _engine().generate([[1, 2, 3, 4]],
                              SamplingParams(max_new_tokens=8))[0]
    out2 = _engine(multi_step=4).generate([[1, 2, 3, 4]],
                                          SamplingParams(max_new_tokens=8))[0]
    assert out1 == out2


# ---- over the wire (unified engine server subprocess) ----


@pytest.mark.slow
@pytest.mark.e2e
def test_server_seed_and_logprobs_over_wire():
    from conftest import SpawnedEngineServer
    from rbg_tpu.engine.protocol import request_once

    with SpawnedEngineServer(
            "--model", "tiny", "--page-size", "8", "--num-pages", "64",
            "--max-seq-len", "128", "--use-pallas", "never") as srv:
        req = {"op": "generate", "prompt": [1, 2, 3, 4],
               "max_new_tokens": 8, "temperature": 0.9, "top_p": 0.9,
               "seed": 77, "logprobs": True}
        r1, _, _ = request_once(srv.addr, req, timeout=180)
        r2, _, _ = request_once(srv.addr, req, timeout=180)
        assert "error" not in r1, r1
        assert r1["tokens"] == r2["tokens"]          # seeded → reproducible
        assert len(r1["logprobs"]) == len(r1["tokens"])
        assert all(lp <= 0 for lp in r1["logprobs"])
        # invalid params fail the request, not the server
        bad, _, _ = request_once(srv.addr,
                                 {"op": "generate", "prompt": [1],
                                  "top_p": 5.0}, timeout=30)
        assert "error" in bad and "top_p" in bad["error"]
        h, _, _ = request_once(srv.addr, {"op": "health"}, timeout=5)
        assert h["ok"]


@pytest.mark.slow
@pytest.mark.e2e
def test_server_cancels_generation_on_client_disconnect():
    """A streaming client that goes away mid-generation must not leave the
    request occupying a batch slot for its whole max_new_tokens budget
    (the HTTP edge cuts streams at stop strings this way)."""
    import socket
    import time

    from conftest import SpawnedEngineServer
    from rbg_tpu.engine.protocol import recv_msg, request_once, send_msg

    with SpawnedEngineServer(
            "--model", "tiny", "--page-size", "8", "--num-pages", "2048",
            "--max-seq-len", "8192", "--use-pallas", "never") as srv:
        # Start a long streaming generation, read one frame, vanish.
        conn = socket.create_connection(("127.0.0.1", srv.port), timeout=60)
        send_msg(conn, {"op": "generate", "prompt": [1, 2, 3],
                        "max_new_tokens": 8000, "stream": True})
        frame, _, _ = recv_msg(conn)
        assert frame and "tokens" in frame
        conn.close()
        # The engine must abort the request well before 8000 tokens.
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            m, _, _ = request_once(srv.addr, {"op": "metrics"}, timeout=10)
            st = m["metrics"]
            if st["running"] == 0 and st["waiting"] == 0:
                break
            time.sleep(0.2)
        assert st["running"] == 0 and st["waiting"] == 0, st
        assert st["decode_tokens"] < 8000, st


@pytest.mark.slow
def test_extreme_seed_values_do_not_crash():
    # Wire seeds are arbitrary ints; uint32 masking must keep the engine
    # loop alive (NumPy 2.x raises OverflowError on bad conversions).
    for seed in (2**40, -1, 2**63 - 1):
        sp = SamplingParams(max_new_tokens=3, temperature=1.0, seed=seed)
        out = _engine().generate([[1, 2, 3]], sp)[0]
        assert len(out) == 3


def test_out_of_vocab_prompt_rejected_at_admission():
    eng = _engine()
    V = eng.mcfg.vocab_size
    with pytest.raises(ValueError, match="vocab"):
        eng.add_request([1, V], SamplingParams(max_new_tokens=2))
    with pytest.raises(ValueError, match="vocab"):
        eng.add_request([-1], SamplingParams(max_new_tokens=2))
    with pytest.raises(ValueError, match="empty"):
        eng.add_request([], SamplingParams(max_new_tokens=2))
    # the engine still works after rejections
    assert len(eng.generate([[1, 2]], SamplingParams(max_new_tokens=2))[0]) == 2


@pytest.mark.slow
def test_seeded_output_invariant_under_preemption():
    """Preemption folds output into prompt for re-prefill; penalty counts
    and position-keyed sampling must survive so a seeded request yields
    the SAME tokens whether or not it was preempted."""
    sp = SamplingParams(max_new_tokens=24, temperature=1.0,
                        presence_penalty=0.6, repetition_penalty=1.2,
                        seed=11)
    prompts = [[1, 2, 3, 4], [5, 6, 7, 8], [2, 4, 6, 8]]

    def run(num_pages):
        eng = Engine(EngineConfig(model="tiny", page_size=8,
                                  num_pages=num_pages, max_seq_len=128,
                                  use_pallas="never",
                                  enable_radix_cache=False))
        out = eng.generate(prompts, sp)
        return out, eng.metrics["preemptions"]

    big, pre_big = run(64)
    small, pre_small = run(9)
    assert pre_big == 0
    assert pre_small > 0, "small pool must actually preempt"
    assert big == small
