"""The plain reference against the program, on the CPU at tiny widths,
and the controls that must fail the stated tolerance."""

import json
import math
import os

import jax
import numpy as np
import pytest

from harness import reference, serve

CONFIGS = os.path.join(os.path.dirname(__file__), "rehearse", "configs")


def _cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def _rms(a, b):
    d = np.asarray(a, np.float64) - np.asarray(b, np.float64)
    return math.sqrt(float(np.mean(d * d)))


def _served(cfg, name, params, prompt, new, **engine_kw):
    """Greedy tokens and their logprobs through the program's engine:
    chunked prefill, paged cache, cached decode steps."""
    from rbg_tpu.engine import Engine, EngineConfig
    from rbg_tpu.engine.config import SamplingParams
    from rbg_tpu.models import config as presets
    presets._PRESETS[name] = serve.model_config(cfg, name)
    eng = Engine(EngineConfig(model=name, **cfg["server"], **engine_kw),
                 params=params)
    eng.add_request(prompt, SamplingParams(max_new_tokens=new,
                                           logprobs=True))
    toks, lps = [], []
    while eng.has_work():
        for ev in eng.step():
            toks.append(ev.token)
            lps.append(ev.logprob)
    return toks, lps


# The latent cache alone in int8 moves this toy by less than its limit
# (one normalised latent of 32 numbers a token); every full control fails.
@pytest.mark.parametrize("name,controls", [
    ("tiny-dense", ("bf16", "int8", "fp8", "kv_int8")),
    ("tiny-moe", ("bf16", "int8", "fp8", "kv_int8")),
    ("tiny-latent-shared", ("bf16", "int8", "fp8"))])
def test_program_agrees_with_reference_and_controls_do_not(name, controls):
    cfg = _cfg(name)
    limit = cfg["correct"]["limit"]
    # the module the configuration names (the default for the first two)
    reference = serve.load_reference(cfg)
    params = reference.make_params(cfg, 3000000019)
    prompt = np.random.default_rng(1).integers(
        1, cfg["vocab_size"], 80).tolist()
    toks, lps = _served(cfg, name, params, prompt, 8)
    assert len(toks) == 8
    ref = reference.chosen_logprobs(cfg, params, prompt, toks)
    assert _rms(lps, ref) <= limit
    # the reference one precision step down, in the program's place
    # float32 here: bf16 is the step; kv_int8 rounds the cached K, V alone
    assert set(controls) <= set(reference.CONTROLS)
    for quant in controls:
        ctl = reference.chosen_logprobs(cfg, params, prompt, toks, quant)
        assert _rms(ctl, ref) > 3 * limit, quant


def test_the_programs_own_int8_cache_fails_the_tolerance():
    cfg = _cfg("tiny-dense")
    params = reference.make_params(cfg, 5)
    prompt = np.random.default_rng(2).integers(
        1, cfg["vocab_size"], 80).tolist()
    toks, lps = _served(cfg, "tiny-dense", params, prompt, 8,
                        kv_dtype="int8")
    ref = reference.chosen_logprobs(cfg, params, prompt, toks)
    assert _rms(lps, ref) > cfg["correct"]["limit"]


def test_moe_reference_routes_two_experts_and_renormalises():
    cfg = _cfg("tiny-moe")
    z = reference.sizes(cfg)
    params = reference.make_params(cfg, 9)
    blk = jax.tree_util.tree_map(lambda x: x[0], params["blocks"])
    x = jax.random.normal(jax.random.key(0), (5, z["d"]))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(reference._moe(z, blk, x, None))
    # the published block, token by token in numpy
    xs = np.asarray(x, np.float64)
    want = np.zeros_like(xs)
    for t in range(xs.shape[0]):
        logits = xs[t] @ np.asarray(blk["router"], np.float64)
        p = np.exp(logits - logits.max())
        p /= p.sum()
        top = np.argsort(-p)[:2]
        for e in top:
            g = xs[t] @ np.asarray(blk["moe_gate"][e], np.float64)
            u = xs[t] @ np.asarray(blk["moe_up"][e], np.float64)
            h = g / (1 + np.exp(-g)) * u
            want[t] += p[e] / p[top].sum() * (
                h @ np.asarray(blk["moe_down"][e], np.float64))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)


def test_weights_follow_the_seed_and_the_served_dtype():
    cfg = _cfg("tiny-dense")
    a = reference.make_params(cfg, 1)
    b = reference.make_params(cfg, 1)
    c = reference.make_params(cfg, 2 ** 31 + 5)
    assert np.array_equal(a["blocks"]["wq"], b["blocks"]["wq"])
    assert not np.array_equal(a["blocks"]["wq"], c["blocks"]["wq"])
    assert a["embed"].dtype == np.dtype(cfg["torch_dtype"])
    assert abs(float(np.std(a["blocks"]["w_up"])) - 0.02) < 0.002
