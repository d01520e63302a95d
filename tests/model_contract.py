"""What "served" guarantees for a model configuration, written once.

A model the engine serves from its pools is a CASE of this contract:
``tests/test_<model>_contract.py`` holds a ``Case`` (the tiny configuration
file's name and the few numbers no file says) and takes the contract's tests
with ``globals().update(contract_of(CASE))`` (the delta-rule models in two
files, ``part="served"`` and ``part="state"``); the model's own file,
``tests/test_<model>.py``, keeps only what is that model's mechanism. This
module is not collected (the precedent is ``kda_packed_case.py``).

Every test below reads what it needs off the case's ``ModelConfig`` and its
file (``benchmark/tests/rehearse/configs/<tiny>.json``, whose plain reference
under ``benchmark/references/`` shares no code with the program), never off a
model's name. ``where`` says which models a test is of.

``scripts/correct_readings.py`` imports ``serve``, ``faulty`` and ``left_out``
from here: the controls the chip's ``correct`` limits were set against are
the functions tier-1 runs.
"""

import contextlib
import dataclasses
import functools
import json
import math
import os
import re
import sys
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rbg_tpu.engine import Engine, EngineConfig, SamplingParams
from rbg_tpu.models import config as presets
from rbg_tpu.models import get_config, init_params, llama

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.append(BENCH)          # the benchmark's ``harness`` package


def read(*path) -> dict:
    """The benchmark's JSON file ``benchmark/<path>``."""
    with open(os.path.join(BENCH, *path)) as f:
        return json.load(f)


# ---- a case, and what is loaded for it ----------------------------------------


@dataclasses.dataclass(frozen=True)
class Case:
    tiny: str           # the preset, and the rehearsal file of that name
    # the reference's own controls: {quantisation: limits it has to be past}
    controls: tuple
    # a recurrent model: the bytes of one row's state, read and written once
    row_bytes: int = 0
    # ((params key, leaf, factor), ...) on the reference's weights
    scaled: tuple = ()


class Bench(NamedTuple):
    cfg: dict           # the tiny configuration file
    reference: object   # its module under ``benchmark/references/``
    params: dict        # that module's weights
    preset: object      # the preset registered from the file, ``<tiny>-file``


@functools.lru_cache(maxsize=None)
def load(case: Case) -> Bench:
    """The file's reference, weights and preset, once a process."""
    from harness import serve as harness
    cfg = read("tests", "rehearse", "configs", case.tiny + ".json")
    reference = harness.load_reference(cfg)
    params = reference.make_params(cfg, 3000000019)
    for key, leaf, factor in case.scaled:
        params[key][leaf] = factor * params[key][leaf]
    name = case.tiny + "-file"
    presets._PRESETS[name] = harness.model_config(cfg, name)
    return Bench(cfg, reference, params, presets._PRESETS[name])


def engine(bench: Bench, model=None, **kw) -> Engine:
    """The file's server, ``kw`` over it."""
    return Engine(EngineConfig(model=model or bench.preset.name,
                               **{**bench.cfg["server"], **kw}),
                  params=bench.params)


def drive(eng, ids=None, between=None):
    """Step ``eng`` until it has no work: ``(tokens, logprobs)`` a request,
    in the order of ``ids``. ``between(eng, out)`` runs after every step."""
    out = {}
    while eng.has_work():
        for ev in eng.step():
            toks, lps = out.setdefault(ev.request_id, ([], []))
            toks.append(ev.token)
            lps.append(ev.logprob)
        if between is not None:
            between(eng, out)
    return out if ids is None else [out[i] for i in ids]


def serve(eng, prompts, new):
    """``prompts`` side by side, ``new`` tokens each."""
    ids = [eng.add_request(p, SamplingParams(max_new_tokens=new,
                                             logprobs=True)) for p in prompts]
    return drive(eng, ids)


def rms(a, b):
    d = np.asarray(a, np.float64) - np.asarray(b, np.float64)
    return math.sqrt(float(np.mean(d * d)))


def prompts(cfg, lens, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg["vocab_size"], n).tolist() for n in lens]


def error(bench: Bench, prompt, served, params=None, quant=None):
    """RMS of ``served``'s log-probabilities against the reference's on the
    same tokens; with ``quant`` or other ``params``, of the reference so
    altered against itself."""
    toks, lps = served
    ref = bench.reference.chosen_logprobs(bench.cfg, bench.params, prompt,
                                          toks)
    if params is not None or quant is not None:
        lps = bench.reference.chosen_logprobs(
            bench.cfg, params or bench.params, prompt, toks, quant)
    return rms(lps, ref)


# ---- the faults, under one set of names ---------------------------------------

FAULTS = ("not carried", "not zeroed")


@contextlib.contextmanager
def mixers(wrap):
    """``models/llama.py``'s recurrent mixers, each replaced by
    ``wrap(real)`` inside the block. A mixer is a program of its own
    (``_<kind>_mixer``) and keeps what it traced, so a patched name and a
    cached trace would disagree: those programs forget theirs on the way in
    and out (a step program is its engine's own, and no other cache goes)."""
    real = {name: getattr(llama, name)
            for name in ("_kda_attention", "_conv_attention")}

    def put(fns):
        for name in real:
            setattr(llama, name, fns(real[name]))
        for program in llama._RECURRENT_MIXERS.values():
            program.clear_cache()

    put(wrap)
    try:
        yield
    finally:
        put(lambda fn: fn)


def faulty(fault):
    """The recurrent mixers with ``fault`` in them: ``not carried`` (every
    chunk of a prompt starts from a zero state) or ``not zeroed`` (no row
    starts from zeros: it goes on from what its slot's last row left). The
    decode kernel takes its ``fresh`` rows from the same positions."""
    assert fault in FAULTS, fault

    def breaker(real):
        def broken(g, blk, x, state, layer, addr, use_pallas):
            pos = addr.positions
            if fault == "not carried":
                if x.shape[1] > 1:      # every chunk of a prompt looks first
                    pos = pos - pos[..., :1] if addr.row_ids is None else \
                        jnp.where(addr.token_mask, 0, pos)
            else:
                pos = jnp.where(pos == 0, 1 << 20, pos)   # never looks first
            return real(g, blk, x, state, layer,
                        addr._replace(positions=pos), use_pallas)
        return broken

    return mixers(breaker)


def left_out(m, page_size: int = 16) -> dict:
    """``{fault: ModelConfig fields}``: the preset ``m`` with one of the
    rules it sets left out. A model with window layers: the MODEL reads the
    window of its window kind's group config (``window_layer``) and the
    ENGINE the preset's own ``sliding_window``, so a fault of either is the
    two set apart: the mask and the walk without the window over pages
    given back as ever, or pages given back one page (``page_size`` slots)
    early under the mask and the walk as they are (``broken_preset``
    builds it: the constructor refuses a preset of two windows). ``fitted``
    cuts the weights to a fault that changes a head count."""
    rules = {}
    if m.attn_gate:
        rules["gate dropped"] = dict(attn_gate=False)
    if m.kda_beta_scale != 1.0:
        rules["b not scaled"] = dict(kda_beta_scale=1.0)
    if not m.use_rope and not m.mla:
        rules["rotated"] = dict(use_rope=True)
    if m.rope_scaling:
        rules["yarn dropped"] = dict(rope_scaling="")
    if m.partial_rotary_factor != 1.0:
        rules["whole head rotated"] = dict(partial_rotary_factor=1.0)
    if m.sliding_window:
        kind = dict(m.window_layer)
        rules["window not kept"] = dict(
            window_layer={**kind, "sliding_window": 1 << 20})
        rules["page given back early"] = dict(
            sliding_window=m.sliding_window - page_size,
            window_layer={**kind, "sliding_window": m.sliding_window})
        if kind.get("num_heads", m.num_heads) != m.num_heads:
            rules["head counts as one"] = dict(
                window_layer={**kind, "num_heads": m.num_heads})
    if m.loop_steps > 1:
        rules["last pass dropped"] = dict(loop_steps=m.loop_steps - 1)
        rules.update({fault: {} for fault in LOOP_FAULTS})
    if m.post_norms:
        rules.update({fault: {} for fault in POST_NORM_FAULTS})
    return rules


# The rules of a looped model and of sandwich norms that no field states:
# each is left out by a patch of ``models/llama.py`` (``patched``), the
# preset as it is.
LOOP_FAULTS = ("pass norm between passes dropped",
               "every pass on pass 0's pages")
POST_NORM_FAULTS = ("mixer's post norm dropped", "MLP's post norm dropped")


@contextlib.contextmanager
def patched(fault):
    """``models/llama.py`` with ``fault`` in it, for the faults of
    ``left_out`` that are no field of the preset; any other fault: the
    module as it is. ``pass norm between passes dropped``: the final norm
    is applied once, after the last pass, as in a model that runs its
    layers once. ``every pass on pass 0's pages``: pass ``t`` of layer ``l``
    reads and writes entry ``l`` for ``t * L + l``. ``mixer's`` / ``MLP's
    post norm dropped``: that residual adds the sub-layer's output as it
    is. (A step program is its engine's own: build the engine inside.)"""
    real = {name: getattr(llama, name)
            for name in ("_pass_norm", "_pass_addr", "_post_norm", "_head")}
    if fault == "pass norm between passes dropped":
        llama._pass_norm = lambda params, cfg, x: x
        llama._head = lambda params, cfg, x: real["_head"](
            params, cfg._kind(loop_steps=1), x)     # which norms, once
    elif fault == "every pass on pass 0's pages":
        llama._pass_addr = lambda addr, t, entry_pages: addr
    elif fault in POST_NORM_FAULTS:
        which = ("attn_post_norm" if fault.startswith("mixer")
                 else "mlp_post_norm")

        def post_norm(cfg, blk, x, out, norm):
            return x + out if norm == which else real["_post_norm"](
                cfg, blk, x, out, norm)
        llama._post_norm = post_norm
    try:
        yield
    finally:
        for name, fn in real.items():
            setattr(llama, name, fn)


def broken_preset(m, name: str, fields: dict):
    """The preset ``m`` under ``name`` with a fault's ``fields``
    (``left_out``). ``window_layer`` is set behind the constructor, which
    holds a preset's two readings of the window to one value: the fault is
    the only config whose model and engine read different windows."""
    fields = dict(fields)
    kind = fields.pop("window_layer", None)
    broken = dataclasses.replace(m, name=name, **fields)
    if kind is not None:
        object.__setattr__(broken, "window_layer",
                           type(m.window_layer)(sorted(kind.items())))
    return broken


def fitted(m, params: dict) -> dict:
    """``params`` cut to the preset ``m`` where a fault gave its window
    layers fewer heads than the weights hold: the first heads' columns of
    ``wq`` and ``wg`` and rows of ``wo`` (the others are dropped, and a
    query head reads the KV head of the other kind's grouping)."""
    kind = dict(m.window_layer)
    if "window_mixers" not in params or "num_heads" not in kind:
        return params
    h, hd = kind["num_heads"], m.head_dim_
    held = params["window_mixers"]
    out = 1 if m.proj_out_in else 2     # ``wq``'s axis of heads
    if held["wq"].shape[out] == h * hd:
        return params
    cut = {**held, "wq": jnp.take(held["wq"], jnp.arange(h * hd), axis=out),
           "wo": held["wo"][:, :h * hd], "wg": held["wg"][..., :h]}
    return {**params, "window_mixers": cut}


def places(m, kind) -> int:
    """In how many places ``_hybrid_layers`` walks ``m``'s mixer of
    ``kind``: a run of layers is one, a stretch of turns one a kind."""
    return sum(pair[0] == kind + "_mixers" for seg in llama._hybrid_plan(m)
               for pair in (seg[1:2] if seg[0] == "run" else seg[1:3]))


# ---- which tests a case takes -------------------------------------------------


def where(applies, part="served"):
    """Marks a test as one of the models whose preset ``applies`` holds of,
    and of that part of the contract."""
    def mark(fn):
        fn.applies, fn.part = applies, part
        return fn
    return mark


def contract_of(case: Case, part=None) -> dict:
    """The contract's tests and fixtures for ``case``, by name, in the
    order they run: for a case file's ``globals()``. A model whose contract
    is too long for one worker (a file is one worker's under ``--dist
    loadfile``) takes it in two files, ``part`` "served" and "state"."""
    m = get_config(case.tiny)
    taken = {"case": case_of_the_module, "bench": bench,
             "pytest_generate_tests": pytest_generate_tests}
    for name, fn in globals().items():
        if (name.startswith("test_")
                and getattr(fn, "applies", lambda m: True)(m)
                and part in (None, getattr(fn, "part", "served"))):
            taken[name] = fn
    return taken


@pytest.fixture(scope="module", name="case")
def case_of_the_module(request) -> Case:
    return request.module.CASE


@pytest.fixture(scope="module")
def bench(case) -> Bench:
    return load(case)


def family(m) -> str:
    """Which of ``REFUSALS``' families the preset ``m`` is of."""
    return ("recurrent" if m.recurrent else "window" if m.sliding_window
            else "looped")


def pytest_generate_tests(metafunc):
    """The parameters that depend on the case's preset: the forms it is
    served in (by row AND densely dispatched at once only where no layer is
    recurrent: a recurrent model's rows and its dense dispatch each have a
    form already, and the fifth costs its file 13-25 s), the rules it sets,
    and the kernels' form of a fault where its state has a kernel."""
    m = get_config(metafunc.module.CASE.tiny)
    if "form" in metafunc.fixturenames:
        metafunc.parametrize("form", [f for f in FORMS if not (
            m.unbuilt_for and f == "rows-dense")])
    if "rule" in metafunc.fixturenames:
        metafunc.parametrize("rule", sorted(left_out(m)))
    if "refused" in metafunc.fixturenames:
        metafunc.parametrize("refused", sorted(REFUSALS[family(m)]))
    if "state_by" in metafunc.fixturenames:
        metafunc.parametrize("state_by", ["auto", "always"]
                             if m.mixer_count("kda") else ["auto"])


# ---- the contract -------------------------------------------------------------


def test_the_file_reaches_the_preset_and_the_programs_parameters(bench, case):
    """The rehearsal file maps to the preset the program ships under the
    file's name, and the reference's weights are ``init_params``' tree."""
    own = get_config(case.tiny)
    free = dict(name=own.name, max_seq_len=own.max_seq_len)
    if own.mla:         # latent attention reads no ``head_dim``
        free["head_dim"] = own.head_dim
    assert dataclasses.replace(bench.preset, **free) == own
    made = jax.eval_shape(lambda: init_params(bench.preset,
                                              jax.random.key(0)))
    shapes = lambda tree: jax.tree_util.tree_map(
        lambda x: (x.shape, x.dtype), tree)
    assert shapes(bench.params) == shapes(made)


# {id: (ragged, the experts in the hit form, use_pallas)}
FORMS = {"packed-hit": ("auto", True, "auto"),
         "rows-hit": ("off", True, "auto"),
         "packed-dense": ("auto", False, "auto"),
         "rows-dense": ("off", False, "auto"),
         "packed-hit-kernels": ("auto", True, "always")}


def test_served_path_agrees_with_the_plain_reference(
        bench, monkeypatch, interpreted, form):
    """Three prompts side by side, the longest of three prefill chunks:
    whatever the layers keep (pages, a state, a convolution's tail) is
    carried from chunk to chunk (packed with the other rows' decode steps,
    or by row), then decode steps through the pools, the experts in the hit
    form or densely dispatched: in plain XLA, and by every kernel the model
    reaches in one step."""
    cfg, m = bench.cfg, bench.preset
    ragged, hit, use_pallas = FORMS[form]
    if not hit:
        monkeypatch.setattr(llama, "hit_experts_pay", lambda c, rows: False)
    asked = prompts(cfg, (80, 23, 40))
    eng = engine(bench, ragged=ragged, use_pallas=use_pallas)
    served = serve(eng, asked, 8)
    if m.num_experts:
        assert (eng.metrics["moe_experts_visited"] > 0) == hit
    for prompt, got in zip(asked, served):
        assert len(got[0]) == 8
        assert error(bench, prompt, got) <= cfg["correct"]["limit"]
    if m.unbuilt_for:   # no prefix is kept: every slot and page comes back
        assert eng.state is None or eng.state.held == 0
        assert eng.allocator.free_pages == cfg["server"]["num_pages"] - 1
    if m.sliding_window:    # both classes of page end where they began
        pool = eng.window_allocator
        assert pool.free_pages == pool.num_pages - 1
        assert eng.metrics["kv_window_pages_released"] > 0


def test_num_params_counts_what_init_makes(case):
    m = get_config(case.tiny)
    made = jax.eval_shape(lambda: init_params(m, jax.random.key(0)))
    assert m.num_params == sum(a.size for a in jax.tree_util.tree_leaves(made))


@functools.lru_cache(maxsize=None)
def whole_prompt(case: Case):
    """A prompt of 90 tokens and what it is served as in one chunk (once a
    process: an engine's step programs are its own, and compile anew)."""
    bench = load(case)
    prompt, = prompts(bench.cfg, (90,), seed=7)
    return prompt, serve(engine(bench, prefill_chunk=128), [prompt], 6)[0]


@pytest.mark.parametrize("chunk", [16, 48, 128])
def test_a_prompt_in_chunks_equals_it_whole(bench, case, chunk):
    prompt, whole = whole_prompt(case)
    got = serve(engine(bench, prefill_chunk=chunk), [prompt], 6)[0]
    assert got[0] == whole[0] and rms(got[1], whole[1]) < 1e-5
    assert error(bench, prompt, got) <= bench.cfg["correct"]["limit"]


def test_the_controls_fail_the_tiny_limits(bench, case):
    """What the limit is for: the reference one precision down, and the
    router without its selection bias (other experts at most positions),
    are far from the reference on the tokens the sound program served; so is
    the program's own int8 cache, where the model may have one."""
    cfg, m, limit = bench.cfg, bench.preset, bench.cfg["correct"]["limit"]
    prompt, = prompts(cfg, (80,))
    got, = serve(engine(bench), [prompt], 8)
    assert error(bench, prompt, got) <= limit
    for quant, limits in case.controls:
        assert error(bench, prompt, got, quant=quant) > limits * limit, quant
    if m.moe_select_bias:
        flat = jax.tree_util.tree_map_with_path(
            lambda path, a: jnp.zeros_like(a)
            if path[-1].key == "router_bias" else a, bench.params)
        assert error(bench, prompt, got, params=flat) > 30 * limit
    if not m.unbuilt_for:   # a state, a window class: neither has an int8 form
        got8, = serve(engine(bench, kv_dtype="int8"), [prompt], 8)
        assert error(bench, prompt, got8) > limit


def test_the_counters_count_state_rows_and_expert_visits(bench, case):
    m = bench.preset
    eng = engine(bench)
    serve(eng, prompts(bench.cfg, (40, 20)), 5)
    c = dict(eng.metrics)
    if m.recurrent:
        assert c["state_resets"] == 2
        assert 0 < c["state_slots_live"] <= c["state_slots_held"]
        row = case.row_bytes
        assert eng.state.row_bytes == row
        # every row-step of a decode or unified step moved one row's state
        assert c["state_bytes_moved"] % row == 0
        assert c["state_bytes_moved"] // row >= c["decode_tokens"]
        assert c["prefix_skipped"] == 2 and c["radix_hit_tokens"] == 0
    if m.sliding_window:
        # a prompt of 40 passes several windows: pages came back, and what
        # is held is the live windows' pages, never more than a row's most
        assert c["prefix_skipped"] == 2 and c["radix_hit_tokens"] == 0
        assert c["kv_window_pages_released"] > 0
        live, held = (c["kv_window_live_token_steps"],
                      c["kv_window_held_slot_steps"])
        assert 0 < live <= held < 3 * live
        assert live <= 2 * m.sliding_window * c["steps_run"]
    if m.num_experts:
        # slots count the experts held, in the expert layers alone; (row,
        # chosen expert) pairs the held share of rows x top k x layers
        layers = m.num_moe_layers
        a_row = layers * m.experts_per_token * m.experts_here // m.num_experts
        assert c["decode_steps_run"] > 0
        assert c["moe_expert_slots"] == (c["decode_steps_run"] * layers
                                         * m.experts_here)
        assert 0 < c["moe_experts_visited"] <= c["moe_expert_slots"]
        # (a decode token packed beside a prompt's chunk came from a unified
        # step, which visits no expert)
        assert c["moe_routed_rows"] % a_row == 0
        assert 0 < c["moe_routed_rows"] <= a_row * c["decode_tokens"]
        # Two prompts that one step prefills whole: every decode token after
        # is a decode step's, and the pairs are exactly a row-step's each.
        serve(eng, prompts(bench.cfg, (5, 3)), 6)
        d = {k: v - c[k] for k, v in eng.metrics.items() if k in c}
        assert d["unified_steps_run"] == 1 and d["decode_tokens"] == 10
        assert d["moe_routed_rows"] == a_row * d["decode_tokens"]
        assert d["moe_experts_visited"] > 0
        if m.experts_here == m.num_experts:     # every pair is a held one
            assert d["moe_experts_visited"] <= d["moe_routed_rows"]


@where(lambda m: m.experts_held)
def test_the_shares_partial_results_add_up_to_the_whole_expert_layer(bench):
    """Every chip's share of one expert layer, the shared expert counted
    once, is the layer; the hit form computes the same share and visits
    held experts only; the router keeps its width."""
    g = next(g for _, g, _, _ in bench.preset.layer_groups if g.num_experts)
    whole = dataclasses.replace(g, experts_held=None, half="mlp")
    blk = llama._init_blocks(whole, jax.random.key(5), 1, lambda k, s, sc: (
        jax.random.normal(k, s, jnp.float32) * sc), 0.1, 0.1)
    blk = {k: v[0] for k, v in blk.items()}
    E, n = g.num_experts, g.experts_here
    # a shared expert is taken off every share and added back once
    tol = dict(rtol=1e-4, atol=1e-5) if g.moe_shared_expert else dict(
        rtol=1e-5, atol=1e-6)
    x = jax.random.normal(jax.random.key(2), (6, 1, g.hidden_size))
    total = np.asarray(llama._moe_mlp(whole, blk, x), np.float64)
    shared = np.asarray(llama._shared_expert(blk, x), np.float64) \
        if g.moe_shared_expert else 0.0
    parts = np.zeros_like(total)
    for lo in range(0, E, n):
        share = dataclasses.replace(whole, experts_held=(lo, lo + n))
        held = {k: (v[lo:lo + n] if k in llama._EXPERT_STACKS else v)
                for k, v in blk.items()}
        part = np.asarray(llama._moe_mlp(share, held, x), np.float64)
        parts += part - shared
        stacks = {k: held[k][None] for k in llama._EXPERT_STACKS}
        got, visited = jax.jit(lambda b, x, s: llama._moe_mlp_hit(
            share, b, x, s, jnp.int32(0), jnp.ones((6, 1), bool)))(
                held, x, stacks)
        np.testing.assert_allclose(got, part, **tol)
        w = np.asarray(llama._route(share, held, x))[:, 0]
        assert w.shape == (6, E)
        assert int(visited) == (w[:, lo:lo + n] > 0).any(0).sum() <= n
    np.testing.assert_allclose(parts + shared, total, **tol)
    assert not g.moe_shared_expert or np.abs(shared).max() > 1e-3


# (of the second file where a model's contract is in two: the first is longer)
@where(lambda m: left_out(m), part="state")
def test_each_rule_left_out_moves_the_logits_far_past_the_limit(bench, rule):
    """The rules the preset sets, left out of the served path one at a
    time, as the chip's controls leave them out: each is far from the
    reference, which keeps them all."""
    cfg = bench.cfg
    name = bench.preset.name + "-broken"
    broken = presets._PRESETS[name] = broken_preset(
        bench.preset, name,
        left_out(bench.preset, cfg["server"]["page_size"])[rule])
    prompt, = prompts(cfg, (80,), seed=5)
    with patched(rule):
        got, = serve(Engine(EngineConfig(model=name, **cfg["server"]),
                            params=fitted(broken, bench.params)), [prompt], 8)
    # (rotation moves a toy least, 30 limits: its scores are near uniform)
    assert error(bench, prompt, got) > 10 * cfg["correct"]["limit"]
    # and the sound program on the same prompt is within it
    got, = serve(engine(bench), [prompt], 8)
    assert error(bench, prompt, got) <= cfg["correct"]["limit"]


@where(lambda m: not m.unbuilt_for)
def test_contiguous_forward_agrees_with_the_reference_and_trains(bench):
    cfg, m = bench.cfg, bench.preset
    toks = np.random.default_rng(3).integers(1, cfg["vocab_size"],
                                             24).tolist()
    logits, cache = llama.forward(
        bench.params, m, jnp.asarray([toks], jnp.int32),
        llama.KVCache.create(m, 1, 32))
    assert cache.k.shape[0] == m.cache_layers and int(cache.length[0]) == 24
    lp = jax.nn.log_softmax(logits[0], -1)
    got = lp[jnp.arange(15, 23), jnp.asarray(toks[16:24])]
    assert error(bench, toks[:16], (toks[16:], got)) <= \
        cfg["correct"]["limit"]
    train = llama.forward_train(bench.params, m,
                                jnp.asarray([toks], jnp.int32), remat=True)
    np.testing.assert_allclose(np.asarray(train), np.asarray(logits),
                               rtol=1e-3, atol=1e-4)


# ---- a model with recurrent layers: its state is a row's own ------------------

recurrent = where(lambda m: m.recurrent, part="state")
# ... and what holds of every model that keeps no prefix: recurrent layers,
# or window layers, whose second class of page is given back as it is served
pools_alone = where(lambda m: m.unbuilt_for, part="state")
TINY_KW = dict(page_size=8, num_pages=32, max_seq_len=64, max_batch=2,
               prefill_chunk=16)


@recurrent
@pytest.mark.parametrize("program", ["decode", "ragged"])
def test_a_step_program_traces_each_recurrent_mixer_once(case, program):
    """The walk meets a recurrent mixer in more places than one (Kimi's
    dense first layer alone and the expert layers' loop; LFM2's dense
    layers' loop and the expert layers' turns): ``_<kind>_mixer`` makes
    them one trace, called from each place (a third body cost 16 s of warm
    set-up on the chip; PERF.md, PR 38)."""
    from kda_packed_case import eqns, step_jaxpr
    m = get_config(case.tiny)
    traced = []
    with mixers(lambda real: lambda *a: traced.append(1) or real(*a)):
        jaxpr = step_jaxpr(m, init_params(m, jax.random.key(0)), program, R=3)
    assert len(traced) == len(m.recurrent_kinds)
    called = [e.params.get("name") for e, _ in eqns(jaxpr)]
    for kind in ("kda", "conv"):
        assert called.count(f"_{kind}_mixer") == places(m, kind)


@recurrent
def test_a_slot_reused_after_finish_and_after_preemption_starts_from_zeros(
        bench):
    limit = bench.cfg["correct"]["limit"]
    a, b, c = prompts(bench.cfg, (70, 50, 33), seed=2)
    alone = serve(engine(bench, max_batch=1), [b], 6)[0]
    eng = engine(bench, max_batch=1)
    serve(eng, [a], 6)                      # leaves its state in slot 0
    assert eng.state.held == 0
    assert max(float(jnp.abs(s[:, 0]).max())
               for s in eng.state.arrays.values()) > 0
    again = serve(eng, [b], 6)[0]           # the same slot
    assert again[0] == alone[0] and rms(again[1], alone[1]) < 1e-5
    assert eng.metrics["state_resets"] == 2
    # a row preempted in the middle of its prompt, then another in its slot
    rid = eng.add_request(a, SamplingParams(max_new_tokens=6, logprobs=True))
    eng.step()
    req = eng.requests[rid]
    assert req.state == "prefill" and req.state_slot == 0
    eng._preempt(req)
    assert req.state_slot is None and eng.state.held == 0
    eng.waiting.remove(req)
    eng.requests.pop(rid)
    after = serve(eng, [c], 6)[0]
    assert error(bench, c, after) <= limit
    assert eng.metrics["state_resets"] == 4


@pools_alone
def test_a_preempted_requests_second_run_gives_the_first_runs_logits(bench):
    prompt, other = prompts(bench.cfg, (60, 30), seed=3)
    whole = serve(engine(bench), [prompt], 12)[0]

    eng = engine(bench)
    ids = [eng.add_request(p, SamplingParams(max_new_tokens=12,
                                             logprobs=True))
           for p in (prompt, other)]
    done = []

    def preempt_once(eng, out):
        req = eng.requests.get(ids[0])
        if not done and req is not None and len(out.get(ids[0], ([],))[0]) >= 5:
            for ev in eng._drain_decode():      # tokens in flight first
                out[ev.request_id][0].append(ev.token)
                out[ev.request_id][1].append(ev.logprob)
            if req.state == "running":
                eng._preempt(req)
                done.append(len(req.prompt))

    toks, lps = drive(eng, ids, preempt_once)[0]
    assert done and done[0] > len(prompt)       # prefilled again from token 0
    assert eng.metrics["preemptions"] == 1
    assert toks == whole[0] and rms(lps, whole[1]) < 1e-4
    assert error(bench, prompt, (toks, lps)) <= bench.cfg["correct"]["limit"]


@pools_alone
def test_a_cached_prefix_matches_nothing_and_still_agrees(bench):
    first, tail = prompts(bench.cfg, (64, 20), seed=4)
    eng = engine(bench)
    assert eng.radix is not None
    serve(eng, [first], 4)
    second = first + tail                   # its first 64 tokens were served
    got, = serve(eng, [second], 6)
    m = eng.metrics
    assert m["radix_hit_tokens"] == 0 and m["prefix_skipped"] == 2
    assert eng.radix.match(first[:-1])[0] == 0      # nothing was inserted
    assert m["prefill_tokens"] == len(first) + len(second)
    assert error(bench, second, got) <= bench.cfg["correct"]["limit"]


@where(lambda m: not m.unbuilt_for)
def test_a_cached_prefix_is_reused_and_still_agrees(bench):
    """A model of one class of page keeps its prefixes: a prompt that
    shares another's served tokens takes their pages (every entry of the
    pool's leading axis lies behind a page id) and prefills the rest."""
    first, tail = prompts(bench.cfg, (64, 20), seed=4)
    eng = engine(bench)
    serve(eng, [first], 4)
    second = first + tail
    got, = serve(eng, [second], 6)
    m = eng.metrics
    assert m["radix_hit_tokens"] == 64 and m["prefix_skipped"] == 0
    assert m["prefill_tokens"] == len(first) + len(tail)
    assert error(bench, second, got) <= bench.cfg["correct"]["limit"]
    alone, = serve(engine(bench), [second], 6)
    assert got[0] == alone[0] and rms(got[1], alone[1]) < 1e-4


# {what the model has: {what is asked for: (EngineConfig fields, the
# refusal's message)}}: ``Engine._refuse_unbuilt``'s reasons, by mechanism
REFUSALS = {
    "recurrent": {
        "speculative": (dict(speculative="ngram"),
                        "speculative decoding: a rejected draft"),
        "int8": (dict(kv_dtype="int8"),
                 "the state pool has no quantised form"),
        "prefill role": (dict(mode="prefill"),
                         "PD bundle carries pages, not the recurrent state"),
        "decode role": (dict(mode="decode"),
                        "PD bundle carries pages, not the recurrent state"),
        "host tier": (dict(host_tier_bytes=1 << 20),
                      "host tier keeps prefixes"),
        "mesh": (dict(mesh=True),
                 "a device mesh: the state pool has no sharding"),
    },
    "window": {
        "speculative": (dict(speculative="ngram"),
                        "the verify step has no window table"),
        "int8": (dict(kv_dtype="int8"),
                 "the window class of page has no quantised form"),
        "prefill role": (dict(mode="prefill"),
                         "PD bundle carries one class of page"),
        "decode role": (dict(mode="decode"),
                        "PD bundle carries one class of page"),
        "host tier": (dict(host_tier_bytes=1 << 20),
                      "a prefix's window pages were given back"),
        "mesh": (dict(mesh=True),
                 "the window class of page has no sharding"),
    },
    "looped": {
        "speculative": (dict(speculative="ngram"),
                        "speculative decoding: no test holds a verify"),
        "prefill role": (dict(mode="prefill"),
                         "PD bundle is sent and taken in windows of layers"),
        "decode role": (dict(mode="decode"),
                        "PD bundle is sent and taken in windows of layers"),
        "mesh": (dict(mesh=True),
                 "sharding specs know neither the norms after a sub-layer"),
    },
}


def _named(cfg) -> str:
    """How a refusal names what ``cfg`` has, as a pattern."""
    return re.escape(cfg.unbuilt_for or cfg.looped_for)


@where(lambda m: m.unbuilt_for or m.looped_for, part="state")
def test_engine_refuses_what_the_model_does_not_support(case, refused):
    """``Engine._refuse_unbuilt``'s and ``_refuse_looped``'s reasons, by
    message: each names the mechanism that is missing for what the model
    has."""
    cfg = get_config(case.tiny)
    kw, match = REFUSALS[family(cfg)][refused]
    kw, mesh = dict(kw), None
    if kw.pop("mesh", False):
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                    ("dp", "tp"))
    with pytest.raises(ValueError, match=match) as e:
        Engine(EngineConfig(model=case.tiny, **TINY_KW, **kw), mesh=mesh)
    assert re.search(_named(cfg), str(e.value))


@pools_alone
def test_other_paths_refuse_a_model_served_from_its_pools_alone(case):
    cfg = get_config(case.tiny)
    params = init_params(cfg, jax.random.key(0))
    eng = Engine(EngineConfig(model=case.tiny, **TINY_KW), params=params)
    L, D = cfg.num_layers, cfg.hidden_size
    with pytest.raises(ValueError, match="groups of layers"):
        eng.load_lora("a", {"wo": (np.zeros((L, D, 4), np.float32),
                                   np.zeros((L, 4, D), np.float32))})
    with pytest.raises(ValueError, match="state at its end"):
        eng.add_request_with_prefix(list(range(1, 20)), None, 8, None, None)
    tokens = jnp.ones((1, 4), jnp.int32)
    named = _named(cfg)
    with pytest.raises(NotImplementedError,
                       match=named + ": the contiguous cache"):
        llama.forward(params, cfg, tokens, llama.KVCache(
            k=jnp.zeros((L, 1, 8, 1, 64)), v=jnp.zeros((L, 1, 8, 1, 16)),
            length=jnp.zeros((1,), jnp.int32)))
    with pytest.raises(NotImplementedError,
                       match=named + ": the cache-free forward"):
        llama.forward_train(params, cfg, tokens)
    with pytest.raises(NotImplementedError, match="walked whole"):
        llama.paged_layers(params, cfg, None, (), None, layers=(0, 4))
    from rbg_tpu.parallel import pipeline
    with pytest.raises(NotImplementedError, match="groups"):
        pipeline.pipeline_forward_train(params, cfg, tokens, mesh=None)


@recurrent
@pytest.mark.parametrize("fault", FAULTS)
def test_a_wrong_state_fails_the_tiny_limits(bench, interpreted, fault,
                                             state_by):
    """What the check's several-chunk prompt is for: a program that starts
    every chunk from zeros, or one that goes on from what a slot's last row
    left, is far from the reference (the decode steps in plain XLA, and by
    the state's kernel where it has one). A delta rule's wrong state moves
    a toy's logits hundreds of limits; a tail of two inputs is a small
    state, and a wrong one moves them tens."""
    far = 100 if bench.preset.mixer_count("kda") else 10
    first, second = prompts(bench.cfg, (80, 72), seed=5)
    with faulty(fault):
        eng = engine(bench, max_batch=1, use_pallas=state_by)
        got, = serve(eng, [first], 8)
        if fault == "not zeroed":           # the second row inherits a state
            got, = serve(eng, [second], 8)
            first = second
    assert error(bench, first, got) > far * bench.cfg["correct"]["limit"]
