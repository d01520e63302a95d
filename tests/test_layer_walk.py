"""The one walk over the paged layers (``models/llama.py::paged_layers``).

A chain of layer windows is the whole walk, pool included: the layer-sliced
decode admission (``engine/pd.py``) rests on it. And the
programs the engine jits carry the scope names the benchmark's
``device.*_share`` metrics read, on the operations that carry them.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rbg_tpu.engine import Engine, EngineConfig
from rbg_tpu.engine.kvcache import PagedKVCache
from rbg_tpu.engine.pd import DecodeWorker
from rbg_tpu.models import get_config, init_params
from rbg_tpu.models.llama import PoolAddr, paged_layers

I32 = jnp.int32
ROWS, PAGE, PAGES_PER_ROW = 3, 8, 2


def _addr(T, start, packed):
    """``ROWS`` rows of ``T`` tokens each from position ``start``, as a
    batch of rows or packed row-major on one token axis."""
    pos = jnp.broadcast_to(start + jnp.arange(T, dtype=I32), (ROWS, T))
    table = jnp.arange(ROWS * PAGES_PER_ROW, dtype=I32).reshape(ROWS, -1)
    lens = jnp.full((ROWS,), start + T, I32)
    if not packed:
        return PoolAddr(pos, jnp.ones((ROWS, T), bool), lens, table)
    return PoolAddr(pos.reshape(1, -1), jnp.ones((1, ROWS * T), bool), lens,
                    table, jnp.repeat(jnp.arange(ROWS, dtype=I32), T), T)


def _close(a, b):
    # int8 pages may round a last-bit difference to the next code.
    atol = 1 if a.dtype == np.int8 else 1e-6
    np.testing.assert_allclose(a.astype(np.float32), b.astype(np.float32),
                               rtol=1e-5, atol=atol)


# Two dense layers before four expert layers: two groups, two scans.
_GROUPS = dict(num_layers=6, first_dense_layers=2)


@pytest.mark.parametrize("preset,quantize,packed,shape", [
    ("tiny", False, False, {}), ("tiny-moe", False, False, {}),
    ("tiny-mla", False, False, {}), ("tiny", True, False, {}),
    ("tiny", False, True, {}), ("tiny-joyai", False, False, _GROUPS),
    ("tiny-joyai", False, True, _GROUPS)],
    ids=["dense", "moe", "mla", "int8-pool", "packed", "groups",
         "groups-packed"])
def test_chain_of_layer_windows_is_the_whole_walk(preset, quantize, packed,
                                                  shape):
    """Windows of two layers or more run the whole walk's loop body and
    agree with it to the bit. A window of ONE layer is a loop of one trip,
    which XLA unrolls and fuses with its surroundings: on the CPU a decode
    step then differs in the last bit (the token streams of
    ``test_kvtransfer.py``'s layer-sliced admissions do not). A model of
    two groups of layers is one scan a group, and a window that spans the
    boundary runs its part of each."""
    cfg = dataclasses.replace(get_config(preset),
                              **(shape or {"num_layers": 4}))
    L = cfg.num_layers
    params = init_params(cfg, jax.random.key(1))
    cache = PagedKVCache.create(cfg, ROWS * PAGES_PER_ROW, PAGE,
                                quantize=quantize)
    pool = (cache.k_pages, cache.v_pages, cache.k_scales, cache.v_scales)

    def walk(x, pool, addr, windows):
        for lo, hi in windows:
            # addr is closed over: its max_q_len is static.
            x, pool, _ = jax.jit(
                lambda x, pool, lo=lo, hi=hi: paged_layers(
                    params, cfg, x, pool, addr, layers=(lo, hi),
                    use_pallas="never"))(x, pool)
        return x, pool

    # L = 6, groups [0, 2) and [2, 6): "exact" spans the boundary with two
    # layers either side of it, "one-layer" leaves one layer of a group.
    chains = {"whole": [(0, L)], "exact": [(0, L - 2), (L - 2, L)],
              "one-layer": [(0, L - 3), (L - 3, L)]}
    pools = dict.fromkeys(chains, pool)
    # A prefill chunk into the empty pool, then a decode step that reads it.
    for T, start in ((5, 0), (1, 5)):
        shape = (1, ROWS * T) if packed else (ROWS, T)
        x = jax.random.normal(jax.random.key(T), shape + (cfg.hidden_size,),
                              cfg.jax_dtype)
        addr = _addr(T, start, packed)
        out = {}
        for name, windows in chains.items():
            out[name], pools[name] = walk(x, pools[name], addr, windows)
        assert np.isfinite(np.asarray(out["whole"], np.float32)).all()
        for name, same in (("exact", np.testing.assert_array_equal),
                           ("one-layer", _close)):
            same(np.asarray(out[name]), np.asarray(out["whole"]))
            for a, b in zip(pools[name], pools["whole"]):
                assert (a is None) == (b is None)
                if a is not None:
                    same(np.asarray(a), np.asarray(b))
    assert np.asarray(pools["whole"][0]).any()      # the steps did write
    assert (pools["whole"][2] is not None) == quantize


# ---- the scope names the benchmark reads device time by ---------------------


def _paths_of_dots(lowered) -> list:
    """The scope path of each ``dot_general`` of a lowered program
    (``jit(f)/while/body/attention/dot_general``), split at ``/``."""
    text = lowered.as_text(debug_info=True)
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    dots = re.findall(r'stablehlo\.dot_general.*loc\((#loc\d+)\)', text)
    assert dots
    return [names[d].split("/") for d in dots]


def _scopes_of_dots(lowered) -> list:
    """For each ``dot_general``, the model scopes on its path."""
    from rbg_tpu.obs.names import MODEL_SCOPES
    return [sorted(set(MODEL_SCOPES) & set(path))
            for path in _paths_of_dots(lowered)]


@pytest.fixture(scope="module", params=["tiny-moe", "tiny-joyai"])
def moe_engine(request):
    cfg = EngineConfig(model=request.param, page_size=8, num_pages=64,
                       max_seq_len=128, max_batch=4, prefill_chunk=16,
                       enable_radix_cache=False, use_pallas="never")
    return Engine(cfg)


def _lower(eng, program):
    from rbg_tpu.engine.sampler import row_keys
    S = jax.ShapeDtypeStruct
    cfg, pool = eng.cfg, eng.cache
    B, P, T = cfg.max_batch, cfg.max_pages_per_seq, 2 * cfg.prefill_chunk
    vec, table = S((B,), I32), S((B, P), I32)
    state = eng._state_kw([], B)     # a recurrent model's pool and slots
    if program == "decode":
        temps, ks, tps, mps, seeds, rids, _, _, _ = eng._sampling_rows([], B)
        return eng._get_decode_fn(B, False, False).lower(
            eng.params, vec, vec, vec, table, S((B, cfg.multi_step), bool),
            vec, pool.k_pages, pool.v_pages, None, None,
            row_keys(seeds, eng._sample_base, rids), jnp.asarray(temps),
            jnp.asarray(ks), jnp.asarray(tps), jnp.asarray(mps), **state)
    if program == "ragged":
        return eng._get_ragged_fn(B, T).lower(
            eng.params, S((1, T), I32), S((1, T), I32), S((1, T), bool),
            S((T,), I32), vec, table, pool.k_pages, pool.v_pages, None, None,
            S((1, T), I32), S((eng._rows_max,), I32),
            S((eng._rows_max,), I32), **state)
    worker = DecodeWorker(cfg, params=eng.params)
    return worker._get_window_fn(0, 1, B).lower(
        S((B, 1, eng.mcfg.hidden_size), eng.mcfg.jax_dtype), S((B, 1), I32),
        S((B, 1), bool), vec, table, pool.k_pages, pool.v_pages, None, None)


@pytest.mark.parametrize("program,want", [
    ("decode", {"attention", "moe", "lm_head"}),
    ("ragged", {"attention", "moe", "lm_head"}),
    ("window", {"attention", "moe"})])
def test_every_dot_of_a_step_program_sits_in_one_named_scope(
        moe_engine, program, want):
    """``device.attention_share``, ``device.moe_share`` and
    ``device.lm_head_share`` sum a trace's device time by these names: a
    dot outside every scope, or inside two, is time counted nowhere or
    twice."""
    scopes = _scopes_of_dots(_lower(moe_engine, program))
    assert all(len(s) == 1 for s in scopes), scopes
    if moe_engine.mcfg.first_dense_layers:
        # the dense layer before the expert layers; a window of layer 0
        # alone holds no expert layer
        want = want | {"mlp"} if program != "window" else {"attention", "mlp"}
    assert {s[0] for s in scopes} == want


def test_router_and_shared_expert_open_inside_the_expert_scope(moe_engine):
    """``device.moe_router_share`` and ``device.moe_shared_share`` read the
    paths ``moe/router`` and ``moe/shared``: both dots of a decode step's
    expert layer that are not a visit's."""
    from rbg_tpu.obs.names import MOE_INNER_SCOPES
    inner = {path[path.index("moe") + 1]
             for path in _paths_of_dots(_lower(moe_engine, "decode"))
             if "moe" in path}
    want = {"router"} | ({"shared"} if moe_engine.mcfg.moe_shared_expert
                         else set())
    assert want <= inner
    assert inner & set(MOE_INNER_SCOPES) == want


@pytest.mark.parametrize("program", ["decode", "ragged"])
@pytest.mark.parametrize("model,inner", [("tiny-kimi-linear", "kda"),
                                         ("tiny-lfm2", "conv"),
                                         ("tiny-laguna", "window")])
def test_every_dot_of_the_recurrent_layers_sits_under_attention_and_kda(
        program, model, inner):
    """``device.kda_share`` reads the path ``attention/kda``,
    ``device.conv_share`` the path ``attention/conv`` and
    ``device.window_attn_share`` the path ``attention/window``: every dot
    of a recurrent layer's, or of a window layer's, mixer (projections, the
    state's or the attend's dots, ``wo``) carries its kind's, no dot of a
    full-attention layer or of an MLP does, and each dot of the step sits
    in one model scope still (these layers' time is part of
    ``device.attention_share``)."""
    from rbg_tpu.obs.names import ATTENTION_INNER_SCOPES
    assert ATTENTION_INNER_SCOPES == ("kda", "conv", "window")
    eng = Engine(EngineConfig(
        model=model, page_size=8, num_pages=64, max_seq_len=128,
        max_batch=4, prefill_chunk=16, use_pallas="never"))
    # The compiled module's metadata, which is what a device trace
    # carries: the lowered text's locations stop at the scan that walks a
    # chunk's sub-chunks (``ops/kda.py``), the metadata joins them.
    from rbg_tpu.obs.names import MODEL_SCOPES
    text = _lower(eng, program).compile().as_text()
    paths = [p.split("/") for p in sorted(set(re.findall(
        r'op_name="([^"]*dot_general)"', text)))]
    scopes = [sorted(set(MODEL_SCOPES) & set(p)) for p in paths]
    assert all(len(s) == 1 for s in scopes), [
        p for p, s in zip(paths, scopes) if len(s) != 1]
    assert {s[0] for s in scopes} == {"attention", "mlp", "moe", "lm_head"}
    others = set(ATTENTION_INNER_SCOPES) - {inner}
    assert not [p for p in paths if others & set(p)]
    kda = [p for p in paths if inner in p]
    assert kda and all(p[p.index(inner) - 1] == "attention" for p in kda)
    assert [p for p in paths if "attention" in p and inner not in p]
    assert not [p for p in kda if {"moe", "mlp", "lm_head"} & set(p)]
    # distinct paths: the projections' and ``wo``'s, and the state's dots
    assert len(kda) >= 2
    # a KDA layer's projections and ``wo`` sit one scope further in, which
    # ``device.kda_proj_share`` reads; the recurrence's dots do not
    proj = [p for p in kda if "proj" in p]
    assert bool(proj) == (inner == "kda") and len(proj) < len(kda)
    assert all(p[p.index(inner) + 1] == "proj" for p in proj)


@pytest.mark.parametrize("program", ["decode", "ragged"])
def test_the_gate_and_the_kda_projections_have_scopes_of_their_own(program):
    """``tiny-solar-open2``: ``device.attn_gate_share`` reads the path
    ``attention/gate`` (the gate's projection, on the attention layers
    alone) and ``device.kda_proj_share`` the path ``attention/kda/proj``;
    every dot of the step sits in one model scope, none under ``mlp`` (no
    layer is dense)."""
    from rbg_tpu.obs.names import ATTENTION_PART_SCOPES, MODEL_SCOPES
    assert ATTENTION_PART_SCOPES == ("gate", "kda/proj")
    eng = Engine(EngineConfig(
        model="tiny-solar-open2", page_size=8, num_pages=64, max_seq_len=128,
        max_batch=4, prefill_chunk=16, use_pallas="never"))
    text = _lower(eng, program).compile().as_text()
    paths = [p.split("/") for p in sorted(set(re.findall(
        r'op_name="([^"]*dot_general)"', text)))]
    scopes = [sorted(set(MODEL_SCOPES) & set(p)) for p in paths]
    assert all(len(s) == 1 for s in scopes), [
        p for p, s in zip(paths, scopes) if len(s) != 1]
    assert {s[0] for s in scopes} == {"attention", "moe", "lm_head"}
    gate = [p for p in paths if "gate" in p]
    assert gate and all(p[p.index("gate") - 1] == "attention"
                        and "kda" not in p for p in gate)
    kda = [p for p in paths if "kda" in p]
    proj = [p for p in kda if "proj" in p]
    assert proj and len(proj) < len(kda)
    assert all(p[p.index("kda") - 1:p.index("kda") + 2] == [
        "attention", "kda", "proj"] for p in proj)
    # q, k, v and the attend's own dots: under ``attention`` alone
    assert [p for p in paths if "attention" in p and "kda" not in p
            and "gate" not in p]
