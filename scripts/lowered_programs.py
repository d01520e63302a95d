"""The lowered text of the programs that walk the paged layers, one file each.

Run from the root of a checkout (it imports that checkout's ``rbg_tpu``), on
the CPU, once in the parent's tree and once in the change's, then compare the
two directories: a refactor of ``models/llama.py`` that leaves every file the
same has changed no program.

    python scripts/lowered_programs.py --out /root/scratch/lowered/change
    diff -r /root/scratch/lowered/parent /root/scratch/lowered/change

Nothing here runs a program, so nothing here is a timing.
"""

import argparse
import base64
import dataclasses
import functools
import json
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp
import numpy as np

from rbg_tpu.engine import Engine, EngineConfig
from rbg_tpu.engine import engine as E
from rbg_tpu.engine.pd import DecodeWorker
from rbg_tpu.engine.sampler import row_keys
from rbg_tpu.models import config as presets
from rbg_tpu.models import get_config

I32 = jnp.int32
TINY_KW = dict(page_size=8, num_pages=64, max_seq_len=128, max_batch=4,
               prefill_chunk=16, enable_radix_cache=False)
# benchmark/configs/mixtral-8x7b-v0.1.json: its widths, layers and server.
CELL_KW = dict(page_size=16, num_pages=8192, max_seq_len=8192, max_batch=8,
               prefill_chunk=256)
CELL_T = 512


def _lora_stack(eng):
    rng = np.random.default_rng(0)
    L = eng.mcfg.num_layers
    adapter = {}
    for tgt in ("wq", "wo", "w_gate"):
        _, d_in, d_out = eng.params["blocks"][tgt].shape
        adapter[tgt] = (rng.normal(size=(L, d_in, 4)).astype(np.float32),
                        rng.normal(size=(L, 4, d_out)).astype(np.float32))
    eng.load_lora("a", adapter, alpha=8.0)


def programs(eng, S, T, lora=False, window=None):
    """{file name: lowered text} of the engine's programs at ``max_batch``
    rows and ``T`` packed tokens. ``S`` makes an abstract argument."""
    cfg = eng.cfg
    B, P, K = cfg.max_batch, cfg.max_pages_per_seq, cfg.multi_step
    vec, pool = S((B,), I32), eng.cache
    scales = (pool.k_scales, pool.v_scales)
    kw = dict(lora=eng.lora_stack, lids=vec) if lora else {}
    # A model with recurrent layers: the state pool and its rows' slots ride
    # every step program; one with window layers: the window class's pools
    # and its rows' lines. Neither has a draft to verify.
    kw.update(eng._state_kw([], B))
    temps, ks, tps, mps, seeds, rids, _, _, _ = eng._sampling_rows([], B)
    tail = (row_keys(seeds, eng._sample_base, rids), jnp.asarray(temps),
            jnp.asarray(ks), jnp.asarray(tps), jnp.asarray(mps))
    out = {}
    out["rbg_fused_decode"] = eng._get_decode_fn(
        B, False, False, la=lora).lower(
        eng.params, vec, vec, vec, S((B, P), I32), S((B, K), bool), vec,
        pool.k_pages, pool.v_pages, *scales, *tail, **kw)
    for name, Tq in (("rbg_paged_fwd", cfg.prefill_chunk),
                     ("rbg_paged_fwd.decode", 1)):
        out[name] = eng._get_fwd(B, Tq, lora).lower(
            eng.params, S((B, Tq), I32), S((B, Tq), I32), S((B, Tq), bool),
            vec, S((B, P), I32), pool.k_pages, pool.v_pages, *scales, **kw)
    Kq = 5
    # (nor has a looped model: its engine refuses speculative decoding)
    if not eng.mcfg.unbuilt_for and not eng.mcfg.looped_for:
        out["rbg_spec_verify"] = eng._get_spec_fn(B, False, la=lora).lower(
            eng.params, S((B, Kq), I32), S((B, Kq), I32), S((B, Kq), bool),
            vec, S((B, P), I32), pool.k_pages, pool.v_pages, *scales, *tail,
            **kw)
    if not lora:
        out["rbg_ragged_fwd"] = eng._get_ragged_fn(B, T).lower(
            eng.params, S((1, T), I32), S((1, T), I32), S((1, T), bool),
            S((T,), I32), vec, S((B, P), I32), pool.k_pages, pool.v_pages,
            *scales, S((1, T), I32), S((eng._rows_max,), I32),
            S((eng._rows_max,), I32), **kw)
    if window is not None:
        D, L = eng.mcfg.hidden_size, eng.mcfg.num_layers
        for lo, hi in ((0, 1), (1, L)):
            out[f"rbg_pd_window.{lo}-{hi}"] = window._get_window_fn(
                lo, hi, B).lower(
                S((B, 1, D), eng.mcfg.jax_dtype), S((B, 1), I32),
                S((B, 1), bool), vec, S((B, P), I32), pool.k_pages,
                pool.v_pages, None, None)
    return {k: _kernels_without_locations(v.as_text())
            for k, v in out.items()}


_BODY = re.compile(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)')


def _kernels_without_locations(text: str) -> str:
    """A Pallas kernel rides its custom call as serialised MLIR, source
    paths and line numbers included, which differ between two checkouts of
    the same kernel: put the kernel's text without them in its place."""
    from jax._src.lib.mlir import ir

    def plain(m):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            kernel = ir.Module.parse(base64.b64decode(m.group(2)))
            asm = kernel.operation.get_asm(enable_debug_info=False)
        return m.group(1) + "\n" + asm + m.group(3)

    return _BODY.sub(plain, text)


def tiny_cases():
    S = jax.ShapeDtypeStruct
    for case, model, extra in (("tiny", "tiny", {}),
                               ("tiny-moe", "tiny-moe", {}),
                               ("tiny-mla", "tiny-mla", {}),
                               ("tiny-int8", "tiny", {"kv_dtype": "int8"}),
                               ("tiny-moe-int8", "tiny-moe",
                                {"kv_dtype": "int8"}),
                               ("tiny-lora", "tiny", {}),
                               ("tiny-joyai", "tiny-joyai", {}),
                               ("tiny-kimi-linear", "tiny-kimi-linear", {}),
                               ("tiny-lfm2", "tiny-lfm2", {}),
                               ("tiny-solar-open2", "tiny-solar-open2", {}),
                               ("tiny-laguna", "tiny-laguna", {}),
                               ("tiny-ouro", "tiny-ouro", {})):
        if model not in presets._PRESETS:   # an older checkout
            continue
        cfg = EngineConfig(model=model, use_pallas="never",
                           **{**TINY_KW, **extra})
        eng = Engine(cfg)
        lora = case == "tiny-lora"
        if lora:
            _lora_stack(eng)
        window = None
        # the decode role refuses an int8 pool, a recurrent model, one
        # with window layers and a looped one
        if not extra and not lora and not eng.mcfg.unbuilt_for \
                and not eng.mcfg.looped_for:
            window = DecodeWorker(cfg, params=eng.params)
        yield case, programs(eng, S, 2 * cfg.prefill_chunk, lora, window)


# Cells lowered from their configuration files. Ouro's, Solar's and
# Laguna's pools (and Mixtral's, below) took the decode walk with the
# kernel's own copies in PR 51 and a change to that walk for other pools
# must not move their programs; LFM2's packed heads and JoyAI's and Kimi's
# latents take it since PR 53.
CELL_FILES = [("cell-ouro-v5e", "ouro-2.6b"),
              ("cell-lfm2-v5e", "lfm2-24b-a2b"),
              ("cell-joyai-v5e", "joyai-llm-flash"),
              ("cell-kimi-linear-v5e", "kimi-linear-48b-a3b"),
              ("cell-solar-v5e", "solar-open2-250b"),
              ("cell-laguna-v5e", "laguna-xs2")]


@functools.cache
def _described_chip():
    """One chip of a described v5e, as a sharding."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def kernel_cases():
    """The four decode kernels alone: at LFM2's packed heads and the bf16
    latent pools, which copy their own pages since PR 53
    (``page_walk.kernel_copies``), and at int8 pools with their scales,
    K/V and latent, which keep the pipeline."""
    from rbg_tpu.ops.pallas import paged_attention_kernel as K
    S = functools.partial(jax.ShapeDtypeStruct, sharding=_described_chip())
    bf, i8, f32 = jnp.bfloat16, jnp.int8, jnp.float32
    NP, rows, P = 8192, 32, 256
    tail = (S((rows, P), I32), S((rows,), I32))
    lat = lambda dt: (S((rows, 32, 512), bf), S((rows, 32, 64), bf),
                      S((NP, 16, 1, 512), dt), S((NP, 16, 1, 128), dt))
    yield "kernels", {k: _kernels_without_locations(v.as_text()) for k, v in {
        "decode_call.packed": K._decode_call.lower(
            S((rows, 4, 8, 128), bf), *[S((NP, 16, 4, 128), bf)] * 2, *tail,
            head_dim=64),
        "decode_call_q": K._decode_call_q.lower(
            S((rows, 8, 4, 128), bf), *[S((NP, 16, 8, 128), i8)] * 2,
            *[S((NP, 16, 8), f32)] * 2, *tail),
        "mla_decode_call": K._mla_decode_call.lower(*lat(bf), *tail,
                                                    scale=0.1),
        "mla_decode_call_q": K._mla_decode_call_q.lower(
            *lat(i8), *[S((NP, 16, 1), f32)] * 2, *tail, scale=0.1),
    }.items()}


def cell_cases():
    """The programs of the cell ``mixtral.longgen`` and of the cells of
    ``CELL_FILES`` (those from their configuration files, through the
    benchmark's own preset mapping), lowered for one chip of a described
    v5e with the Pallas kernels in (as ``tests/test_chip_compile.py``
    does): parameters and pool are shapes, so nothing is allocated."""
    chip = _described_chip()
    on = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=chip),
        tree)
    init, create = E.init_params, E.PagedKVCache.create
    E.init_params = lambda m, key: on(jax.eval_shape(lambda: init(m, key)))
    E.PagedKVCache.create = staticmethod(
        lambda *a, **kw: on(jax.eval_shape(lambda: create(*a, **kw))))
    cells = [("cell-mixtral-3l-v5e", dataclasses.replace(
        get_config("mixtral-8x7b"), name="cell", num_layers=3), CELL_KW)]
    sys.path.insert(0, os.path.join(os.getcwd(), "benchmark"))
    from harness import serve
    for case, stem in CELL_FILES:
        path = f"benchmark/configs/{stem}.json"
        if not os.path.exists(path):        # an older checkout
            continue
        with open(path) as f:
            served = json.load(f)
        cells.append((case, serve.model_config(served, "cell"),
                      served["server"]))
    S = functools.partial(jax.ShapeDtypeStruct, sharding=chip)
    try:
        for case, preset, kw in cells:
            presets._PRESETS["cell"] = preset
            eng = Engine(EngineConfig(model="cell", use_pallas="always",
                                      **kw))
            yield case, programs(eng, S, CELL_T)
    finally:
        E.init_params, E.PagedKVCache.create = init, staticmethod(create)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    for case, progs in [*tiny_cases(), *kernel_cases(), *cell_cases()]:
        for name, text in progs.items():
            path = os.path.join(args.out, f"{case}.{name}.txt")
            with open(path, "w") as f:
                f.write(text)
            print(f"{path}: {len(text.splitlines())} lines", flush=True)


if __name__ == "__main__":
    main()
