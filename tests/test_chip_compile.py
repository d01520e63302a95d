"""Every Pallas kernel the serving path can reach, compiled by the TPU's
own compiler for a described (not attached) v5e at published widths.

Interpret mode checks a kernel's arithmetic and nothing about whether
Mosaic can lay it out: the block-ragged kernels passed every interpret
test and were refused on first contact. These cases cost no chip time
and guard each later PR. Nothing runs here, so nothing below is a
statement about results or speed — ``chip_smoke.py`` is the run.
"""

import functools
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs in /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from rbg_tpu.engine import EngineConfig
from rbg_tpu.ops import pallas
from rbg_tpu.ops.pallas import page_walk

BF16, I8, F32, I32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32

# The shapes of one mixed serving step: 8 rows over a 4096-page pool of
# 16-token pages, up to 128 pages a row, 256 packed tokens.
NP, PAGE, R, P, T = 4096, 16, 8, 128, 256
# The benchmark's mixtral.longgen cell: max_seq_len 8192 makes the table
# 512 wide over a pool of 8192 pages; its heads are llama3-8b's.
CELL_NP, CELL_P = 8192, 512

# heads / kv heads / head dim as published.
GQA_WIDTHS = {"llama3-1b": (32, 8, 64), "llama3-8b": (32, 8, 128),
              "qwen2-0.5b": (14, 2, 64)}
# deepseek-v2-lite latent dims: heads, kv_lora_rank, qk_rope_head_dim.
MLA_H, MLA_DC, MLA_DR = 16, 512, 64
MLA_SCALE = (128 + MLA_DR) ** -0.5


@pytest.fixture(scope="module")
def chip():
    """Sharding on one chip of a described v5e 2x2 host."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu in this install
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    # A compile for a described chip is written to JAX's persistent cache
    # but cannot be read back without one: keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(chip, tree):
    """Shapes of ``tree``'s leaves, placed on the described chip."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=chip),
        tree)


def _compiles_with_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _gqa_args(chip, width, quantized, ragged, num_pages=NP, table_width=P):
    H, KV, hd = GQA_WIDTHS[width]
    S = functools.partial(jax.ShapeDtypeStruct, sharding=chip)
    pages = S((num_pages, PAGE, KV, hd), I8 if quantized else BF16)
    table = S((R, table_width), I32)
    if ragged:
        args = [S((1, T, H, hd), BF16), pages, pages, table,
                S((1, T), I32), S((R,), I32), S((T,), I32)]
    else:
        args = [S((R, 1, H, hd), BF16), pages, pages, table,
                S((R, 1), I32), S((R,), I32)]
    if quantized:
        args += [S((num_pages, PAGE, KV, 1), F32)] * 2
    return args


GQA_KERNELS = [
    "paged_attention_pallas", "paged_attention_pallas_q",
    "ragged_paged_attention_pallas", "ragged_paged_attention_pallas_q",
]


@pytest.mark.parametrize("width", sorted(GQA_WIDTHS))
@pytest.mark.parametrize("kernel", GQA_KERNELS)
def test_gqa_kernel_compiles_for_v5e(chip, kernel, width):
    args = _gqa_args(chip, width, quantized=kernel.endswith("_q"),
                     ragged=kernel.startswith("ragged"))
    _compiles_with_kernel(pallas.kernel(kernel), *args)


@pytest.mark.parametrize("width", ["llama3-1b", "qwen2-0.5b"])
@pytest.mark.parametrize("kernel", ["paged_attention_pallas",
                                    "ragged_paged_attention_pallas"])
def test_gqa_kernel_compiles_on_a_pool_of_heads_side_by_side(chip, kernel,
                                                             width):
    """Heads of 64 as the engine's pool holds them since PR 39, two to a
    lane tile (``kvcache.heads_per_lane_tile``: ``[NP, page, KV / 2, 128]``,
    the benchmark's lfm2 cell's 8 heads as 4, qwen2's 2 as 1): the
    kernels take each head's queries zero in the other head's lanes."""
    H, KV, hd = GQA_WIDTHS[width]
    args = _gqa_args(chip, width, quantized=False,
                     ragged=kernel.startswith("ragged"))
    pages = jax.ShapeDtypeStruct((NP, PAGE, KV // 2, 2 * hd), BF16,
                                 sharding=chip)
    args[1] = args[2] = pages
    _compiles_with_kernel(pallas.kernel(kernel), *args)


@pytest.mark.parametrize("kernel", GQA_KERNELS)
def test_gqa_kernel_compiles_at_the_cells_table_width(chip, kernel):
    """The table's width reaches a kernel only as the shape of a
    scalar-prefetched array; Mosaic must take it at the benchmark's own."""
    args = _gqa_args(chip, "llama3-8b", quantized=kernel.endswith("_q"),
                     ragged=kernel.startswith("ragged"),
                     num_pages=CELL_NP, table_width=CELL_P)
    _compiles_with_kernel(pallas.kernel(kernel), *args)


@pytest.mark.parametrize("kernel", [
    "paged_mla_attention_pallas", "paged_mla_attention_pallas_q",
    "ragged_paged_mla_attention_pallas",
    "ragged_paged_mla_attention_pallas_q",
])
def test_mla_kernel_compiles_for_v5e(chip, kernel):
    S = functools.partial(jax.ShapeDtypeStruct, sharding=chip)
    quantized, ragged = kernel.endswith("_q"), kernel.startswith("ragged")
    dt = I8 if quantized else BF16
    pools = [S((NP, PAGE, 1, MLA_DC), dt), S((NP, PAGE, 1, MLA_DR), dt)]
    scales = [S((NP, PAGE, 1, 1), F32)] * 2 if quantized else []
    kern = pallas.kernel(kernel)
    if ragged:
        fn = lambda ql, qp, c, pe, tab, pos, lens, rows, *sc: kern(
            ql, qp, c, pe, tab, pos, lens, rows, MLA_SCALE, *sc)
        args = [S((1, T, MLA_H, MLA_DC), BF16), S((1, T, MLA_H, MLA_DR), BF16),
                *pools, S((R, P), I32), S((1, T), I32), S((R,), I32),
                S((T,), I32), *scales]
    else:
        fn = lambda ql, qp, c, pe, tab, pos, lens, *sc: kern(
            ql, qp, c, pe, tab, pos, lens, MLA_SCALE, *sc)
        args = [S((R, 1, MLA_H, MLA_DC), BF16), S((R, 1, MLA_H, MLA_DR), BF16),
                *pools, S((R, P), I32), S((R, 1), I32), S((R,), I32), *scales]
    _compiles_with_kernel(fn, *args)


# ---- the four decode kernels at the shapes of every cell of the benchmark ----

# cell: (rows, the table's width, the pool's pages over all its layers, and
# for GQA (heads, kv heads, head size), for latents the heads).
CELL_DECODE = {
    "mixtral.longgen": (8, 512, 3 * 8192, (32, 8, 128)),
    "lfm2.longgen32": (32, 256, 10 * 8192, (32, 8, 64)),
    "joyai.longgen16": (16, 256, 5 * 8192, 32),
    "kimi-linear.longgen16": (16, 256, 7 * 4096, 32),
    "solar-open2.longgen32": (32, 256, 2 * 8192, (64, 8, 128)),
}


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("cell", sorted(CELL_DECODE))
def test_decode_kernel_compiles_at_the_cells_shapes(chip, cell, quantized):
    """A decode kernel's scalar operands are the walk's item table
    (``page_walk.walk_items``): ``rows * ceil(width / pages a block) + 1``
    items, 1025 at 32 rows under a table 256 wide, which Mosaic has to
    hold in SMEM beside the rows' lengths; and the pools are the cells'
    own, all layers flat (LFM2's bf16 heads of 64 two to a lane tile,
    ``[81920, 16, 4, 128]``; the latent pools without their singleton
    axis, the rotary key's a lane tile wide)."""
    rows, width, pages, heads = CELL_DECODE[cell]
    S = functools.partial(jax.ShapeDtypeStruct, sharding=chip)
    dt = I8 if quantized else BF16
    tail = [S((rows, width), I32), S((rows, 1), I32), S((rows,), I32)]
    if isinstance(heads, int):
        kern = pallas.kernel("paged_mla_attention_pallas"
                             + "_q" * quantized)
        scales = [S((pages, PAGE, 1, 1), F32)] * 2 if quantized else []
        fn = lambda ql, qp, c, pe, tab, pos, lens, *sc: kern(
            ql, qp, c, pe, tab, pos, lens, MLA_SCALE, *sc)
        args = [S((rows, 1, heads, MLA_DC), BF16),
                S((rows, 1, heads, MLA_DR), BF16),
                S((pages, PAGE, 1, MLA_DC), dt), S((pages, PAGE, 1, 128), dt),
                *tail, *scales]
    else:
        H, KV, hd = heads
        # int8 pools keep a head a tile (no cell serves one)
        p = 1 if quantized else 128 // hd
        pool = S((pages, PAGE, KV // p, p * hd), dt)
        fn = pallas.kernel("paged_attention_pallas" + "_q" * quantized)
        scales = [S((pages, PAGE, KV, 1), F32)] * 2 if quantized else []
        args = [S((rows, 1, H, hd), BF16), pool, pool, *tail, *scales]
    text = _compiles_with_kernel(fn, *args).as_text()
    n = page_walk.decode_pages_per_block(PAGE)
    # a bf16 pool of whole-tile pages is walked with the kernel's own
    # copies (PRs 51, 53: every cell's): no item table; an int8 pool with
    # its scales keeps it
    pools = args[1:3] if not isinstance(heads, int) else jax.eval_shape(
        page_walk.latent_pools, *args[2:4])
    copies = page_walk.kernel_copies([*pools, *scales])
    assert copies == (not quantized)
    assert (f"s32[{n},{rows * (width // n) + 1}]" in text) != copies


# What ``page_walk.kernel_copies`` takes (PRs 51, 53): (rows, the table's
# width, the pool's pages over the layers of its class, (the pool's heads,
# queries a head of the pool) or for latents the heads, a window layer's
# width, a head's size where the pool holds two to a lane tile).
CELL_COPIES = {
    "laguna-xs2.window": (32, 256, 15 * 1185, (8, 8), 512, None),
    "laguna-xs2.full": (32, 256, 5 * 8192, (8, 6), None, None),
    "mixtral": (8, 512, 3 * 8192, (8, 4), None, None),
    "solar-open2.gqa": (32, 256, 2 * 8192, (8, 8), None, None),
    "ouro": (4, 80, 192 * 320, (16, 1), None, None),
    "lfm2": (32, 256, 10 * 8192, (4, 8), None, 64),
    "joyai.latent": (16, 256, 5 * 8192, 32, None, None),
    "kimi.latent": (16, 256, 7 * 4096, 32, None, None),
}


def _decode_walk(chip, rows, width, pages, page, heads, window=None,
                 head_dim=None):
    """(call, arguments, the pools as the kernel receives them) of a decode
    walk over bf16 pools of ``pages`` pages ``page``: ``[slots, KV, hd]``
    under ``heads`` queries a head of the pool (``_decode``, what
    ``_decode_call`` jits), or the latents' ``[slots, dc]`` beside a
    rotary key's pool a lane tile wide under ``heads`` heads
    (``_mla_decode``)."""
    from rbg_tpu.ops.pallas import paged_attention_kernel as K
    S = functools.partial(jax.ShapeDtypeStruct, sharding=chip)
    tail = (S((rows, width), I32), S((rows,), I32))
    if len(page) == 2:
        pools = (S((pages, page[0], 1, page[1]), BF16),
                 S((pages, page[0], 1, 128), BF16))
        fn = lambda ql, qp, c, pe, t, n: K._mla_decode(
            ql, qp, (c, pe), t, n, MLA_SCALE, False)
        args = (S((rows, heads, page[1]), BF16),
                S((rows, heads, MLA_DR), BF16), *pools, *tail)
        return fn, args, jax.eval_shape(page_walk.latent_pools, *pools)
    pools = (S((pages,) + page, BF16),) * 2
    fn = lambda q, k, v, t, n: K._decode(q, (k, v), t, n, False, head_dim,
                                         window)
    return fn, (S((rows, page[1], heads, page[2]), BF16), *pools, *tail), pools


@pytest.mark.parametrize("kind", sorted(CELL_COPIES))
def test_decode_kernel_with_its_own_copies_compiles_at_the_cells_shapes(
        chip, kind):
    """``_decode_call``'s and ``_mla_decode_call``'s kernels on the path
    that issues its own page copies (``page_walk.walk_with_copies``): the
    pools stay in HBM, a page is sliced out of one and lands at an offset
    of a VMEM buffer, which Mosaic takes for ``[16, KV, 128]`` at KV 8 and
    16, for LFM2's ``[16, 4, 128]`` (heads of 64 two to a lane tile) and
    for the latent pools' ``[16, 512]`` / ``[16, 128]``; the scalar
    operands are the page table and the lengths, no item table and no
    block counts."""
    rows, width, pages, heads, window, head_dim = CELL_COPIES[kind]
    page, heads = ((PAGE, MLA_DC), heads) if isinstance(heads, int) else (
        (PAGE, heads[0], 128), heads[1])
    fn, args, pools = _decode_walk(chip, rows, width, pages, page, heads,
                                   window, head_dim)
    assert page_walk.kernel_copies(pools)
    text = _compiles_with_kernel(fn, *args).as_text()
    n = page_walk.decode_pages_per_block(PAGE)
    assert f"s32[{n},{rows * (width // n) + 1}]" not in text


# The predicate's borders: (the pools' page ``[page, ...]``, whether
# ``page_walk.kernel_copies`` takes it). What it takes compiles; what it
# refuses among bf16 pairs is what Mosaic refuses when the walk is forced
# onto them (a slice that is no whole number of tiles).
COPIES_BORDERS = {
    "packed-kv2": ((16, 2, 128), True),
    "kv24": ((16, 24, 128), True),
    "kv8-page8": ((8, 8, 128), True),
    "kv4-hd256": ((16, 4, 256), True),
    "latent-page8": ((8, 512), True),
    "latent-page32-dc256": ((32, 256), True),
    "packed-kv1": ((16, 1, 128), False),
    "kv12": ((16, 12, 128), False),
    "hd64": ((16, 8, 64), False),
    "latent-page4": ((4, 512), False),
}


@pytest.mark.parametrize("border", sorted(COPIES_BORDERS))
def test_kernel_copies_takes_what_mosaic_compiles(chip, monkeypatch, border):
    page, takes = COPIES_BORDERS[border]
    fn, args, pools = _decode_walk(chip, 8, 64, NP, page, heads=4)
    assert page_walk.kernel_copies(pools) is takes
    if takes:
        _compiles_with_kernel(fn, *args)
        return
    monkeypatch.setattr(page_walk, "kernel_copies", lambda pools: True)
    with pytest.raises(Exception, match="aligned to tiling"):
        jax.jit(fn).lower(*args).compile()


# ---- the engine's own step programs, whole, at chip_smoke's serving size ----


def _abstract_engine(chip, monkeypatch, **serve_config):
    """An ``Engine`` whose parameters and KV pool are shapes on the
    described chip: its jitted step programs are the ones a server on the
    chip compiles, pool and program together against 16 GB. The engine
    picks the kernel from ``jax.default_backend()``, which is the CPU
    here, so the test asks for it outright."""
    from rbg_tpu.engine import engine as E

    init, create = E.init_params, E.PagedKVCache.create
    monkeypatch.setattr(
        E, "init_params",
        lambda mcfg, key: _on(chip, jax.eval_shape(lambda: init(mcfg, key))))
    monkeypatch.setattr(
        E.PagedKVCache, "create",
        lambda *a, **kw: _on(chip, jax.eval_shape(lambda: create(*a, **kw))))
    return E.Engine(EngineConfig(use_pallas="always", **serve_config))


@pytest.fixture()
def abstract_engine(chip, monkeypatch):
    """llama3-1b at ``chip_smoke.py``'s serving size."""
    import chip_smoke
    return _abstract_engine(chip, monkeypatch, **chip_smoke.SERVE_CONFIG)


def _state_kw(chip, eng, rows):
    """A recurrent model's state pool and slots, a window model's second
    class of page and its rows' lines, as shapes on the chip."""
    kw = {}
    if eng.state is not None:
        kw.update(state=eng.state.arrays, slots=jnp.zeros(rows, I32))
    if eng.window_allocator is not None:
        kw.update(window=eng.cache.window_pages,
                  wtable=jnp.zeros((rows, eng.cfg.max_pages_per_seq), I32))
    return _on(chip, kw)


def _compile_unified(chip, eng, rows=None):
    Rb = rows or eng.cfg.max_batch
    Tb = Rb * eng.cfg.prefill_chunk
    S = functools.partial(jax.ShapeDtypeStruct, sharding=chip)
    return eng._get_ragged_fn(Rb, Tb).lower(
        eng.params, S((1, Tb), I32), S((1, Tb), I32), S((1, Tb), bool),
        S((Tb,), I32), S((Rb,), I32), S((Rb, eng.cfg.max_pages_per_seq), I32),
        eng.cache.k_pages, eng.cache.v_pages, None, None,
        S((1, Tb), I32), S((eng._rows_max,), I32), S((eng._rows_max,), I32),
        **_state_kw(chip, eng, Rb)).compile()


def _compile_decode(chip, eng):
    from rbg_tpu.engine.sampler import row_keys
    B, Pm, Kw = eng.cfg.max_batch, eng.cfg.max_pages_per_seq, eng.cfg.multi_step
    temps, ks, tps, mps, seeds, rids, _, _, _ = eng._sampling_rows([], B)
    small = _on(chip, (
        jnp.zeros(B, I32), jnp.zeros(B, I32), jnp.zeros(B, I32),
        jnp.zeros((B, Pm), I32), jnp.zeros((B, Kw), bool), jnp.zeros(B, I32)))
    tail = _on(chip, (row_keys(seeds, eng._sample_base, rids),
                      jnp.asarray(temps), jnp.asarray(ks), jnp.asarray(tps),
                      jnp.asarray(mps)))
    return eng._get_decode_fn(B, False, False).lower(
        eng.params, *small, eng.cache.k_pages, eng.cache.v_pages, None, None,
        *tail, **_state_kw(chip, eng, B)).compile()


@pytest.mark.parametrize("rows", [None, 1])
def test_unified_step_of_llama3_1b_fits_and_holds_the_kernel(
        chip, abstract_engine, rows):
    """At the widest row bucket and at the narrowest: a packed program of
    ONE row holds both walks like any other."""
    text = _compile_unified(chip, abstract_engine, rows).as_text()
    assert "tpu_custom_call" in text
    for walk in ("_decode_call", "_block_ragged_call"):
        assert walk in text, walk


# Half a minute of compile, so outside tier-1; chip_smoke.py's reference
# phase lowers the same program on the chip in every run.
@pytest.mark.slow
def test_fused_decode_of_llama3_1b_fits_and_holds_the_kernel(
        chip, abstract_engine):
    assert "tpu_custom_call" in _compile_decode(chip,
                                                abstract_engine).as_text()


# ---- the benchmark's cells: the two step programs of each ---------------------

MB = 1 << 20


def _cell_engine(chip, monkeypatch, file):
    """``benchmark/configs/<file>`` as served: (the file, the engine on the
    described chip, its parameters and pools shapes)."""
    from model_contract import read     # (puts ``harness`` on the path)
    from harness import serve
    from rbg_tpu.models import config as presets
    cfg = read("configs", file)
    name = file[:-len(".json")] + "-cell"
    monkeypatch.setitem(presets._PRESETS, name, serve.model_config(cfg, name))
    return cfg, _abstract_engine(chip, monkeypatch, model=name,
                                 **cfg["server"])


# cell -> what its two step programs are held to. ``shapes``: parameters
# (``group/leaf``), pools (``k_pages`` / ``v_pages``) and state arrays
# (``state/<name>``) as served; ``walk``: the page walks' kernels, (the decode
# step's, the ragged one): a decode step holds the first, a unified step BOTH
# (its rows of one token take the decode step's walk, the rows that hold a
# chunk the ragged one); ``temps``: the ceiling on temporaries of
# (decode, unified); every
# program holds ``tpu_custom_call``, a decode step alone the experts' walk
# ``_moe_visit_call`` (PR 43; a unified step dispatches densely), and no
# program a ``copy`` of the size of a page pool or of a state array. The
# other entries are switched on by what a cell has, below.
CELL_PROGRAMS = {
    # 1 dense + 4 expert layers, 16 rows, a table 256 wide over 8192 pages
    # of latents. Neither program keeps a temporary the size of one layer's
    # expert matrix (0.8 GB): not a copy of the scan's slice (the hit form
    # reads ``(layer, expert)`` in place, the dense dispatch fuses the
    # layer's slice into its dot) and not a copy of the latent pool, which a
    # kernel handed the pool with its singleton axis cost a decode step
    # (1.5 GB of temporaries before ``page_walk.latent_pools``). Nor a
    # ``copy`` of a latent pool's shape at all: the second copy was the
    # rotary key's pool, ``bf16[5,8192,16,1,64]``: a last dim under a lane
    # tile gave that entry parameter a layout with the page axis minor, and
    # every step program transposed the whole pool on the way in and back
    # before the result (two pools' worth of temporaries, 170 MB, and 7.3 %
    # of the cell's device time). Held a whole lane tile wide
    # (``kvcache.rope_pool_width``) it aliases through untouched: 10.5 and
    # 7.0 MB of temporaries. Since PR 48 the unified program's head runs on
    # the sampling rows alone, so its result is ``[16, V]`` and no longer a
    # packed line's ``f32[1,1024,129280]`` (529 MB), in whose buffer the
    # dense dispatch's ``bf16[1024,256,768]`` (403 MB) used to live: that
    # one intermediate is a temporary now, 405.6 MB in all, and result plus
    # temporaries fell from 536 MB to 414.
    "joyai": dict(
        file="joyai-llm-flash.json",
        shapes={"blocks/moe_gate": (4, 256, 2048, 768),
                "v_pages": (5, 8192, 16, 1, 128)},
        walk=("_mla_decode_call", "_block_ragged_mla_call"), copies=5,
        temps=(32 * MB, 416 * MB), some_copy=True),
    # all 27 layers (one dense recurrent layer, 19 recurrent and 7 latent
    # expert layers), 16 of 256 experts a layer, 16 rows, pages for the 7
    # latent layers alone and a state slot a row beside them. Each KIND of
    # layer is one loop body read from its halves' stacks by a dynamic
    # index, the dense first layer on its own before the loops: no temporary
    # the size of a layer's held experts (226 MB; 14 MB and 85 MB when
    # written) or of a pool, and no ``copy`` of the page pools, of the state
    # pool ``f32[20,16,32,128,128]`` or of the convolution tails (held flat:
    # with an axis of 3 before the channels every step program copied them
    # whole, 24 MB, to pad that axis to a tile).
    "kimi": dict(
        file="kimi-linear-48b-a3b.json",
        shapes={"moe_mlps/moe_gate": (26, 16, 2304, 1024),
                "moe_mlps/router": (26, 2304, 256),
                "kda_mixers/kda_qkv": (20, 2304, 12288),
                "k_pages": (7, 4096, 16, 1, 512),
                "state/s": (20, 16, 32, 128, 128),
                "state/conv": (20, 16, 36864)},
        walk=("_mla_decode_call", "_block_ragged_mla_call"), copies=7,
        temps=(128 * MB, 128 * MB), some_copy=True),
    # all 40 layers (two dense layers with the gated short convolution, then
    # 10 attention and 28 convolution layers with 8 of 64 experts each), 32
    # rows, pages for the 10 attention layers alone, 8 heads of 64 held two
    # to a lane tile, and the convolutions' tails a slot a row beside them.
    # No ``copy`` of a pool's shape: with the heads as ``[.., 8, 64]`` every
    # step program copied both page pools whole on the way in and out,
    # padded to 128 lanes (2.68 GB of pools; ROADMAP S2 (b)); and no
    # temporary the size of a layer's held experts (151 MB).
    "lfm2": dict(
        file="lfm2-24b-a2b.json",
        shapes={"moe_mlps/moe_gate": (38, 8, 2048, 1536),
                "moe_mlps/router": (38, 2048, 64),
                "conv_mixers/conv_in": (30, 2048, 6144),
                "lm_head": None,                        # a tied head
                "k_pages": (10, 8192, 16, 4, 128),
                "state/tail": (30, 32, 4096)},
        walk=("_decode_call", "_block_ragged_call"), copies=10,
        temps=(128 * MB, 128 * MB)),
    # 8 layers in two turns A K K K (A: 64 / 8 heads of 128 without
    # positions, gated; K: 64 delta-rule heads of 128), 20 of 320 experts a
    # layer, 32 rows, K/V pages for the 2 attention layers and beside them a
    # state slot a row for the 6 recurrent ones, ``f32[6,32,64,128,128]``
    # (805 MB) and the tails ``bf16[6,32,73728]``. A decode step holds the
    # attention layers' walk and the state's kernel in one program; no
    # temporary the size of a layer's held experts (629 MB), and the
    # router's 320 outputs (2.5 lane tiles) compile. 512 MB of temporaries
    # when written for the unified step's 2048 packed tokens (their q, k, v
    # in float32 a row a line, the dense dispatch's [2048, 20, 1280]); a
    # decode step's stay under 256 MB.
    "solar": dict(
        file="solar-open2-250b.json",
        shapes={"moe_mlps/moe_gate": (8, 20, 4096, 1280),
                "moe_mlps/router": (8, 4096, 320),
                "kda_mixers/kda_qkv": (6, 4096, 24576),
                "mixers/wg": (2, 4096, 8192),
                "lm_head": (4096, 24576),
                "k_pages": (2, 8192, 16, 8, 128),
                "state/s": (6, 32, 64, 128, 128),
                "state/conv": (6, 32, 73728)},
        walk=("_decode_call", "_block_ragged_call"), copies=2,
        temps=(256 * MB, 640 * MB)),
    # 20 layers in five periods F W W W (F: 48 / 8 heads of 128, half of
    # each rotated under YaRN; W: 64 / 8 heads within 512 tokens), a gate a
    # head, a dense first layer and 32 of 256 experts in the other 19, 32
    # rows. TWO classes of page: the full layers' pool over 8192 pages, the
    # window layers' over 32 rows x 37 + 1, each walked by the two kernels
    # (a window layer's with its first live block and its lower mask), and
    # neither copied. No temporary the size of a layer's held experts
    # (201 MB) or of a pool, and none the size of a mixer's projections:
    # held ``[L, in, out]`` the three stacks were transposed whole in every
    # step's entry computation (0.75 GB read and written, 782 MB of a decode
    # step's temporaries: ROADMAP S23 + S14, hoisted out of a loop of three
    # trips); held ``[L, out, in]`` (``proj_out_in``) a decode step keeps
    # 6 MB, a unified step of 32 x 64 packed tokens 81.
    "laguna": dict(
        file="laguna-xs2.json",
        shapes={"moe_mlps/moe_gate": (19, 32, 2048, 512),
                "moe_mlps/router": (19, 2048, 256),
                "mixers/wq": (5, 6144, 2048), "mixers/wg": (5, 2048, 48),
                "window_mixers/wq": (15, 8192, 2048),
                "window_mixers/wk": (15, 1024, 2048),
                "window_mixers/wg": (15, 2048, 64),
                "lm_head": (2048, 12544),
                "k_pages": (5, 8192, 16, 8, 128),
                "window_k": (15, 1185, 16, 8, 128)},
        walk=("_decode_call", "_block_ragged_call"), copies=20,
        temps=(32 * MB, 192 * MB)),
    # all 48 layers, run 4 times a token: ONE class of page whose leading
    # axis is a (pass, layer) entry, 192 over 48 layers of weights, 320
    # pages of 16 x 16 heads of 128 (8.05 GB in the two pools), 4 rows. The
    # pools ride TWO nested loops as a carry (the passes' scan, and inside
    # it the layers' scan over the flat view) and neither loop copies them:
    # a copy of a pool is 4 GB of temporaries and does not fit. Dense: no
    # expert kernel in either program. A group of ONE query head a
    # key/value head in both attention kernels. q, k and v are held ``[L,
    # out, in]`` (``proj_out_in``): held ``[L, in, out]`` the three stacks
    # ``bf16[48,2048,2048]`` were transposed whole in every step's entry
    # computation, hoisted out of the passes' loop (1,210 MB of temporaries
    # in both programs; 1.9 and 5.4 MB now).
    "ouro": dict(
        file="ouro-2.6b.json",
        shapes={"blocks/wq": (48, 2048, 2048),
                "blocks/w_gate": (48, 2048, 5632),
                "blocks/attn_post_norm": (48, 2048),
                "blocks/mlp_post_norm": (48, 2048),
                "exit_gate/w": (2048,),
                "lm_head": (2048, 49152),
                "k_pages": (192, 320, 16, 16, 128)},
        walk=("_decode_call", "_block_ragged_call"), copies=192,
        temps=(16 * MB, 64 * MB)),
}


def _served_shapes(eng):
    """{``group/leaf``, ``k_pages``, ``v_pages``, ``state/<name>``: shape} of
    what the engine holds on the chip."""
    held = {"k_pages": eng.cache.k_pages, "v_pages": eng.cache.v_pages,
            "state": eng.state.arrays if eng.state else {}, **eng.params}
    if eng.cache.window_k is not None:
        held.update(window_k=eng.cache.window_k, window_v=eng.cache.window_v)
    return {"/".join(str(k.key) for k in path): leaf.shape for path, leaf
            in jax.tree_util.tree_flatten_with_path(held)[0]}


def _packed_queries(eng, dims):
    """Whether ``dims`` is a unified step's packed queries of one of the
    model's layer kinds: ``[..., heads, head_dim]`` or ``[..., kv heads,
    group, head_dim]`` over ``max_batch x prefill_chunk`` tokens."""
    packed = eng.cfg.max_batch * eng.cfg.prefill_chunk
    for _, cfg, _, _ in eng.mcfg.layer_groups:
        kv, hd = cfg.num_kv_heads, cfg.head_dim_
        for tail in ((cfg.num_heads, hd), (kv, cfg.num_heads // kv, hd)):
            if (dims[-len(tail):] == tail
                    and np.prod(dims[:-len(tail)]) == packed):
                return True
    return False


def _shaped(text, shape, ops=r"\w[\w-]*"):
    """The operations of ``ops`` in a compiled module's text whose result is
    float32 of ``shape``."""
    dims = ",".join(str(d) for d in shape)
    return re.findall(rf"= f32\[{dims}\]\S* ({ops})\(", text)


@pytest.mark.parametrize("program", ["decode", "unified"])
@pytest.mark.parametrize("cell", sorted(CELL_PROGRAMS))
def test_step_programs_of_a_cell_fit_and_copy_no_pool(chip, monkeypatch, cell,
                                                      program):
    """A cell's configuration as served (its file's server, parameters and
    pools as shapes on the described chip): both step programs compile for
    the chip within their ceiling of temporaries, hold the kernels the cell
    reaches, and copy no pool. ``CELL_PROGRAMS`` says why, cell by cell."""
    want = CELL_PROGRAMS[cell]
    cfg, eng = _cell_engine(chip, monkeypatch, want["file"])
    served = _served_shapes(eng)
    for path, shape in want["shapes"].items():
        assert served.get(path) == shape, path
    # ... and every array of the state pool is one the row names (LFM2's
    # state is its tails alone)
    assert {p for p in served if p.startswith("state/")} <= set(want["shapes"])
    assert (eng.cfg.max_batch, eng.cfg.max_pages_per_seq) == (
        cfg["server"]["max_batch"],
        cfg["server"]["max_seq_len"] // cfg["server"]["page_size"])
    # the decode walks a step that copy their own pages (PRs 51, 53): read
    # off the pools, Laguna's two classes, a pass a layer in Ouro, LFM2's
    # packed heads, the latent layers of JoyAI and Kimi
    assert eng.metrics["decode_walk_kernel_copies"] == want["copies"]
    decode = program == "decode"
    compiled = (_compile_decode if decode else _compile_unified)(chip, eng)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for walk in want["walk"][:1 if decode else 2]:
        assert walk in text, walk
    assert ("_moe_visit_call" in text) == (decode
                                           and bool(eng.mcfg.num_experts))
    assert compiled.memory_analysis().temp_size_in_bytes < want["temps"][
        0 if decode else 1]
    # A pool under any of its shapes (whole, flat over layers, without its
    # singleton axis) has a pool's count of values.
    state = eng.state.arrays if eng.state else {}
    pools = {eng.cache.k_pages.size, eng.cache.v_pages.size,
             *(a.size for a in state.values()),
             *(a.size for a in eng.cache.window_pages or ())}
    copied = re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", text)
    assert not [dims for dims in copied if np.prod(
        [int(d) for d in dims.split(",")]) in pools]
    # (the pattern does find this program's copies, where it is known to
    # have some)
    assert copied or not want.get("some_copy")
    if "dense_mlps" in eng.params:
        # No loop body copies a matrix of the dense MLP (Kimi's: 42.5 MB
        # each): as operands of a ``conditional`` in the recurrent layers'
        # body, ``w_down`` and ``w_up`` were copied into VMEM in every one
        # of its 20 trips (``copy-done bf16[1,9216,2304]``, 1.77 ms of a
        # decode step). What the compiler prefetches once a step stands in
        # the entry computation.
        # (found by its count of values, under any shape. One activation
        # has as many, and is named: a unified step's packed queries of a
        # layer kind, Laguna's 32 x 64 tokens x 64 window heads x 128 =
        # 8192 x 2048, which the ragged kernel's wrapper lays out by tile
        # as ``[256,8,64,128]`` and ``[256,8,8,8,128]``)
        dense = eng.params["dense_mlps"]["w_down"].size
        in_loops = [dims for dims in (
            tuple(int(d) for d in found.split(",")) for found in re.findall(
                r"= \w+\[([\d,]+)\]\S* copy(?:-done)?\(",
                text[:text.index("\nENTRY ")]))
            if np.prod(dims) == dense
            and not (not decode and _packed_queries(eng, dims))]
        assert not in_loops
    if "s" in state:
        # A decode step advances the delta rule's states where they lie
        # (the kernel of ``ops/pallas/kda_kernel.py``, aliased onto the
        # pool inside the layer scan): nothing gathers the rows' states out
        # of the pool or scatters them back, and no pass over ``[rows, H,
        # dk, dv]`` is left. Since PR 42 a unified step does the same for
        # its rows of one token, and walks the rows that hold a chunk one a
        # trip: it too has no pass over every row's state, nor over every
        # row's line ``[rows, chunk, H, dk]``, and what it writes into the
        # pool is a row's slot, in place.
        assert "_kda_decode_call" in text
        layers, rows, H, dk, dv = state["s"].shape
        assert not _shaped(text, (rows, H, dk, dv))
        assert not _shaped(text, (rows, eng.cfg.prefill_chunk, H, dk))
        whole = _shaped(text, state["s"].shape, "fusion|scatter|copy")
        # A unified step inlines the mixer's program once for each place
        # the walk meets it (Kimi's twice: the dense first layer, the
        # loop), and each is the update of a chunk row's slot, its root a
        # dynamic-update-slice of the pool: in place, as the pages' are.
        from model_contract import places
        assert whole == ([] if decode else
                         ["fusion"] * places(eng.mcfg, "kda"))
        dims = ",".join(str(d) for d in state["s"].shape)
        assert len(re.findall(
            rf"ROOT \S+ = f32\[{dims}\]\S* dynamic-update-slice\(",
            text)) == len(whole)


@pytest.mark.parametrize("rows,heads,layers,heads_per_block", [
    (16, 32, 20, 8), (16, 32, 20, 16), (32, 64, 6, None)],
    ids=["kimi-8", "kimi-16", "solar-64-heads"])
def test_kda_decode_kernel_compiles_for_v5e_in_place(chip, rows, heads, layers,
                                                     heads_per_block):
    """The decode kernel alone at the Kimi cell's widths (16 rows, 32 heads
    of 128 x 128 float32, 20 layers' pool of 16 slots, 671 MB) and at the
    Solar cell's (32 rows, 64 heads, four head blocks of ``HEADS_PER_BLOCK``
    a row where Kimi's 32 heads make two, 6 layers' pool of 32 slots, 805
    MB): the call takes the pool and gives it back as one buffer."""
    from rbg_tpu.ops.pallas.kda_kernel import HEADS_PER_BLOCK, kda_decode_pallas
    assert 64 // HEADS_PER_BLOCK == 4
    S = functools.partial(jax.ShapeDtypeStruct, sharding=chip)
    line = S((rows, heads, 128), F32)
    pool = S((layers, rows, heads, 128, 128), F32)
    kw = {"heads_per_block": heads_per_block} if heads_per_block else {}
    compiled = jax.jit(functools.partial(kda_decode_pallas, **kw),
                       donate_argnums=(5,)).lower(
        line, line, line, line, S((rows, heads), F32), pool, S((), I32),
        S((rows,), I32), S((rows,), bool)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == layers * rows * heads * 128 * 128 * 4
    assert memory.temp_size_in_bytes < 1 << 20


# ---- the sampler's gates, at Mixtral's head and vocabulary -------------------


def _unguarded_sorts(hlo: str):
    """(sorts in a compiled module, those of them reached from the entry
    without passing through a branch of a ``conditional``)."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) .*\{$", line)
        if head:
            name = "ENTRY" if line.startswith("ENTRY") else head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    sorts = {c: sum(" sort(" in ln for ln in lines)
             for c, lines in comps.items()}
    seen, todo = set(), ["ENTRY"]
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for ln in comps[c]:
            todo += re.findall(
                r"(?:calls|to_apply|body|condition)=(%[\w.\-]+)", ln)
    return sum(sorts.values()), sum(sorts[c] for c in seen)


def test_sampler_in_a_decode_scan_keeps_its_sorts_in_conditionals(chip):
    """The head and ``sample`` of a decode window at ``[8, 32000]``, as the
    TPU's compiler leaves them: the gates of ``sample`` have to stay
    ``conditional`` operations with each sort inside a branch. Flattened
    to selects, a batch of greedy rows would sort the vocabulary again."""
    from rbg_tpu.engine.sampler import sample, step_keys
    B, D, V = R, 4096, 32000
    S = functools.partial(jax.ShapeDtypeStruct, sharding=chip)

    def window(x, head, keys, temps, ks, tps, mps):
        def body(pos, _):
            logits = jnp.dot(x * (1 + pos)[:, None].astype(x.dtype), head,
                             preferred_element_type=F32)
            toks, _ = sample(logits, step_keys(keys, pos + 1), temps, ks,
                             tps, mps)
            return pos + 1, toks
        return jax.lax.scan(body, jnp.zeros(B, I32), None, length=4)

    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), B))
    compiled = jax.jit(window).lower(
        S((B, D), BF16), S((D, V), BF16), _on(chip, keys), S((B,), F32),
        S((B,), I32), S((B,), F32), S((B,), F32)).compile()
    text = compiled.as_text()
    total, unguarded = _unguarded_sorts(text)
    assert " conditional(" in text and total == 2 and unguarded == 0


# ---- the hit-experts form of a decode step, at Mixtral's widths -------------


def test_hit_experts_read_the_stacked_weights_in_place(chip):
    """``_moe_mlp_hit`` inside a layer scan at Mixtral-8x7B's widths: the
    slice of one expert out of ``[L, E, D, F]`` has to fuse into its dot.
    An expert matrix copied first (117 MB; a layer's ``[E, D, F]`` 0.94 GB)
    is what handing the scan's own slice to the inner loop costs, and it
    shows as temporary memory."""
    import dataclasses
    from rbg_tpu.models import get_config
    from rbg_tpu.models.llama import _moe_mlp_hit
    cfg = dataclasses.replace(get_config("mixtral-8x7b"), num_layers=3)
    L, E, D, F = cfg.num_layers, cfg.num_experts, cfg.hidden_size, cfg.moe_f
    S = functools.partial(jax.ShapeDtypeStruct, sharding=chip)

    def experts(x, live, router, gate, up, down):
        stacks = {"moe_gate": gate, "moe_up": up, "moe_down": down}

        def layer(h, xs):
            li, r = xs
            out, visited = _moe_mlp_hit(cfg, {"router": r}, h, stacks, li,
                                        live)
            return h + out, visited
        return jax.lax.scan(layer, x, (jnp.arange(L, dtype=I32), router))

    compiled = jax.jit(experts).lower(
        S((R, 1, D), BF16), S((R, 1), bool), S((L, D, E), BF16),
        S((L, E, D, F), BF16), S((L, E, D, F), BF16),
        S((L, E, F, D), BF16)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


# ---- the walk over the hit experts, at each cell's widths -------------------

# cell: (rows, D, F, experts held, layers of the stacks): the benchmark's
# five cells' ``[L, E, D, F]`` expert stacks as served.
CELL_EXPERTS = {"mixtral": (8, 4096, 14336, 8, 3),
                "joyai": (16, 2048, 768, 256, 4),
                "kimi": (16, 2304, 1024, 16, 26),
                "lfm2": (32, 2048, 1536, 8, 38),
                "solar": (32, 4096, 1280, 20, 8)}


@pytest.mark.parametrize("cell", sorted(CELL_EXPERTS))
def test_visit_kernel_compiles_at_the_cells_widths_in_place(chip, cell):
    """``moe_visit_pallas`` inside a layer scan over the cell's stacked
    expert weights: Mosaic takes the tile ``tile_f`` picks (a whole expert
    of JoyAI, Kimi and LFM2, half of Solar's, a fourteenth of Mixtral's)
    within the VMEM the call asks for, and the program holds no temporary
    the size of an expert's matrix: the stacks are read where they lie."""
    rows, D, F, E, L = CELL_EXPERTS[cell]
    S = functools.partial(jax.ShapeDtypeStruct, sharding=chip)

    def experts(x, w, ids, visited, gate, up, down):
        stacks = {"moe_gate": gate, "moe_up": up, "moe_down": down}

        def layer(h, li):
            out = pallas.kernel("moe_visit_pallas")(h, w, stacks, li, ids,
                                                    visited)
            return h + out.astype(h.dtype), None
        return jax.lax.scan(layer, x, jnp.arange(L, dtype=I32))[0]

    compiled = _compiles_with_kernel(
        experts, S((rows, D), BF16), S((rows, E), F32), S((E,), I32),
        S((), I32), S((L, E, D, F), BF16), S((L, E, D, F), BF16),
        S((L, E, F, D), BF16))
    assert "_moe_visit_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


def test_decode_program_of_the_mixtral_cell_holds_the_visit_kernel(
        chip, monkeypatch):
    """``benchmark/configs/mixtral-8x7b-v0.1.json`` as served: 3 layers,
    ``[3, 8, 4096, 14336]`` expert stacks, 8 rows. The fused decode program
    holds the page walk's kernel and the experts' (14 tiles of 1024 a
    visit), and no temporary the size of an expert's matrix (117 MB)."""
    _, eng = _cell_engine(chip, monkeypatch, "mixtral-8x7b-v0.1.json")
    assert eng.params["blocks"]["moe_gate"].shape == (3, 8, 4096, 14336)
    text = (compiled := _compile_decode(chip, eng)).as_text()
    assert "_decode_call" in text and "_moe_visit_call" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
