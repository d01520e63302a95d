"""Paged KV cache: device-side page pool + host-side page allocator.

The serving engine's memory system (SGLang/vLLM-equivalent, see PAPERS.md
"Ragged Paged Attention" for the TPU kernel this layout feeds):

* Device: ``k_pages/v_pages [L, num_pages, page_size, KV, hd]`` — one shared
  pool for all sequences, static shapes (XLA-friendly). Heads smaller than
  a lane tile lie side by side in it, ``[..., KV / p, p * hd]``
  (``heads_per_lane_tile``). A latent-attention
  model's pair is the latent ``[L, NP, page, 1, dc]`` and the rotary key
  ``[L, NP, page, 1, dr rounded up to 128]`` (``rope_pool_width``).
* Host: ``PageAllocator`` free list + per-sequence page tables (plain ints —
  page logistics never enter the compiled graph; only gather/scatter indices
  do).

A model with recurrent layers (``cfg.mixer_kinds``: ``kda``, ``conv``) keeps
pages for its attention layers alone (``[attention layers, NP, ...]``, a
layer's pages by its ordinal among them) and, beside them, a ``StatePool``:
a slot a live row, holding each recurrent layer's fixed state.

A model with window layers (``cfg.sliding_window``) keeps TWO CLASSES of
page in one cache: the full class above for its full-attention layers, and
a window class ``window_k/window_v [window layers, NPw, page, KV, hd]`` with
an allocator, a pool size (``window_pool_pages``) and a table line a row of
its own. Both classes index a row's line by absolute column (``position //
page``), so the write is one rule; a window line's entries below the row's
live range name pages that were given back (the engine writes 0 there), and
the walk never follows them.

Sharding: pages shard over ``tp`` on the KV-head dim like the contiguous
cache (see rbg_tpu.parallel.sharding.cache_specs).
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from rbg_tpu.models.config import ModelConfig


_LANES = 128    # a TPU lane tile: the minor dim of every tiled layout

# What the window class's null page holds in every slot of K and V. A window
# line's entries below the live range are 0, the null page, and the walk
# masks whatever of it a first live block brings along; a page given back
# too early, or a mask that forgot the window, would ATTEND it. Zeros there
# would pass for sixteen more keys of no weight (measured on the chip, PR
# 46: a page given back one page early read 1.03-1.32 times the sound
# program's largest `correct` readings); keys of this size take the softmax
# of every head whose query sums positive, so such a read is loud. Finite,
# and a masked slot still leaves nothing behind, though not because its
# probability is 0 throughout: a query row wholly masked in its first block
# (a later token of a ragged tile) starts from a running maximum of -1e30,
# so that block's slots weigh 1 each and these values stand in its
# accumulator until the first block with a live slot raises the maximum
# and its ``alpha = exp(-1e30 - m) = 0`` wipes sum and accumulator alike.
WINDOW_NULL_PAGE = 8.0


def rope_pool_width(cfg: ModelConfig) -> int:
    """Channels of a latent model's rotary-key pool: ``qk_rope_head_dim``
    rounded up to whole lane tiles (64 -> 128), the rest zero. A pool whose
    last dim is under a lane tile enters a step program in a layout with
    the PAGE axis minor, while the step's scatter and the kernels want
    channels minor, so every step program transposed the whole pool on the
    way in and again on the way out (7.3 % of the device time of the
    benchmark's joyai cell; PERF.md, PR 33). The price is a page of 4 KB
    where it was 2 in each block's copies: the latent walk is 1.4 % slower
    a block inside that cell's step programs. The write pads the key
    (``models/llama.py::_pool_attention``), the attends use the pool's
    first ``dr`` channels."""
    return -(-cfg.qk_rope_head_dim // _LANES) * _LANES


def heads_per_lane_tile(cfg: ModelConfig, tp: int = 1) -> int:
    """How many KV heads a GQA pool keeps side by side on its minor axis,
    ``p``: the pool is ``[L, NP, page, KV / p, p * hd]``, the row-major
    view of ``[..., KV, hd]`` with whole lane tiles last. Heads of 64 lie
    two to a tile (``p = 2``); heads of a tile or more, or a head count
    that ``p`` (times the ``tp`` shards of the head axis) does not divide,
    stay as they are (``p = 1``). The reason is ``rope_pool_width``'s: a
    last dim under a lane tile gives a step program's pool parameter
    another layout than its scatter and its kernels use, and every step
    program copied the whole pool on the way in and out, padded to 128
    lanes (3U of HBM for a pool of U; ROADMAP S2 (b), seen on llama3-1b in
    PR 21). Padding the heads as the rotary key is padded would double
    the cache; side by side nothing is added. A token's write is a free
    reshape (``ops/paged_attention.py::_as_stored``); the kernels attend a
    tile's heads at once with each head's queries zero in the other
    heads' lanes (``ops/pallas/page_walk.py::pack_queries``). An int8 pool
    stays ``[..., KV, hd]``: its scales are a (slot, head)."""
    hd, kv = cfg.head_dim_, cfg.num_kv_heads
    if cfg.mla or hd >= _LANES or _LANES % hd:
        return 1
    p = _LANES // hd
    return p if kv % (p * tp) == 0 else 1


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedKVCache:
    """k/v pages [L, NP, page, KV, hd] (heads under a lane tile side by
    side: ``heads_per_lane_tile``). With int8 quantization the pages are
    int8 and per-(slot, head) scales live alongside ([L, NP, page, KV, 1]) —
    halving KV HBM at a small accuracy cost (per-vector absmax scaling).
    For a latent-attention model ``k_pages`` is the latent ``[L, NP, page,
    1, dc]`` and ``v_pages`` the shared rotary key ``[L, NP, page, 1,
    rope_pool_width(cfg)]``, zero beyond its first ``dr`` channels."""

    k_pages: jnp.ndarray
    v_pages: jnp.ndarray
    k_scales: Optional[jnp.ndarray] = None
    v_scales: Optional[jnp.ndarray] = None
    # The window class (a model with window layers): [Lw, NPw, page, KV, hd]
    window_k: Optional[jnp.ndarray] = None
    window_v: Optional[jnp.ndarray] = None

    @property
    def window_pages(self) -> Optional[tuple]:
        """The window class's pools as a step program takes them."""
        return None if self.window_k is None else (self.window_k,
                                                   self.window_v)

    @property
    def page_size(self) -> int:
        return self.k_pages.shape[2]

    @property
    def num_pages(self) -> int:
        return self.k_pages.shape[1]

    @property
    def quantized(self) -> bool:
        return self.k_scales is not None

    @staticmethod
    def create(cfg: ModelConfig, num_pages: int, page_size: int = 16,
               dtype=None, quantize: bool = False,
               tp: int = 1, window_num_pages: int = 0) -> "PagedKVCache":
        """``tp``: over how many devices the pool's head axis will be
        sharded (``Engine._shard_state``). ``window_num_pages``: pages of
        the window class, where the model has window layers."""
        layers = paged_layer_count(cfg)
        if cfg.mla:
            # MLA latent pool: k holds the compressed latent, v the shared
            # RoPE key — ~an order of magnitude less HBM than per-head KV.
            # int8 halves it again: per-token absmax over the latent/rope
            # vector (the write path quantizes generically — the latent is
            # just a 1-head "KV" with dc/dr channel dims; the rotary key's
            # zero channels move no absmax, so its scales are what they
            # were).
            kshape = (layers, num_pages, page_size, 1, cfg.kv_lora_rank)
            vshape = (layers, num_pages, page_size, 1, rope_pool_width(cfg))
            if quantize:
                sshape = kshape[:-1] + (1,)
                return PagedKVCache(
                    k_pages=jnp.zeros(kshape, jnp.int8),
                    v_pages=jnp.zeros(vshape, jnp.int8),
                    k_scales=jnp.zeros(sshape, jnp.float32),
                    v_scales=jnp.zeros(sshape, jnp.float32),
                )
            dtype = dtype or cfg.jax_dtype
            return PagedKVCache(k_pages=jnp.zeros(kshape, dtype),
                                v_pages=jnp.zeros(vshape, dtype))
        shape = (layers, num_pages, page_size, cfg.num_kv_heads, cfg.head_dim_)
        if quantize:
            sshape = shape[:-1] + (1,)
            return PagedKVCache(
                k_pages=jnp.zeros(shape, jnp.int8),
                v_pages=jnp.zeros(shape, jnp.int8),
                k_scales=jnp.zeros(sshape, jnp.float32),
                v_scales=jnp.zeros(sshape, jnp.float32),
            )
        dtype = dtype or cfg.jax_dtype
        p = heads_per_lane_tile(cfg, tp)
        shape = shape[:3] + (cfg.num_kv_heads // p, p * cfg.head_dim_)
        window = {}
        if cfg.sliding_window:
            wshape = (paged_layer_count(cfg, "window"),
                      window_num_pages) + shape[2:]
            window = {name: jnp.zeros(wshape, dtype).at[:, 0].set(
                WINDOW_NULL_PAGE) for name in ("window_k", "window_v")}
        return PagedKVCache(k_pages=jnp.zeros(shape, dtype),
                            v_pages=jnp.zeros(shape, dtype), **window)

    @staticmethod
    def hbm_bytes(cfg: ModelConfig, num_pages: int, page_size: int = 16,
                  dtype_bytes: int = 2, kind: str = "full") -> int:
        """Bytes of the two page pools ``create`` allocates for the class
        of page ``kind`` (``full``, or ``window`` at its own ``num_pages``;
        an int8 pool's scales are 4 bytes a (slot, head) more, not
        counted). A model with recurrent layers holds
        ``StatePool.hbm_bytes`` more."""
        layers = paged_layer_count(cfg, kind)
        if cfg.mla:
            per_tok = cfg.kv_lora_rank + rope_pool_width(cfg)
            return layers * num_pages * page_size * per_tok * dtype_bytes
        return (2 * layers * num_pages * page_size
                * cfg.num_kv_heads * cfg.head_dim_ * dtype_bytes)


def paged_layer_count(cfg: ModelConfig, kind: str = "full") -> int:
    """Entries on the leading axis of the pools of the class ``kind``:
    ``full`` (this config's attention; a looped model keeps one a pass a
    layer, ``cfg.cache_layers``) or ``window``; the recurrent layers keep
    none."""
    return cfg.cache_layers if kind == "full" else cfg.mixer_count(kind)


def window_pages_per_row(cfg: ModelConfig, page_size: int,
                         ahead: int) -> int:
    """The most pages of the window class one row can hold: its live
    range is the ``sliding_window - 1`` slots below the oldest query of a
    step and up to ``ahead`` slots from that query on (a prompt's chunk,
    or a fused decode window), and either end can stand inside a page."""
    return pages_for_tokens(cfg.sliding_window - 1 + ahead, page_size) + 1


def window_pool_pages(cfg: ModelConfig, page_size: int, max_batch: int,
                      ahead: int) -> int:
    """Pages of the window class's pool: every row's most, and the null
    page. Sized by the rows' WINDOWS, not their contexts: it never runs
    out, so nothing is preempted for it."""
    return max_batch * window_pages_per_row(cfg, page_size, ahead) + 1


class StatePool:
    """The recurrent layers' cache: ``slots`` slots, one a live row, each
    holding every recurrent layer's state. Which arrays there are follows
    the mixer kinds the model has (``array_shapes``): a KDA layer keeps
    ``s``, the state ``[H, dk, dk]`` in float32, and ``conv``, the last
    ``K - 1`` inputs of q, k and v (``models/llama.py::_kda_attention``); a
    gated short convolution keeps ``tail`` alone, the last ``K - 1`` gated
    inputs (``_conv_attention``). Each array leads with its own kind's
    layers, and a tail is held flat. The step programs carry ``arrays`` as
    they carry the pages. The host side is a free list. A slot is never
    cleared on the device: a row whose tokens start at position 0 starts
    from zeros in the step program itself, so a slot taken at admission is
    a zero state whatever it held."""

    def __init__(self, cfg: ModelConfig, slots: int):
        self.slots = slots
        self.arrays = {name: jnp.zeros(shape, dtype) for name, (shape, dtype)
                       in self.array_shapes(cfg, slots).items()}
        self._free: List[int] = list(range(slots - 1, -1, -1))
        # Bytes one live row's state moves a step if each recurrent layer
        # reads and writes it once (the wire counter ``state_bytes_moved``).
        self.row_bytes = 2 * self.hbm_bytes(cfg, slots) // slots

    @staticmethod
    def array_shapes(cfg: ModelConfig, slots: int) -> dict:
        """``{name: (shape, dtype)}`` of the arrays the model's recurrent
        mixers keep. A tail's taps lie side by side on the minor axis: a
        second-minor axis of ``K - 1`` (3, 2) would be padded to a whole
        tile of 16, and every step program copied such an array whole."""
        out = {}
        kda, conv = cfg.mixer_count("kda"), cfg.mixer_count("conv")
        if kda:
            out["s"] = ((kda, slots, cfg.kda_num_heads, cfg.kda_head_dim,
                         cfg.kda_head_dim), jnp.dtype(jnp.float32))
            out["conv"] = ((kda, slots, (cfg.kda_conv_kernel - 1) * 3
                            * cfg.kda_num_heads * cfg.kda_head_dim),
                           cfg.jax_dtype)
        if conv:
            out["tail"] = ((conv, slots, (cfg.conv_kernel - 1)
                            * cfg.hidden_size), cfg.jax_dtype)
        return out

    @staticmethod
    def hbm_bytes(cfg: ModelConfig, slots: int) -> int:
        """Bytes of the arrays a pool of ``slots`` slots allocates."""
        return sum(math.prod(shape) * dtype.itemsize for shape, dtype
                   in StatePool.array_shapes(cfg, slots).values())

    @property
    def held(self) -> int:
        return self.slots - len(self._free)

    def take(self) -> int:
        """A free slot (admission holds at most ``slots`` rows)."""
        return self._free.pop()

    def release(self, slot: int) -> None:
        assert slot not in self._free, f"double free of state slot {slot}"
        self._free.append(slot)


class PageAllocator:
    """Host-side page free list with reference counting (shared prefix pages
    from the radix cache hold refcount > 1; copy-on-write is avoided by only
    sharing fully-frozen pages)."""

    def __init__(self, num_pages: int):
        # page 0 is reserved as the null page (padding rows in page tables
        # point at it; their slots are masked out by seq_lens anyway).
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._refs = np.zeros(num_pages, np.int32)
        self._refs[0] = 1

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Allocate n pages or None (caller evicts/preempts and retries)."""
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self._refs[p] = 1
        return out

    def share(self, pages: List[int]) -> None:
        for p in pages:
            assert self._refs[p] > 0, f"share of free page {p}"
            self._refs[p] += 1

    def release(self, pages: List[int]) -> None:
        for p in pages:
            self._refs[p] -= 1
            if self._refs[p] == 0:
                self._free.append(p)
            assert self._refs[p] >= 0, f"double free of page {p}"

    def refcount(self, p: int) -> int:
        """Current reference count of one page (the host-tier spill hook
        reads it: a page another holder still pins must not spill — it
        stays device-resident)."""
        return int(self._refs[p])


def pages_for_tokens(n_tokens: int, page_size: int) -> int:
    return (n_tokens + page_size - 1) // page_size
