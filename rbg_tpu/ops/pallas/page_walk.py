"""The page walk every paged-attention kernel here shares.

A row's K/V lives in ``ceil(len / page)`` pages of a pool, named by its
line of the page table; the table is as wide as ``max_seq_len`` allows and
almost all of it is dead. The kernels used to make the table's width a
grid axis and skip the dead steps one by one: a grid step that does
nothing still costs its index maps and the pipeline's bookkeeping, and at
512 pages a row those steps were nearly all of a call.

Here the walk is a flat list of LIVE work items. A kernel's work falls
into *segments* (a decode row; in the ragged kernels a row's first token
in a query tile), each of which attends its row's pages in ascending
order, a *block* of consecutive pages an item (``pages_per_block``;
the decode kernels' blocks are wider, ``decode_pages_per_block``).
``live_block_starts`` turns the segments' live token counts into
cumulative block counts in XLA; the grid is one axis whose DYNAMIC length
is their total. Nothing a kernel does depends on the table's width any
more.

Which (segment, block, physical pages) grid step ``w`` is, two ways:

* The ragged kernels and ``_kda_decode_call`` BISECT: ``find_item`` maps
  ``w`` back to (segment, block of the segment) over the scalar-prefetched
  starts, and ``page_of_block`` walks the segment's line of the page
  table, in every index map of every operand of every step (a pipeline
  evaluates the maps of the step ahead as well). Their segments are a
  step's packed tokens, up to 512 x 64 items, and they are a few per
  cent of a cell's device time.
* The decode kernels (``paged_attention_kernel.py``), where the pipeline
  brings their pages, READ A TABLE:
  ``walk_items`` resolves every item once a call, in XLA, from the same
  starts, lengths and page table, and an index map is one read of it.
  A block of four pages and two pools has ten index maps; bisecting,
  each cost about fifty scalar operations, and the walk was bound by
  them, not by bytes: a block of 64 cached tokens took 1.31-1.50 us on a
  v5e where its 128 KB are 0.16 us of HBM time, 0.81-0.86 us with the
  table (PERF.md, PR 40).

A block's pages are scattered over the pool, and there are two ways for
them to arrive, chosen by what the pool's shape and dtype say
(``kernel_copies``; no flag, field or bucket):

* THE PIPELINE. Each pool is handed to the kernel once per page of a
  block (``block_specs``): the Pallas pipeline fetches the pages side by
  side, double-buffered, and ``load_blocks`` joins them in VMEM. Every
  page is an operand of its own, with its index map, its revisit test,
  its start and its wait on the scalar core, and since ``walk_items`` a
  block's time is those: 0.30-0.42 us a grid step and 0.10-0.14 us a page
  where a page's 64 KB of K and V are 0.08 us of HBM time (PERF.md, PRs
  40, 46). Every pool can take this path. The ragged kernels and
  ``_kda_decode_call`` take no other; of the decode kernels' pools it is
  left with those the other path refuses: int8 pools with their scales
  (K/V and latent: four pools), float32 pools, and a last dim under a
  lane tile (heads of 64 unpacked, the tests' small presets). No cell of
  the benchmark serves one of these.
* THE KERNEL'S OWN COPIES (``walk_with_copies``, one loop under both
  decode kernels). The pools stay in HBM, the page table and the rows'
  lengths are the scalar operands, and the kernel walks a row's blocks
  in a loop of its own: for a block it starts one copy a page a pool
  into consecutive slots of ONE buffer (``start_block_copies``: the
  block arrives joined), the block after it, or the next live row's
  first, before it waits for this one (``wait_block_copies``: one wait a
  pool) and attends. No grid step a block, no operand a page, no item
  table and nothing beside the kernel in XLA. Mosaic takes such a copy
  only where a page is whole tiles in the pool and in the buffer, which
  ``kernel_copies`` reads off the pools: two bf16 pools whose last dim is
  whole lane tiles, K/V pools of 8 or 16 heads of 128 (Mixtral's, Solar's
  GQA layers', both kinds of Laguna's, Ouro's), of 4 or 2 (heads of 64
  packed two to a lane tile: LFM2's ``[NP, 16, 4, 128]``), and the latent
  pools ``[NP, 16, 512]`` / ``[NP, 16, 128]`` (JoyAI's, Kimi's). PR 25
  wrote this first and dropped it untimed, because the pools of its day
  (hd 64, KV 2, scales, the rotary key 64 wide) were refused; PR 51
  brought it back for the GQA kernel's whole-tile pools (a block 1.4 ->
  1.05 us), PR 53 for the packed and the latent pools (1.31 -> 0.95 and
  1.20 -> 0.82 us a block; PERF.md).

A per-step cost is paid once for ``pages_per_block·page`` slots either
way.

The two softmax updates (``gqa_attend``, ``mla_attend``) are the ones the
grid kernels had, over a block instead of a page: same masks, same
ascending order, same int8 scale folding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_I32 = np.int32

# Slots of one block of the ragged kernels. A per-step cost (0.5 us on a
# v5e) is paid once a block, so wider is faster per page: 0.78, 0.51,
# 0.39, 0.33, 0.30 us at 1, 2, 4, 8, 16 pages of 16 slots (PERF.md, PR
# 25). But every page of a block is one more operand per pool, whose
# bisecting index map (``find_item``) is traced and lowered in each step
# program an engine warms: at 128 slots warm-up was 5 s longer than at 64,
# for 0.4 % of a decode step.
_BLOCK_SLOTS = 64

# Slots of one block of the decode kernels, whose index maps are one read
# of ``walk_items``' table each and cost next to nothing to trace: the
# Python calls inside ``.lower()`` of a cell's decode program are 1.284 M
# on PR 39's tree, 1.221 M with the table at 64 slots, 1.251 M at 128,
# 1.310 M at 256 (lfm2; mixtral 0.902, 0.877, 0.906 M). A block then
# costs 0.30-0.42 us a step and 0.10-0.14 us a page of 16 slots where
# the bisecting maps cost 0.5 and 0.27 (a v5e, PERF.md, PR 40; us a call
# of ``_decode_call`` at 32 rows of 128-2048 tokens and heads of 64,
# ``_mla_decode_call`` at 16 rows, ``_decode_call`` at 8 rows and heads of
# 128): 912 / 447 / 159 bisecting, then 490 / 261 / 104 at 64 slots,
# 410 / 198 / 89 at 128, 401 / 164 / 83 at 256. 128 takes 15-24 % off
# every one; 256 another 2 % and 6 % off the GQA calls for twice the
# operands again. The price is a row under 65 tokens, whose one block now
# fetches its last live page eight times over where it was four.
_DECODE_BLOCK_SLOTS = 128


def pages_per_block(page: int) -> int:
    """Pages one work item of a ragged kernel attends (4 pages of 16
    slots)."""
    return max(1, _BLOCK_SLOTS // page)


def decode_pages_per_block(page: int) -> int:
    """Pages one work item of a decode kernel attends (8 pages of 16
    slots)."""
    return max(1, _DECODE_BLOCK_SLOTS // page)


def live_block_starts(live_tokens, page: int, at_least_one, n: int = None):
    """Cumulative live blocks of the segments, ``[S + 1]`` int32 (XLA),
    a block ``n`` pages (``pages_per_block`` unless given).

    ``live_tokens [S]`` is how many slots of its row each segment
    attends (<= 0: none). A segment where ``at_least_one`` holds gets one
    item even then, which attends nothing: the kernels initialise and
    write an output block in the items of its first and last segment, so
    every output block needs one. ``starts[-1]`` is the grid's length.

    A window layer's segment starts at the block that holds its first
    live token (``first_live_block``): its ``live_tokens`` are counted from
    that block's first slot, so the blocks below it get no item."""
    block = (n or pages_per_block(page)) * page
    blocks = jnp.maximum(-(-live_tokens // block), 0)
    blocks = jnp.where(at_least_one, jnp.maximum(blocks, 1), blocks)
    return jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(blocks, dtype=jnp.int32)])


def first_live_block(first_token, page: int, n: int = None):
    """The block (``n`` pages; ``pages_per_block`` unless given) that holds
    a segment's first live token, and that block's first slot: where a
    window layer's walk of the row starts. ``first_token`` below 0 (a
    window longer than the row) is the row's start."""
    block = (n or pages_per_block(page)) * page
    first = jnp.maximum(first_token, 0) // block
    return first, first * block


def find_item(starts_ref, w, n_segments: int):
    """(segment, block of the segment) of work item ``w``: the LAST
    segment whose start is <= w (segments without items share their
    successor's start and are never found). ``starts_ref`` holds
    ``n_segments + 1`` scalars, the last the number of items. A fixed-trip
    search over SMEM scalars, so it can run inside an index map: from
    segment 0 up by falling powers of two, a step taken while the start
    there is still <= w. An item past the grid's end, which a pipeline may
    ask for ahead of time, resolves to the last segment.

    Written in ``lax`` primitives, here and in ``page_of_block``: every
    index map of every page of a block traces and lowers this code, in
    each of the dozens of step programs an engine warms, and a ``jnp``
    call costs a nested ``jit`` each time (half a minute of set-up).
    Unrolled: as a loop the scalar core pays a branch a trip in every
    index map of every step (`_decode_call` 0.135 -> 0.26 ms on the chip)."""
    lo = _I32(0)
    steps = max(n_segments - 1, 1).bit_length()
    exact = 1 << steps == n_segments    # a packed axis is a power of two
    for k in reversed(range(steps)):
        # Four scalar operations a trip in every map's trace (a bisection
        # between two bounds takes six).
        at = lax.add(lo, _I32(1 << k))
        if exact:
            lo = lax.select(lax.le(starts_ref[at], w), at, lo)
        else:       # ``at`` may pass the last segment: it is not taken
            inside = lax.lt(at, _I32(n_segments))
            start = starts_ref[lax.min(at, _I32(n_segments))]
            lo = lax.select(lax.bitwise_and(inside, lax.le(start, w)), at, lo)
    return lo, lax.sub(w, starts_ref[lo])


def _last_live_page(live_tokens, page: int):
    """The column of a row's line that names its last live page (-1: the
    row is empty)."""
    return lax.sub(lax.div(lax.add(live_tokens, _I32(page - 1)), _I32(page)),
                   _I32(1))


def page_of_block(table_ref, row, block, j: int, live_tokens, page: int):
    """Physical page of page ``j`` of ``row``'s block ``block``. Past the
    row's last live page it is that last page again (its slots are masked:
    they lie beyond ``live_tokens``), so no copy follows a table entry
    beyond the live pages, whatever it names."""
    last = _last_live_page(live_tokens, page)
    p = lax.add(lax.mul(block, _I32(pages_per_block(page))), _I32(j))
    p = lax.clamp(_I32(0), lax.min(p, last), _I32(table_ref.shape[1] - 1))
    return table_ref[row, p]


def walk_items(starts, live_tokens, table, page: int, n: int,
               first_block=None):
    """The walk's items, resolved once a call (XLA): for every work item
    ``w`` the decode kernels may be asked for, its row, its block of the
    row and the physical page of each page of that block, so that a
    kernel's index maps are one read each.

    ``starts [S + 1]`` are ``live_block_starts`` of ``live_tokens [S]``
    at ``n`` pages a block, ``table [S, P]`` the rows' lines of the page
    table. Returns ``item_row, item_block [W + 1]`` and ``item_page
    [n, W + 1]`` int32 (page ``j`` of item ``w`` at ``[j, w]``; held this
    way round because a last dim of ``n`` would pad to a lane tile in
    SMEM), with ``W = S * ceil(P / n)``, the most items the table can
    hold: the table re-indexed, item by item where it was row by row.
    The rules are ``find_item``'s and ``page_of_block``'s: an item at or
    past the grid's end (``starts[S]``), which a pipeline may ask for
    ahead of time (hence the ``+ 1``), resolves to the last row; a page
    past a row's last live page is that last page again, so no entry
    beyond the live pages is ever in ``item_page``, whatever it names.
    ``first_block [S]`` (a window layer's walk): the block of its row at
    which each row's items start, ``live_tokens`` still the row's whole
    length; the entries of the line below that block are not followed
    either, and those of that block below the window are whatever the
    line holds there (the null page: ``engine``'s lines hold 0 for a page
    given back), masked by the kernel.

    A dozen small fusions, 4-8 us a call on a v5e: an item's row by
    comparing ``w`` with every start, not by a search, and its pages as
    ONE slice of ``n`` entries of the row's line (a gather of single
    entries took 55 us at 32 rows under a table 256 wide)."""
    S, P = table.shape
    blocks = -(-P // n)                            # of a whole line
    w = jnp.arange(S * blocks + 1, dtype=jnp.int32)[:, None]
    # The last row's end is open: the items past the grid's end are its.
    ends = jnp.concatenate(
        [starts[1:S], jnp.full((1,), np.iinfo(np.int32).max, jnp.int32)])
    own = (starts[None, :S] <= w) & (w < ends[None])          # [W + 1, S]
    last = jnp.clip(-(-live_tokens // page) - 1, 0, P - 1)    # its column
    last_page = jnp.sum(jnp.where(
        jnp.arange(P, dtype=jnp.int32)[None] == last[:, None], table, 0),
        axis=1, dtype=jnp.int32)
    per_row = (jnp.arange(S, dtype=jnp.int32), starts[:S], last, last_page)
    if first_block is not None:
        per_row += (first_block,)
    row, first, last, last_page, *below = (
        jnp.sum(jnp.where(own, of_row[None], 0), axis=1, dtype=jnp.int32)
        for of_row in per_row)
    block = w[:, 0] - first
    if below:
        block = block + below[0]
    lines = jnp.pad(table, ((0, 0), (0, blocks * n - P))).reshape(-1, n)
    pages = lines.at[jnp.minimum(row * blocks + block, S * blocks - 1)].get(
        mode="promise_in_bounds")                             # [W + 1, n]
    column = block[:, None] * n + jnp.arange(n, dtype=jnp.int32)[None]
    pages = jnp.where(column <= last[:, None], pages, last_page[:, None])
    return row, block, pages.T


def block_specs(pools, page_id, n: int = None):
    """Block specs and operands that hand each of ``pools`` (arrays
    ``[NP, page, ...]``) to a kernel once per page of a block of ``n``
    pages (``pages_per_block`` unless given):
    ``page_id(j, *grid_and_prefetch)`` is the physical page of the item's
    ``j``-th page. The kernel receives, pool by pool, ``n`` refs
    ``[1, page, ...]``."""
    n = n or pages_per_block(pools[0].shape[1])
    specs, operands = [], []
    for pool in pools:
        tail = (0,) * (pool.ndim - 1)
        for j in range(n):
            specs.append(pl.BlockSpec(
                (1,) + pool.shape[1:],
                lambda *a, j=j, tail=tail: (page_id(j, *a),) + tail))
            operands.append(pool)
    return specs, operands


def kernel_copies(pools) -> bool:
    """Whether a decode kernel may copy ``pools``' pages itself
    (``start_block_copies``) or has to take them from the pipeline
    (``block_specs``): read off the pools as a kernel receives them, set
    nowhere. Mosaic slices a page out of a pool in HBM and lands it in
    ``page`` of a VMEM buffer's slots only where the slice is whole tiles
    on both sides (``tests/test_chip_compile.py`` compiles each for a
    described v5e): two bf16 pools, no scales beside them, whose last dim
    is a multiple of a lane tile (128) and whose dim before it is

    * for K/V pools ``[NP, page, KV, hd]`` the heads, a dim the page and
      the buffer share whole: a multiple of 8, or 2 or 4 (a smaller tile;
      heads of 64 packed two to a lane tile come as ``KV / 2`` heads of
      128: LFM2's ``[NP, 16, 4, 128]``). 1, 6 or 12 heads are refused;
    * for the latent pools ``[NP, page, d]`` (``latent_pools``) the page,
      a slice of the buffer's slots: a multiple of 8.

    float32 pools, int8 pools with their ``[page, KV]`` or ``[page, 1]``
    scales (four pools) and a last dim under a lane tile (heads of 64
    unpacked, the tests' small presets) fall on the pipeline's side."""

    def whole_tiles(p):
        if p.dtype != jnp.bfloat16 or p.ndim not in (3, 4) \
                or p.shape[-1] % 128:
            return False
        return p.shape[-2] % 8 == 0 or (p.ndim == 4 and p.shape[-2] in (2, 4))

    return len(pools) == 2 and all(whole_tiles(p) for p in pools)


def start_block_copies(pools, bufs, sems, table_ref, row, block,
                       live_tokens, half, n: int):
    """Start the copies that land block ``block`` of ``row`` in half
    ``half`` of each pool's buffer: ``n`` a pool, page ``j`` of the block
    into slots ``j·page ..`` of ``bufs[i] [2, n·page, ...]``, so the block
    arrives joined. ``pools`` are refs in HBM, ``table_ref`` the page table
    in SMEM; a pool's copies signal one semaphore, ``sems[i, half]``.
    ``page_of_block``'s rule: past the row's last live page the last live
    page again, so no entry beyond it is followed."""
    page = pools[0].shape[1]
    last = _last_live_page(live_tokens, page)
    first = lax.mul(block, _I32(n))

    def start(j, _):
        p = lax.clamp(_I32(0), lax.min(lax.add(first, j), last),
                      _I32(table_ref.shape[1] - 1))
        page_id = table_ref[row, p]
        slot = pl.multiple_of(lax.mul(j, _I32(page)), page)
        for i, (pool, buf) in enumerate(zip(pools, bufs)):
            pltpu.make_async_copy(
                pool.at[page_id], buf.at[half, pl.ds(slot, page)],
                sems.at[i, half]).start()

    # Unrolled when lowered, traced once: every step program an engine
    # warms traces this body (``find_item`` has the price of a ``jnp`` call
    # and of a loop the scalar core runs).
    lax.fori_loop(0, n, start, None, unroll=True)


def wait_block_copies(bufs, sems, half):
    """Wait for the block ``start_block_copies`` sent to ``half``: one wait
    a pool, for the whole half (a DMA semaphore counts bytes, and the
    pool's ``n`` copies are the half's)."""
    for i, buf in enumerate(bufs):
        pltpu.make_async_copy(buf.at[half], buf.at[half],
                              sems.at[i, half]).wait()


def walk_with_copies(pools, bufs, sems, table_ref, kv_lens_ref, out_ref,
                     m_ref, l_ref, acc_ref, attend, window=None):
    """The decode walk of a kernel that issues its own page copies, one
    program over every row of ``out_ref [B, ...]``: the rows in a loop, a
    row's live blocks in a loop inside it, block ``i + 1``'s pages (at a
    row's end the next live row's first block's) started before block ``i``
    is waited for and attended, in the two halves of ``bufs`` (a pool's
    ``[2, n·page, ...]``), one semaphore a pool a half (``sems [pools, 2]``).
    ``attend(b, half, block, kv_len)`` updates the softmax state from the
    block that has landed in ``half``; the state is initialised before a
    row's first block and finalised into ``out_ref[b]`` after its last (an
    empty row attended nothing and writes zeros). ``window``: a window
    layer's walk starts at the block that holds token ``kv_len - window``.
    The blocks and their order are the pipeline's (``live_block_starts``,
    ``first_live_block``)."""
    B = out_ref.shape[0]
    page = pools[0].shape[1]
    n = bufs[0].shape[1] // page
    slots = n * page

    def span(b):
        """Row ``b``'s length and its walk's first block and end."""
        kv_len = kv_lens_ref[b]
        first = _I32(0) if window is None else lax.div(
            lax.max(lax.sub(kv_len, _I32(window)), _I32(0)), _I32(slots))
        return kv_len, first, lax.div(lax.add(kv_len, _I32(slots - 1)),
                                      _I32(slots))

    def start(b, block, kv_len, half):
        start_block_copies(pools, bufs, sems, table_ref, b, block, kv_len,
                           half, n)

    def row(b, carry):
        half, on_its_way = carry
        kv_len, first, end = span(b)
        after = lax.min(lax.add(b, _I32(1)), _I32(B - 1))
        after_len, after_first, after_end = span(after)
        after_live = lax.bitwise_and(lax.lt(lax.add(b, _I32(1)), _I32(B)),
                                     lax.lt(after_first, after_end))
        live = lax.lt(first, end)
        init_softmax(m_ref, l_ref, acc_ref)

        # The call's first live row, or one after an empty row.
        @pl.when(lax.bitwise_and(live, lax.eq(on_its_way, _I32(0))))
        def _first():
            start(b, first, kv_len, half)

        def one_block(block, half):
            ahead = lax.add(block, _I32(1))
            more = lax.lt(ahead, end)
            other = lax.sub(_I32(1), half)

            @pl.when(lax.bitwise_or(more, after_live))
            def _ahead():
                start(lax.select(more, b, after),
                      lax.select(more, ahead, after_first),
                      lax.select(more, kv_len, after_len), other)

            wait_block_copies(bufs, sems, half)
            attend(b, half, block, kv_len)
            return other

        half = lax.fori_loop(first, end, one_block, half)
        out_ref[b] = finalize_softmax(l_ref, acc_ref, out_ref.dtype)
        return half, lax.convert_element_type(
            lax.bitwise_and(live, after_live), jnp.int32)

    lax.fori_loop(0, B, row, (_I32(0), _I32(0)))


def latent_pools(c_pages, pe_pages):
    """The latent pools ``[NP, page, 1, d]`` as ``[NP, page, d]``, inside
    the jitted call that hands them to a kernel. A custom call takes its
    operands in the default layout, and a 4-D pool's trailing ``(1, d)``
    tiles unlike the ``(page, d)`` the step's scatter keeps the pool in:
    handed over 4-D, the whole pool was copied before every call (0.75 GB
    a layer a step at the benchmark's joyai cell). The reshape is free."""
    return tuple(p.reshape(p.shape[:2] + p.shape[3:])
                 for p in (c_pages, pe_pages))


def pack_queries(qg, p: int):
    """Queries ``[..., KV, G, hd]`` for a pool that keeps ``p`` heads side
    by side, ``[NP, page, KV / p, p * hd]`` (``kvcache.heads_per_lane_tile``):
    ``[..., KV / p, p * G, p * hd]``, in which row ``i * G + g`` of a tile
    holds head ``i``'s query ``g`` in lanes ``i * hd ..`` and zeros in the
    other heads' lanes. A dot with the tile's keys over all ``p * hd``
    lanes is then that head's own score, the zeros taking the other heads
    out, and the kernels run as on ``KV / p`` heads of ``p * hd`` with
    ``p * G`` queries each (at ``p`` times the MXU work, of a walk that
    memory bounds). In XLA, on a step's queries. ``p = 1`` changes
    nothing."""
    if p == 1:
        return qg
    *lead, KV, G, hd = qg.shape
    q5 = qg.reshape(*lead, KV // p, p, G, 1, hd)
    own = jnp.eye(p, dtype=qg.dtype).reshape(p, 1, p, 1)
    return (q5 * own).reshape(*lead, KV // p, p * G, p * hd)


def unpack_outputs(out, p: int):
    """The inverse on a kernel's output ``[..., KV / p, p * G, p * hd]``:
    each row's own head's lanes, ``[..., KV, G, hd]``."""
    if p == 1:
        return out
    *lead, KVp, pG, phd = out.shape
    G, hd = pG // p, phd // p
    o6 = out.reshape(*lead, KVp, p, G, p, hd)
    own = jnp.stack([o6[..., i, :, i, :] for i in range(p)], axis=-3)
    return own.reshape(*lead, KVp * p, G, hd)


def load_latent_blocks(page_refs, n: int = None):
    """``load_blocks`` of the latent pools ``c, pe [n·page, d]`` and, for
    int8 pools, their per-slot scales ``[n·page]`` (else None, None)."""
    c, pe, *scales = load_blocks(page_refs, n)
    cs, ps = (s[:, 0] for s in scales) if scales else (None, None)
    return c, pe, cs, ps


def load_blocks(page_refs, n: int = None):
    """The item's block of each pool, ``[n·page, ...]``, from the page refs
    a kernel received (``block_specs``' order: pool by pool, ``n`` each)."""
    n = n or pages_per_block(page_refs[0].shape[1])
    return [jnp.concatenate([r[0] for r in page_refs[i:i + n]], axis=0)
            for i in range(0, len(page_refs), n)]


def init_softmax(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def finalize_softmax(l_ref, acc_ref, dtype):
    """acc / l, with rows that attended nothing (pads, empty rows)
    finalizing to zero through the denominator's guard."""
    return (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(dtype)


def _update(scores, mask, m_ref, l_ref, acc_ref, values):
    """One online-softmax step; ``values(probs)`` is the block's
    probability-weighted value sum."""
    scores = jnp.where(mask, scores, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # A fully masked query row: m_new == m_prev → alpha 1, probs 0 → its
    # state is untouched by this block (no special casing).
    probs = jnp.exp(scores - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + jnp.sum(probs, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + values(probs)


def gqa_attend(q, k, v, ks, vs, token0, limit, m_ref, l_ref, acc_ref,
               head_dim=None, lower=None):
    """Attend query rows ``q [KV, rows, hd]`` to one block ``k, v
    [S, KV, hd]`` whose first slot is token ``token0`` of the row; query
    row ``j`` sees slots ``< limit`` (a scalar, or ``[rows, 1]``) and, in
    a window layer, ``>= lower`` (the same shapes). Scores
    are scaled by ``head_dim ** -0.5``: ``hd``, but for a pool of packed
    heads (``pack_queries``), whose ``hd`` here is ``p`` heads wide.

    int8 pools hand their per-(slot, head) scales ``ks, vs [S, KV]``:
    they factor out of both dots (scores ·= ks, pv = (probs·vs)·v), so
    the pages are never multiplied elementwise."""
    q = q.astype(jnp.float32)
    rows, hd = q.shape[1], head_dim or q.shape[2]
    k_t = jnp.transpose(k.astype(jnp.float32), (1, 0, 2))   # [KV, S, hd]
    v_t = jnp.transpose(v.astype(jnp.float32), (1, 0, 2))
    scores = jax.lax.dot_general(
        q, k_t, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * (1.0 / (hd ** 0.5))
    if ks is not None:
        scores = scores * jnp.transpose(ks, (1, 0))[:, None, :]
    token_idx = token0 + jax.lax.broadcasted_iota(
        jnp.int32, (rows, k.shape[0]), dimension=1)
    mask = token_idx < limit
    if lower is not None:
        mask = mask & (token_idx >= lower)
    mask = mask[None]                                       # [1, rows, S]

    def values(probs):
        if vs is not None:
            probs = probs * jnp.transpose(vs, (1, 0))[:, None, :]
        return jax.lax.dot_general(
            probs, v_t, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)             # [KV, rows, hd]

    _update(scores, mask, m_ref, l_ref, acc_ref, values)


def mla_attend(ql, qp, c, pe, cs, ps, token0, limit, scale,
               m_ref, l_ref, acc_ref):
    """The latent twin of ``gqa_attend``: ``ql [rows, dc]``, ``qp
    [rows, dr]`` against one block ``c [S, dc]``, ``pe [S, >= dr]``, of
    which the first ``dr`` channels are the rotary key (the pool is a
    whole lane tile wide, zero beyond them: ``kvcache.rope_pool_width``);
    the values are the latents. int8 pools hand per-slot scales ``cs, ps
    [S]``: the latent scale multiplies the latent score term and the
    probabilities before the value dot, the RoPE scale the RoPE term."""
    ql = ql.astype(jnp.float32)
    qp = qp.astype(jnp.float32)
    c = c.astype(jnp.float32)
    pe = pe[:, :qp.shape[-1]].astype(jnp.float32)
    s_c = jax.lax.dot_general(ql, c, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    s_pe = jax.lax.dot_general(qp, pe, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    if cs is not None:
        s_c = s_c * cs[None, :]
        s_pe = s_pe * ps[None, :]
    scores = (s_c + s_pe) * scale                           # [rows, S]
    token_idx = token0 + jax.lax.broadcasted_iota(
        jnp.int32, scores.shape, dimension=1)

    def values(probs):
        if cs is not None:
            probs = probs * cs[None, :]
        return jax.lax.dot_general(probs, c, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    _update(scores, token_idx < limit, m_ref, l_ref, acc_ref, values)
