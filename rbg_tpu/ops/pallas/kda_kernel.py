"""Pallas TPU kernel: one decode step of Kimi Delta Attention, in place on
the state pool.

Why a kernel: ``ops/kda.py::kda_step`` between a gather out of the pool
and a scatter back is five reads and three writes of every live row's
state (the gather, the decay fused into ``S^T k``, the rank-one update,
``S^T q``, the scatter), and the state is all a decode step of a recurrent
layer moves: ``4 H dk dv`` bytes a row a layer in float32 (2.1 MB at 32
heads of 128 x 128, 4.2 MB at 64). Here a row's state goes
HBM -> VMEM once, is decayed, corrected and read out there, and goes back
to the slot it came from.

Design (``page_walk.py`` has the walk's idiom):
* the pool ``[layers, slots, H, dk, dv]`` is an operand AND the output
  (``input_output_aliases``): a block is ``heads_per_block`` heads of one
  slot of one layer, picked by index maps from the scalar-prefetched
  ``layer`` and ``slots``. Blocks the walk does not visit are never
  touched, so no other slot and no other layer moves.
* the grid is ONE axis of dynamic length over the LIVE rows' head blocks
  (a live row names a slot in range). A row of padding names a slot out
  of range; clipped it could be a live row's, and a block that is read
  early and written late would undo that row's update, so padding gets no
  item at all. A call without a live row keeps one item (a grid of length
  zero is nothing Mosaic promises), which copies its block through.
* per head everything is float32 on the VPU: the decay ``Diag(e^g) S``,
  ``S'^T k`` and ``S^T q`` as multiply-and-reduce over ``dk``, the
  rank-one update. ``g``, ``k`` and ``q`` run along ``dk``, the state's
  SECOND-minor axis, so a block's ``[Hb, dk]`` tile of each is transposed
  in VMEM and a head's column is broadcast along the lanes; ``v``, ``u``
  and ``o`` run along ``dv``, the lanes, as they come.
* a fresh row (its tokens start at position 0) starts from zeros whatever
  its slot holds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rbg_tpu.ops.pallas import page_walk as W

_I32 = np.int32

# Heads of one block. The compiler's schedule of a grid step holds some 650
# bundles of index maps and pipeline bookkeeping beside 125 a head, so the
# block has to be large: on the chip 20 layers of 16 rows (0.67 GB each
# way) took 2.35 ms at 8 heads a block and 2.0 ms at 16 and at 32
# (PERF.md, PR 36). 16 heads of [128, 128] float32 are 1 MB in and 1 MB
# out a step, double-buffered 4 MB of VMEM.
HEADS_PER_BLOCK = 16


def _starts(live, blocks: int):
    """Cumulative items of the rows, ``[R + 1]`` int32 (XLA): a live row
    has ``blocks`` (its head blocks), a row of padding none. A call
    without a live row gives its first row one item, which moves
    nothing."""
    alone = (jnp.arange(live.shape[0]) == 0) & ~jnp.any(live)
    items = jnp.where(live, blocks, alone.astype(jnp.int32))
    return jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(items, dtype=jnp.int32)])


def _kda_decode_kernel(
    # scalar prefetch
    layer_ref,        # [1] int32 (SMEM): the layer's ordinal in the pool
    slots_ref,        # [R] int32: each row's slot; out of range = padding
    fresh_ref,        # [R] int32: 1 where the row starts from zeros
    starts_ref,       # [R + 1] int32: cumulative items of the rows
    # blocks
    q_ref,            # [1, Hb, dk] float32
    k_ref,            # [1, Hb, dk]
    g_ref,            # [1, Hb, dk]
    v_ref,            # [1, Hb, dv]
    b_ref,            # [1, Hb, 1]
    s_ref,            # [1, 1, Hb, dk, dv]: the slot's state
    s_out_ref,        # the same block of the same pool
    o_ref,            # [1, Hb, dv]
    *,
    n_slots: int,
):
    row, _ = W.find_item(starts_ref, pl.program_id(0), slots_ref.shape[0])
    slot = slots_ref[row]
    fresh = fresh_ref[row] != 0
    live = (slot >= 0) & (slot < n_slots)

    @pl.when(live)
    def _step():
        q_t = jnp.transpose(q_ref[0])                       # [dk, Hb]
        k_t = jnp.transpose(k_ref[0])
        a_t = jnp.transpose(jnp.exp(g_ref[0]))
        for h in range(s_ref.shape[2]):
            col = slice(h, h + 1)
            S = jnp.where(fresh, 0.0, s_ref[0, 0, h]) * a_t[:, col]
            k = k_t[:, col]                                 # [dk, 1]
            u = b_ref[0, col, :] * (
                v_ref[0, col, :] - jnp.sum(S * k, axis=0, keepdims=True))
            S = S + k * u                                   # [dk, dv]
            s_out_ref[0, 0, h] = S
            o_ref[0, col, :] = jnp.sum(S * q_t[:, col], axis=0, keepdims=True)

    # The one item of a call without a live row.
    @pl.when(jnp.logical_not(live))
    def _through():
        s_out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret", "heads_per_block"))
def _kda_decode_call(q, k, v, g, b, pool, layer, slots, fresh,
                     interpret=False, heads_per_block=HEADS_PER_BLOCK):
    R, H, dk = q.shape
    dv = v.shape[-1]
    n_slots = pool.shape[1]
    Hb = min(heads_per_block, H)
    if H % Hb:
        raise ValueError(f"{H} heads are no whole number of blocks of {Hb}")
    live = (slots >= 0) & (slots < n_slots)
    starts = _starts(live, H // Hb)

    def row_block(w, layer, slots, fresh, starts):
        row, block = W.find_item(starts, w, R)
        return row, block, 0

    def slot_block(w, layer, slots, fresh, starts):
        row, block = W.find_item(starts, w, R)
        slot = lax.clamp(_I32(0), slots[row], _I32(n_slots - 1))
        return layer[0], slot, block, 0, 0

    heads = pl.BlockSpec((1, Hb, dk), row_block)
    state = pl.BlockSpec((1, 1, Hb, dk, dv), slot_block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(starts[R],),
        in_specs=[heads, heads, heads, pl.BlockSpec((1, Hb, dv), row_block),
                  pl.BlockSpec((1, Hb, 1), row_block), state],
        out_specs=[state, pl.BlockSpec((1, Hb, dv), row_block)],
    )
    f32 = jnp.float32
    pool, o = pl.pallas_call(
        functools.partial(_kda_decode_kernel, n_slots=n_slots),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((R, H, dv), f32)],
        # operand 9 (after the four prefetched scalars): the pool
        input_output_aliases={9: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      fresh.astype(jnp.int32), starts,
      q.astype(f32), k.astype(f32), g.astype(f32), v.astype(f32),
      b.astype(f32)[..., None], pool)
    # A row of padding has no item: its lines of ``o`` were never written.
    return jnp.where(live[:, None, None], o, 0.0), pool


def kda_decode_pallas(q, k, v, g, b, pool, layer, slots, fresh,
                      interpret: bool = False,
                      heads_per_block: int = HEADS_PER_BLOCK):
    """``kda.kda_step`` on the pool's own slots. ``q, k, g [R, H, dk]``,
    ``v [R, H, dv]``, ``b [R, H]``; ``pool [layers, slots, H, dk, dv]``
    float32; ``layer`` a scalar, ``slots [R]`` (out of range: a row of
    padding, whose ``g`` and ``b`` are zero), ``fresh [R]`` bool. Returns
    (``o [R, H, dv]`` float32, zeros in a padding row's lines; the pool
    with the live rows' slots of ``layer`` advanced)."""
    return _kda_decode_call(q, k, v, g, b, pool, layer, slots, fresh,
                            interpret=interpret,
                            heads_per_block=heads_per_block)
