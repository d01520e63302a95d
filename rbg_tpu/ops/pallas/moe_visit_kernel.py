"""Pallas TPU kernel: a decode step's visits to the hit experts of one
layer, as one walk over the stacked expert weights.

Why a kernel: ``models/llama.py::_moe_mlp_hit`` visits each expert some
live row routed to, and as an XLA loop a visit is three fusions, each of
which slices ``(layer, expert)`` out of a stack and reads one matrix. A
fusion starts, fills and drains on its own, and where a matrix is 3-10 MB
and lasts 4-13 us that is a third of the visit: 62-70 % of HBM's rate in
the cells whose experts are small, 90 % where a matrix is 117 MB
(PERF.md, PRs 29-41). Here the visits are one grid, and Pallas's pipeline
fetches the next step's three tiles while this step's are multiplied: the
stream of weights does not stop between matrices or between experts.

Design (``kda_kernel.py`` and ``page_walk.py`` have the idiom):
* the hit list is made outside, in XLA: ``ids [E]`` ascending (the first
  ``visited`` entries are the experts to visit) and ``visited`` are
  scalar-prefetched beside ``layer``. The grid is (visit, F tile), the
  visit axis of dynamic length; every index map is one read of ``ids``.
* the stacks ``[L, E, D, F]``, ``[L, E, D, F]``, ``[L, E, F, D]`` are
  read in place, a block being one F tile of one expert of one layer.
* a step computes ``silu(x Wg) * (x Wu)`` in float32, rounds it to the
  activations' dtype, multiplies by the tile of ``Wd`` with float32
  accumulation and adds ``w[:, e] * y`` into the ``[rows, D]`` float32
  output, whose block index never moves, so it stays in VMEM and is
  written once. The operands, precisions and rounding point are the XLA
  loop's; only the float32 sums over F tiles are ordered differently.
* ``visited == 0`` keeps one step of the visit axis (a grid of length
  zero is nothing Mosaic promises), whose body is skipped: zeros.
* the F tile follows the shapes: the largest divisor of F, in whole lane
  tiles, whose three double-buffered tiles fit ``TILE_BUDGET``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128

# VMEM for the three matrices' tiles, double-buffered. A v5e core has
# 128 MiB (Mosaic's default scope is 16); ``vmem_limit_bytes`` is this
# plus ``_VMEM_REST`` for the rows, the weights, the output and the
# step's float32 intermediates.
TILE_BUDGET = 48 << 20
_VMEM_REST = 16 << 20


def tile_f(D: int, F: int, itemsize: int, budget: int = TILE_BUDGET) -> int:
    """The F tile: the largest divisor of ``F`` that is a whole number of
    lane tiles and keeps three double-buffered ``[D, tile]`` tiles within
    ``budget``; the smallest such divisor where none fits, and ``F`` whole
    where it has none (a toy width)."""
    smallest = F
    for n in range(F // _LANES, 0, -1):
        t = n * _LANES
        if F % t:
            continue
        if 6 * D * t * itemsize <= budget:
            return t
        smallest = t
    return smallest


def _moe_visit_kernel(
    # scalar prefetch
    layer_ref,        # [1] int32 (SMEM): the layer's ordinal in the stacks
    ids_ref,          # [E] int32: the hit experts, ascending, first
    visited_ref,      # [1] int32: how many of ``ids`` are hit
    # blocks
    x_ref,            # [R, D] the rows' activations
    w_ref,            # [R, E] float32 combine weights (0: not routed)
    gate_ref,         # [D, Ft] one tile of one expert's gate matrix
    up_ref,           # [D, Ft]
    down_ref,         # [Ft, D]
    out_ref,          # [R, D] float32, the same block in every step
):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    # False only in the one step of a call without a hit expert.
    @pl.when(i < visited_ref[0])
    def _visit():
        x = x_ref[...]
        dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
        h = jax.nn.silu(dot(x, gate_ref[...])) * dot(x, up_ref[...])
        y = dot(h.astype(x.dtype), down_ref[...])
        # Column ``e`` of the weights: E runs along the lanes, so the
        # column is picked by a mask (every other term is an exact zero).
        w = w_ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
        col = jnp.sum(jnp.where(lane == ids_ref[i], w, 0.0), axis=1,
                      keepdims=True)
        out_ref[...] += col * y


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def _moe_visit_call(x, w, gate, up, down, layer, ids, visited,
                    interpret=False, tile=None):
    R, D = x.shape
    E, F = gate.shape[1], gate.shape[3]
    Ft = tile or tile_f(D, F, gate.dtype.itemsize)
    if F % Ft:
        raise ValueError(f"an expert's width {F} is no whole number of "
                         f"tiles of {Ft}")

    def rows(i, j, layer, ids, visited):
        return 0, 0

    def in_tile(i, j, layer, ids, visited):
        return layer[0], ids[i], 0, j

    def out_tile(i, j, layer, ids, visited):
        return layer[0], ids[i], j, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(jnp.maximum(visited, 1), F // Ft),
        in_specs=[pl.BlockSpec((R, D), rows), pl.BlockSpec((R, E), rows),
                  pl.BlockSpec((None, None, D, Ft), in_tile),
                  pl.BlockSpec((None, None, D, Ft), in_tile),
                  pl.BlockSpec((None, None, Ft, D), out_tile)],
        out_specs=pl.BlockSpec((R, D), rows),
    )
    return pl.pallas_call(
        _moe_visit_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=6 * D * Ft * gate.dtype.itemsize + _VMEM_REST),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), ids.astype(jnp.int32),
      jnp.reshape(visited, (1,)).astype(jnp.int32),
      x, w.astype(jnp.float32), gate, up, down)


def moe_visit_pallas(x, w, stacks, layer, ids, visited,
                     interpret: bool = False, tile: Optional[int] = None):
    """``sum_e w[:, e] * swiglu_e(x)`` over the first ``visited`` experts
    of ``ids``: ``x [R, D]``, ``w [R, E]`` float32, ``stacks`` the stacked
    expert weights (``moe_gate`` and ``moe_up`` ``[L, E, D, F]``,
    ``moe_down`` ``[L, E, F, D]``), ``layer`` a scalar. Returns ``[R, D]``
    float32. Rows are padded to the activations' sublane tile here."""
    R = x.shape[0]
    pad = -R % (32 // x.dtype.itemsize)
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        w = jnp.pad(w, ((0, pad), (0, 0)))
    out = _moe_visit_call(x, w, stacks["moe_gate"], stacks["moe_up"],
                          stacks["moe_down"], layer, ids, visited,
                          interpret=interpret, tile=tile)
    return out[:R]
