"""The decode kernels alone, on the chip, at the benchmark cells' shapes.

Run from the root of a checkout (it imports that checkout's ``rbg_tpu``)
through the chip tool, once in the parent's tree and once in the
change's, in one call:

    python scripts/decode_walk_bench.py --out chiprun_out/walk/change.json
    python scripts/decode_walk_bench.py --slots 256 --out ...   # a sweep

Each family is one jitted call as a step program makes it (``_decode_call``
at ``lfm2.longgen32``'s, ``mixtral.longgen``'s, ``solar-open2.longgen32``'s
and ``ouro.longgen4``'s shapes and at those of ``laguna-xs2.longgen32``'s
two kinds of layer, ``_mla_decode_call`` at ``joyai.longgen16``'s and
``kimi-linear.longgen16``'s) over rows of 128-2048 cached tokens (Ouro's
128-1280) drawn from ``--seed``; ``path`` says who copied the pages
(``page_walk.kernel_copies``, asked about the pools as the kernel receives
them; ``--cells`` picks families). A profile of 50
calls gives the device time of the kernel and of the XLA operations beside
it (the walk's block counts and, since PR 40, its item table) a call, and
from the rows' live blocks the time a block. It fails without a TPU:
nothing here is a CPU timing.
"""

import argparse
import json
import os
import sys
from typing import NamedTuple, Optional

sys.path[:0] = [os.getcwd(), os.path.join(os.getcwd(), "benchmark")]

import jax
import jax.numpy as jnp
import numpy as np

import device_profile
from rbg_tpu.ops.pallas import page_walk as W
from rbg_tpu.ops.pallas import paged_attention_kernel as K

BF16 = jnp.bfloat16
PAGE = 16


class Cell(NamedTuple):
    rows: int
    width: int                  # of the page table
    pages: int                  # of one layer of the flat pool
    layers: int                 # of the flat pool (a few: addresses only)
    layer: int                  # the one walked
    gqa: Optional[tuple] = None     # (KV, G, hd) of a whole-tile GQA pool
    window: Optional[int] = None    # a window layer's width
    latent: Optional[int] = None    # heads over the latent pools


CELLS = {"lfm2": Cell(32, 256, 8192, 10, 3),
         "joyai": Cell(16, 256, 8192, 5, 2, latent=32),
         "mixtral": Cell(8, 512, 8192, 3, 1, (8, 4, 128)),
         "laguna-window": Cell(32, 256, 8192, 4, 2, (8, 8, 128), 512),
         "laguna-full": Cell(32, 256, 8192, 5, 2, (8, 6, 128)),
         "solar": Cell(32, 256, 8192, 2, 1, (8, 8, 128)),
         "ouro": Cell(4, 80, 320, 24, 13, (16, 1, 128)),
         # (last: a family's rows are drawn from the seed plus its place)
         "kimi": Cell(16, 256, 4096, 7, 3, latent=32)}
TRACED_CALLS = 50


def _call(name, key):
    """(the call, the pools whose shapes choose its path)."""
    cell = CELLS[name]
    B, keys = cell.rows, jax.random.split(key, 4)
    pool = lambda k, *tail: jax.random.normal(
        k, (cell.layers * cell.pages, PAGE) + tail, BF16)
    if cell.latent:
        ql = jax.random.normal(keys[0], (B, cell.latent, 512), BF16)
        qp = jax.random.normal(keys[1], (B, cell.latent, 64), BF16)
        c, pe = pool(keys[2], 1, 512), pool(keys[3], 1, 128)
        return (lambda t, n: K._mla_decode_call(ql, qp, c, pe, t, n,
                                                scale=576 ** -0.5),
                jax.eval_shape(W.latent_pools, c, pe))
    if name == "lfm2":      # 8 heads of 64, two a lane tile
        q = W.pack_queries(
            jax.random.normal(keys[0], (B, 8, 4, 64), BF16), 2)
        k, v = pool(keys[1], 4, 128), pool(keys[2], 4, 128)
        return lambda t, n: K._decode_call(q, k, v, t, n, head_dim=64), (k, v)
    KV, G, hd = cell.gqa
    q = jax.random.normal(keys[0], (B, KV, G, hd), BF16)
    k, v = pool(keys[1], KV, hd), pool(keys[2], KV, hd)
    return (lambda t, n: K._decode_call(q, k, v, t, n, window=cell.window),
            (k, v))


def _rows(name, rng):
    """The rows' lengths (128-2048, or what the table holds) and their
    lines of the table: distinct pages of the walked layer, dead entries
    naming page 0 of the pool (in a window layer the pages wholly below
    the window too: they were given back)."""
    cell = CELLS[name]
    lens = rng.integers(128, min(cell.width * PAGE, 2048) + 1,
                        size=cell.rows).astype(np.int32)
    table = np.zeros((cell.rows, cell.width), np.int32)
    pages, at = rng.permutation(cell.pages) + cell.layer * cell.pages, 0
    for b, live in enumerate(-(-lens // PAGE)):
        below = 0 if cell.window is None else max(
            lens[b] - cell.window, 0) // PAGE
        table[b, below:live] = pages[at:at + live - below]
        at += live - below
    return jnp.asarray(table), jnp.asarray(lens)


def _live_blocks(name, lens, slots):
    """Blocks of ``slots`` the rows' walks attend."""
    window = CELLS[name].window
    first = 0 if window is None else np.maximum(lens - window, 0) // slots
    return int(np.sum(-(-lens // slots) - first))


def _device_us_a_call(call, args):
    """{operation: device us a call} from a profile of TRACED_CALLS."""
    jax.block_until_ready(call(*args))
    return device_profile.us_a_call(device_profile.device_events(
        lambda: [call(*args) for _ in range(TRACED_CALLS)]), TRACED_CALLS)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slots", type=int, default=0,
                    help="a block's slots, for a sweep (0: the tree's own)")
    ap.add_argument("--cells", default=",".join(CELLS),
                    help="families, comma-separated (default: all)")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"no TPU here ({device.platform}): nothing to time")
    # the decode kernels' own constant, or a tree's one for every kernel
    constant = next(n for n in ("_DECODE_BLOCK_SLOTS", "_BLOCK_SLOTS")
                    if hasattr(W, n))
    if args.slots:
        setattr(W, constant, args.slots)
    slots = getattr(W, constant)
    result = {"device": device.device_kind, "slots": slots, "seed": args.seed,
              "cells": {}}
    for i, name in enumerate(CELLS):
        if name not in args.cells.split(","):
            continue
        rows = _rows(name, np.random.default_rng(args.seed + i))
        call, pools = _call(name, jax.random.key((args.seed + i) % (1 << 31)))
        per = _device_us_a_call(call, rows)
        kernel = sum(us for op, us in per.items() if "decode_call" in op)
        beside = sum(us for op, us in per.items() if "decode_call" not in op)
        blocks = _live_blocks(name, np.asarray(rows[1]), slots)
        copies = getattr(W, "kernel_copies", lambda pools: False)(pools)
        result["cells"][name] = {
            "path": "kernel copies" if copies else "pipeline",
            "blocks": blocks, "kernel_us_a_call": round(kernel, 2),
            "beside_us_a_call": round(beside, 2),
            "operations_beside": len(per) - 1,
            "kernel_us_a_block": round(kernel / blocks, 4)}
        print(name, json.dumps(result["cells"][name]), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
