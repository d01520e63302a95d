"""The decode kernels alone, on the chip, at the benchmark cells' shapes.

Run from the root of a checkout (it imports that checkout's ``rbg_tpu``)
through the chip tool, once in the parent's tree and once in the
change's, in one call:

    python scripts/decode_walk_bench.py --out chiprun_out/walk/change.json
    python scripts/decode_walk_bench.py --slots 256 --out ...   # a sweep

Each family is one jitted call as a step program makes it (``_decode_call``
at ``lfm2.longgen32``'s and ``mixtral.longgen``'s shapes, ``_mla_decode_call``
at ``joyai.longgen16``'s) over rows of 128-2048 cached tokens drawn from
``--seed``. A profile of 50 calls gives the device time of the kernel and
of the XLA operations beside it (the walk's block counts and, since
PR 40, its item table) a call, and from the rows' live blocks the time a
block. It fails without a TPU: nothing here is a CPU timing.
"""

import argparse
import json
import os
import sys

sys.path[:0] = [os.getcwd(), os.path.join(os.getcwd(), "benchmark")]

import jax
import jax.numpy as jnp
import numpy as np

import device_profile
from rbg_tpu.ops.pallas import page_walk as W
from rbg_tpu.ops.pallas import paged_attention_kernel as K

BF16 = jnp.bfloat16
PAGE, NP = 16, 8192
# rows, table width, layers of the flat pool, the layer walked
CELLS = {"lfm2": (32, 256, 10, 3), "joyai": (16, 256, 5, 2),
         "mixtral": (8, 512, 3, 1)}
TRACED_CALLS = 50


def _call(name, key):
    B, _, L, _ = CELLS[name]
    keys = jax.random.split(key, 4)
    pool = lambda k, *tail: jax.random.normal(k, (L * NP, PAGE) + tail, BF16)
    if name == "joyai":
        ql = jax.random.normal(keys[0], (B, 32, 512), BF16)
        qp = jax.random.normal(keys[1], (B, 32, 64), BF16)
        c, pe = pool(keys[2], 1, 512), pool(keys[3], 1, 128)
        return lambda t, n: K._mla_decode_call(ql, qp, c, pe, t, n,
                                               scale=576 ** -0.5)
    if name == "lfm2":      # 8 heads of 64, two a lane tile
        q = W.pack_queries(
            jax.random.normal(keys[0], (B, 8, 4, 64), BF16), 2)
        k, v = pool(keys[1], 4, 128), pool(keys[2], 4, 128)
        return lambda t, n: K._decode_call(q, k, v, t, n, head_dim=64)
    q = jax.random.normal(keys[0], (B, 8, 4, 128), BF16)
    k, v = pool(keys[1], 8, 128), pool(keys[2], 8, 128)
    return lambda t, n: K._decode_call(q, k, v, t, n)


def _rows(name, rng):
    """The rows' lengths and their lines of the table: distinct pages of
    the walked layer, dead entries naming page 0 of the pool."""
    B, P, _, layer = CELLS[name]
    lens = rng.integers(128, 2049, size=B).astype(np.int32)
    table = np.zeros((B, P), np.int32)
    pages, at = rng.permutation(NP) + layer * NP, 0
    for b, live in enumerate(-(-lens // PAGE)):
        table[b, :live] = pages[at:at + live]
        at += live
    return jnp.asarray(table), jnp.asarray(lens)


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
        rows = _rows(name, np.random.default_rng(args.seed + i))
        per = _device_us_a_call(
            _call(name, jax.random.key((args.seed + i) % (1 << 31))), rows)
        kernel = sum(us for op, us in per.items() if "decode_call" in op)
        beside = sum(us for op, us in per.items() if "decode_call" not in op)
        blocks = int(np.sum(-(-np.asarray(rows[1]) // slots)))
        result["cells"][name] = {
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
