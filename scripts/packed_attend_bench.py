"""One attention layer of a packed (unified) step alone, on the chip, at the
benchmark cells' shapes.

Run from the root of a checkout (it imports that checkout's ``rbg_tpu``)
through the chip tool, once in the parent's tree and once in the change's,
in one call:

    python scripts/packed_attend_bench.py --out chiprun_out/packed/change.json
    cd <parent> && python <root>/scripts/packed_attend_bench.py --out ...

A layer kind is ``<configuration>:<mixer>``, a file of ``benchmark/configs``
by its stem and ``full`` or ``window``; by default every configuration
there with each kind of attention layer it has. Each is ``models/llama.py::
_pool_attention`` as a unified step program calls it (projections, the
write of the step's slots, the attend, the gate), the group config and the
server's sizes (rows, chunk, page, table width) read from the file, one
layer's weights random, the pool that of two layers and donated from call
to call. Three packs, drawn from ``--seed``: the server's rows of one
token at 128-2048 cached tokens beside ONE row that holds a chunk; every
row a chunk (the ramp at a window's start); every row one token. A profile of 30 calls gives the device time a
call of each kernel (``_block_ragged*_call``, ``_*decode_call``) and of the
XLA operations beside them. It fails without a TPU: nothing here is a CPU
timing.
"""

import argparse
import dataclasses
import json
import os
import sys

sys.path[:0] = [os.getcwd(), os.path.join(os.getcwd(), "benchmark")]

import jax
import jax.numpy as jnp
import numpy as np

import device_profile
from harness import serve
from rbg_tpu.models import init_params, llama

LAYERS, LAYER = 2, 1
MIXERS = ("full", "window")     # the kinds of layer that attend over pages
PACKS = ("one_chunk_row", "every_row_a_chunk", "no_chunk_row")
TRACED_CALLS = 30


def _config(kind):
    """(the program's preset of the kind's file, its ``server`` sizes)."""
    with open(os.path.join("benchmark", "configs",
                           kind.split(":")[0] + ".json")) as f:
        file = json.load(f)
    return serve.model_config(file, kind), file["server"]


def _kinds():
    """Every configuration's kinds of attention layer."""
    for path in sorted(os.listdir(os.path.join("benchmark", "configs"))):
        stem = path[:-len(".json")]
        cfg, _ = _config(stem)
        has = ({h[0].attention for h in cfg.layer_halves} if cfg.by_kind
               else {cfg.attention})
        yield from (f"{stem}:{m}" for m in MIXERS if m in has)


def _layer(kind, key):
    """(the kind's group config, one layer's mixer weights, random)."""
    cfg, _ = _config(kind)
    mixer = kind.partition(":")[2] or "full"
    if not cfg.by_kind:     # one kind of mixer: a dense layer's holds it
        cfg = dataclasses.replace(cfg, num_layers=1, num_experts=0,
                                  first_dense_layers=0)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    if cfg.by_kind:
        g, group, *_ = next(h for h in cfg.layer_halves
                            if h[0].attention == mixer)
    else:
        group, g, *_ = cfg.layer_groups[0]
    leaves = shapes[group]
    keys = jax.random.split(key, len(leaves))
    return g, {name: (jax.random.normal(k, a.shape[1:], jnp.float32)
                      * 0.02).astype(a.dtype)
               for k, (name, a) in zip(keys, sorted(leaves.items()))}


def _pools(g, rows, server, key):
    """The flat pools of ``LAYERS`` layers, as ``_pool_attention`` takes
    them: (k, v, None, None), and the pages a layer."""
    from rbg_tpu.engine.kvcache import heads_per_lane_tile, rope_pool_width
    PAGE = server["page_size"]
    pages = rows * (2048 + server["prefill_chunk"]) // PAGE + 1
    if g.mla:
        tails = (1, g.kv_lora_rank), (1, rope_pool_width(g))
    else:
        p = heads_per_lane_tile(g)
        tails = ((g.num_kv_heads // p, p * g.head_dim_),) * 2
    k, v = (jax.random.normal(a, (LAYERS * pages, PAGE) + tail, g.jax_dtype)
            for a, tail in zip(jax.random.split(key), tails))
    return (k, v, None, None), pages


def _pack(pack, rows, CHUNK, rng):
    """A unified step as ``Engine._pack_unified`` packs it: (positions
    [1, T], token mask, row ids, kv_lens), padded to the token bucket."""
    n = np.ones(rows, np.int64)
    if pack != "no_chunk_row":
        n[rng.permutation(rows)[:1 if pack == "one_chunk_row" else rows]] = CHUNK
    lens = np.where(n == 1, rng.integers(128, 2049, rows),
                    CHUNK * rng.integers(1, 9, rows)).astype(np.int32)
    T = 8
    while T < n.sum():
        T *= 2
    pos, mask = np.full((1, T), -1, np.int32), np.zeros((1, T), bool)
    ids, at = np.zeros(T, np.int32), 0
    for r in range(rows):
        pos[0, at:at + n[r]] = np.arange(lens[r] - n[r], lens[r])
        mask[0, at:at + n[r]] = True
        ids[at:at + n[r]] = r
        at += n[r]
    return pos, mask, ids, lens


def _table(g, pages, server, pos, ids, lens, rng):
    """The rows' lines of layer ``LAYER``'s pages; a window layer's row
    holds the pages from its first query's oldest slot on, what lies below
    was given back (the null page, entry 0 of the layer)."""
    rows, PAGE = lens.shape[0], server["page_size"]
    table = np.full((rows, server["max_seq_len"] // PAGE), LAYER * pages,
                    np.int32)
    free, at = rng.permutation(pages - 1) + 1 + LAYER * pages, 0
    for r in range(rows):
        lo = 0
        if g.attention == "window":
            first_query = pos[0][(ids == r) & (pos[0] >= 0)].min()
            lo = max(first_query - g.sliding_window + 1, 0) // PAGE
        hi = -(-int(lens[r]) // PAGE)
        table[r, lo:hi] = free[at:at + hi - lo]
        at += hi - lo
    return table


def _device_us_a_call(step, x, pool):
    """{operation: device us a call} from a profile of TRACED_CALLS; the
    pool rides from call to call."""
    state = list(jax.block_until_ready(step(x, pool)))

    def work():
        for _ in range(TRACED_CALLS):
            state[:] = step(x, state[1])
        return state[0]

    per = device_profile.us_a_call(device_profile.device_events(work),
                                   TRACED_CALLS)
    return per, np.asarray(state[0], np.float32), state[1]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kinds", default="",
                    help="<configuration>[:<mixer>],... (none: every one)")
    ap.add_argument("--rows", type=int, default=0,
                    help="rows of a step (0: the server's max_batch)")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"no TPU here ({device.platform}): nothing to time")
    result = {"device": device.device_kind, "seed": args.seed,
              "rows": args.rows, "kinds": {}}
    for i, kind in enumerate(args.kinds.split(",") if args.kinds
                             else _kinds()):
        server = _config(kind)[1]
        rows = args.rows or server["max_batch"]
        keys = jax.random.split(jax.random.key((args.seed + i) % (1 << 31)), 3)
        g, blk = _layer(kind, keys[0])
        pool, pages = _pools(g, rows, server, keys[1])
        result["kinds"][kind] = {}
        for pack in PACKS:
            rng = np.random.default_rng(args.seed + i)
            pos, mask, ids, lens = _pack(pack, rows, server["prefill_chunk"],
                                         rng)
            table = jnp.asarray(_table(g, pages, server, pos, ids, lens, rng))
            addr = llama.PoolAddr(jnp.asarray(pos), jnp.asarray(mask),
                                  jnp.asarray(lens), table, jnp.asarray(ids),
                                  server["prefill_chunk"])
            x = (jax.random.normal(keys[2], (1, pos.shape[1], g.hidden_size),
                                   jnp.float32)).astype(g.jax_dtype)

            def step(x, pool, addr=addr, table=table):
                return llama._pool_attention(g, blk, x, pool, table, addr,
                                             "auto")

            per, out, pool = _device_us_a_call(
                jax.jit(step, donate_argnums=(1,)), x, pool)
            kernels = {op: round(us, 2) for op, us in per.items()
                       if "_call" in op}
            beside = sum(us for op, us in per.items() if "_call" not in op)
            result["kinds"][kind][pack] = {
                "tokens": int(pos.shape[1]), **kernels,
                "beside_us_a_call": round(beside, 2),
                "operations_beside": len(per) - len(kernels),
                "us_a_call": round(sum(per.values()), 2),
                "out_abs_mean": float(np.abs(out[0, mask[0]]).mean())}
            print(kind, pack, json.dumps(result["kinds"][kind][pack]),
                  flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
