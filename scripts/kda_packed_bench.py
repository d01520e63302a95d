"""One delta-rule layer's packed step alone, on the chip, at the cells' shapes.

Run from the root of a checkout (it imports that checkout's ``rbg_tpu``)
through the chip tool, once in the parent's tree and once in the change's,
in one call:

    python scripts/kda_packed_bench.py --out chiprun_out/kda_packed/change.json
    (cd <parent> && python <root>/scripts/kda_packed_bench.py --out ...)

A case is ``models/llama.py::_kda_attention`` of one layer as a unified step
program calls it (``kimi-linear.longgen16``: 16 rows, 32 heads, d 2304;
``solar-open2.longgen32``: 32 rows, 64 heads, d 4096, ``b`` up to 2), over
a step packed as ``Engine._pack_unified`` packs it: ``--chunk-rows`` rows of
a whole chunk of 64 tokens and a token for every other row (one chunk row in
a bucket of 128 tokens, two in 256, every row at the ramp, ``rows x 64``).
The pool rides a loop of ``LAYERS`` layers as it does in a step program, so
a copy of it would show. A profile of 20 calls gives the device time a
layer, and its parts: the projections (scope ``proj``), the decode kernel,
everything else (the convolution and the recurrence). It fails without a
TPU: nothing here is a CPU timing.
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
from harness import serve, trace_reduce
from rbg_tpu.models import llama

CHUNK, LAYERS, TRACED_CALLS = 64, 3, 20
# cell: (configuration file, rows)
CELLS = {"kimi": ("kimi-linear-48b-a3b.json", 16),
         "solar": ("solar-open2-250b.json", 32)}


def _config(name):
    path = os.path.join(os.getcwd(), "benchmark", "configs", CELLS[name][0])
    with open(path) as f:
        return serve.model_config(json.load(f), f"{name}-layer")


def _step(R, chunk_rows, rng):
    """A unified step's address arrays: ``chunk_rows`` rows of ``CHUNK``
    tokens spread among rows of one, each row on from where it stood."""
    lens = np.ones(R, np.int64)
    lens[rng.permutation(R)[:chunk_rows]] = CHUNK
    T = 8
    while T < lens.sum():
        T *= 2
    pos = np.full((1, T), -1, np.int32)
    mask = np.zeros((1, T), bool)
    row_ids = np.zeros(T, np.int32)
    off = 0
    for r, n in enumerate(lens):
        pos[0, off:off + n] = int(rng.integers(64, 2048)) + np.arange(n)
        mask[0, off:off + n] = True
        row_ids[off:off + n] = r
        off += n
    return T, (jnp.asarray(pos), jnp.asarray(mask), jnp.zeros(R, jnp.int32),
               jnp.zeros((R, 1), jnp.int32), jnp.asarray(row_ids),
               jnp.asarray(rng.permutation(R).astype(np.int32)))


def case(name, chunk_rows, seed):
    """(the jitted layers, their arguments) of one case."""
    cfg, R = _config(name), CELLS[name][1]
    T, (pos, mask, kv_lens, table, row_ids, slots) = _step(
        R, chunk_rows, np.random.default_rng(seed))
    dt = cfg.jax_dtype
    nrm = lambda k, shape, scale: (
        jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)
    key = jax.random.key(seed % (1 << 31))
    blks = llama._init_kda(cfg, key, LAYERS, nrm, 0.02, 0.02)
    blks["attn_norm"] = jnp.ones((LAYERS, cfg.hidden_size), dt)
    h, dk = cfg.kda_num_heads, cfg.kda_head_dim
    state = {"s": jax.random.normal(key, (LAYERS, R, h, dk, dk), jnp.float32),
             "conv": jnp.zeros((LAYERS, R, (cfg.kda_conv_kernel - 1) * 3 * h
                                * dk), dt)}
    x = nrm(jax.random.fold_in(key, 1), (1, T, cfg.hidden_size), 1.0)

    def layers(blks, x, state, pos, mask, kv_lens, table, row_ids, slots):
        addr = llama.PoolAddr(pos, mask, kv_lens, table, row_ids, CHUNK, slots)

        def layer(i, carry):
            acc, state = carry
            with jax.named_scope("attention"), jax.named_scope("kda"):
                o, state = llama._kda_attention(
                    cfg, {k: v[i] for k, v in blks.items()}, x, state, i,
                    addr, "always")
            return acc + o.astype(jnp.float32), state

        return jax.lax.fori_loop(
            0, LAYERS, layer, (jnp.zeros((1, T, h, dk), jnp.float32), state))

    fn = jax.jit(layers, donate_argnums=(2,))
    return fn, (blks, x, state, pos, mask, kv_lens, table, row_ids, slots), T


def _device_us_a_layer(fn, args):
    """{"all" | "proj" | "kernel": device us a layer} from a profile."""
    blks, x, state, *addr = args
    _, state = jax.block_until_ready(fn(blks, x, state, *addr))

    def work(state=state):          # the state rides from call to call
        for _ in range(TRACED_CALLS):
            out, state = fn(blks, x, state, *addr)
        return out

    devices = device_profile.device_events(work)
    per, ops = {"all": 0.0, "proj": 0.0, "kernel": 0.0}, {}
    a_layer = 1e6 / (TRACED_CALLS * LAYERS)           # seconds -> us a layer
    for events in devices.values():
        # self time: a ``while`` holds its body's operations
        for op, s in trace_reduce.self_times(events).items():
            ops[op] = ops.get(op, 0.0) + s * a_layer
            per["all"] += s * a_layer
            if "_kda_decode_call" in op:
                per["kernel"] += s * a_layer
        for scope, s in trace_reduce.self_times(events, key=3).items():
            if scope and "/proj" in scope:
                per["proj"] += s * a_layer
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:12]
    return per, top


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"no TPU here ({device.platform}): nothing to time")
    result = {"device": device.device_kind, "seed": args.seed, "cases": {}}
    for name, (_, R) in CELLS.items():
        for chunk_rows in (0, 1, 2, R):
            fn, fargs, T = case(name, chunk_rows, args.seed)
            per, top = _device_us_a_layer(fn, fargs)
            tag = f"{name}.R{R}.T{T}.chunks{chunk_rows}"
            result["cases"][tag] = {
                "layer_us": round(per["all"], 1),
                "proj_us": round(per["proj"], 1),
                "kernel_us": round(per["kernel"], 1),
                "recurrence_us": round(per["all"] - per["proj"], 1),
                "top": [[op[:60], round(us, 1)] for op, us in top]}
            print(tag, json.dumps({k: v for k, v in result["cases"][tag].items()
                                   if k != "top"}), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
