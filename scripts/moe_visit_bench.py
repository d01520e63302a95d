"""One layer's visits to its hit experts alone, on the chip, at the cells' shapes.

Run from the root of a checkout (it imports that checkout's ``rbg_tpu``)
through the chip tool:

    python scripts/moe_visit_bench.py --out chiprun_out/moe_visit/change.json
    (cd <parent> && python <root>/scripts/moe_visit_bench.py --out ...)

A case is ``models/llama.py::_moe_mlp_hit`` of one layer as a fused decode
program calls it, inside a scan of ``LAYERS`` layers over stacked expert
weights ``[L, E, D, F]`` at a cell's published widths, with the cell's rows
and the visits a layer its ledger counts. The router is stood in for by
fixed combine weights (each layer's own), so a case visits exactly that many
experts and times no router. Forms: the XLA loop (``use_pallas="never"``,
the parent's only form), the kernel at the tile ``tile_f`` picks and at the
other tiles that fit, and two plain-XLA forms kept here for the comparison
alone: two experts a trip, and gate and up as one dot over stacks held side
by side. A profile of ``TRACED_CALLS`` calls gives the device us a layer;
a visit's share of its roofline is ``3 D F x 2 B / 819 GB/s`` over the us
a visit. It fails without a TPU: nothing here is a CPU timing.
"""

import argparse
import dataclasses
import functools
import inspect
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

LAYERS, TRACED_CALLS, HBM_BYTES_S = 3, 20, 819e9
# cell: (configuration file, rows, visits a layer as the ledger counts them)
CELLS = {"mixtral": ("mixtral-8x7b-v0.1.json", 8, 7),
         "joyai": ("joyai-llm-flash.json", 16, 87),
         "kimi": ("kimi-linear-48b-a3b.json", 16, 4),
         "lfm2": ("lfm2-24b-a2b.json", 32, 6),
         "solar": ("solar-open2-250b.json", 32, 6)}
HAS_KERNEL = "use_pallas" in inspect.signature(llama._moe_mlp_hit).parameters


def _config(name):
    path = os.path.join(os.getcwd(), "benchmark", "configs", CELLS[name][0])
    with open(path) as f:
        cfg = serve.model_config(json.load(f), f"{name}-visits")
    return dataclasses.replace(cfg, moe_shared_expert=False)


def _weights(cfg, rows, visits, rng):
    """``[LAYERS, rows, 1, E published]`` combine weights in which exactly
    ``visits`` held experts a layer have a row, ``experts_per_token`` (or
    fewer) a row, as a router's top-k leaves them."""
    lo, hi = cfg.experts_held or (0, cfg.num_experts)
    w = np.zeros((LAYERS, rows, 1, cfg.num_experts), np.float32)
    for layer in range(LAYERS):
        hit = lo + rng.permutation(hi - lo)[:visits]
        for i, e in enumerate(hit):                 # every one has a row
            w[layer, i % rows, 0, e] = rng.uniform(0.1, 1.0)
        for r in range(rows):                       # and the rows fill up
            free = cfg.experts_per_token - np.count_nonzero(w[layer, r])
            for e in rng.permutation(hit)[:max(free, 0)]:
                w[layer, r, 0, e] = w[layer, r, 0, e] or rng.uniform(0.1, 1.0)
    return w


def _pair_loop(x, w, stacks, layer, ids, visited):
    """Plain XLA, two experts a trip (an odd last one beside a weight of
    zero): half the trips, six dots a body for the scheduler to overlap."""
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)

    def one(e, on):
        h = jax.nn.silu(dot(x, stacks["moe_gate"][layer, e])) * dot(
            x, stacks["moe_up"][layer, e])
        y = dot(h.astype(x.dtype), stacks["moe_down"][layer, e])
        return jnp.where(on, jax.lax.dynamic_slice_in_dim(w, e, 1, 1), 0) * y

    def trip(i, acc):
        second = jnp.minimum(2 * i + 1, visited - 1)
        return (acc + one(ids[2 * i], True)
                + one(ids[second], 2 * i + 1 < visited))

    return jax.lax.fori_loop(0, (visited + 1) // 2, trip,
                             jnp.zeros(x.shape, jnp.float32))


def _side_by_side_loop(x, w, stacks, layer, ids, visited):
    """Plain XLA, gate and up as one dot over ``[L, E, D, 2 F]``."""
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
    F = stacks["moe_down"].shape[2]

    def visit(i, acc):
        e = ids[i]
        gu = dot(x, stacks["moe_gate_up"][layer, e])
        h = jax.nn.silu(gu[:, :F]) * gu[:, F:]
        y = dot(h.astype(x.dtype), stacks["moe_down"][layer, e])
        return acc + jax.lax.dynamic_slice_in_dim(w, e, 1, axis=1) * y

    return jax.lax.fori_loop(0, visited, visit,
                             jnp.zeros(x.shape, jnp.float32))


def _forms(D, F, itemsize):
    """{form: (policy, what stands in for the loop or the kernel)}."""
    forms = {"loop": ("never", None)}
    if not HAS_KERNEL:
        return forms
    from rbg_tpu.ops.pallas import moe_visit_kernel as MV
    forms["loop.pairs"] = ("never", _pair_loop)
    forms["loop.side_by_side"] = ("never", _side_by_side_loop)
    chosen = MV.tile_f(D, F, itemsize)
    forms[f"kernel.tile{chosen}"] = ("always", None)
    fits = [t for t in range(F, 0, -128)
            if F % t == 0 and t != chosen and 6 * D * t * itemsize <= 80 << 20]
    for t in fits[:3]:
        forms[f"kernel.tile{t}"] = ("always", functools.partial(
            MV.moe_visit_pallas, tile=t))
    return forms


def case(name, form, seed):
    """(the jitted layers, their arguments) of one cell in one form."""
    cfg, (_, rows, visits) = _config(name), CELLS[name]
    E, D, F = cfg.experts_here, cfg.hidden_size, cfg.moe_f
    policy, stand_in = form
    dt = cfg.jax_dtype
    ks = jax.random.split(jax.random.key(seed % (1 << 31)), 4)
    normal = lambda k, shape: jax.random.normal(k, shape, dt) * 0.02
    stacks = {"moe_gate": normal(ks[0], (LAYERS, E, D, F)),
              "moe_up": normal(ks[1], (LAYERS, E, D, F)),
              "moe_down": normal(ks[2], (LAYERS, E, F, D))}
    if stand_in is _side_by_side_loop:
        stacks = {"moe_gate_up": jnp.concatenate(
            [stacks.pop("moe_gate"), stacks.pop("moe_up")], axis=-1),
            "moe_down": stacks["moe_down"]}
    x = jax.random.normal(ks[3], (rows, 1, D), dt)
    w = jnp.asarray(_weights(cfg, rows, visits, np.random.default_rng(seed)))
    live = jnp.ones((rows, 1), bool)

    def layers(x, w, stacks):
        def layer(h, xs):
            li, wl = xs
            with jax.named_scope("moe"):
                out, seen = hit(cfg, {"router": wl}, h, stacks, li, live)
            return h + out, seen
        return jax.lax.scan(layer, x, (jnp.arange(LAYERS, dtype=jnp.int32), w))

    hit = (functools.partial(llama._moe_mlp_hit, use_pallas=policy)
           if HAS_KERNEL else llama._moe_mlp_hit)
    return jax.jit(layers), (x, w, stacks)


def _device_us_a_layer(fn, args):
    """(device us a layer, [(operation, us a layer)] longest first)."""
    jax.block_until_ready(fn(*args))
    devices = device_profile.device_events(
        lambda: [fn(*args) for _ in range(TRACED_CALLS)])
    ops = {}
    a_layer = 1e6 / (TRACED_CALLS * LAYERS)           # seconds -> us a layer
    for events in devices.values():
        # self time: a ``while`` holds its body's operations
        for op, s in trace_reduce.self_times(events).items():
            ops[op] = ops.get(op, 0.0) + s * a_layer
    return sum(ops.values()), sorted(ops.items(), key=lambda kv: -kv[1])[:8]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"no TPU here ({device.platform}): nothing to time")
    result = {"device": device.device_kind, "seed": args.seed, "cases": {}}
    real_route = llama._route
    llama._route = lambda cfg, blk, xm: blk["router"]   # fixed weights
    try:
        for name in args.cells.split(","):
            cfg = _config(name)
            D, F = cfg.hidden_size, cfg.moe_f
            floor_us = 3 * D * F * jnp.dtype(cfg.jax_dtype).itemsize \
                / HBM_BYTES_S * 1e6
            for tag, form in _forms(D, F,
                                    jnp.dtype(cfg.jax_dtype).itemsize).items():
                (policy, stand_in), visits = form, CELLS[name][2]
                fn, fargs = case(name, form, args.seed)
                swap = None
                if stand_in is not None:
                    from rbg_tpu.ops.pallas import moe_visit_kernel as K
                    where = (K, "moe_visit_pallas") if policy == "always" \
                        else (llama, "_visit_loop")
                    swap = (*where, getattr(*where))
                    setattr(*where, stand_in)
                try:
                    us, top = _device_us_a_layer(fn, fargs)
                except Exception as e:  # noqa: BLE001 — a tile Mosaic refuses
                    result["cases"][f"{name}.{tag}"] = {"error": repr(e)[:400]}
                    print(f"{name}.{tag}", "refused:", repr(e)[:400],
                          flush=True)
                    continue
                finally:
                    if swap:
                        setattr(*swap)
                    del fn, fargs
                row = {"rows": CELLS[name][1], "D": D, "F": F,
                       "held": cfg.experts_here, "visits": visits,
                       "layer_us": round(us, 1),
                       "visit_us": round(us / visits, 2),
                       "roofline_share": round(100 * floor_us * visits / us,
                                               1),
                       "top": [[op[:60], round(t, 1)] for op, t in top]}
                result["cases"][f"{name}.{tag}"] = row
                print(f"{name}.{tag}", json.dumps(
                    {k: v for k, v in row.items() if k != "top"}), flush=True)
    finally:
        llama._route = real_route
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
