"""Pipeline parallelism: GPipe-style microbatching over a ``pp`` mesh axis.

The block stack's LAYER axis shards across pipeline stages (each device owns
``L / pp`` consecutive layers); microbatches flow stage→stage over ICI via
``ppermute``. SPMD-friendly formulation: every stage runs the same traced
program each step — "which microbatch am I working on" is data (masked
selects), never control flow, so one compilation serves the whole schedule.

Schedule: plain GPipe fill-drain — step t has stage s processing microbatch
``t - s``; total ``M + S - 1`` steps for M microbatches over S stages.
Bubble fraction = (S-1)/(M+S-1); callers pick M ≥ 2S to amortize.

Differentiable (the schedule is a ``lax.scan``), so the training step uses
this whenever the mesh's ``pp`` axis is > 1. Embedding and the LM head stay
outside (replicated — they're cheap relative to the stack).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def _stage_shard(blocks_local, x_micro, mask_micro, *, cfg, axis):
    """Per-stage body under shard_map.

    blocks_local: block params with the local layer slice [L/S, ...]
    x_micro: [M, Bm, T, D] microbatched embeddings (replicated)
    mask_micro: [M, Bm, T] bool token masks
    Returns final hidden [M, Bm, T, D], replicated via psum (only the last
    stage's contribution is nonzero).
    """
    from rbg_tpu.models.llama import _block

    S = lax.psum(1, axis)
    stage = lax.axis_index(axis)
    M, Bm, T, D = x_micro.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (Bm, T))

    def run_local(h, mask):
        def step(carry, blk):
            out, _, _ = _block(cfg, carry, blk, None, None, positions, mask)
            return out, None
        h, _ = lax.scan(step, h, blocks_local)
        return h

    # No-wraparound shift down the pipe; stage 0 receives zeros (ignored).
    perm = [(i, i + 1) for i in range(S - 1)]

    out0 = jnp.zeros_like(x_micro)
    buf0 = jnp.zeros((Bm, T, D), x_micro.dtype)

    def pipe_step(carry, t):
        buf, out = carry
        # Stage s works on microbatch t - s this step.
        mb = jnp.clip(t - stage, 0, M - 1)
        inp = jnp.where(stage == 0, x_micro[mb], buf)
        h = run_local(inp, mask_micro[mb])
        # Last stage finished microbatch t-(S-1) — record it when valid.
        out_idx = t - (S - 1)
        valid = jnp.logical_and(stage == S - 1,
                                jnp.logical_and(out_idx >= 0, out_idx < M))
        idx = jnp.clip(out_idx, 0, M - 1)
        val = jnp.where(valid, h, out[idx])
        out = lax.dynamic_update_index_in_dim(out, val, idx, axis=0)
        buf = lax.ppermute(h, axis, perm)
        return (buf, out), None

    (_, out), _ = lax.scan(pipe_step, (buf0, out0),
                           jnp.arange(M + S - 1, dtype=jnp.int32))
    # Only the last stage holds real outputs; replicate via psum.
    return lax.psum(out, axis)


def pipeline_blocks(params_blocks, cfg, x, token_mask, mesh: Mesh,
                    num_microbatches: int, axis: str = "pp"):
    """Run the transformer block stack through the pipeline.

    x: [B, T, D] embeddings; token_mask: [B, T]. Returns [B, T, D] final
    hidden (replicated over ``axis``). B must divide by num_microbatches;
    L by the pp size.
    """
    B, T, D = x.shape
    M = num_microbatches
    if B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    S = mesh.shape[axis]
    L = jax.tree_util.tree_leaves(params_blocks)[0].shape[0]
    if L % S:
        raise ValueError(f"layers {L} not divisible by pp={S}")

    x_micro = x.reshape(M, B // M, T, D)
    mask_micro = token_mask.reshape(M, B // M, T)
    body = functools.partial(_stage_shard, cfg=cfg, axis=axis)
    blocks_spec = jax.tree_util.tree_map(
        lambda leaf: P(axis, *([None] * (leaf.ndim - 1))), params_blocks)
    # Replication checking is off: the stage hand-off confuses it.
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(blocks_spec, P(), P()),
        out_specs=P(), check_vma=False,
    )
    out = fn(params_blocks, x_micro, mask_micro)
    return out.reshape(B, T, D)


def pipeline_forward_train(params, cfg, tokens, token_mask=None, *, mesh: Mesh,
                           num_microbatches: int = 0, axis: str = "pp"):
    """forward_train equivalent with the block stack pipelined over ``axis``."""
    from rbg_tpu.models.llama import _head

    if len(cfg.layer_groups) > 1:
        raise NotImplementedError(
            f"{cfg.name}: the pipeline stages one stack of one kind of "
            f"layer; this model has {len(cfg.layer_groups)} groups")
    if cfg.loop_steps > 1:
        raise NotImplementedError(
            f"{cfg.name} {cfg.looped_for}: the pipeline stages one stack "
            f"that a microbatch passes once")
    B, T = tokens.shape
    if token_mask is None:
        token_mask = jnp.ones((B, T), bool)
    if not num_microbatches:
        num_microbatches = min(B, max(2 * mesh.shape[axis], 1))
        while B % num_microbatches:
            num_microbatches -= 1

    x = params["embed"].astype(cfg.jax_dtype)[tokens]
    h = pipeline_blocks(params["blocks"], cfg, x, token_mask, mesh,
                        num_microbatches, axis=axis)
    return _head(params, cfg, h)
