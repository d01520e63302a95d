"""Ring attention: exact attention over sequence shards via ICI neighbor
exchange.

Long-context path (SURVEY.md: "ring attention or all-to-all sequence/context
parallelism for long sequences" is first-class). Each device in the ``sp``
mesh axis holds a sequence shard of Q/K/V; K/V blocks rotate around the ring
with ``ppermute`` while flash-style online-softmax accumulators stay local —
peak memory is O(S/n) per device and the n-step exchange rides ICI,
overlapping with each step's compute (XLA schedules the collective-permute
concurrently with the block matmuls).

Causality is positional: blocks carry their global positions, so the mask is
exact for any layout (contiguous shards here; zig-zag/striped layouts only
change the positions fed in, not the kernel).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


def _ring_attention_shard(q, k, v, q_pos, kv_pos, *, axis: str):
    """Per-shard body (runs under shard_map).

    q: [B, Sq, H, hd]; k/v: [B, Sk, KV, hd]; q_pos: [B, Sq]; kv_pos: [B, Sk].
    Returns [B, Sq, H, hd].
    """
    n = lax.psum(1, axis)
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV

    qf = q.reshape(B, Sq, KV, G, hd).astype(jnp.float32)
    scale = 1.0 / (hd ** 0.5)

    m0 = jnp.full((B, KV, G, Sq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq, 1), jnp.float32)
    acc0 = jnp.zeros((B, KV, G, Sq, hd), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # lax.scan (not fori_loop): reverse-mode AD through the ring requires a
    # scan, so the same kernel serves training (sequence-parallel backprop).
    def body(carry, _):
        m, l, acc, kb, vb, kvp = carry
        kf = kb.astype(jnp.float32)
        vf = vb.astype(jnp.float32)
        scores = jnp.einsum("btkgh,bskh->bkgts", qf, kf) * scale
        causal = kvp[:, None, :] <= q_pos[:, :, None]          # [B, Sq, Sk]
        scores = jnp.where(causal[:, None, None, :, :], scores, _NEG_INF)

        m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        probs = jnp.exp(scores - m_new)
        l = l * alpha + probs.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bkgts,bskh->bkgth", probs, vf)

        # Rotate K/V (and their positions) one hop around the ring.
        kb = lax.ppermute(kb, axis, perm)
        vb = lax.ppermute(vb, axis, perm)
        kvp = lax.ppermute(kvp, axis, perm)
        return (m_new, l, acc, kb, vb, kvp), None

    (m, l, acc, *_), _ = lax.scan(body, (m0, l0, acc0, k, v, kv_pos), None,
                                  length=n)
    out = acc / jnp.maximum(l, 1e-30)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd).astype(q.dtype)


def ring_attention(q, k, v, q_positions, kv_positions, mesh: Mesh,
                   axis: str = "sp"):
    """Causal GQA with Q/K/V sharded over ``axis`` on the sequence dim.

    q: [B, S, H, hd]; k/v: [B, S, KV, hd]; positions: [B, S] global.
    """
    body = functools.partial(_ring_attention_shard, axis=axis)
    spec_qkv = P(None, axis, None, None)
    spec_pos = P(None, axis)
    # Replication checking is off: the ring's collectives confuse it.
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_qkv, spec_qkv, spec_qkv, spec_pos, spec_pos),
        out_specs=spec_qkv, check_vma=False,
    )
    return fn(q, k, v, q_positions, kv_positions)
