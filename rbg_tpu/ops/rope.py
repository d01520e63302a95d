"""Rotary position embeddings.

Frequency ``i`` rotates one pair of the last dim: ``(x_i, x_{i+hd/2})`` in
the "rotate-half" formulation (llama), ``(x_2i, x_2i+1)`` in the interleaved
one (DeepSeek's ``rope_interleave``). Positions are explicit so the same code
path serves prefill (positions = arange) and decode (positions = per-sequence
offsets) without dynamic shapes.
"""

from __future__ import annotations

import jax.numpy as jnp


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    """Inverse frequencies, shape [head_dim // 2], float32."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               interleave: bool = False) -> jnp.ndarray:
    """Apply RoPE.

    Args:
      x: [..., seq, heads, head_dim]
      positions: integer positions broadcastable to [..., seq]
      interleave: pairs are neighbours, and stay where they were.
    """
    head_dim = x.shape[-1]
    inv_freq = rope_frequencies(head_dim, theta)  # [hd/2]
    angles = positions[..., :, None].astype(jnp.float32) * inv_freq  # [..., seq, hd/2]
    cos = jnp.cos(angles)[..., :, None, :]  # [..., seq, 1, hd/2]
    sin = jnp.sin(angles)[..., :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = ((xf[..., 0::2], xf[..., 1::2]) if interleave
              else jnp.split(xf, 2, axis=-1))
    a, b = x1 * cos - x2 * sin, x2 * cos + x1 * sin
    out = (jnp.stack([a, b], axis=-1).reshape(x.shape) if interleave
           else jnp.concatenate([a, b], axis=-1))
    return out.astype(x.dtype)
