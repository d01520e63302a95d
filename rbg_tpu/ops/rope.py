"""Rotary position embeddings.

Frequency ``i`` rotates one pair of the last dim: ``(x_i, x_{i+hd/2})`` in
the "rotate-half" formulation (llama), ``(x_2i, x_2i+1)`` in the interleaved
one (DeepSeek's ``rope_interleave``). Positions are explicit so the same code
path serves prefill (positions = arange) and decode (positions = per-sequence
offsets) without dynamic shapes.

What is rotated, by which frequencies and at what size is a layer KIND's
rotary settings (``rotary_tables``): plain RoPE is the case with no scaling
and the whole head.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax.numpy as jnp
import numpy as np


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    """Inverse frequencies, shape [head_dim // 2], float32."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)


class Rotary(NamedTuple):
    """A layer kind's rotary settings as ``apply_rope`` takes them."""
    rotary_dim: int             # the head's first channels that are rotated
    inv_freq: Optional[np.ndarray]  # [rotary_dim // 2] float32; None: plain
    scale: float                # cos and sin are multiplied by it


def yarn_frequencies(rotary_dim: int, theta: float, factor: float,
                     original_max: int, beta_fast: float,
                     beta_slow: float) -> np.ndarray:
    """YaRN's inverse frequencies ``[rotary_dim // 2]``: frequency ``i``
    turns ``original_max f_i / 2 pi`` times over the original context;
    those that turn more than ``beta_fast`` times keep ``f_i``
    (extrapolated), those that turn less than ``beta_slow`` times become
    ``f_i / factor`` (interpolated), and a linear ramp over the index
    blends the ones between. The bounds are the indices at which a
    frequency turns exactly ``beta`` times, rounded outwards."""
    r = rotary_dim
    f = theta ** -(np.arange(0, r, 2, dtype=np.float64) / r)

    def index_at(turns):
        return r * math.log(original_max / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    lo = max(math.floor(index_at(beta_fast)), 0)
    hi = min(math.ceil(index_at(beta_slow)), r - 1)
    ramp = np.clip((np.arange(r // 2, dtype=np.float64) - lo)
                   / max(hi - lo, 1e-3), 0.0, 1.0)
    return ((f / factor) * ramp + f * (1.0 - ramp)).astype(np.float32)


def rotary_tables(cfg) -> Rotary:
    """The rotary settings of the layer kind ``cfg`` describes (a
    ``ModelConfig``, or a kind's group config): the rotated prefix
    ``partial_rotary_factor x head_dim``, and under ``rope_scaling``
    ``"yarn"`` the blended frequencies and the factor on cos and sin
    (``rope_attention_factor``, or ``0.1 ln(rope_factor) + 1``). Trace-time
    numbers: nothing here is traced."""
    hd = cfg.head_dim_
    r = int(hd * cfg.partial_rotary_factor)
    if cfg.rope_scaling != "yarn":
        return Rotary(r, None, 1.0)
    scale = cfg.rope_attention_factor or 0.1 * math.log(cfg.rope_factor) + 1.0
    return Rotary(r, yarn_frequencies(
        r, cfg.rope_theta, cfg.rope_factor, cfg.rope_original_max,
        cfg.rope_beta_fast, cfg.rope_beta_slow), float(scale))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               interleave: bool = False,
               rotary: Optional[Rotary] = None) -> jnp.ndarray:
    """Apply RoPE.

    Args:
      x: [..., seq, heads, head_dim]
      positions: integer positions broadcastable to [..., seq]
      interleave: pairs are neighbours, and stay where they were.
      rotary: the kind's settings where they are not plain RoPE's: only
        the first ``rotary_dim`` channels are rotated (pairs within them),
        by ``inv_freq`` where given, cos and sin times ``scale``.
    """
    head_dim = x.shape[-1]
    r, freqs, scale = rotary or (head_dim, None, 1.0)
    inv_freq = (rope_frequencies(r, theta) if freqs is None
                else jnp.asarray(freqs))  # [r/2]
    angles = positions[..., :, None].astype(jnp.float32) * inv_freq  # [..., seq, r/2]
    cos = jnp.cos(angles)[..., :, None, :]  # [..., seq, 1, r/2]
    sin = jnp.sin(angles)[..., :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    xf = x.astype(jnp.float32)
    rot = xf if r == head_dim else xf[..., :r]
    x1, x2 = ((rot[..., 0::2], rot[..., 1::2]) if interleave
              else jnp.split(rot, 2, axis=-1))
    a, b = x1 * cos - x2 * sin, x2 * cos + x1 * sin
    out = (jnp.stack([a, b], axis=-1).reshape(rot.shape) if interleave
           else jnp.concatenate([a, b], axis=-1))
    if r != head_dim:
        out = jnp.concatenate([out, xf[..., r:]], axis=-1)
    return out.astype(x.dtype)
