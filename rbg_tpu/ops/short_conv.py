"""Causal depthwise convolution over time, with the tail a row carries.

Two mixers pass a short convolution and differ in what surrounds it:

* Kimi Delta Attention convolves its q/k/v projection and activates the
  result (``kda.short_conv``: ``silu(causal_conv(.))``);
* the LFM2 gated short convolution (``gated_short_conv`` here) splits its
  input projection in three, ``B, C, X``, convolves the gated input ``u =
  B * X``, activates nothing, and gates the result: ``C * conv(u)``.

``causal_conv`` is the walk both share: the row's last ``K - 1`` inputs in,
its new last ``K - 1`` real inputs out, rows of differing real lengths side
by side. What a sequence keeps between steps is that tail alone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_conv(x, tail, w, lens):
    """``y[t] = sum_j w[j] * xx[t + j]`` over ``xx = [tail, x]``: the last
    tap meets the current input, nothing is activated.

    ``x [R, C, ch]`` the rows' new inputs (row ``r`` has ``lens[r]`` real
    ones, from index 0), ``tail [R, K-1, ch]`` the ``K - 1`` inputs before
    them (zeros where the sequence starts), ``w [K, ch]``. Returns (``y
    [R, C, ch]`` float32, the new tail: the last ``K - 1`` real inputs of
    each row, which for a row of no real input is the tail it came with)."""
    K = w.shape[0]
    C = x.shape[1]
    xx = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    y = sum(xx[:, j:j + C].astype(jnp.float32) * w[j].astype(jnp.float32)
            for j in range(K))
    new_tail = jax.vmap(
        lambda a, n: jax.lax.dynamic_slice_in_dim(a, n, K - 1, 0))(xx, lens)
    return y, new_tail.astype(tail.dtype)


def gated_short_conv(b, c, x, tail, w, lens):
    """The LFM2 mixer between its two projections: ``c * causal_conv(b *
    x)``. ``b, c, x [R, C, ch]``; ``tail [R, K-1, ch]`` holds the last
    ``K - 1`` values of ``b * x``. Returns (``[R, C, ch]`` in ``x``'s
    dtype, the new tail)."""
    y, tail = causal_conv(b * x, tail, w, lens)
    return (c.astype(jnp.float32) * y).astype(x.dtype), tail
