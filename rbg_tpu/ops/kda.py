"""Kimi Delta Attention: a gated delta rule with a decay per key channel.

A head keeps a state ``S [dk, dv]`` in float32 in place of cached keys and
values. For a token with key ``k`` (unit length), value ``v``, query ``q``,
log-decay ``g <= 0`` a key channel (``a = exp(g)``) and write strength
``b`` in (0, 1), or in (0, 2) where the model lets ``I - b k k^T`` have
negative eigenvalues (``ModelConfig.kda_beta_scale``; all three forms take
``b`` as data)::

    S <- (I - b k k^T) Diag(a) S + b k v^T        o = S^T q

that is, with ``S' = Diag(a) S``: ``u = b (v - S'^T k)``, ``S <- S' + k
u^T``. Three forms of the one recurrence, the decode one in plain XLA and
as a kernel:

* ``kda_step``: one token a row (a decode step). On the state pool it is
  ``kda_decode``: on a TPU the Pallas kernel that reads each live row's
  state out of its slot and writes it back once, in place
  (``pallas/kda_kernel.py``; the trace prints it ``_kda_decode_call``, under
  the ``attention/kda`` scope), elsewhere ``kda_step_in_pool``, ``kda_step``
  between a gather and a scatter, which is also what the kernel has to
  equal (``dispatch_pallas``'s one policy);
* ``kda_chunk``: ``C`` tokens a row at once, rows in parallel (a prompt's
  chunk: every row of a step by row, and one row a call in a packed step,
  where ``models/llama.py::_kda_packed`` hands it the rows that hold a
  chunk one after another and the rows of one token go to ``kda_decode``).
  Sub-chunks of ``SUB`` tokens are walked in order; inside one,
  with ``G`` the running sum of ``g`` from its start, the pseudo-values
  ``u`` solve the unit lower-triangular system ``(I + Diag(b) A) U =
  Diag(b) (V - (K e^G) S)``, ``A[t, i] = (k_t e^{G_t}) . (k_i e^{-G_i})``
  for ``i < t``, and ``O = (Q e^G) S + tril(Q e^G (K e^-G)^T) U``, ``S <-
  Diag(e^{G_last}) S + (K e^{G_last - G})^T U``. The inverse of ``I + N``
  (``N`` strictly lower, so ``N^SUB = 0``) is the product ``(I - N)(I +
  N^2)(I + N^4)...``. ``e^{-G}`` is held to ``e^80``: the form equals the
  recurrence while a channel decays by less than that inside one
  sub-chunk (0.007 a token on average);
* ``kda_recurrence``: ``kda_step`` token by token, which is what the other
  two have to equal (``tests/test_kimi_linear.py``).

A token that is padding has ``g = 0`` and ``b = 0``: it leaves the state
as it was. The state is float32. A decode step's two dots run at
``highest`` precision (a few MFLOP, and the state lives for thousands of
tokens; the kernel's are float32 products and sums on the VPU); a chunk's
dozen at the default one, their inputs rounded to
bfloat16 as every other dot of a bfloat16 model rounds its own (at
``highest`` they were 40 % of a step program's compile time, paid in every
one of a server's thirty packed programs); the decay and the sums into the
state stay float32.

``short_conv`` is the causal depthwise convolution over time that q, k and
v pass first, then SiLU; its cache is the last ``K - 1`` inputs of the row
(the walk is ``short_conv.causal_conv``, which the LFM2 mixer shares).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from rbg_tpu.ops.pallas import dispatch_pallas
from rbg_tpu.ops.short_conv import causal_conv

SUB = 16            # tokens of a sub-chunk
_MAX_LOG = 80.0     # bound of -G inside a sub-chunk

_step_einsum = functools.partial(
    jnp.einsum, precision=jax.lax.Precision.HIGHEST,
    preferred_element_type=jnp.float32)
_einsum = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)


def short_conv(x, tail, w, lens):
    """Causal depthwise convolution over time, then SiLU.

    ``x [R, C, ch]`` the rows' new inputs (row ``r`` has ``lens[r]`` real
    ones, from index 0), ``tail [R, K-1, ch]`` the ``K - 1`` inputs before
    them (zeros where the sequence starts), ``w [K, ch]`` (the last tap
    meets the current input). Returns (``y [R, C, ch]``, the new tail:
    the last ``K - 1`` real inputs of each row)."""
    y, new_tail = causal_conv(x, tail, w, lens)
    return jax.nn.silu(y).astype(x.dtype), new_tail


def kda_step(q, k, v, g, b, S):
    """One token a row. ``q, k, g [R, H, dk]``, ``v [R, H, dv]``, ``b [R,
    H]``, ``S [R, H, dk, dv]`` float32. Returns (``o [R, H, dv]`` float32,
    the new state)."""
    q, k, v, g, b = (a.astype(jnp.float32) for a in (q, k, v, g, b))
    S = S * jnp.exp(g)[..., None]
    u = b[..., None] * (v - _step_einsum("rhkv,rhk->rhv", S, k))
    S = S + k[..., None] * u[..., None, :]
    return _step_einsum("rhkv,rhk->rhv", S, q), S


def kda_step_in_pool(q, k, v, g, b, pool, layer, slots, fresh):
    """``kda_step`` on the rows' slots of a pool ``[layers, slots, H, dk,
    dv]``, in plain XLA: gathered (a row of padding names a slot out of
    range and reads a clipped one; a ``fresh`` row starts from zeros),
    advanced, scattered back (padding dropped). Returns (``o``, pool)."""
    S = jnp.where(fresh[:, None, None, None], 0.0,
                  pool.at[layer, slots].get(mode="clip"))
    o, S = kda_step(q, k, v, g, b, S)
    return o, pool.at[layer, slots].set(S, mode="drop")


def kda_decode(q, k, v, g, b, pool, layer, slots, fresh, *,
               use_pallas: str = "auto"):
    """A decode step on the pool: the kernel that reads and writes each
    live row's state once, in place (``pallas/kda_kernel.py``), or
    ``kda_step_in_pool``, by the one policy (``dispatch_pallas``). The
    kernel leaves zeros in a padding row's lines of ``o``."""
    return dispatch_pallas(use_pallas, "kda_decode_pallas", kda_step_in_pool,
                           (q, k, v, g, b, pool, layer, slots, fresh))


def kda_recurrence(q, k, v, g, b, S):
    """``kda_step`` over the ``C`` tokens of ``[R, C, H, d]`` inputs in
    order (``b [R, C, H]``). Returns (``o [R, C, H, dv]``, state)."""
    def one(S, xs):
        o, S = kda_step(*xs, S)
        return S, o

    S, o = jax.lax.scan(one, S, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, b)))
    return jnp.moveaxis(o, 0, 1), S


def _unit_lower_inverse(N):
    """``(I + N)^-1`` for strictly lower-triangular ``N [..., c, c]``."""
    c = N.shape[-1]
    eye = jnp.eye(c, dtype=N.dtype)
    inv, P, n = eye - N, N, 2
    while n < c:
        P = _einsum("...ij,...jk->...ik", P, P)
        inv = _einsum("...ij,...jk->...ik", inv, eye + P)
        n *= 2
    return inv


def kda_chunk(q, k, v, g, b, S):
    """``C`` tokens a row at once; shapes as ``kda_recurrence``, which it
    equals. Real tokens lead a row; padding (``g = 0``, ``b = 0``) follows
    and changes nothing."""
    R, C, H, dk = q.shape
    pad = -C % SUB
    if pad:
        q, k, v, g, b = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                                 (a.ndim - 2)) for a in (q, k, v, g, b))
    n = (C + pad) // SUB

    def subs(a):            # [R, C, H, ...] -> [n, R, H, SUB, ...]
        a = a.astype(jnp.float32).reshape((R, n, SUB) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    lower = jnp.tril(jnp.ones((SUB, SUB), bool))
    strict = jnp.tril(jnp.ones((SUB, SUB), bool), -1)

    def one(S, xs):
        q, k, v, g, b = xs                    # [R, H, SUB, d]; b [R, H, SUB]
        G = jnp.cumsum(g, axis=2)
        eG = jnp.exp(G)
        k_in = k * eG                         # the key as the old state meets it
        k_out = k * jnp.exp(jnp.minimum(-G, _MAX_LOG))
        q_in = q * eG
        A = jnp.where(strict, _einsum("rhtk,rhik->rhti", k_in, k_out), 0.0)
        T = _unit_lower_inverse(b[..., None] * A)
        rhs = b[..., None] * (v - _einsum("rhtk,rhkv->rhtv", k_in, S))
        U = _einsum("rhti,rhiv->rhtv", T, rhs)
        B = jnp.where(lower, _einsum("rhtk,rhik->rhti", q_in, k_out), 0.0)
        o = (_einsum("rhtk,rhkv->rhtv", q_in, S)
             + _einsum("rhti,rhiv->rhtv", B, U))
        G_last = G[:, :, -1:]
        S = (S * jnp.exp(G_last[:, :, 0])[..., None]
             + _einsum("rhtk,rhtv->rhkv", k * jnp.exp(G_last - G), U))
        return S, o

    S, o = jax.lax.scan(one, S, (subs(q), subs(k), subs(v), subs(g),
                                 subs(b[..., None])[..., 0]))
    # [n, R, H, SUB, dv] -> [R, C, H, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)
    return o.reshape(R, n * SUB, H, -1)[:, :C], S
