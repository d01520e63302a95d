"""Token sampling — jittable, per-row parameters as arrays (one compiled
sampler serves every batch mix of greedy/temperature/top-k/top-p/min-p,
with optional repetition/presence/frequency penalties and logprobs).

Design notes (TPU-first):

* **Per-row PRNG streams** — every row samples with its own key,
  ``fold_in(row_key, position)``. Randomness is a pure function of
  (request seed, token position): per-request ``seed`` gives OpenAI-style
  reproducibility, and a decode-state rebuild (batch recomposition,
  preemption resume) replays the identical stream instead of depending on
  how many scan windows ran before it.
* **Gumbel-max** instead of ``jax.random.categorical`` so the per-row keys
  vmap cleanly: ``argmax(logits/T + G)`` with row-keyed Gumbel noise is
  exactly categorical sampling.
* **Masking is value-space** — top-k/top-p/min-p thresholds are computed on
  sorted copies and applied by comparing against the threshold *value*
  (ties at the boundary are kept), which keeps everything O(V log V) sorts
  + elementwise, no scatters, fully fusable by XLA.
* **Penalties are optional state** — they need token-count tensors
  ([B, V]); the engine only threads them through the fused decode scan when
  some request in the batch actually uses penalties, so the common greedy
  path compiles without the arrays entirely.

Semantics follow the de-facto engine conventions (SGLang/vLLM):
repetition_penalty divides positive / multiplies negative logits of any
token seen in prompt or output; presence/frequency penalties subtract from
output-seen tokens; temperature scales before top-k/top-p/min-p; logprobs
report the model distribution after penalties but before temperature.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30  # avoid -inf NaN traps in (masked - masked) style arithmetic


def apply_penalties(
    logits: jnp.ndarray,        # [B, V] f32
    prompt_mask: jnp.ndarray,   # [B, V] bool — token appears in the prompt
    out_counts: jnp.ndarray,    # [B, V] int32 — occurrences in the output
    rep: jnp.ndarray,           # [B] f32; 1.0 = disabled
    pres: jnp.ndarray,          # [B] f32; 0.0 = disabled
    freq: jnp.ndarray,          # [B] f32; 0.0 = disabled
) -> jnp.ndarray:
    seen = prompt_mask | (out_counts > 0)
    rp = rep[:, None]
    logits = jnp.where(
        seen, jnp.where(logits > 0, logits / rp, logits * rp), logits)
    out_seen = out_counts > 0
    logits = logits - pres[:, None] * out_seen
    logits = logits - freq[:, None] * out_counts.astype(logits.dtype)
    return logits


def _sorted_desc(x: jnp.ndarray) -> jnp.ndarray:
    """``x`` sorted descending along its last axis. Values alone are
    sorted, so a stable and an unstable sort give the same array; the TPU's
    compiler takes 21 s for the stable one at ``[16, 129280]`` and 8 s for
    this (compiled for a described v5e), in every program that holds a
    sampler: each decode bucket's, each host-path sampler's."""
    return jnp.sort(x, axis=-1, stable=False)[:, ::-1]


def _mask_top_k(scaled: jnp.ndarray, top_k: jnp.ndarray) -> jnp.ndarray:
    B, V = scaled.shape
    sorted_desc = _sorted_desc(scaled)
    k_idx = jnp.clip(top_k - 1, 0, V - 1)
    kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=1)
    return jnp.where((top_k[:, None] > 0) & (scaled < kth), NEG_INF, scaled)


def _mask_top_p_min_p(scaled: jnp.ndarray, top_p: jnp.ndarray,
                      min_p: jnp.ndarray) -> jnp.ndarray:
    probs = jax.nn.softmax(scaled, axis=-1)
    # top-p: keep the smallest prefix of sorted-desc probs whose exclusive
    # cumulative mass is < top_p; threshold = smallest kept probability.
    sp = _sorted_desc(probs)
    cum_excl = jnp.cumsum(sp, axis=-1) - sp
    kept = cum_excl < top_p[:, None]
    thresh = jnp.min(jnp.where(kept, sp, jnp.inf), axis=-1, keepdims=True)
    scaled = jnp.where((top_p[:, None] < 1.0) & (probs < thresh),
                       NEG_INF, scaled)
    # min-p: drop tokens whose prob is below min_p * max-prob.
    pmax = jnp.max(probs, axis=-1, keepdims=True)
    scaled = jnp.where((min_p[:, None] > 0.0) & (probs < min_p[:, None] * pmax),
                       NEG_INF, scaled)
    return scaled


@jax.named_scope("sampler")   # metadata only: names the ops in a profile
def sample(
    logits: jnp.ndarray,        # [B, V] f32
    keys: jax.Array,            # [B] typed PRNG keys — per-row streams
    temperature: jnp.ndarray,   # [B] f32; 0 = greedy
    top_k: jnp.ndarray,         # [B] int32; 0 = full vocab
    top_p: jnp.ndarray,         # [B] f32; 1.0 = disabled
    min_p: jnp.ndarray,         # [B] f32; 0.0 = disabled
    *,
    prompt_mask: Optional[jnp.ndarray] = None,   # [B, V] bool
    out_counts: Optional[jnp.ndarray] = None,    # [B, V] int32
    rep: Optional[jnp.ndarray] = None,           # [B] f32
    pres: Optional[jnp.ndarray] = None,          # [B] f32
    freq: Optional[jnp.ndarray] = None,          # [B] f32
    want_logprobs: bool = False,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Returns (token ids [B] int32, logprobs [B] f32 or None).

    Penalty arguments are all-or-nothing: pass every one of prompt_mask /
    out_counts / rep / pres / freq, or none (the caller compiles separate
    variants so the penalty-free path never materializes [B, V] state).

    Every stage past the greedy argmax runs only when some row of the
    batch asks for it, decided on the device from the per-row arrays: the
    temperature divide, noise and second argmax when a row samples, each
    O(V log V) sort when a sampling row sets top-k, or top-p / min-p. The
    predicates read the [B] parameter arrays and never the logits, so a
    ``vmap`` over logits (the speculative verify) keeps them conditionals.
    A row's token is the same to the bit whichever branches its batch
    takes: a closed stage is one that would have changed no row.
    """
    if prompt_mask is not None:
        logits = apply_penalties(logits, prompt_mask, out_counts,
                                 rep, pres, freq)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    samples = temperature > 0

    def sampled():
        scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
        scaled = jax.lax.cond(
            jnp.any(samples & (top_k > 0)),
            lambda s: _mask_top_k(s, top_k), lambda s: s, scaled)
        scaled = jax.lax.cond(
            jnp.any(samples & ((top_p < 1.0) | (min_p > 0.0))),
            lambda s: _mask_top_p_min_p(s, top_p, min_p), lambda s: s,
            scaled)
        # Gumbel-max with per-row keys == per-row categorical.
        noise = jax.vmap(lambda k, row: jax.random.gumbel(
            k, row.shape, row.dtype))(keys, scaled)
        drawn = jnp.argmax(scaled + noise, axis=-1).astype(jnp.int32)
        return jnp.where(samples, drawn, greedy)

    toks = jax.lax.cond(jnp.any(samples), sampled, lambda: greedy)

    lps = None
    if want_logprobs:
        # Model-distribution logprob of the chosen token (post-penalty,
        # pre-temperature — the OpenAI ``logprobs`` convention).
        full = jax.nn.log_softmax(logits, axis=-1)
        lps = jnp.take_along_axis(full, toks[:, None], axis=-1)[:, 0]
    return toks, lps


@jax.jit
def _row_keys(seed_vals: jnp.ndarray, has_seed: jnp.ndarray,
              rids: jnp.ndarray, fallback_key: jax.Array) -> jax.Array:
    ks = jax.vmap(jax.random.key)(seed_vals)
    kf = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(fallback_key, rids)
    kd = jnp.where(has_seed[:, None], jax.random.key_data(ks),
                   jax.random.key_data(kf))
    return jax.random.wrap_key_data(kd)


def row_keys(seeds, fallback_key: jax.Array, ids,
             put=jnp.asarray) -> jax.Array:
    """Build a [B] key array: rows with a seed get ``key(seed)`` (stable,
    user-reproducible); rows without get ``fold_in(fallback, request id)``
    (distinct streams per request, stable across decode-state rebuilds).
    One fused dispatch — this runs on every decode-state rebuild, inside
    the host scheduling path. ``put`` places the three host lines on the
    device (the engine's step paths pass the put they count)."""
    # Mask into uint32 — wire seeds are arbitrary ints and NumPy 2.x raises
    # OverflowError on out-of-range conversion (a request must never be able
    # to kill the engine loop thread).
    seed_vals = put(np.asarray(
        [((s if s is not None else 0) & 0xFFFFFFFF) for s in seeds],
        np.uint32))
    has_seed = put(np.asarray([s is not None for s in seeds], bool))
    rids = put(np.asarray([int(i) & 0xFFFFFFFF for i in ids], np.uint32))
    return _row_keys(seed_vals, has_seed, rids, fallback_key)


def step_keys(keys: jax.Array, pos: jnp.ndarray) -> jax.Array:
    """Per-row key for sampling the token at position ``pos`` (jittable)."""
    return jax.vmap(jax.random.fold_in)(keys, pos)
