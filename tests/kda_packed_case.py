"""A packed step of one delta-rule layer against the recurrence, row by row.

Shared by ``test_kimi_linear.py`` (32 heads, ``b`` < 1) and
``test_solar_open2.py`` (64 heads, ``b`` up to 2), with the delta rule's
inputs (``kda_inputs``) and a step program's jaxpr (``step_jaxpr``) that
both files' cases start from: the step's rows are
packed on one token axis as ``Engine._pack_unified`` packs them (a row's
tokens side by side, padding at the end of the token bucket and of the row
bucket), ``llama._kda_packed`` walks them, and each row is held to
``kda.kda_recurrence`` over its own tokens from its own slot.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from rbg_tpu.engine.kvcache import PagedKVCache, StatePool
from rbg_tpu.models import get_config, llama
from rbg_tpu.ops import kda

LAYERS, LAYER, SLOTS, DK, TAPS = 3, 1, 12, 16, 4

# name: (row bucket, token bucket, longest row, [(tokens, fresh)] a row)
STEPS = {
    # rows of 1, 2, 17 and 64 tokens, a fresh one of each kind, a row with
    # no token, two rows of padding and 101 tokens of it
    "a chunk row among one-token rows": (12, 256, 64, [
        (1, 0), (64, 0), (1, 0), (2, 0), (0, 0), (17, 0), (1, 1), (64, 1),
        (1, 0), (2, 1)]),
    "no row holds a chunk": (8, 8, 8, [(1, 0), (1, 1), (0, 0), (1, 0),
                                       (1, 0)]),
    # the ramp: the token bucket is full
    "every row holds a chunk": (4, 256, 64, [(64, 1), (64, 0), (64, 1),
                                             (64, 0)]),
    # the warm dispatch: no row, no token
    "all padding": (4, 64, 16, []),
    # a lone chunk whose window ends past the token bucket's end
    "a short chunk at the end of the bucket": (4, 32, 16, [(16, 0), (1, 0),
                                                           (15, 1)]),
}


def kda_inputs(R, C, H, dk, lens, seed=0, b_scale=1.0, b_spread=1.0):
    """``(q, k, v, g, b, S)`` of ``[R, C, H, dk]`` tokens with unit keys, and
    which of them are real: a row's first ``lens[r]``, the padding after
    them with ``g = 0`` and ``b = 0`` as ``_kda_attention`` masks them.
    ``b = b_scale sigmoid(b_spread n)``, ``n`` normal."""
    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (R, C, H, dk))
    k = jax.random.normal(ks[1], (R, C, H, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (R, C, H, dk))
    g = -jax.random.uniform(ks[3], (R, C, H, dk)) * 0.7
    b = b_scale * jax.nn.sigmoid(
        b_spread * jax.random.normal(ks[4], (R, C, H)))
    real = jnp.arange(C)[None] < jnp.asarray(lens)[:, None]
    g = jnp.where(real[..., None, None], g, 0.0)
    b = jnp.where(real[..., None], b, 0.0)
    S = jax.random.normal(ks[5], (R, H, dk, dk))
    return (q, k, v, g, b, S), np.asarray(real)


def step_jaxpr(cfg, params, program, R=4, use_pallas="auto"):
    """The jaxpr of a recurrent model's decode step (by row, the hit
    experts' form) or its ragged step (packed), ``R`` rows."""
    T = 1 if program == "decode" else 16
    cache, pool = PagedKVCache.create(cfg, 64, 8), StatePool(cfg, R)
    I32 = jnp.int32
    table = jnp.zeros((R, 8), I32)
    slots = {"state": pool.arrays, "state_slots": jnp.arange(R, dtype=I32),
             "use_pallas": use_pallas}
    if program == "decode":
        fn = functools.partial(llama.forward_paged, params, cfg,
                               experts_whole=True, **slots)
        args = (jnp.ones((R, T), I32), jnp.zeros((R, T), I32),
                jnp.ones((R, T), bool), jnp.ones(R, I32), table)
    else:
        fn = functools.partial(llama.forward_ragged, params, cfg,
                               max_q_len=T, **slots)
        args = (jnp.ones((1, T), I32), jnp.zeros((1, T), I32),
                jnp.ones((1, T), bool), jnp.zeros(T, I32),
                jnp.full(R, T, I32), table)
    return jax.make_jaxpr(fn)(*args, cache.k_pages, cache.v_pages).jaxpr


def eqns(jaxpr, in_loop=False):
    """Every equation of ``jaxpr`` at any depth, each with whether it sits
    inside the body of a ``while`` or a ``scan``."""
    for eqn in jaxpr.eqns:
        yield eqn, in_loop
        inner = in_loop or eqn.primitive.name in ("while", "scan")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from eqns(sub, inner)


def packed_step(name, heads, b_scale, seed=0):
    """The arguments of ``_kda_packed`` for ``STEPS[name]`` and what each
    row is: ``(cfg, blk, qkv, g, beta, state, addr), rows`` with ``rows`` a
    list of ``(first packed index, tokens, fresh, slot)``."""
    R, T, C, lens = STEPS[name]
    ch = heads * DK
    cfg = dataclasses.replace(get_config("tiny-kimi-linear"),
                              kda_num_heads=heads, kda_head_dim=DK)
    ks = jax.random.split(jax.random.key(seed), 6)
    qkv = jax.random.normal(ks[0], (1, T, 3 * ch))
    g = -jax.random.uniform(ks[1], (1, T, heads, DK)) * 0.7
    beta = b_scale * jax.nn.sigmoid(jax.random.normal(ks[2], (1, T, heads)))
    blk = {"kda_conv": jax.random.normal(ks[3], (TAPS, 3 * ch)) * 0.5}
    state = {"s": jax.random.normal(ks[4], (LAYERS, SLOTS, heads, DK, DK)),
             "conv": jax.random.normal(ks[5],
                                       (LAYERS, SLOTS, (TAPS - 1) * 3 * ch))}
    rng = np.random.default_rng(seed)
    slot_of = rng.permutation(SLOTS)[:len(lens)]
    pos = np.full((1, T), -1, np.int32)
    mask = np.zeros((1, T), bool)
    row_ids = np.zeros(T, np.int32)
    slots = np.full(R, SLOTS, np.int32)
    rows, off = [], 0
    for r, (n, fresh) in enumerate(lens):
        at = 0 if fresh else int(rng.integers(1, 500))
        pos[0, off:off + n] = at + np.arange(n)
        mask[0, off:off + n] = True
        row_ids[off:off + n] = r
        slots[r] = slot_of[r]
        rows.append((off, n, bool(fresh), int(slot_of[r])))
        off += n
    addr = llama.PoolAddr(jnp.asarray(pos), jnp.asarray(mask),
                          jnp.zeros(R, jnp.int32), jnp.zeros((R, 1), jnp.int32),
                          jnp.asarray(row_ids), None, jnp.asarray(slots))
    return (cfg, blk, qkv, g, beta, state, addr), rows


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _walk(cfg, C, use_pallas, blk, qkv, g, beta, state, addr):
    """``addr.max_q_len`` is static, so it rides beside ``addr``."""
    return llama._kda_packed(cfg, blk, qkv, g, beta, state, LAYER,
                             addr._replace(max_q_len=C), use_pallas)


def assert_rows_equal_the_recurrence(name, heads, b_scale, use_pallas,
                                     seed=0):
    """``_kda_packed`` on ``STEPS[name]``: every row with a token equals
    ``kda_recurrence`` over its own tokens in ``o``, in its slot's state
    and in its tail; every other slot, and every other layer, is as it
    was, bit for bit."""
    args, rows = packed_step(name, heads, b_scale, seed)
    cfg, blk, qkv, g, beta, state, addr = args
    o, new = _walk(cfg, STEPS[name][2], use_pallas, blk, qkv, g, beta, state,
                   addr)
    assert o.shape == (1, qkv.shape[1], heads, DK) and o.dtype == jnp.float32
    touched = np.zeros((LAYERS, SLOTS), bool)
    for off, n, fresh, slot in rows:
        if not n:
            continue
        touched[LAYER, slot] = True
        cut = slice(off, off + n)
        tail = jnp.where(fresh, 0.0, state["conv"][LAYER, slot])[None]
        S = jnp.where(fresh, 0.0, state["s"][LAYER, slot])[None]
        q, k, v, tail = llama._kda_conv_qkv(
            heads, DK, blk["kda_conv"], qkv[:, cut], tail, jnp.zeros(1, bool),
            jnp.asarray([n], jnp.int32))
        want, S = kda.kda_recurrence(q, k, v, g[:, cut], beta[:, cut], S)
        np.testing.assert_allclose(o[0, cut], want[0], rtol=2e-4, atol=2e-5,
                                   err_msg=f"o of the row at {off}")
        np.testing.assert_allclose(new["s"][LAYER, slot], S[0], rtol=2e-4,
                                   atol=2e-5, err_msg=f"state, row at {off}")
        np.testing.assert_array_equal(new["conv"][LAYER, slot], tail[0])
    for key in ("s", "conv"):
        np.testing.assert_array_equal(np.asarray(new[key])[~touched],
                                      np.asarray(state[key])[~touched])
    assert set(new) == set(state)


def inside_the_mixer(jaxpr):
    """What a step program's ``_kda_mixer`` holds, at any depth: (the
    shapes of its float32 values, the names of its primitives)."""
    mixer = next(e for e, _ in eqns(jaxpr)
                 if e.params.get("name") == "_kda_mixer")
    inside = [e for e, _ in eqns(mixer.params["jaxpr"].jaxpr)]
    shapes = {v.aval.shape for e in inside for v in e.outvars
              if getattr(v.aval, "dtype", None) == jnp.float32}
    return shapes, {e.primitive.name for e in inside}


def assert_no_line_and_no_state_for_every_row(cfg, params):
    """A delta-rule model's unified program with the kernel in holds no
    float32 array of ``[R, C, H, dk]`` (every row's line) or ``[R, H, dk,
    dv]`` (every row's state), which the decode step by row, in plain XLA,
    has; it has the kernel, and a loop over the rows that hold a chunk, a
    row a trip (``[1, C, H, dk]``)."""
    R, C, H, dk = 5, 16, cfg.kda_num_heads, cfg.kda_head_dim
    wide = {(R, C, H, dk), (R, H, dk, dk)}
    shapes, names = inside_the_mixer(
        step_jaxpr(cfg, params, "ragged", R, use_pallas="always"))
    assert not shapes & wide and (1, C, H, dk) in shapes
    assert {"while", "pallas_call"} <= names
    shapes, names = inside_the_mixer(
        step_jaxpr(cfg, params, "decode", R, use_pallas="never"))
    assert (R, H, dk, dk) in shapes and "pallas_call" not in names
