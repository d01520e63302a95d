"""The plain reference of ``lfm2_moe`` (LiquidAI LFM2-24B-A2B): the layers as
the model's ``config.json`` and the public implementation of the family
state them.

Every layer is pre-norm: ``h = x + mixer(RMSNorm(x))``, ``y = h +
ffn(RMSNorm(h))``; after the last layer one RMSNorm, then the head (tied to
the embedding: ``assumed`` in the configuration's file).

**A conv layer** (``layer_types[i] == "conv"``; ``conv_L_cache`` = ``K``
taps, ``conv_bias`` false). With ``a`` the normed input: ``[B, C, X] = a
W_in`` (three parts of ``hidden_size`` each), ``u = B * X``, ``v_t =
sum_{j<K} w[j] * u_{t-(K-1)+j}`` (depthwise, causal, ``u`` before the
sequence is 0, **no activation**), ``out = (C * v) W_out``. What a sequence
carries from token to token is ``u_{t-K+1} .. u_{t-1}``.

**An attention layer** (``"full_attention"``): ``q = a W_q``, ``k = a
W_k``, ``v = a W_v`` with ``num_attention_heads`` query and
``num_key_value_heads`` key/value heads of ``hidden_size /
num_attention_heads``; ``q`` and ``k`` pass an RMSNorm over each head with
a learned weight (``q_layernorm``, ``k_layernorm``), then rotate-half RoPE
(``rope_parameters.rope_theta``); causal softmax at ``head_dim ** -0.5``;
``W_o``. No bias anywhere.

**The FFN**: the first ``num_dense_layers`` layers a SwiGLU of
``intermediate_size``; every other layer ``num_experts`` experts of
``moe_intermediate_size`` and no shared one: ``s = sigmoid(m W_g)``, the top
``num_experts_per_tok`` of ``s + expert_bias`` (``use_expert_bias``: the bias
picks and never weighs), ``w = s[chosen] / (sum of them + 1e-6)``
(``norm_topk_prob``), times ``routed_scaling_factor``. **Only the experts
the configuration holds** (``preset.experts_held``, a chip's share of a
layer) are computed: the router scores and chooses over all the published
experts, and what the absent ones would add is left out, here as in the
program.

Departures, and what the source does not state (the file's ``assumed``):
the head size (hidden / heads), the tied head, bfloat16, how the router, the
bias and the taps are drawn. The program divides the chosen scores by
``max(sum, 1e-9)`` where this module follows the published ``sum + 1e-6``:
the sum of four sigmoids is near 2, so the two differ by 5e-7 relative.

Nothing of ``rbg_tpu.models`` or ``rbg_tpu.ops``. The general pieces
(``_mm``, ``_fake_quant``, ``_rms_norm``, ``_rope``, ``_swiglu``,
``_random_leaf``) are the default module's. The weight layout is the
program's: stacks of HALF-layers, each in layer order: the mixers by kind
(``conv_mixers``: input norm, ``conv_in``, ``conv_w``, ``wo``; ``mixers``:
input norm, ``wq``, ``wk``, ``wv``, the two head norms, ``wo``) and the FFNs
by kind (``dense_mlps``, ``moe_mlps``: input norm, the SwiGLU or the router,
its bias and the held experts). Under a control, ``kv_<p>`` rounds what a
cache would hold (K and V; the ``u`` a later token's taps read back);
``<p>`` also every weight and matmul input.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from harness import reference as base

CONTROLS = base.CONTROLS

HEAD_BLOCKS = 8
PAD_TO = 512
RENORM_EPS = 1e-6       # the published ``sum + 1e-6`` of the chosen scores
KINDS = {"conv": "conv_mixers", "full_attention": "mixers"}


def sizes(cfg: dict) -> dict:
    """The numbers the forward pass needs, from the published keys, the
    share of the experts held and the file's ``assumed`` values."""
    a, L = cfg["assumed"], cfg["num_hidden_layers"]
    held = cfg["preset"].get("experts_held") or [0, cfg["num_experts"]]
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {
        "d": d, "h": h, "kv": cfg["num_key_value_heads"],
        "hd": a.get("head_dim") or d // h,
        "v": cfg["vocab_size"], "L": L,
        "types": tuple(cfg["layer_types"][:L]),
        "n_dense": cfg["num_dense_layers"],
        "f_dense": cfg["intermediate_size"],
        "f_routed": cfg["moe_intermediate_size"],
        "E": cfg["num_experts"], "K": cfg["num_experts_per_tok"],
        "held": tuple(held), "taps": cfg["conv_L_cache"],
        "theta": float(cfg["rope_parameters"]["rope_theta"]),
        "eps": float(cfg["norm_eps"]),
        "scale": float(cfg["routed_scaling_factor"]),
        "renorm": bool(cfg["norm_topk_prob"]),
        "bias": bool(cfg["use_expert_bias"]),
        "s_router": float(a["router_init_scale"]),
        "s_bias": float(a["expert_bias_scale"]),
    }


def kinds(z: dict) -> list:
    """Each layer's (mixer's params key, FFN's params key), in layer
    order."""
    return [(KINDS[t], "dense_mlps" if n < z["n_dense"] else "moe_mlps")
            for n, t in enumerate(z["types"])]


def param_shapes(cfg: dict):
    """``({path: (shape, scale, dtype)}, {path: shape}, dtype)``: the random
    leaves (normal with a scale) and the norms (ones), by path into the
    nested dict."""
    z = sizes(cfg)
    d, h, kv, hd, L = z["d"], z["h"], z["kv"], z["hd"], z["L"]
    dt = jnp.dtype(cfg["preset"].get("dtype", "bfloat16"))
    s_in, s_out = base.S_IN, base.S_IN / math.sqrt(2.0 * L)
    random = {("embed",): ((z["v"], d), s_in, dt)}
    ones = {("final_norm",): (d,)}
    held = z["held"][1] - z["held"][0]
    count = {}
    for halves in kinds(z):
        for g in halves:
            count[g] = count.get(g, 0) + 1
    for g, n in sorted(count.items()):
        if g == "conv_mixers":
            leaves = {"conv_in": ((n, d, 3 * d), s_in),
                      "conv_w": ((n, z["taps"], d), z["taps"] ** -0.5),
                      "wo": ((n, d, d), s_out)}
            ones[(g, "attn_norm")] = (n, d)
        elif g == "mixers":
            leaves = {"wq": ((n, d, h * hd), s_in),
                      "wk": ((n, d, kv * hd), s_in),
                      "wv": ((n, d, kv * hd), s_in),
                      "wo": ((n, h * hd, d), s_out)}
            ones.update({(g, "attn_norm"): (n, d),
                         (g, "q_head_norm"): (n, hd),
                         (g, "k_head_norm"): (n, hd)})
        elif g == "dense_mlps":
            f = z["f_dense"]
            leaves = {"w_gate": ((n, d, f), s_in), "w_up": ((n, d, f), s_in),
                      "w_down": ((n, f, d), s_out)}
            ones[(g, "mlp_norm")] = (n, d)
        else:
            f = z["f_routed"]
            leaves = {"router": ((n, d, z["E"]), z["s_router"]),
                      "moe_gate": ((n, held, d, f), s_in),
                      "moe_up": ((n, held, d, f), s_in),
                      "moe_down": ((n, held, f, d), s_out)}
            ones[(g, "mlp_norm")] = (n, d)
            random[(g, "router_bias")] = ((n, z["E"]), z["s_bias"],
                                          jnp.float32)
        random.update({(g, k): (*v, dt) for k, v in leaves.items()})
    return random, ones, dt


def make_params(cfg: dict, seed: int):
    """Every served weight from ``seed``, on the default device, in one
    jitted program, matrix by matrix (``base._random_leaf``). A leaf's key
    is its rank among the sorted random paths."""
    random, ones, dt = param_shapes(cfg)
    order = sorted(random)

    @jax.jit
    def build(key):
        out = {}

        def put(path, leaf):
            node = out
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = leaf

        for path, shape in ones.items():
            put(path, jnp.ones(shape, dt))
        for k, path in zip(jax.random.split(key, len(order)), order):
            put(path, base._random_leaf(k, *random[path]))
        return out

    # The chip's own bit generator (``rbg``), as the other large
    # configurations: the default one takes most of a minute for 3.8
    # billion numbers.
    seed = int(seed)
    return build(jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31))


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------


def _kv_quant(quant):
    return None if quant is None else quant.removeprefix("kv_")


def _conv_mixer(z, blk, a, quant):
    """The gated short convolution over one whole sequence ``a [T, d]``."""
    T, d, taps = a.shape[0], z["d"], z["taps"]
    b, c, x = jnp.split(base._mm(a, blk["conv_in"], quant), 3, axis=-1)
    u = b * x
    # The control's cache holds the earlier tokens' ``u`` rounded; a
    # token's own is never cached before it is used.
    earlier = base._fake_quant(u, _kv_quant(quant))
    padded = jnp.concatenate([jnp.zeros((taps - 1, d), jnp.float32), earlier])
    w = blk["conv_w"].astype(jnp.float32)
    v = w[taps - 1] * u + sum(padded[j:j + T] * w[j]
                              for j in range(taps - 1))
    return base._mm(c * v, blk["wo"], quant)


def _attention(z, blk, a, quant):
    T, h, kv, hd = a.shape[0], z["h"], z["kv"], z["hd"]
    pos = jnp.arange(T)
    q = base._mm(a, blk["wq"], quant).reshape(T, h, hd)
    k = base._mm(a, blk["wk"], quant).reshape(T, kv, hd)
    v = base._mm(a, blk["wv"], quant).reshape(T, kv, hd)
    q = base._rope(base._rms_norm(q, blk["q_head_norm"], z["eps"]), pos,
                   z["theta"])
    k = base._rope(base._rms_norm(k, blk["k_head_norm"], z["eps"]), pos,
                   z["theta"])
    if quant is not None:       # the control's cache holds them rounded
        k, v = (base._fake_quant(k, _kv_quant(quant)),
                base._fake_quant(v, _kv_quant(quant)))
    q = q.reshape(T, kv, h // kv, hd)
    s = jnp.einsum("tkgd,skd->kgts", q, k) / math.sqrt(hd)
    s = jnp.where((pos[:, None] >= pos[None, :])[None, None], s, -jnp.inf)
    o = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, axis=-1), v)
    return base._mm(o.reshape(T, h * hd), blk["wo"], quant)


def _combine_weights(z, blk, m, quant):
    """``[T, E]``: ``routed_scaling_factor * s_e / (sum of the chosen s +
    1e-6)`` for the top ``K`` experts by ``s + bias``, 0 for every other."""
    E, K = z["E"], z["K"]
    s = jax.nn.sigmoid(base._mm(m, blk["router"], quant))           # [T, E]
    pick = s + blk["router_bias"].astype(jnp.float32) if z["bias"] else s
    _, top_i = jax.lax.top_k(pick, K)
    top_w = jnp.take_along_axis(s, top_i, axis=-1)
    if z["renorm"]:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + RENORM_EPS)
    return jnp.sum(jax.nn.one_hot(top_i, E, dtype=jnp.float32)
                   * (z["scale"] * top_w)[..., None], axis=1)


def _experts(z, blk, m, quant):
    """The held experts' part of the layer, one expert at a time."""
    lo, hi = z["held"]
    w = _combine_weights(z, blk, m, quant)[:, lo:hi]

    def one(acc, e):
        y = base._swiglu(m, blk["moe_gate"][e], blk["moe_up"][e],
                         blk["moe_down"][e], quant)
        return acc + w[:, e][:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(hi - lo))
    return out


def _head(z, params, x, quant):
    """Log-probabilities ``[T, vocab]``, the tied head a block of the
    vocabulary's rows of the embedding at a time."""
    v = z["v"]
    nb = HEAD_BLOCKS if v % HEAD_BLOCKS == 0 else 1
    vb = v // nb

    def block(i):
        w = jax.lax.dynamic_slice_in_dim(params["embed"], i * vb, vb, 0)
        return base._mm(x, w.T, quant)

    logits = jax.lax.map(block, jnp.arange(nb))                # [nb, T, vb]
    logits = jnp.moveaxis(logits, 0, 1).reshape(x.shape[0], v)
    return jax.nn.log_softmax(logits, axis=-1)


@functools.partial(jax.jit, static_argnames=("zt", "rows", "quant"))
def _forward(params, tokens, start, zt, rows, quant):
    """Log-probabilities ``[rows, vocab]`` after positions ``start ..`` of
    the one sequence ``tokens`` (what follows them, padding, changes
    nothing before it: every layer is causal)."""
    z = dict(zt)
    x = params["embed"][tokens].astype(jnp.float32)                 # [T, d]
    if quant is not None and not quant.startswith("kv_"):
        x = base._fake_quant(x, quant)

    def layer(mixer, ffn, x, mix, blk):
        a = base._rms_norm(x, mix["attn_norm"], z["eps"])
        x = x + (_conv_mixer if mixer == "conv_mixers" else _attention)(
            z, mix, a, quant)
        m = base._rms_norm(x, blk["mlp_norm"], z["eps"])
        if ffn == "moe_mlps":
            return x + _experts(z, blk, m, quant)
        return x + base._swiglu(m, blk["w_gate"], blk["w_up"], blk["w_down"],
                                quant)

    # One walk over the layers; a layer's weights are its mixer's and its
    # FFN's stacks, each at the layer's ordinal among that kind.
    halves = kinds(z)
    combos = sorted(set(halves))
    at = [(combos.index(hv), [m for m, _ in halves[:n]].count(hv[0]),
           [p for _, p in halves[:n]].count(hv[1]))
          for n, hv in enumerate(halves)]

    def branch(mixer, ffn):
        def run(x, i, j):
            pick = jax.tree_util.tree_map
            return layer(mixer, ffn, x, pick(lambda w: w[i], params[mixer]),
                         pick(lambda w: w[j], params[ffn]))
        return run

    def step(x, xs):
        return jax.lax.switch(xs[0], [branch(*c) for c in combos], x,
                              xs[1], xs[2]), None

    x, _ = jax.lax.scan(step, x, tuple(
        jnp.asarray(col, jnp.int32) for col in zip(*at)))
    x = jax.lax.dynamic_slice_in_dim(x, start, rows)
    x = base._rms_norm(x, params["final_norm"], z["eps"])
    return _head(z, params, x, quant)


def chosen_logprobs(cfg: dict, params, prompt, served, quant=None):
    """Reference log-probability of each served token, given the prompt
    and the served tokens before it (teacher forcing). The sequence is
    padded to a whole number of ``PAD_TO`` tokens, so that the check's two
    prompt lengths are one compiled shape."""
    seq = list(prompt) + list(served)
    seq += [0] * (-len(seq) % PAD_TO)
    with jax.default_matmul_precision("highest"):
        lp = _forward(params, jnp.asarray(seq, jnp.int32),
                      jnp.int32(len(prompt) - 1),
                      tuple(sorted(sizes(cfg).items())), len(served), quant)
    return lp[jnp.arange(len(served)), jnp.asarray(served, jnp.int32)]
