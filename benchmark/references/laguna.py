"""The plain reference of ``laguna`` (poolside Laguna-XS.2): the layers as the
model's ``config.json`` states them.

Every layer is pre-norm: ``y = x + mixer(RMSNorm(x))``, ``z = y +
ffn(RMSNorm(y))``; after the last layer one RMSNorm, then the untied head.

**The mixer** is grouped-query attention in two kinds (``layer_types``), with
``num_key_value_heads`` KV heads of ``head_dim`` in both and
``num_attention_heads_per_layer[l]`` query heads (48 in a full layer, 64 in a
window layer; query head ``h`` reads KV head ``h // (heads / kv)``): ``q = a
W_q``, ``k = a W_k``, ``v = a W_v``, no bias, no norm over the heads.

* ``full_attention``: the first ``r = partial_rotary_factor x head_dim``
  channels of each head are rotated (rotate-half within those ``r``), the rest
  pass unrotated. The frequencies are YaRN's (``rope_parameters
  .full_attention``): with ``f_i = theta^(-2i/r)``, ``c(n) = r ln(L / (2 pi
  n)) / (2 ln theta)`` (the index whose frequency turns ``n`` times over the
  original ``L`` positions), ``lo = max(floor(c(beta_fast)), 0)``, ``hi =
  min(ceil(c(beta_slow)), r - 1)``, ``ramp_i = clip((i - lo) / (hi - lo), 0,
  1)``: ``inv_freq_i = (f_i / factor) ramp_i + f_i (1 - ramp_i)``; cos and sin
  are multiplied by ``attention_factor``. Causal softmax at ``head_dim **
  -0.5``.
* ``sliding_attention``: plain rotate-half RoPE over the whole head at its own
  ``rope_theta``; causal and ``j > i - sliding_window`` (the query's own token
  and the ``sliding_window - 1`` before it).

**The gate** (``gating`` true): ``g = sigmoid(a W_g)``, ``W_g [d, heads]``, one
scalar a head a token from the layer's normed input; the mixer's output is
``concat_h(g_h o_h) W_o``.

**The FFN** (``mlp_layer_types``): ``dense`` a SwiGLU of ``intermediate_size``;
``sparse`` ``num_experts`` experts of ``moe_intermediate_size``: ``s =
sigmoid(m W_r)``, the top ``num_experts_per_tok`` of ``s + bias`` (the bias
picks and never weighs), ``w = s[chosen] / sum of them``, times
``moe_routed_scaling_factor``, on the experts' outputs
(``moe_apply_router_weight_on_input`` false); beside them one shared expert of
``shared_expert_intermediate_size``, ungated. **Only the experts the
configuration holds** (``preset.experts_held``, a chip's share of a layer) are
computed: the router scores and chooses over all the published experts, and
what the absent ones would add is left out, here as in the program.

Departures, and what the source does not state (the file's ``assumed``): the
gate's form (the config says ``gating: true`` and no more; the headwise sigmoid
of arXiv:2505.06708 is what the published 33.4 B parameters leave room for),
the router's scoring, bias and renormalisation (the rule of the family whose
scaling factor 2.5 the config carries), no q/k norm, an ungated shared expert,
the rotate-half pairing, bfloat16, how the router and the bias are drawn. The
program divides the chosen scores by ``max(sum, 1e-9)``; the sum of eight
sigmoids is near 4, so the guard never acts.

Nothing of ``rbg_tpu.models`` or ``rbg_tpu.ops``. The general pieces (``_mm``,
``_fake_quant``, ``_rms_norm``, ``_swiglu``, ``_random_leaf``) are the default
module's. The weight layout is the program's: stacks of HALF-layers, each in
layer order: the mixers by kind (``mixers``, ``window_mixers``: input norm,
``wq``, ``wk``, ``wv`` each held ``[out, in]`` as the program holds a model
with window layers (``ModelConfig.proj_out_in``: the layout the chip's dots
take), ``wg``, ``wo``) and the
FFNs by kind (``dense_mlps``;
``moe_mlps``: input norm, router, bias, held experts, shared expert). Under a
control, ``kv_<p>`` rounds what a cache would hold (K rotated, and V);
``<p>`` also every weight and matmul input.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from harness import reference as base

CONTROLS = base.CONTROLS

HEAD_BLOCKS = 8
PAD_TO = 512
KINDS = {"full_attention": "mixers", "sliding_attention": "window_mixers"}


def sizes(cfg: dict) -> dict:
    """The numbers the forward pass needs, from the published keys, the
    share of the experts held and the file's ``assumed`` values. The
    per-layer lists are the published ones, read below
    ``num_hidden_layers``."""
    a, L = cfg["assumed"], cfg["num_hidden_layers"]
    held = cfg["preset"].get("experts_held") or [0, cfg["num_experts"]]
    types = tuple(cfg["layer_types"][:L])
    heads = cfg["num_attention_heads_per_layer"][:L]
    by_kind = {t: {n for n, u in zip(heads, types) if u == t} for t in KINDS}
    assert all(len(v) <= 1 for v in by_kind.values()), by_kind
    rope = cfg["rope_parameters"]
    full, window = rope["full_attention"], rope["sliding_attention"]
    assert full["rope_type"] == "yarn" and window["rope_type"] == "default"
    return {
        "d": cfg["hidden_size"], "kv": cfg["num_key_value_heads"],
        "hd": cfg["head_dim"], "v": cfg["vocab_size"], "L": L,
        "types": types,
        "h_full": max(by_kind["full_attention"], default=0),
        "h_window": max(by_kind["sliding_attention"], default=0),
        "window": cfg["sliding_window"],
        "dense": tuple(t == "dense" for t in cfg["mlp_layer_types"][:L]),
        "f_dense": cfg["intermediate_size"],
        "f_routed": cfg["moe_intermediate_size"],
        "f_shared": cfg["shared_expert_intermediate_size"],
        "E": cfg["num_experts"], "K": cfg["num_experts_per_tok"],
        "held": tuple(held), "eps": float(cfg["rms_norm_eps"]),
        "scale": float(cfg["moe_routed_scaling_factor"]),
        "yarn": tuple(sorted((k, float(v)) for k, v in full.items()
                             if k != "rope_type")),
        "window_theta": float(window["rope_theta"]),
        "window_rotary": float(window["partial_rotary_factor"]),
        "s_router": float(a["router_init_scale"]),
        "s_bias": float(a["e_score_correction_bias_scale"]),
    }


def kinds(z: dict) -> list:
    """Each layer's (mixer's params key, FFN's params key), in layer
    order."""
    return [(KINDS[t], "dense_mlps" if dense else "moe_mlps")
            for t, dense in zip(z["types"], z["dense"])]


def param_shapes(cfg: dict):
    """``({path: (shape, scale, dtype)}, {path: shape}, dtype)``: the random
    leaves (normal with a scale) and the norms (ones), by path into the
    nested dict."""
    z = sizes(cfg)
    d, kv, hd, L = z["d"], z["kv"], z["hd"], z["L"]
    dt = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))
    s_in, s_out = base.S_IN, base.S_IN / math.sqrt(2.0 * L)
    random = {("embed",): ((z["v"], d), s_in, dt),
              ("lm_head",): ((d, z["v"]), s_in, dt)}
    ones = {("final_norm",): (d,)}
    held = z["held"][1] - z["held"][0]
    count = {}
    for halves in kinds(z):
        for g in halves:
            count[g] = count.get(g, 0) + 1
    for g, n in sorted(count.items()):
        if g in ("mixers", "window_mixers"):
            h = z["h_full"] if g == "mixers" else z["h_window"]
            # the input projections as [out, in] (``ModelConfig.proj_out_in``)
            leaves = {"wq": ((n, h * hd, d), s_in),
                      "wk": ((n, kv * hd, d), s_in),
                      "wv": ((n, kv * hd, d), s_in),
                      "wg": ((n, d, h), s_in),
                      "wo": ((n, h * hd, d), s_out)}
            ones[(g, "attn_norm")] = (n, d)
        elif g == "dense_mlps":
            f = z["f_dense"]
            leaves = {"w_gate": ((n, d, f), s_in), "w_up": ((n, d, f), s_in),
                      "w_down": ((n, f, d), s_out)}
            ones[(g, "mlp_norm")] = (n, d)
        else:
            f, fs = z["f_routed"], z["f_shared"]
            leaves = {"router": ((n, d, z["E"]), z["s_router"]),
                      "moe_gate": ((n, held, d, f), s_in),
                      "moe_up": ((n, held, d, f), s_in),
                      "moe_down": ((n, held, f, d), s_out),
                      "w_gate": ((n, d, fs), s_in),
                      "w_up": ((n, d, fs), s_in),
                      "w_down": ((n, fs, d), s_out)}
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
    # configurations: the default one takes most of a minute for 2.8
    # billion numbers.
    seed = int(seed)
    return build(jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31))


# ---------------------------------------------------------------------------
# positions
# ---------------------------------------------------------------------------


def yarn_inv_freq(r: int, theta: float, factor: float, original: float,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """The module docstring's ``inv_freq [r / 2]``, in float64."""
    i = np.arange(r // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / r)

    def c(n):
        return r * math.log(original / (2 * math.pi * n)) / (
            2 * math.log(theta))

    lo = max(math.floor(c(beta_fast)), 0)
    hi = min(math.ceil(c(beta_slow)), r - 1)
    ramp = np.clip((i - lo) / (hi - lo), 0.0, 1.0)
    return (f / factor) * ramp + f * (1.0 - ramp)


def _rotate(x, positions, inv_freq, factor: float):
    """Rotate-half over the first ``2 len(inv_freq)`` channels of ``x [T,
    heads, hd]``; the rest pass. cos and sin times ``factor``."""
    r = 2 * len(inv_freq)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)[None, :]
    cos, sin = (factor * jnp.cos(ang))[:, None, :], \
        (factor * jnp.sin(ang))[:, None, :]
    a, b, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------


def _kv_quant(quant):
    return None if quant is None else quant.removeprefix("kv_")


def _attention(z, window: bool, blk, a, quant):
    """One mixer over the whole sequence ``a [T, d]`` with a dense ``[T,
    T]`` mask, a KV head's group of query heads at a time."""
    T, kv, hd = a.shape[0], z["kv"], z["hd"]
    h = z["h_window"] if window else z["h_full"]
    pos = jnp.arange(T)
    q = base._mm(a, blk["wq"].T, quant).reshape(T, h, hd)
    k = base._mm(a, blk["wk"].T, quant).reshape(T, kv, hd)
    v = base._mm(a, blk["wv"].T, quant).reshape(T, kv, hd)
    if window:
        r = int(hd * z["window_rotary"])
        inv = z["window_theta"] ** (-2.0 * np.arange(r // 2) / r)
        factor = 1.0
    else:
        y = dict(z["yarn"])
        r = int(hd * y["partial_rotary_factor"])
        inv = yarn_inv_freq(r, y["rope_theta"], y["factor"],
                            y["original_max_position_embeddings"],
                            y["beta_fast"], y["beta_slow"])
        factor = y["attention_factor"]
    q, k = _rotate(q, pos, inv, factor), _rotate(k, pos, inv, factor)
    if quant is not None:       # the control's cache holds them rounded
        k, v = (base._fake_quant(k, _kv_quant(quant)),
                base._fake_quant(v, _kv_quant(quant)))
    mask = pos[:, None] >= pos[None, :]
    if window:
        mask = mask & (pos[None, :] > pos[:, None] - z["window"])
    q = jnp.moveaxis(q.reshape(T, kv, h // kv, hd), 1, 0)   # [kv, T, g, hd]

    def group(qkv):
        qg, kg, vg = qkv                    # [T, g, hd], [T, hd], [T, hd]
        s = jnp.einsum("tgd,sd->gts", qg, kg) / math.sqrt(hd)
        s = jnp.where(mask[None], s, -jnp.inf)
        return jnp.einsum("gts,sd->tgd", jax.nn.softmax(s, axis=-1), vg)

    o = jax.lax.map(group, (q, jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    o = jnp.moveaxis(o, 0, 1).reshape(T, h, hd)             # heads in order
    gate = jax.nn.sigmoid(base._mm(a, blk["wg"], quant))    # [T, h]
    return base._mm((o * gate[..., None]).reshape(T, h * hd), blk["wo"],
                    quant)


def _combine_weights(z, blk, m, quant):
    """``[T, E]``: ``moe_routed_scaling_factor * s_e / (sum of the chosen
    s)`` for the top ``K`` experts by ``s + bias``, 0 for every other."""
    E, K = z["E"], z["K"]
    s = jax.nn.sigmoid(base._mm(m, blk["router"], quant))           # [T, E]
    _, top_i = jax.lax.top_k(s + blk["router_bias"].astype(jnp.float32), K)
    top_w = jnp.take_along_axis(s, top_i, axis=-1)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(top_i, E, dtype=jnp.float32)
                   * (z["scale"] * top_w)[..., None], axis=1)


def _experts(z, blk, m, quant):
    """The held experts' part of the layer, one expert at a time, and the
    shared expert once."""
    lo, hi = z["held"]
    w = _combine_weights(z, blk, m, quant)[:, lo:hi]

    def one(acc, e):
        y = base._swiglu(m, blk["moe_gate"][e], blk["moe_up"][e],
                         blk["moe_down"][e], quant)
        return acc + w[:, e][:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(hi - lo))
    return out + base._swiglu(m, blk["w_gate"], blk["w_up"], blk["w_down"],
                              quant)


def _head(z, params, x, quant):
    """Log-probabilities ``[T, vocab]``, the head a block of the
    vocabulary's columns at a time."""
    v = z["v"]
    nb = HEAD_BLOCKS if v % HEAD_BLOCKS == 0 else 1
    vb = v // nb

    def block(i):
        w = jax.lax.dynamic_slice_in_dim(params["lm_head"], i * vb, vb, 1)
        return base._mm(x, w, quant)

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
        x = x + _attention(z, mixer == "window_mixers", mix, a, quant)
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
    padded to a whole number of ``PAD_TO`` tokens, so that the check's
    prompt lengths are few compiled shapes."""
    seq = list(prompt) + list(served)
    seq += [0] * (-len(seq) % PAD_TO)
    with jax.default_matmul_precision("highest"):
        lp = _forward(params, jnp.asarray(seq, jnp.int32),
                      jnp.int32(len(prompt) - 1),
                      tuple(sorted(sizes(cfg).items())), len(served), quant)
    return lp[jnp.arange(len(served)), jnp.asarray(served, jnp.int32)]
