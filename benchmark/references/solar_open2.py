"""The plain reference of ``solar_open2`` (Solar-Open2-250B): the layers as
the model's ``config.json`` states them, with the three rules it does not
state taken from the file's ``assumed``.

Every layer is pre-norm: ``x += mixer(RMSNorm(x))``, ``x += mlp(RMSNorm(x))``.
With ``a`` the normed input of a layer's mixer:

**A grouped-query attention layer** (``gqa_layers``, numbered from 0):
``q = a W_q`` (``num_attention_heads`` heads of ``head_dim``), ``k, v = a
W_k, a W_v`` (``num_key_value_heads`` heads); **nothing is rotated**
(``use_rope`` false); ``o = softmax(q k^T / sqrt(head_dim)) v``, causal;
``y = W_o (o * sigmoid(a W_gate))``, ``W_gate [d, heads x head_dim]``, a
gate a channel of every head (``use_gqa_gate``; the form is ``assumed``:
the elementwise gate on the attention output of arXiv:2505.06708).

**A gated delta-rule layer** (every other layer; ``linear_attn_config``:
heads ``h = 1..H`` of ``dk`` channels, ``num_kv_heads`` null, so as many
key heads). ``[q~, k~, v~] = a W_qkv``; each passes a causal depthwise
convolution over time of ``short_conv_kernel_size`` taps (zeros before the
first token), then SiLU; ``q = l2norm_head(.) / sqrt(dk)``, ``k =
l2norm_head(.)``, ``v`` as is; the decay a key channel ``g = -exp(A_log[h])
softplus(a W_f_down W_f_up + dt_bias)`` (``kda_use_full_proj`` false:
low-rank); the write strength a head **``b = 2 sigmoid(a W_b)``**
(``kda_allow_neg_eigval``: ``I - b k k^T`` has eigenvalues in [-1, 1]); the
state ``S [dk, dk]`` a head in float32, zero before the first token, token
by token::

    S <- (I - b k k^T) Diag(exp g) S + b k v^T        o = S^T q

and ``y = [RMSNorm_head(o) * sigmoid(a W_g_down W_g_up)] W_o``.

**The MLP** of every layer (``first_k_dense_replace`` 0): ``n_routed_experts``
sigmoid-scored experts, the top ``num_experts_per_tok`` of score + bias
chosen, their scores renormalised over the chosen (``norm_topk_prob``) and
scaled by ``routed_scaling_factor``, beside ``n_shared_experts`` shared
expert. **Only the experts the configuration holds**
(``preset.experts_held``, a chip's share of a layer) are computed: the
router scores and chooses over all the published experts, and what the
absent ones would add is left out, here as in the program. The vocabulary
is the file's ``vocab_size`` (a slice of the published one is a smaller
vocabulary).

Departures and what the source does not state (the file's ``assumed``): the
gate's form, the decay's low-rank form and its rank, the gate's rank, how
``A_log``, ``dt_bias`` and the convolution are drawn, the l2norm's epsilon
1e-6, sigmoid scoring with a selection bias, and the router's scale.

Nothing of ``rbg_tpu.models`` or ``rbg_tpu.ops``. The general pieces
(``_mm``, ``_fake_quant``, ``_rms_norm``, ``_swiglu``, ``_random_leaf``)
are the default module's. The weight layout is the program's: stacks of
HALF-layers, each in layer order: the mixers by kind (``mixers`` for
attention, ``kda_mixers``: input norm, projections, ``wo``) and the MLPs
(``moe_mlps``: input norm, router, held experts and shared expert). Under
a control, ``kv_<p>`` rounds the cached K and V and the recurrent state
(after every token); ``<p>`` also every weight and matmul input.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from harness import reference as base

CONTROLS = base.CONTROLS

HEAD_BLOCKS = 8
L2_EPS = 1e-6
PAD_TO = 512


def sizes(cfg: dict) -> dict:
    """The numbers the forward pass needs, from the published keys, the
    share of the experts held and the file's ``assumed`` values."""
    a, lin, L = cfg["assumed"], cfg["linear_attn_config"], \
        cfg["num_hidden_layers"]
    held = cfg["preset"].get("experts_held") or [0, cfg["n_routed_experts"]]
    return {
        "d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
        "kv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
        "v": cfg["vocab_size"], "L": L,
        "gqa": tuple(n for n in cfg["gqa_layers"] if n < L),
        "rope": bool(cfg["use_rope"]), "gate": bool(cfg["use_gqa_gate"]),
        "f_routed": cfg["moe_intermediate_size"],
        "f_shared": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        "E": cfg["n_routed_experts"], "K": cfg["num_experts_per_tok"],
        "held": tuple(held),
        "kh": lin["num_heads"], "dk": lin["head_dim"],
        "taps": lin["short_conv_kernel_size"], "r": a["kda_rank"],
        "b_scale": 2.0 if cfg["kda_allow_neg_eigval"] else 1.0,
        "renorm": bool(cfg["norm_topk_prob"]),
        "scale": float(cfg["routed_scaling_factor"]),
        "eps": float(cfg["rms_norm_eps"]),
        "s_router": float(a["router_init_scale"]),
        "s_bias": float(a["e_score_correction_bias_scale"]),
        "a_log": tuple(a["kda_a_log_range"]),
        "dt_bias": tuple(a["kda_dt_bias_range"]),
    }


def kinds(z: dict) -> list:
    """Each layer's mixer's params key, in layer order (every MLP is
    ``moe_mlps``)."""
    return ["mixers" if n in z["gqa"] else "kda_mixers"
            for n in range(z["L"])]


def param_shapes(cfg: dict):
    """``({path: (shape, scale or (lo, hi), dtype)}, {path: shape}, dtype)``:
    the random leaves (normal with a scale, uniform in a range) and the
    norms (ones), by path into the nested dict."""
    z = sizes(cfg)
    if z["rope"] or not z["gate"] or cfg["first_k_dense_replace"]:
        raise ValueError("this reference is of the published solar_open2: "
                         "use_rope false, use_gqa_gate true, no dense layer")
    d, h, kv, hd, L = z["d"], z["h"], z["kv"], z["hd"], z["L"]
    dt = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))
    s_in, s_out = base.S_IN, base.S_IN / math.sqrt(2.0 * L)
    random = {("embed",): ((z["v"], d), s_in, dt),
              ("lm_head",): ((d, z["v"]), s_in, dt)}
    ones = {("final_norm",): (d,)}
    ch, r = z["kh"] * z["dk"], z["r"]
    held = z["held"][1] - z["held"][0]
    n = len(z["gqa"])
    g, leaves = "mixers", {
        "wq": ((n, d, h * hd), s_in), "wk": ((n, d, kv * hd), s_in),
        "wv": ((n, d, kv * hd), s_in), "wg": ((n, d, h * hd), s_in),
        "wo": ((n, h * hd, d), s_out)}
    ones[(g, "attn_norm")] = (n, d)
    random.update({(g, k): (*v, dt) for k, v in leaves.items()})
    n = L - n
    g, leaves = "kda_mixers", {
        "kda_qkv": ((n, d, 3 * ch), s_in),
        "kda_conv": ((n, z["taps"], 3 * ch), z["taps"] ** -0.5),
        "kda_f_down": ((n, d, r), s_in), "kda_f_up": ((n, r, ch), s_in),
        "kda_wb": ((n, d, z["kh"]), s_in),
        "kda_g_down": ((n, d, r), s_in), "kda_g_up": ((n, r, ch), s_in),
        "wo": ((n, ch, d), s_out)}
    random[(g, "kda_a_log")] = ((n, z["kh"]), z["a_log"], jnp.float32)
    random[(g, "kda_dt_bias")] = ((n, ch), z["dt_bias"], jnp.float32)
    ones.update({(g, "attn_norm"): (n, d), (g, "kda_o_norm"): (n, z["dk"])})
    random.update({(g, k): (*v, dt) for k, v in leaves.items()})
    g, f = "moe_mlps", z["f_routed"]
    leaves = {
        "w_gate": ((L, d, z["f_shared"]), s_in),
        "w_up": ((L, d, z["f_shared"]), s_in),
        "w_down": ((L, z["f_shared"], d), s_out),
        "router": ((L, d, z["E"]), z["s_router"]),
        "moe_gate": ((L, held, d, f), s_in),
        "moe_up": ((L, held, d, f), s_in),
        "moe_down": ((L, held, f, d), s_out)}
    random[(g, "router_bias")] = ((L, z["E"]), z["s_bias"], jnp.float32)
    ones[(g, "mlp_norm")] = (L, d)
    random.update({(g, k): (*v, dt) for k, v in leaves.items()})
    return random, ones, dt


def make_params(cfg: dict, seed: int):
    """Every served weight from ``seed``, on the default device, in one
    jitted program, matrix by matrix (``base._random_leaf``; a range draws
    uniformly). A leaf's key is its rank among the sorted random paths."""
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
            shape, scale, dtype = random[path]
            if isinstance(scale, tuple):
                put(path, jax.random.uniform(k, shape, dtype, *scale))
            else:
                put(path, base._random_leaf(k, shape, scale, dtype))
        return out

    # The chip's own bit generator (``rbg``), as the other large
    # configurations: the default one takes most of a minute for 3.9
    # billion numbers.
    seed = int(seed)
    return build(jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31))


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------


def _kv_quant(quant):
    return None if quant is None else quant.removeprefix("kv_")


def _delta_rule(z, blk, a, quant):
    """The gated delta rule over one whole sequence ``a [T, d]``, token by
    token."""
    T, H, dk = a.shape[0], z["kh"], z["dk"]
    f32 = jnp.float32
    taps = z["taps"]
    x = base._mm(a, blk["kda_qkv"], quant)                      # [T, 3 H dk]
    xx = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), f32), x])
    w = blk["kda_conv"].astype(f32)
    y = jax.nn.silu(sum(xx[j:j + T] * w[j] for j in range(taps)))
    q, k, v = (p.reshape(T, H, dk) for p in jnp.split(y, 3, axis=-1))
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) \
        / math.sqrt(dk)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    f = base._mm(base._mm(a, blk["kda_f_down"], quant), blk["kda_f_up"],
                 quant)
    g = -jnp.exp(blk["kda_a_log"].astype(f32))[:, None] * jax.nn.softplus(
        f + blk["kda_dt_bias"].astype(f32)).reshape(T, H, dk)
    b = z["b_scale"] * jax.nn.sigmoid(base._mm(a, blk["kda_wb"], quant))
    state_quant = _kv_quant(quant)

    def token(S, xs):
        q, k, v, g, b = xs
        S = S * jnp.exp(g)[..., None]                           # Diag(a) S
        u = b[:, None] * (v - jnp.einsum("hkv,hk->hv", S, k))
        S = S + k[..., None] * u[:, None, :]
        if state_quant is not None:     # the control's cache holds it rounded
            S = base._fake_quant(S, state_quant)
        return S, jnp.einsum("hkv,hk->hv", S, q)

    _, o = jax.lax.scan(token, jnp.zeros((H, dk, dk), f32), (q, k, v, g, b))
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + z["eps"]) \
        * blk["kda_o_norm"].astype(f32)
    gate = jax.nn.sigmoid(base._mm(base._mm(a, blk["kda_g_down"], quant),
                                   blk["kda_g_up"], quant))
    return base._mm(o.reshape(T, H * dk) * gate, blk["wo"], quant)


def _attention(z, blk, a, quant):
    """Grouped-query attention without positions and with the output gate,
    a key-value head's group of query heads at a time."""
    T, h, kv, hd = a.shape[0], z["h"], z["kv"], z["hd"]
    pos = jnp.arange(T)
    q = base._mm(a, blk["wq"], quant).reshape(T, kv, h // kv, hd)
    k = base._mm(a, blk["wk"], quant).reshape(T, kv, hd)
    v = base._mm(a, blk["wv"], quant).reshape(T, kv, hd)
    if quant is not None:       # the control's cache holds them rounded
        k, v = (base._fake_quant(k, _kv_quant(quant)),
                base._fake_quant(v, _kv_quant(quant)))
    causal = (pos[:, None] >= pos[None, :])[None]

    def group(qkv):
        q, k, v = qkv                           # [T, g, hd], [T, hd] x 2
        s = jnp.einsum("tgd,sd->gts", q, k) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sd->tgd", p, v)

    o = jax.lax.map(group, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v)))
    o = jnp.moveaxis(o, 0, 1).reshape(T, h * hd)
    gate = jax.nn.sigmoid(base._mm(a, blk["wg"], quant))
    return base._mm(o * gate, blk["wo"], quant)


def _combine_weights(z, blk, m, quant):
    """``[T, E]``: ``routed_scaling_factor * s_e / sum of the chosen s`` for
    the top ``K`` experts by ``s + bias``, 0 for every other."""
    E, K = z["E"], z["K"]
    s = jax.nn.sigmoid(base._mm(m, blk["router"], quant))           # [T, E]
    _, top_i = jax.lax.top_k(s + blk["router_bias"].astype(jnp.float32), K)
    top_w = jnp.take_along_axis(s, top_i, axis=-1)
    if z["renorm"]:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
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
    """Log-probabilities ``[T, vocab]``, the head a block of the vocabulary
    at a time."""
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

    def layer(mixer, x, mix, blk):
        a = base._rms_norm(x, mix["attn_norm"], z["eps"])
        x = x + (_attention if mixer == "mixers" else _delta_rule)(
            z, mix, a, quant)
        m = base._rms_norm(x, blk["mlp_norm"], z["eps"])
        return x + base._swiglu(m, blk["w_gate"], blk["w_up"], blk["w_down"],
                                quant) + _experts(z, blk, m, quant)

    # One walk over the layers; a layer's weights are its mixer's stack at
    # the layer's ordinal among that kind and the MLPs' at its number.
    mixers = kinds(z)
    combos = sorted(set(mixers))
    at = [(combos.index(m), mixers[:n].count(m), n)
          for n, m in enumerate(mixers)]

    def branch(mixer):
        def run(x, i, j):
            pick = jax.tree_util.tree_map
            return layer(mixer, x, pick(lambda w: w[i], params[mixer]),
                         pick(lambda w: w[j], params["moe_mlps"]))
        return run

    def step(x, xs):
        return jax.lax.switch(xs[0], [branch(c) for c in combos], x,
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
