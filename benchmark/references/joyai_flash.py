"""The plain reference of ``joyai_llm_flash`` (JoyAI-LLM-Flash, 48B-A2.7B):
the DeepSeek-V3 block as its ``config.json`` states it.

Every layer: pre-norm latent attention. ``a = RMSNorm(x)``; the query is
low-rank, ``q = RMSNorm(a W_qa) W_qb``, per head ``[q_nope | q_pe]``;
``a W_kva`` gives the latent and one rotary key for all heads,
``[c_kv | k_pe]``; ``c = RMSNorm(c_kv)``; ``q_pe`` and ``k_pe`` are rotated
in interleaved pairs ``(x_2i, x_2i+1)`` by ``pos * theta^(-2i/dr)``
(``rope_interleave``); every head's keys and values are up-projections of
the latent, ``c W_kvb -> [k_nope | v]``, written here in the materialised
form: per-head ``k_nope`` and ``v`` exist for every position, nothing is
absorbed into the query or the output. ``score = (q_nope . k_nope + q_pe .
k_pe) / sqrt(dn + dr)``, causal softmax, ``x += concat_h(p v) W_o``.

The first ``first_k_dense_replace`` layers: ``x += SwiGLU(RMSNorm(x))`` of
width ``intermediate_size``. Every other layer: ``m = RMSNorm(x)``; ``s =
sigmoid(m W_r)`` over all ``n_routed_experts`` (``scoring_func``); the top
``num_experts_per_tok`` of ``s + b`` are chosen (``topk_method`` noaux_tc:
the bias ``e_score_correction_bias`` picks and never weighs; ``n_group`` 1,
so the choice is over all experts at once); ``w = routed_scaling_factor *
s / (sum of the chosen s + 1e-20)`` (``norm_topk_prob``); ``x += sum_e w_e
SwiGLU_e(m) + SwiGLU_shared(m)``, the shared expert of width
``n_shared_experts * moe_intermediate_size``. The experts are walked one at
a time, so one expert's float32 matrices exist at once, and the head in
blocks of the vocabulary.

Departures from the published model, both also the configuration file's:
the drafting layer (``num_nextn_predict_layers`` 1) is not computed and
its weights are not made: it adds nothing to the next token's logits, and
an engine with speculation off does not hold it; the router's initial
scale and the bias are seeded values the source does not give
(``assumed``).

Nothing of ``rbg_tpu.models`` or ``rbg_tpu.ops``. The general pieces
(``_mm``, ``_fake_quant``, ``_rms_norm``, ``_swiglu``, ``_random_leaf``)
are the default module's; its ``_rope`` (rotate-half) and ``_moe``
(softmax) are not used. The weight layout is the program's: a stacked
group ``dense_blocks`` of the leading dense layers and a stacked group
``blocks`` of the expert layers, ``[in, out]`` matrices.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from harness import reference as base

CONTROLS = base.CONTROLS

HEAD_BLOCKS = 8


def sizes(cfg: dict) -> dict:
    """The numbers the forward pass needs, from the published keys and the
    file's ``assumed`` values."""
    a = cfg["assumed"]
    return {
        "d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
        "v": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
        "n_dense": cfg["first_k_dense_replace"],
        "f_dense": cfg["intermediate_size"],
        "f_routed": cfg["moe_intermediate_size"],
        "f_shared": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        "E": cfg["n_routed_experts"], "K": cfg["num_experts_per_tok"],
        "rq": cfg["q_lora_rank"], "dc": cfg["kv_lora_rank"],
        "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
        "dv": cfg["v_head_dim"],
        "scale": float(cfg["routed_scaling_factor"]),
        "theta": float(cfg["rope_theta"]), "eps": float(cfg["rms_norm_eps"]),
        "s_router": float(a["router_init_scale"]),
        "s_bias": float(a["e_score_correction_bias_scale"]),
    }


def param_shapes(cfg: dict):
    """``({path: (shape, scale, dtype)}, {path: shape})``: the random leaves
    and the norms (ones), by path into the nested dict."""
    z = sizes(cfg)
    d, h, L, E = z["d"], z["h"], z["L"], z["E"]
    dt = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))
    s_in, s_out = base.S_IN, base.S_IN / math.sqrt(2.0 * L)
    random = {("embed",): ((z["v"], d), s_in, dt),
              ("lm_head",): ((d, z["v"]), s_in, dt)}
    ones = {("final_norm",): (d,)}
    groups = (("dense_blocks", z["n_dense"], z["f_dense"], False),
              ("blocks", L - z["n_dense"], z["f_shared"], True))
    for g, n, f, experts in groups:
        leaves = {
            "wq_a": ((n, d, z["rq"]), s_in),
            "wq_b": ((n, z["rq"], h * (z["dn"] + z["dr"])), s_in),
            "w_dkv": ((n, d, z["dc"] + z["dr"]), s_in),
            "w_uk": ((n, z["dc"], h * z["dn"]), s_in),
            "w_uv": ((n, z["dc"], h * z["dv"]), s_in),
            "wo": ((n, h * z["dv"], d), s_out),
            "w_gate": ((n, d, f), s_in), "w_up": ((n, d, f), s_in),
            "w_down": ((n, f, d), s_out),
        }
        if experts:
            leaves.update({
                "router": ((n, d, E), z["s_router"]),
                "moe_gate": ((n, E, d, z["f_routed"]), s_in),
                "moe_up": ((n, E, d, z["f_routed"]), s_in),
                "moe_down": ((n, E, z["f_routed"], d), s_out),
            })
            random[(g, "router_bias")] = ((n, E), z["s_bias"], jnp.float32)
        random.update({(g, k): (*v, dt) for k, v in leaves.items()})
        ones.update({(g, "attn_norm"): (n, d), (g, "mlp_norm"): (n, d),
                     (g, "q_norm"): (n, z["rq"]),
                     (g, "kv_norm"): (n, z["dc"])})
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

    seed = int(seed)
    return build(jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                                    seed >> 31))


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------


def _rope_interleaved(x, positions, theta):
    """Pairs ``(x_2i, x_2i+1)`` rotated by ``pos * theta^(-2i/hd)``, left
    where they were. x: [T, heads, hd]."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _latent_attention(z, blk, a, quant):
    T, h = a.shape[0], z["h"]
    dc, dn, dr, dv = z["dc"], z["dn"], z["dr"], z["dv"]
    pos = jnp.arange(T)
    c_q = base._rms_norm(base._mm(a, blk["wq_a"], quant), blk["q_norm"],
                         z["eps"])
    q = base._mm(c_q, blk["wq_b"], quant).reshape(T, h, dn + dr)
    q_pe = _rope_interleaved(q[..., dn:], pos, z["theta"])
    kv = base._mm(a, blk["w_dkv"], quant)
    c = base._rms_norm(kv[:, :dc], blk["kv_norm"], z["eps"])    # the latent
    k_pe = _rope_interleaved(kv[:, None, dc:], pos, z["theta"])[:, 0]
    if quant is not None:       # the control's cache holds them rounded
        kv_quant = quant.removeprefix("kv_")
        c, k_pe = (base._fake_quant(c, kv_quant),
                   base._fake_quant(k_pe, kv_quant))
    k_nope = base._mm(c, blk["w_uk"], quant).reshape(T, h, dn)
    v = base._mm(c, blk["w_uv"], quant).reshape(T, h, dv)
    s = (jnp.einsum("thn,shn->hts", q[..., :dn], k_nope)
         + jnp.einsum("thr,sr->hts", q_pe, k_pe)) / math.sqrt(dn + dr)
    s = jnp.where((pos[:, None] >= pos[None, :])[None], s, -jnp.inf)
    o = jnp.einsum("hts,shv->thv", jax.nn.softmax(s, axis=-1), v)
    return base._mm(o.reshape(T, h * dv), blk["wo"], quant)


def _combine_weights(z, blk, m, quant):
    """``[T, E]``: ``routed_scaling_factor * s_e / sum of the chosen s`` for
    the top ``K`` experts by ``s + bias``, 0 for every other."""
    E, K = z["E"], z["K"]
    s = jax.nn.sigmoid(base._mm(m, blk["router"], quant))           # [T, E]
    _, top_i = jax.lax.top_k(s + blk["router_bias"].astype(jnp.float32), K)
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    top_w = z["scale"] * top_s / (jnp.sum(top_s, axis=-1, keepdims=True)
                                  + 1e-20)
    return jnp.sum(jax.nn.one_hot(top_i, E, dtype=jnp.float32)
                   * top_w[..., None], axis=1)


def _experts(z, blk, m, quant):
    w = _combine_weights(z, blk, m, quant)

    def one(acc, e):
        y = base._swiglu(m, blk["moe_gate"][e], blk["moe_up"][e],
                         blk["moe_down"][e], quant)
        return acc + w[:, e][:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(z["E"]))
    return out


def _head(z, params, x, quant):
    """Log-probabilities ``[T, vocab]``, the head a block of the vocabulary
    at a time (the control rounds a weight by output column, so blocks of
    columns round as the whole does)."""
    v = z["v"]
    nb = HEAD_BLOCKS if v % HEAD_BLOCKS == 0 else 1
    vb = v // nb

    def block(i):
        w = jax.lax.dynamic_slice_in_dim(params["lm_head"], i * vb, vb, 1)
        return base._mm(x, w, quant)

    logits = jax.lax.map(block, jnp.arange(nb))                # [nb, T, vb]
    logits = jnp.moveaxis(logits, 0, 1).reshape(x.shape[0], v)
    return jax.nn.log_softmax(logits, axis=-1)


@functools.partial(jax.jit, static_argnames=("zt", "start", "quant"))
def _forward(params, tokens, zt, start, quant):
    z = dict(zt)
    x = params["embed"][tokens].astype(jnp.float32)                 # [T, d]
    if quant is not None and not quant.startswith("kv_"):
        x = base._fake_quant(x, quant)

    def layer(experts, x, blk):
        a = base._rms_norm(x, blk["attn_norm"], z["eps"])
        x = x + _latent_attention(z, blk, a, quant)
        m = base._rms_norm(x, blk["mlp_norm"], z["eps"])
        y = base._swiglu(m, blk["w_gate"], blk["w_up"], blk["w_down"], quant)
        if experts:                       # y is the shared expert's part
            y = y + _experts(z, blk, m, quant)
        return x + y, None

    x, _ = jax.lax.scan(functools.partial(layer, False), x,
                        params["dense_blocks"])
    x, _ = jax.lax.scan(functools.partial(layer, True), x, params["blocks"])
    x = base._rms_norm(x[start:], params["final_norm"], z["eps"])
    return _head(z, params, x, quant)


def chosen_logprobs(cfg: dict, params, prompt, served, quant=None):
    """Reference log-probability of each served token, given the prompt
    and the served tokens before it (teacher forcing)."""
    seq = list(prompt) + list(served)
    with jax.default_matmul_precision("highest"):
        lp = _forward(params, jnp.asarray(seq, jnp.int32),
                      tuple(sorted(sizes(cfg).items())), len(prompt) - 1,
                      quant)
    return lp[jnp.arange(len(served)), jnp.asarray(served, jnp.int32)]
