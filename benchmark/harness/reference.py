"""The plain reference: weights from the seed and a float32 forward pass.

Everything here is the benchmark's own. It imports nothing from
``rbg_tpu.models`` or ``rbg_tpu.ops``; sizes come from the benchmark's
configuration file (the published ``config.json`` keys), weights from
``make_params`` below, which is also what the server under test is given
to serve (``serve.py``), so neither side reads anything the other made.

* ``make_params(cfg, seed)`` - one jitted call that makes every weight on
  the device, in bfloat16 (the type they are served in), matrix by matrix
  under ``lax.map`` so that no float32 copy of a stacked leaf ever exists.
* ``logprobs(cfg, params, tokens, start, quant)`` - the architecture's
  forward pass over one whole sequence in ``jax.numpy`` and float32 at
  ``default_matmul_precision("highest")``: no cache, no paging, no kernel,
  no batching. A llama-family block (RMSNorm, rotate-half RoPE, grouped
  query attention, SwiGLU) and, where the configuration has experts, the
  published Mixtral block: softmax over the expert logits, top-2,
  renormalise, sum of the chosen experts' SwiGLU outputs.
* ``quant`` is the control of the correctness check, never the reference:
  it rounds every weight matrix, every matmul input and the cached K and V
  to int8 (per-row symmetric) or float8_e4m3, the precision one step below
  the configuration's bfloat16; ``kv_int8`` rounds the cached K and V alone.

The weight layout (stacked over layers, ``[in, out]`` matrices) is the one
``rbg_tpu.models.llama`` reads; that is the single thing taken from the
program, and it is a layout, not a value.

This module is the default of a configuration that names no ``reference``
of its own (``serve.py``, ``README.md``: the contract is ``make_params``,
``chosen_logprobs`` and ``CONTROLS``). Another architecture's reference is
a module of its own under ``benchmark/references/`` and may import what
is general here: ``random_params``, ``_mm``, ``_fake_quant``, ``_rms_norm``,
``_rope``, ``_attention``, ``_swiglu``, ``_moe``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

S_IN = 0.02

# The values of ``quant`` that ``chosen_logprobs`` knows: the controls.
CONTROLS = ("int8", "fp8", "bf16", "kv_int8", "kv_fp8", "kv_bf16")


def sizes(cfg: dict) -> dict:
    """The numbers the forward pass needs, from the published keys."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {
        "d": d, "h": h, "kv": cfg["num_key_value_heads"],
        "hd": cfg.get("head_dim") or d // h,
        "f": cfg["intermediate_size"], "v": cfg["vocab_size"],
        "L": cfg["num_hidden_layers"],
        "E": cfg.get("num_local_experts", 0),
        "K": cfg.get("num_experts_per_tok", 0),
        "theta": float(cfg["rope_theta"]), "eps": float(cfg["rms_norm_eps"]),
    }


def param_shapes(cfg: dict) -> dict:
    """``{path: (shape, scale)}`` of every random leaf; norms are ones."""
    z = sizes(cfg)
    d, h, kv, hd, f, v, L, E = (z[k] for k in "d h kv hd f v L E".split())
    s_out = S_IN / math.sqrt(2.0 * L)
    blocks = {
        "wq": ((L, d, h * hd), S_IN), "wk": ((L, d, kv * hd), S_IN),
        "wv": ((L, d, kv * hd), S_IN), "wo": ((L, h * hd, d), s_out),
    }
    if E:
        blocks.update({
            "router": ((L, d, E), S_IN),
            "moe_gate": ((L, E, d, f), S_IN), "moe_up": ((L, E, d, f), S_IN),
            "moe_down": ((L, E, f, d), s_out)})
    else:
        blocks.update({
            "w_gate": ((L, d, f), S_IN), "w_up": ((L, d, f), S_IN),
            "w_down": ((L, f, d), s_out)})
    return {"embed": ((v, d), S_IN), "lm_head": ((d, v), S_IN),
            "blocks": blocks}


def _random_leaf(key, shape, scale, dtype):
    """Normal(0, scale) in ``dtype``; the trailing matrix is the unit of
    work, so the float32 draw never exceeds one matrix."""
    lead, mat = shape[:-2], shape[-2:]
    n = math.prod(lead) if lead else 1
    keys = jax.random.split(key, n)

    def one(k):
        return (jax.random.normal(k, mat, jnp.float32) * scale).astype(dtype)

    out = jax.lax.map(one, keys)
    return out.reshape(shape)


def random_params(random: dict, ones: dict, dtype, seed: int):
    """One jitted program that makes every leaf on the default device in
    ``dtype``: ``random`` is ``{path: (shape, scale)}`` (normal, drawn matrix
    by matrix), ``ones`` is ``{path: shape}`` (the norms); a path is a tuple
    of one or two keys into the nested dict that comes back. The key of a
    leaf is its rank among the random paths (one-key paths first, each
    group sorted), so a set of paths always draws the same weights."""
    order = sorted(random, key=lambda path: (len(path), path))

    @jax.jit
    def build(key):
        out = {}

        def put(path, leaf):
            node = out
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = leaf

        for path, shape in ones.items():
            put(path, jnp.ones(shape, dtype))
        for k, path in zip(jax.random.split(key, len(order)), order):
            put(path, _random_leaf(k, *random[path], dtype))
        return out

    # Key data is 2 x uint32: fold a seed of any size into it.
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    return build(key)


def make_params(cfg: dict, seed: int):
    """Every weight of the configuration from ``seed``, on the default
    device, in one jitted program."""
    z = sizes(cfg)
    shapes = param_shapes(cfg)
    random = {("embed",): shapes["embed"], ("lm_head",): shapes["lm_head"]}
    random.update({("blocks", n): v for n, v in shapes["blocks"].items()})
    ones = {("blocks", "attn_norm"): (z["L"], z["d"]),
            ("blocks", "mlp_norm"): (z["L"], z["d"]),
            ("final_norm",): (z["d"],)}
    return random_params(random, ones,
                         jnp.dtype(cfg.get("torch_dtype", "bfloat16")), seed)


# ---------------------------------------------------------------------------
# the control's rounding (never used by the reference itself)
# ---------------------------------------------------------------------------


def _fake_quant(x, quant, axis=-1):
    """Round ``x`` to ``quant``'s grid and back to float32."""
    if quant is None:
        return x
    if quant == "int8":
        scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
        scale = jnp.where(scale == 0, 1.0, scale)
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    if quant == "fp8":
        scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
        scale = jnp.where(scale == 0, 1.0, scale)
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    if quant == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    raise ValueError(f"unknown control precision {quant!r}")


def _mm(x, w, quant):
    """``x @ w`` in float32; under a control both operands are rounded
    first (activations per row, weights per output column)."""
    w = w.astype(jnp.float32)
    if quant is not None and not quant.startswith("kv_"):
        x = _fake_quant(x, quant, axis=-1)
        w = _fake_quant(w, quant, axis=0)
    return x @ w


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, positions, theta):
    """Rotate-half RoPE (the published Mistral convention).
    x: [T, heads, hd]."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(z, blk, x, quant):
    T = x.shape[0]
    h, kv, hd = z["h"], z["kv"], z["hd"]
    pos = jnp.arange(T)
    q = _rope(_mm(x, blk["wq"], quant).reshape(T, h, hd), pos, z["theta"])
    k = _rope(_mm(x, blk["wk"], quant).reshape(T, kv, hd), pos, z["theta"])
    v = _mm(x, blk["wv"], quant).reshape(T, kv, hd)
    if quant is not None:          # the control's cache holds rounded K, V
        kv_quant = quant.removeprefix("kv_")
        k, v = _fake_quant(k, kv_quant), _fake_quant(v, kv_quant)
    g = h // kv
    q = q.reshape(T, kv, g, hd)
    s = jnp.einsum("tkgd,skd->kgts", q, k) / math.sqrt(hd)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", p, v).reshape(T, h * hd)
    return _mm(o, blk["wo"], quant)


def _swiglu(x, w_gate, w_up, w_down, quant):
    return _mm(jax.nn.silu(_mm(x, w_gate, quant)) * _mm(x, w_up, quant),
               w_down, quant)


def _moe(z, blk, x, quant):
    """Mixtral's sparse block as published: softmax over all expert
    logits, the top ``K``, renormalised; each token's output is the
    weighted sum of its chosen experts. Experts are visited one at a time
    (one expert's float32 matrices exist at once)."""
    E, K = z["E"], z["K"]
    probs = jax.nn.softmax(_mm(x, blk["router"], quant), axis=-1)   # [T, E]
    top_p, top_i = jax.lax.top_k(probs, K)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(top_i, E, dtype=jnp.float32)
                   * top_p[..., None], axis=1)                      # [T, E]

    def one(acc, e):
        y = _swiglu(x, blk["moe_gate"][e], blk["moe_up"][e],
                    blk["moe_down"][e], quant)
        return acc + gate[:, e][:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(E))
    return out


@functools.partial(jax.jit, static_argnames=("zt", "start", "quant"))
def _forward(params, tokens, zt, start, quant):
    z = dict(zt)
    x = params["embed"].astype(jnp.float32)[tokens]                 # [T, d]
    if quant is not None and not quant.startswith("kv_"):
        x = _fake_quant(x, quant)

    def layer(x, blk):
        a = _rms_norm(x, blk["attn_norm"], z["eps"])
        x = x + _attention(z, blk, a, quant)
        m = _rms_norm(x, blk["mlp_norm"], z["eps"])
        if z["E"]:
            y = _moe(z, blk, m, quant)
        else:
            y = _swiglu(m, blk["w_gate"], blk["w_up"], blk["w_down"], quant)
        return x + y, None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = _rms_norm(x[start:], params["final_norm"], z["eps"])
    logits = _mm(x, params["lm_head"], quant)
    return jax.nn.log_softmax(logits, axis=-1)


def logprobs(cfg: dict, params, tokens, start: int, quant=None):
    """Log-probabilities ``[len(tokens) - start, vocab]`` of the token that
    follows each of positions ``start..`` of the one sequence ``tokens``."""
    zt = tuple(sorted(sizes(cfg).items()))
    with jax.default_matmul_precision("highest"):
        return _forward(params, jnp.asarray(tokens, jnp.int32), zt,
                        int(start), quant)


def chosen_logprobs(cfg: dict, params, prompt, served, quant=None):
    """Reference log-probability of each served token, given the prompt
    and the served tokens before it (teacher forcing): what the served
    path's prefill and cached decode steps must reproduce."""
    seq = list(prompt) + list(served)
    lp = logprobs(cfg, params, seq, len(prompt) - 1, quant)
    idx = jnp.asarray(served, jnp.int32)
    return lp[jnp.arange(len(served)), idx]
