"""The plain reference of ``ouro`` (ByteDance Ouro-2.6B, the LoopLM of
arXiv:2510.25741): the layers as the model's ``config.json`` states them,
the rules it does not state taken from the model's own ``modeling_ouro.py``
and the paper, as the file's ``assumed`` lists them.

With ``L = num_hidden_layers`` layers of weights ``theta_l`` and ``T =
total_ut_steps`` passes::

    h = E[tokens]
    for t in 0..T-1:                  # the same L layers' weights every pass
      for l in 0..L-1:
        a = RMSNorm(h; g1_l);  q, k, v = a Wq_l, a Wk_l, a Wv_l
        q, k = RoPE(q), RoPE(k)       # rotate-half, the whole head
        o = causal softmax(q k^T / sqrt(head_dim)) v     # pass t's own k, v
        h = h + RMSNorm(o Wo_l; g2_l)                    # the norm AFTER the mixer
        m = RMSNorm(h; g3_l)
        h = h + RMSNorm((silu(m Wg_l) * (m Wu_l)) Wd_l; g4_l)   # and the MLP
      h = RMSNorm(h; g_final)         # closes EVERY pass; enters pass t + 1
    logits = h W_head                 # of the last pass

Plain multi-head attention (``num_key_value_heads`` = ``num_attention_heads``),
no bias, no q/k norm. A token's keys and values are recomputed from the
hidden state of each pass, so pass ``t`` attends pass ``t``'s keys: this
module keeps no cache, so there is nothing to index by pass; a served
path that let a pass read another pass's entries would differ from it.

**The one departure:** the exit gate ``lambda_t = sigmoid(h w_exit +
b_exit)`` is held among the parameters (``exit_gate``) and not computed: at
the published ``early_exit_threshold`` of 1 no token leaves before pass
``T``, so the gate decides nothing. A threshold below 1 is refused here
as in the program.

Nothing of ``rbg_tpu.models`` or ``rbg_tpu.ops``. The general pieces
(``_mm``, ``_fake_quant``, ``_rms_norm``, ``_rope``, ``_attention``,
``_swiglu``, ``random_params``) are the default module's. The weight layout
is the program's: one stack ``blocks`` over the ``L`` layers, ``[in, out]``
matrices but for ``wq``, ``wk`` and ``wv``, which a looped model holds
``[out, in]`` (``q = a Wq^T`` here), the four norms a layer, ``final_norm``,
``exit_gate``. Under a
control, ``kv_<p>`` rounds every pass's K and V; ``<p>`` also every weight
and matmul input.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from harness import reference as base

CONTROLS = base.CONTROLS

# The projections held ``[L, out, in]`` (the layout is the program's).
OUT_IN = ("wq", "wk", "wv")
HEAD_BLOCKS = 8
PAD_TO = 128


def sizes(cfg: dict) -> dict:
    """The numbers the forward pass needs, from the published keys."""
    if float(cfg.get("early_exit_threshold", 1)) != 1.0:
        raise ValueError(
            f"early_exit_threshold {cfg['early_exit_threshold']}: the "
            f"reference runs every token through every pass (threshold 1)")
    z = base.sizes(cfg)
    z["T"] = int(cfg["total_ut_steps"])
    return z


def make_params(cfg: dict, seed: int):
    """Every weight of the configuration from ``seed``, on the default
    device, in one jitted program: the default module's dense shapes and
    scales, the two norms after a sub-layer, and the exit gate's ``w [d]``
    (normal, 0.02; drawn as a ``[1, d]`` matrix, the unit ``random_params``
    draws) and ``b [1]`` (zero, made here: ``random_params`` makes normals
    and ones). Three scales are the file's own (``assumed``; a file without
    them gets the default module's): ``embed_init_scale`` of the embedding,
    and ``attn_post_norm_init`` / ``mlp_post_norm_init``, the constant the
    weight of the norm after the mixer / after the MLP is filled with (a
    sub-layer's output reaches the residual stream at that RMS)."""
    z = base.sizes(cfg)
    L, d = z["L"], z["d"]
    assumed = cfg.get("assumed", {})
    shapes = base.param_shapes(cfg)
    embed = (shapes["embed"][0],
             float(assumed.get("embed_init_scale", base.S_IN)))
    random = {("embed",): embed, ("lm_head",): shapes["lm_head"],
              ("exit_gate", "w"): ((1, d), base.S_IN)}
    random.update({("blocks", n): v for n, v in shapes["blocks"].items()})
    for w in OUT_IN:        # [L, in, out] -> [L, out, in]
        (lead, a, b), scale = random[("blocks", w)]
        random[("blocks", w)] = ((lead, b, a), scale)
    ones = {("blocks", n): (L, d) for n in (
        "attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm")}
    ones[("final_norm",)] = (d,)
    dtype = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))
    params = base.random_params(random, ones, dtype, seed)
    for n in ("attn_post_norm", "mlp_post_norm"):
        fill = float(assumed.get(n + "_init", 1.0))
        params["blocks"][n] = (params["blocks"][n] * fill).astype(dtype)
    params["exit_gate"] = {"w": params["exit_gate"]["w"][0],
                           "b": jnp.zeros((1,), dtype)}
    return params


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
    nothing before it: every layer of every pass is causal)."""
    z = dict(zt)
    eps = z["eps"]
    x = params["embed"][tokens].astype(jnp.float32)                 # [T, d]
    if quant is not None and not quant.startswith("kv_"):
        x = base._fake_quant(x, quant)

    def layer(h, blk):
        blk = {**blk, **{w: blk[w].T for w in OUT_IN}}      # [in, out]
        a = base._rms_norm(h, blk["attn_norm"], eps)
        h = h + base._rms_norm(base._attention(z, blk, a, quant),
                               blk["attn_post_norm"], eps)
        m = base._rms_norm(h, blk["mlp_norm"], eps)
        y = base._swiglu(m, blk["w_gate"], blk["w_up"], blk["w_down"], quant)
        return h + base._rms_norm(y, blk["mlp_post_norm"], eps), None

    def one_pass(h, _):
        h, _ = jax.lax.scan(layer, h, params["blocks"])
        return base._rms_norm(h, params["final_norm"], eps), None

    x, _ = jax.lax.scan(one_pass, x, None, length=z["T"])
    # (normed already: the final norm closed the last pass)
    return _head(z, params, jax.lax.dynamic_slice_in_dim(x, start, rows),
                 quant)


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
