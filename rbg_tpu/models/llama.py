"""Flagship llama-family decoder: pre-norm, RoPE, GQA, SwiGLU.

TPU-first design decisions:

* **Stacked layer params + ``lax.scan``** — one transformer block is traced and
  compiled once regardless of depth (80-layer Llama-70B compiles as fast as a
  2-layer toy); parameters carry a leading ``[num_layers, ...]`` axis.
* **Static shapes everywhere** — sequence length, cache size, and batch are
  shapes; positions/lengths are data. One compiled program serves prefill and
  decode at a given (batch, seq) bucket.
* **Functional params pytree** — plain nested dict of arrays, so
  ``jax.sharding`` specs attach uniformly (see ``rbg_tpu.parallel.sharding``).

The reference (sgl-project/rbg) orchestrates engines that implement this; the
model families it deploys in ``examples/inference/*.yaml`` (Qwen2, Llama-3,
DeepSeek via SGLang) map onto the presets in ``rbg_tpu.models.config``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from rbg_tpu.models.config import ModelConfig
from rbg_tpu.ops.attention import gqa_attention
from rbg_tpu.ops.norms import rms_norm
from rbg_tpu.ops.pallas import dispatch_pallas
from rbg_tpu.ops.rope import apply_rope, rotary_tables


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """Contiguous KV cache: slot index == absolute position.

    k, v: [num_layers, B, S, KV, head_dim] (a looped model: an entry a pass
    a layer, ``cfg.cache_layers``); length: [B] int32 filled length.
    """

    k: jnp.ndarray
    v: jnp.ndarray
    length: jnp.ndarray

    @staticmethod
    def create(cfg: ModelConfig, batch: int, max_len: int, dtype=None) -> "KVCache":
        dtype = dtype or cfg.jax_dtype
        if cfg.mla:
            # MLA: k holds the compressed latent (kv_lora_rank), v the
            # shared RoPE key (qk_rope_head_dim) — one "head" each.
            return KVCache(
                k=jnp.zeros((cfg.num_layers, batch, max_len, 1,
                             cfg.kv_lora_rank), dtype),
                v=jnp.zeros((cfg.num_layers, batch, max_len, 1,
                             cfg.qk_rope_head_dim), dtype),
                length=jnp.zeros((batch,), jnp.int32),
            )
        shape = (cfg.cache_layers, batch, max_len, cfg.num_kv_heads,
                 cfg.head_dim_)
        return KVCache(
            k=jnp.zeros(shape, dtype),
            v=jnp.zeros(shape, dtype),
            length=jnp.zeros((batch,), jnp.int32),
        )


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    """Random init (normal, 0.02 scale on input projections, depth-scaled on
    output projections) in cfg.dtype. One stacked dict of block weights per
    group of ``cfg.param_groups``, under the group's key."""
    d, v, dt = cfg.hidden_size, cfg.vocab_size, cfg.jax_dtype
    s_in = 0.02
    s_out = 0.02 / jnp.sqrt(2.0 * cfg.num_layers)

    def nrm(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    params = {
        "embed": nrm(jax.random.split(key, 8)[0], (v, d), s_in),
        "final_norm": jnp.ones((d,), dt),
    }
    for i, (name, g, n) in enumerate(cfg.param_groups):
        # The group ``blocks`` draws from ``key`` itself, as the one group
        # of a one-kind model always has.
        gkey = key if name == "blocks" else jax.random.fold_in(key, 1000 + i)
        params[name] = _init_blocks(g, gkey, n, nrm, s_in, s_out)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = nrm(jax.random.fold_in(key, 99), (d, v), s_in)
    if cfg.exit_gate:   # held, not computed (``ModelConfig.exit_gate``)
        params["exit_gate"] = {"w": nrm(jax.random.fold_in(key, 98), (d,),
                                        s_in),
                               "b": jnp.zeros((1,), dt)}
    return params


def _init_blocks(cfg: ModelConfig, key, L: int, nrm, s_in, s_out) -> dict:
    """``L`` stacked layers of the one kind ``cfg`` describes, or that
    half of them ``cfg.half`` names."""
    d, f = cfg.hidden_size, cfg.intermediate_size
    hd, h, kv = cfg.head_dim_, cfg.num_heads, cfg.num_kv_heads
    dt = cfg.jax_dtype
    ks = jax.random.split(key, 8)
    blocks = {
        "attn_norm": jnp.ones((L, d), dt),
        "mlp_norm": jnp.ones((L, d), dt),
    }
    if cfg.post_norms:      # the norms AFTER the mixer and the MLP
        blocks.update(attn_post_norm=jnp.ones((L, d), dt),
                      mlp_post_norm=jnp.ones((L, d), dt))
    if cfg.half == "mlp":
        del blocks["attn_norm"]
    elif cfg.attention == "kda":
        blocks.update(_init_kda(cfg, jax.random.fold_in(key, 11), L, nrm,
                                s_in, s_out))
    elif cfg.attention == "conv":
        # The taps are drawn as KDA's: the convolution's output has about
        # its input's size.
        blocks.update({
            "conv_in": nrm(ks[1], (L, d, 3 * d), s_in),
            "conv_w": nrm(ks[2], (L, cfg.conv_kernel, d),
                          cfg.conv_kernel ** -0.5),
            "wo": nrm(ks[4], (L, d, d), s_out),
        })
    elif cfg.mla:
        dc, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
        dr, dv = cfg.qk_rope_head_dim, cfg.v_head_dim
        if cfg.q_lora_rank:
            rq = cfg.q_lora_rank
            blocks.update({
                "wq_a": nrm(ks[1], (L, d, rq), s_in),
                "q_norm": jnp.ones((L, rq), dt),
                "wq_b": nrm(jax.random.fold_in(ks[1], 1),
                            (L, rq, h * (dn + dr)), s_in),
            })
        else:
            blocks["wq"] = nrm(ks[1], (L, d, h * (dn + dr)), s_in)
        blocks.update({
            "w_dkv": nrm(ks[2], (L, d, dc + dr), s_in),
            "kv_norm": jnp.ones((L, dc), dt),
            "w_uk": nrm(ks[3], (L, dc, h * dn), s_in),
            "w_uv": nrm(jax.random.fold_in(ks[3], 1), (L, dc, h * dv), s_in),
            "wo": nrm(ks[4], (L, h * dv, d), s_out),
        })
    else:
        # ``cfg.proj_out_in``: the input projections as ``[L, out, in]``
        into = (lambda n: (L, n, d)) if cfg.proj_out_in else (
            lambda n: (L, d, n))
        blocks.update({
            "wq": nrm(ks[1], into(h * hd), s_in),
            "wk": nrm(ks[2], into(kv * hd), s_in),
            "wv": nrm(ks[3], into(kv * hd), s_in),
            "wo": nrm(ks[4], (L, h * hd, d), s_out),
        })
        if cfg.qk_norm:
            blocks.update({"q_head_norm": jnp.ones((L, hd), dt),
                           "k_head_norm": jnp.ones((L, hd), dt)})
        if cfg.attn_gate:       # a gate a channel of every head, or a head
            blocks["wg"] = nrm(
                jax.random.fold_in(ks[1], 2),
                (L, d, h if cfg.gate_a_head else h * hd), s_in)
    if cfg.half == "mixer":
        del blocks["mlp_norm"]
        return blocks
    dense_mlp = cfg.num_experts == 0 or cfg.moe_shared_expert
    if dense_mlp:
        # The shared expert (DeepSeek-style) can be narrower than the
        # dense FFN (moe_shared_expert_size); plain dense models use f.
        fs = cfg.moe_shared_f if cfg.num_experts else f
        blocks["w_gate"] = nrm(ks[5], (L, d, fs), s_in)
        blocks["w_up"] = nrm(ks[6], (L, d, fs), s_in)
        blocks["w_down"] = nrm(ks[7], (L, fs, d), s_out)
    if cfg.num_experts:
        E, mf = cfg.num_experts, cfg.moe_f
        ke = jax.random.split(jax.random.fold_in(key, 7), 4)
        blocks["router"] = nrm(ke[0], (L, d, E), s_in)
        if cfg.moe_select_bias:
            # Selects, never weighs; float32 as the scores it is added to.
            # N(0, 0.03) moves the chosen set at most positions and leaves
            # routing near uniform, as a trained balancing bias does (the
            # benchmark's joyai-llm-flash draws the same; PERF.md section 2).
            blocks["router_bias"] = 0.03 * jax.random.normal(
                jax.random.fold_in(ke[0], 1), (L, E), jnp.float32)
        E = cfg.experts_here     # the router is whole, the stacks are held
        blocks["moe_gate"] = nrm(ke[1], (L, E, d, mf), s_in)
        blocks["moe_up"] = nrm(ke[2], (L, E, d, mf), s_in)
        blocks["moe_down"] = nrm(ke[3], (L, E, mf, d), s_out)
    return blocks


# Ranges the initialiser draws the decay's parameters from, uniformly.
KDA_A_LOG = (-1.4, 0.0)         # exp: 0.25 .. 1
KDA_DT_BIAS = (-2.5, 0.3)       # softplus: 0.08 .. 0.85


def _init_kda(cfg: ModelConfig, key, L: int, nrm, s_in, s_out) -> dict:
    """The recurrent mixer's weights (``_kda_attention``). The decay's
    ``kda_a_log`` and ``kda_dt_bias`` and the convolution are drawn so that
    a token's decay ``exp(-exp(a_log) softplus(f + dt_bias))`` spreads
    over about (0.5, 1) across channels and the convolution's output has
    about its input's size; the benchmark's configuration draws the same
    (``assumed`` in its file)."""
    d, h, dk, r = (cfg.hidden_size, cfg.kda_num_heads, cfg.kda_head_dim,
                   cfg.kda_rank)
    ch, dt = h * dk, cfg.jax_dtype
    ks = jax.random.split(key, 10)
    uniform = functools.partial(jax.random.uniform, dtype=jnp.float32)
    return {
        "kda_qkv": nrm(ks[0], (L, d, 3 * ch), s_in),
        "kda_conv": nrm(ks[1], (L, cfg.kda_conv_kernel, 3 * ch),
                        cfg.kda_conv_kernel ** -0.5),
        "kda_f_down": nrm(ks[2], (L, d, r), s_in),
        "kda_f_up": nrm(ks[3], (L, r, ch), s_in),
        "kda_a_log": uniform(ks[4], (L, h), minval=KDA_A_LOG[0],
                             maxval=KDA_A_LOG[1]),
        "kda_dt_bias": uniform(ks[5], (L, ch), minval=KDA_DT_BIAS[0],
                               maxval=KDA_DT_BIAS[1]),
        "kda_wb": nrm(ks[6], (L, d, h), s_in),
        "kda_g_down": nrm(ks[7], (L, d, r), s_in),
        "kda_g_up": nrm(ks[8], (L, r, ch), s_in),
        "kda_o_norm": jnp.ones((L, dk), dt),
        "wo": nrm(ks[9], (L, ch, d), s_out),
    }


def lora_delta(x, A, B_, ids):
    """Batched multi-LoRA (punica/S-LoRA BGMV shape): per-row adapter
    gather + two skinny matmuls.

    x [B,T,d]; A [n,d,r]; B_ [n,r,o] with the per-target alpha/r scale
    FOLDED INTO B at stack-build time (per-target, so mixed-rank adapters
    scale correctly); ids [B] int32 per-row adapter slot. Returns [B,T,o]
    in x.dtype. Slot 0 is the reserved no-adapter slot (zero weights)."""
    a = A[ids]                                   # [B, d, r]
    b = B_[ids]                                  # [B, r, o]
    mid = jnp.einsum("btd,bdr->btr", x, a.astype(x.dtype))
    return jnp.einsum("btr,bro->bto", mid, b.astype(x.dtype))


def _lora_proj(xa, base_w, name, lora, lora_ids):
    y = xa @ base_w
    if lora is not None and name in lora:
        A, B_ = lora[name]
        y = y + lora_delta(xa, A, B_, lora_ids)
    return y


def _qkv(cfg: ModelConfig, blk, x, positions, lora=None, lora_ids=None):
    """Shared pre-attention math: norm → projections (+opt bias) → (opt
    RMSNorm of each query and key head, ``cfg.qk_norm``) → RoPE by the
    layer kind's rotary settings (``rope.rotary_tables`` of ``cfg``, the
    kind's group config; none without ``cfg.use_rope``: keys are cached as
    projected)."""
    B, T, _ = x.shape
    hd, h, kv = cfg.head_dim_, cfg.num_heads, cfg.num_kv_heads
    xa = rms_norm(x, blk["attn_norm"], cfg.rms_norm_eps)
    if cfg.proj_out_in:     # held [out, in]: a window model, no adapters
        q, k, vv = (jnp.einsum("btd,nd->btn", xa, blk[w])
                    for w in ("wq", "wk", "wv"))
    else:
        q = _lora_proj(xa, blk["wq"], "wq", lora, lora_ids)
        k = _lora_proj(xa, blk["wk"], "wk", lora, lora_ids)
        vv = _lora_proj(xa, blk["wv"], "wv", lora, lora_ids)
    if "bq" in blk:  # Qwen2-style attention bias
        q = q + blk["bq"]
        k = k + blk["bk"]
        vv = vv + blk["bv"]
    q = q.reshape(B, T, h, hd)
    k = k.reshape(B, T, kv, hd)
    vv = vv.reshape(B, T, kv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, blk["q_head_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, blk["k_head_norm"], cfg.rms_norm_eps)
    if cfg.use_rope:
        rotary = rotary_tables(cfg)
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_interleave,
                       rotary)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_interleave,
                       rotary)
    return q, k, vv


def _attn_gate(cfg: ModelConfig, blk, x, attn):
    """``cfg.attn_gate``: grouped-query attention's output ``[B, T, h, hd]``
    times ``sigmoid(x~ wg)``, a gate a channel of every head (``"channel"``,
    ``wg [d, h hd]``) or a gate a head (``"head"``, ``wg [d, h]``), from the
    layer's normed input (the norm is ``_qkv``'s own, which the compiler
    computes once). Called inside the ``attention`` scope: its operations'
    paths hold ``attention/gate``. Without the field, ``attn`` as it is."""
    if not cfg.attn_gate:
        return attn
    with jax.named_scope("gate"):
        xa = rms_norm(x, blk["attn_norm"], cfg.rms_norm_eps)
        gate = jax.nn.sigmoid((xa @ blk["wg"]).astype(jnp.float32))
        gate = (gate[..., None] if cfg.gate_a_head
                else gate.reshape(attn.shape))
        return (attn * gate).astype(attn.dtype)


def _mla_qkv(cfg: ModelConfig, blk, x, positions, lora=None, lora_ids=None):
    """MLA pre-attention math in the absorbed form: norm → q projection
    (split nope/rope, absorb W_uk into q) → latent down-projection
    (+kv-norm) and shared RoPE key. Returns (q_lat [B,T,h,dc],
    q_pe [B,T,h,dr], c [B,T,dc], k_pe [B,T,dr]). With ``q_lora_rank`` the
    query is low-rank: wq_a → RMSNorm → wq_b; without ``use_rope`` nothing
    is rotated and the ``dr`` channels are plain ones. LoRA applies to the plain
    input projections (wq, w_dkv); the absorbed up-projections
    (w_uk/w_uv) are not adapter targets."""
    B, T, _ = x.shape
    h = cfg.num_heads
    dc, dn, dr = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    xa = rms_norm(x, blk["attn_norm"], cfg.rms_norm_eps)
    if cfg.q_lora_rank:
        q = rms_norm(xa @ blk["wq_a"], blk["q_norm"],
                     cfg.rms_norm_eps) @ blk["wq_b"]
    else:
        q = _lora_proj(xa, blk["wq"], "wq", lora, lora_ids)
    q = q.reshape(B, T, h, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    if cfg.use_rope:
        q_pe = apply_rope(q_pe, positions, cfg.rope_theta,
                          cfg.rope_interleave)
    # Absorb: q_lat·c == q_nope·(c @ W_uk) — per-head K never materializes.
    w_uk = blk["w_uk"].reshape(dc, h, dn)
    q_lat = jnp.einsum("bthn,chn->bthc", q_nope, w_uk)
    kv = _lora_proj(xa, blk["w_dkv"], "w_dkv", lora, lora_ids)  # [B,T,dc+dr]
    c = rms_norm(kv[..., :dc], blk["kv_norm"], cfg.rms_norm_eps)
    if cfg.use_rope:
        k_pe = apply_rope(kv[..., None, dc:], positions, cfg.rope_theta,
                          cfg.rope_interleave)[:, :, 0]
    else:
        k_pe = kv[..., dc:]
    return q_lat, q_pe, c, k_pe


def _mla_out(cfg: ModelConfig, blk, attn_lat):
    """Latent attention output [B,T,h,dc] → per-head values [B,T,h,dv]
    via W_uv (the value-side absorption)."""
    dc, h, dv = cfg.kv_lora_rank, cfg.num_heads, cfg.v_head_dim
    w_uv = blk["w_uv"].reshape(dc, h, dv)
    return jnp.einsum("bthc,chv->bthv", attn_lat, w_uv)


def _mla_scale(cfg: ModelConfig) -> float:
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


# The scope of a mixer's output projection, by what mixes tokens: a KDA
# layer's lies with its input projections (``_kda_attention``).
_WO_SCOPES = {"full": "attention", "window": "attention/window",
              "conv": "attention/conv", "kda": "attention/kda/proj"}


def _post_norm(cfg: ModelConfig, blk, x, out, norm: str):
    """A sandwich norm's residual add (``cfg.post_norms``): ``x +
    RMSNorm(out; blk[norm])``, ``out`` a mixer's or an MLP's output. Under
    a scope of its own, beside ``attention`` and ``mlp`` and inside
    neither."""
    with jax.named_scope("post_norm"):
        return x + rms_norm(out, blk[norm], cfg.rms_norm_eps)


def _post_attention(cfg: ModelConfig, blk, x, attn, lora=None,
                    lora_ids=None, hit_experts=None):
    """Shared post-attention math: residual → norm → MLP/MoE → residual;
    with ``cfg.post_norms`` each residual adds the sub-layer's output
    normed once more (``_post_norm``). With ``hit_experts``
    (``_moe_mlp_hit``'s stacks, layer, live rows and kernel policy) the
    experts are the hit ones only, and their count is returned too."""
    B, T, _ = x.shape
    with jax.named_scope(_WO_SCOPES[cfg.attention]):
        out = _lora_proj(attn.reshape(B, T, -1), blk["wo"], "wo", lora,
                         lora_ids)
        if not cfg.post_norms:
            x = x + out
    if cfg.post_norms:
        x = _post_norm(cfg, blk, x, out, "attn_post_norm")
    visited = None
    with jax.named_scope("moe" if cfg.num_experts else "mlp"):
        xm = rms_norm(x, blk["mlp_norm"], cfg.rms_norm_eps)
        if hit_experts is not None:
            out, visited = _moe_mlp_hit(cfg, blk, xm, *hit_experts)
        else:
            out = _mlp(cfg, blk, xm, lora, lora_ids)
        if not cfg.post_norms:
            x = x + out
    if cfg.post_norms:
        x = _post_norm(cfg, blk, x, out, "mlp_post_norm")
    return x if hit_experts is None else (x, visited)


def _mlp(cfg: ModelConfig, blk, xm, lora=None, lora_ids=None):
    if cfg.num_experts:
        return _moe_mlp(cfg, blk, xm)   # LoRA targets dense layers only
    gate = jax.nn.silu(_lora_proj(xm, blk["w_gate"], "w_gate", lora,
                                  lora_ids))
    up = _lora_proj(xm, blk["w_up"], "w_up", lora, lora_ids)
    return _lora_proj(gate * up, blk["w_down"], "w_down", lora, lora_ids)


def _route(cfg: ModelConfig, blk, xm):
    """Combine weights ``[B, T, E]``: the scores of the top-k experts and
    exact zeros for every other expert (``_moe_mlp_hit`` reads the zeros).
    The rule is the configuration's: scores are the softmax or the sigmoid
    of the router's logits in float32; with ``moe_select_bias`` the top-k
    is taken of ``scores + router_bias``, which picks and never weighs;
    the chosen scores are renormalised to sum 1 (``moe_renormalize``) and
    scaled by ``moe_routed_scale``."""
    with jax.named_scope("router"):
        k = cfg.experts_per_token
        if cfg.moe_scoring == "sigmoid":
            # Scores near 0.5 lie a bf16 rounding apart: float32 logits.
            logits = jnp.einsum("btd,de->bte", xm, blk["router"],
                                preferred_element_type=jnp.float32)
            scores = jax.nn.sigmoid(logits)
        else:
            logits = (xm @ blk["router"]).astype(jnp.float32)  # [B, T, E]
            scores = jax.nn.softmax(logits, axis=-1)
        if cfg.moe_select_bias:
            # By index: the bias breaks "score >= k-th largest score".
            _, top = jax.lax.top_k(scores + blk["router_bias"], k)
            chosen = jnp.any(top[..., None] == jnp.arange(
                cfg.num_experts, dtype=top.dtype), axis=-2)
        else:
            top_vals, _ = jax.lax.top_k(scores, k)              # [B, T, K]
            chosen = scores >= top_vals[..., -1:]               # k-th largest
        weights = jnp.where(chosen, scores, 0.0)
        if cfg.moe_renormalize:
            weights = weights / jnp.maximum(
                weights.sum(-1, keepdims=True), 1e-9)
        if cfg.moe_routed_scale != 1.0:
            weights = weights * cfg.moe_routed_scale
        return weights.astype(xm.dtype)


def _shared_expert(blk, xm):
    with jax.named_scope("shared"):
        gate = jax.nn.silu(xm @ blk["w_gate"])
        return (gate * (xm @ blk["w_up"])) @ blk["w_down"]


def _held(cfg: ModelConfig, weights):
    """The combine weights of the experts this device holds
    (``cfg.experts_held``): the router chose and renormalised over all the
    published experts, and the absent ones' terms are left out, not stood
    in for."""
    if cfg.experts_held is None:
        return weights
    return weights[..., cfg.experts_held[0]:cfg.experts_held[1]]


def _moe_mlp(cfg: ModelConfig, blk, xm):
    """Top-k sparse MoE (DeepSeek/Mixtral-style) in the dense-dispatch
    formulation: every expert is evaluated and combined with its (mostly
    zero) routing weight. TPU-first rationale: the expert dim shards over
    the ``ep`` mesh axis (each device computes only its experts; XLA psums
    the weighted combine over ep), shapes stay static, and no sort/dispatch
    scalar code enters the graph. Right wherever a step's tokens hit every
    expert anyway (prefill, training); a step of few rows takes
    ``_moe_mlp_hit``. Routing math is exact either way."""
    weights = _held(cfg, _route(cfg, blk, xm))
    hg = jnp.einsum("btd,edf->btef", xm, blk["moe_gate"])
    hu = jnp.einsum("btd,edf->btef", xm, blk["moe_up"])
    h = jax.nn.silu(hg) * hu
    out = jnp.einsum("bte,btef,efd->btd", weights, h, blk["moe_down"])

    if cfg.moe_shared_expert:
        out = out + _shared_expert(blk, xm)
    return out


_EXPERT_STACKS = ("moe_gate", "moe_up", "moe_down")


def hit_experts_pay(cfg: ModelConfig, rows: int) -> bool:
    """Whether a step of ``rows`` tokens should visit hit experts only.
    The expected share of experts hit is 1 - (1 - K/E)^rows, of the
    published E whichever of them this device holds; at rows·K =
    2·E it is 0.87-0.90 (Mixtral: 8 rows), and a step with every expert
    hit costs 1.4 % over the dense dispatch (PERF.md, PR 29). Above that
    there is nothing to skip."""
    return bool(cfg.num_experts) and (
        rows * cfg.experts_per_token <= 2 * cfg.num_experts)


def _visit_loop(x, w, stacks, layer, ids, visited):
    """The visits in plain XLA: a loop over the first ``visited`` experts
    of ``ids``, three fusions a visit, each of which slices ``(layer,
    expert)`` out of a stack at its dot. ``x [R, D]``, ``w [R, E]``
    float32. Returns ``[R, D]`` float32. The CPU's path, the one under a
    mesh, and what ``pallas/moe_visit_kernel.py`` is held equal to."""
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)

    def visit(i, acc):
        e = ids[i]
        h = jax.nn.silu(dot(x, stacks["moe_gate"][layer, e])) * dot(
            x, stacks["moe_up"][layer, e])
        y = dot(h.astype(x.dtype), stacks["moe_down"][layer, e])
        return acc + jax.lax.dynamic_slice_in_dim(w, e, 1, axis=1) * y

    return jax.lax.fori_loop(0, visited, visit,
                             jnp.zeros(x.shape, jnp.float32))


def _moe_mlp_hit(cfg: ModelConfig, blk, xm, stacks, layer, live,
                 use_pallas: str = "never"):
    """``_moe_mlp`` for a step of few rows: only the experts some LIVE
    row routed to are evaluated, so only their weights are read. Decode is
    bound by expert bytes, and 8 rows x top-2 of 8 experts hit 7.2 of them
    on average (4 / 2 / 1 rows: 5.5 / 3.5 / 2). Every term left out is a
    product with a combine weight of exactly 0. Weights stay in their
    dtype; gate, up and the combine accumulate in float32.

    ``stacks`` are the STACKED expert weights ``[L, E, D, F]`` /
    ``[L, E, F, D]`` and ``layer`` the scan's index: each visit reads
    ``(layer, expert)`` in place, as one walk of a kernel over the hit
    experts on a TPU (``pallas/moe_visit_kernel.py``) and as a slice that
    XLA fuses into each dot elsewhere (``_visit_loop``), by
    ``dispatch_pallas``'s one policy. The scan's
    own slice ``blk["moe_gate"] [E, D, F]`` would be an operand of the
    inner loop, and so a copy of 0.94 GB per matrix per layer.
    ``live [B, T]`` marks the real rows: a padded or finished row routes
    nowhere, so it makes no expert live and only the shared expert adds
    to it. Returns (out ``[B, T, D]``, the number of experts visited)."""
    B, T, D = xm.shape
    E = cfg.experts_here
    x = xm.reshape(B * T, D)
    w = jnp.where(live.reshape(B * T, 1),
                  _held(cfg, _route(cfg, blk, xm)).reshape(B * T, E), 0)
    w = w.astype(jnp.float32)
    hit = jnp.any(w > 0, axis=0)                                # [E]
    visited = jnp.sum(hit, dtype=jnp.int32)
    ids = jnp.nonzero(hit, size=E, fill_value=0)[0].astype(jnp.int32)
    out = dispatch_pallas(use_pallas, "moe_visit_pallas", _visit_loop,
                          (x, w, stacks, layer, ids, visited))
    out = out.astype(xm.dtype).reshape(B, T, D)
    if cfg.moe_shared_expert:
        out = out + _shared_expert(blk, xm)
    return out, visited


def _head(params, cfg: ModelConfig, x) -> jnp.ndarray:
    """Shared epilogue: final norm + (tied) LM head, f32 logits. A looped
    model's hidden states come normed: its final norm closed the last pass
    as it closes every pass (``_pass_norm``)."""
    with jax.named_scope("lm_head"):
        if cfg.loop_steps == 1:
            x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        head = params.get("lm_head")
        if head is None:
            head = params["embed"].T
        return (x @ head.astype(cfg.jax_dtype)).astype(jnp.float32)


def _block(cfg: ModelConfig, x, blk, k_cache, v_cache, positions, kv_valid):
    """One transformer block over the contiguous cache. x: [B, T, D].

    With caches: reads/writes [B, S, KV, hd] slices (serving path).
    Without (``k_cache is None``): attends over the current tokens only
    (training path — no scatter, grads flow through plain matmuls).
    """
    B = x.shape[0]
    b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]      # [B, 1]
    if cfg.mla:
        from rbg_tpu.ops.mla_attention import mla_attention
        q_lat, q_pe, c, k_pe = _mla_qkv(cfg, blk, x, positions)
        if k_cache is not None:
            # k_cache holds the latent, v_cache the shared RoPE key.
            k_cache = k_cache.at[b_idx, positions].set(
                c[:, :, None, :].astype(k_cache.dtype), mode="drop")
            v_cache = v_cache.at[b_idx, positions].set(
                k_pe[:, :, None, :].astype(v_cache.dtype), mode="drop")
            attn_lat = mla_attention(q_lat, q_pe, k_cache[:, :, 0],
                                     v_cache[:, :, 0], positions, kv_valid,
                                     _mla_scale(cfg))
        else:
            T = x.shape[1]
            valid = kv_valid[:, :T] if kv_valid.shape[1] >= T else kv_valid
            attn_lat = mla_attention(q_lat, q_pe, c, k_pe, positions, valid,
                                     _mla_scale(cfg))
        attn = _mla_out(cfg, blk, attn_lat)
        return _post_attention(cfg, blk, x, attn), k_cache, v_cache
    with jax.named_scope("attention"):
        q, k, vv = _qkv(cfg, blk, x, positions)
        if k_cache is not None:
            # Write new K/V at their absolute positions (scatter per row).
            k_cache = k_cache.at[b_idx, positions].set(
                k.astype(k_cache.dtype), mode="drop")
            v_cache = v_cache.at[b_idx, positions].set(
                vv.astype(v_cache.dtype), mode="drop")
            attn = gqa_attention(q, k_cache, v_cache, positions, kv_valid)
        else:
            attn = gqa_attention(q, k, vv, positions, kv_valid)
        attn = _attn_gate(cfg, blk, x, attn)
    return _post_attention(cfg, blk, x, attn), k_cache, v_cache


def forward(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,               # [B, T] int32
    cache: KVCache,
    positions: Optional[jnp.ndarray] = None,  # [B, T] int32; default length+arange
    token_mask: Optional[jnp.ndarray] = None,  # [B, T] bool — real (non-pad) tokens
) -> Tuple[jnp.ndarray, KVCache]:
    """Run the decoder over ``tokens``, reading+writing ``cache``.

    Serves prefill (T = prompt bucket, cache.length = 0) and decode (T = 1)
    with the same traced program. Returns (logits [B, T, V], updated cache).

    Capacity contract: the caller (the serving scheduler,
    ``rbg_tpu.engine``) must guarantee ``max(positions) < cache capacity`` —
    real-token writes past capacity are dropped silently (they cannot raise
    under jit). The static part (T ≤ S) is checked at trace time.
    """
    _no_recurrent(cfg, "the contiguous cache (models.llama.forward)")
    B, T = tokens.shape
    if T > cache.k.shape[2]:
        raise ValueError(
            f"token block T={T} exceeds KV cache capacity S={cache.k.shape[2]}"
        )
    if positions is None:
        positions = cache.length[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    if token_mask is None:
        token_mask = jnp.ones((B, T), bool)

    new_length = jnp.maximum(
        cache.length,
        jnp.max(jnp.where(token_mask, positions + 1, 0), axis=1),
    )
    S = cache.k.shape[2]
    # A slot is valid if below the post-write length. (Queries additionally
    # apply the causal rule inside gqa_attention.)
    kv_valid = jnp.arange(S, dtype=jnp.int32)[None, :] < new_length[:, None]
    # Pad queries: park their writes out of bounds (mode="drop" discards them).
    write_positions = jnp.where(token_mask, positions, S)

    x = params["embed"].astype(cfg.jax_dtype)[tokens]  # [B, T, D]

    # A looped model's passes are unrolled here (this path serves tests and
    # the tiny loops, not the engine): pass ``t`` over the cache's entries
    # ``[t L, (t + 1) L)``. One pass: the loop as it always was.
    k_new, v_new = [], []
    for t in range(cfg.loop_steps):
        base = t * cfg.num_layers
        for name, g, lo, hi in cfg.layer_groups:
            def step(carry, xs, g=g):
                blk, kc, vc = xs
                h, kc, vc = _block(g, carry, blk, kc, vc, write_positions,
                                   kv_valid)
                return h, (kc, vc)

            x, (k, v) = jax.lax.scan(
                step, x, (params[name], cache.k[base + lo:base + hi],
                          cache.v[base + lo:base + hi]))
            k_new.append(k)
            v_new.append(v)
        if cfg.loop_steps > 1:
            x = _pass_norm(params, cfg, x)
    logits = _head(params, cfg, x)
    return logits, KVCache(k=jnp.concatenate(k_new), v=jnp.concatenate(v_new),
                           length=new_length)


def _pass_norm(params, cfg: ModelConfig, x):
    """What closes every pass of a looped model: the model's one final
    norm, whose output enters the next pass (or the head, which then does
    not norm again)."""
    with jax.named_scope("pass_norm"):
        return rms_norm(x, params["final_norm"], cfg.rms_norm_eps)


def _pass_addr(addr: "PoolAddr", t, entry_pages: int) -> "PoolAddr":
    """``addr`` for pass ``t`` of a looped model: the rows' one page table
    moved to that pass's cache entries, ``entry_pages`` (layers x pages a
    layer) further on in the flat pool a pass. A page id is a page of every
    (pass, layer) entry, so the allocator, the table and the prefix cache
    know nothing of passes."""
    return addr._replace(page_table=addr.page_table + t * entry_pages)


def _no_recurrent(cfg: ModelConfig, what: str) -> None:
    """Recurrent layers and window layers are served over the paged pools
    alone (``cfg.unbuilt_for`` names which the model has)."""
    if cfg.unbuilt_for:
        keeps = ("keeps no state for them" if cfg.recurrent else
                 "keeps every key and has no window mask")
        raise NotImplementedError(
            f"{cfg.name} {cfg.unbuilt_for}: {what} {keeps}; serve it "
            f"through the engine (forward_paged / forward_ragged)")


class PoolAddr(NamedTuple):
    """How a step's tokens address the paged KV pool: a ``[B, T]`` batch has
    a table line per row; a packed ``[1, T]`` step (``forward_ragged``) names
    each token's line with ``row_ids``."""
    positions: jnp.ndarray      # [B, T] int32 absolute positions
    token_mask: jnp.ndarray     # [B, T] bool — real (non-pad) tokens
    kv_lens: jnp.ndarray        # [R] int32 — cache length AFTER this step
    page_table: jnp.ndarray     # [R, P] int32 physical page ids
    row_ids: Optional[jnp.ndarray] = None   # [T] int32 token → row
    max_q_len: Optional[int] = None         # static, packed steps only
    # [R] int32: each row's slot of the recurrent-state pool (a model with
    # recurrent layers only); a row of padding names a slot out of range.
    state_slots: Optional[jnp.ndarray] = None
    # [R, P] int32: the rows' lines of the WINDOW class of page (a model
    # with window layers only), by absolute column like ``page_table``;
    # an entry whose page was given back is 0 and is never attended.
    window_table: Optional[jnp.ndarray] = None
    # ([R] int32, [R] int32), packed steps only: each row's token count and
    # its first packed offset (``_row_spans``), read once a step.
    spans: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None


def _row_spans(addr: PoolAddr):
    """What a packed step's rows hold, as its mixers read it
    (``_pool_attention``, ``_kda_packed``): ``(q_len [R], start [R])``, a
    row's real tokens in this step and the packed offset of its first
    (``T``: it has none). A row's tokens lie side by side on the packed
    axis, so the two say everything; they are read off ``row_ids`` and
    ``token_mask``, once a step where ``forward_ragged`` left them in
    ``addr.spans``."""
    if addr.spans is not None:
        return addr.spans
    T, R = addr.row_ids.shape[0], addr.kv_lens.shape[0]
    I32 = jnp.int32
    mine = (addr.row_ids == jnp.arange(R, dtype=I32)[:, None]) \
        & addr.token_mask                                        # [R, T]
    return (jnp.sum(mine, axis=1, dtype=I32),
            jnp.min(jnp.where(mine, jnp.arange(T, dtype=I32), T), axis=1))


def _pool_attention(cfg: ModelConfig, blk, x, pool, table, addr: PoolAddr,
                    use_pallas, lora=None, lora_ids=None):
    """The attention half of one layer over the flat pool: q/k/v (or the
    MLA latents, which ride the pool as the (c, k_pe) pair), the write of
    this step's slots, the attend, by the row or the packed operations as
    the input says (``row_ids``). ``table`` is the layer's own. Returns
    (attn ``[B, T, h, dv]``, pool). For a recurrent layer (``cfg.attention``
    ``kda`` or ``conv``) ``pool`` is the state pool's arrays and ``table``
    the layer's ordinal in them (``_kda_attention``, ``_conv_attention``);
    for a window layer (``window``) ``pool`` is the window class's pools
    and ``table`` the layer's own of ``addr.window_table``: the write is
    the same, the attend keeps to ``cfg.sliding_window``.

    A PACKED step's write is one scatter over the whole pack; its attend
    is split by what each row holds (``_row_spans``), as ``_kda_packed``
    splits the delta rule's, for GQA, window and latent layers alike:

    * A row of ONE token (a decoding row; a prompt whose last chunk is one
      token) takes the decode step's own attend: its query gathered
      ``[R, 1]``, ``paged_attention`` / ``paged_mla_attention`` over the
      same ``table`` with ``kv_lens`` zeroed for every other row, which
      that walk gives one item that attends nothing. ``kv_lens`` of such a
      row is its token's position + 1, so the decode kernel's causal limit
      and a window's lower end are the packed form's.
    * Rows of two tokens or more stay with the ragged attend, they alone:
      it is handed the positions with -1 at every one-token row's token,
      padding FOR THE ATTEND ONLY, and padding leads no item there.
    * A packed token takes the first attend's line if its row holds one
      token, else the second's (one scatter of ``R`` lines).

    A ragged kernel walks a one-token row at several times the decode
    kernel's cost (``ops/pallas/ragged_attention_kernel.py``); a step
    whose rows all hold chunks pays one decode call over ``R`` empty
    items. On a backend without the kernels both attends are XLA forms
    over ``paged_attention_xla``."""
    from rbg_tpu.ops.mla_attention import (paged_mla_attention,
                                            ragged_paged_mla_attention)
    from rbg_tpu.ops.paged_attention import paged_attention, write_kv_pages
    from rbg_tpu.ops.ragged_paged_attention import (ragged_paged_attention,
                                                    write_kv_pages_ragged)

    mixer = {"kda": _kda_attention, "conv": _conv_attention}
    if cfg.attention in mixer:
        with jax.named_scope(cfg.attention):
            return mixer[cfg.attention](cfg, blk, x, pool, table, addr,
                                        use_pallas)
    positions, token_mask, kv_lens, _, row_ids, max_q_len, *_ = addr
    packed = row_ids is not None
    write, rows = ((write_kv_pages_ragged, (row_ids,)) if packed
                   else (write_kv_pages, ()))
    if cfg.mla:
        *q, c, k_pe = _mla_qkv(cfg, blk, x, positions, lora, lora_ids)
        # The rotary key's pool is a whole lane tile wide
        # (``kvcache.rope_pool_width``): the key goes in zero-padded, the
        # attends read its first ``dr`` channels.
        k_pe = jnp.pad(k_pe, ((0, 0), (0, 0),
                              (0, pool[1].shape[-1] - k_pe.shape[-1])))
        k, v = c[:, :, None, :], k_pe[:, :, None, :]
    else:
        q, k, v = _qkv(cfg, blk, x, positions, lora, lora_ids)
        q = [q]
    kpf, vpf, ksf, vsf = pool = write(*pool[:2], k, v, table, *rows,
                                      positions, token_mask, *pool[2:])
    if cfg.mla:
        attends, scale = (paged_mla_attention,
                          ragged_paged_mla_attention), (_mla_scale(cfg),)
        walk = {"use_pallas": use_pallas, "c_scales": ksf, "pe_scales": vsf}
    else:
        attends, scale = (paged_attention, ragged_paged_attention), ()
        walk = {"use_pallas": use_pallas, "k_scales": ksf, "v_scales": vsf}
        if cfg.attention == "window":   # static: another walk, another mask
            walk["window"] = cfg.sliding_window

    def by_row(q, positions, kv_lens):      # a table line a row of queries
        return attends[0](*q, kpf, vpf, table, positions, kv_lens, *scale,
                          **walk)

    if not packed:
        attn = by_row(q, positions, kv_lens)
    else:
        T = positions.shape[1]
        q_len, start = _row_spans(addr)
        one = q_len == 1
        first = jnp.minimum(start, T - 1)
        # where the one-token rows' tokens lie (T, out of range: no such row)
        lone = jnp.where(one, start, T)
        attn = attends[1](*q, kpf, vpf, table,    # a table line a token
                          positions.at[0, lone].set(-1, mode="drop"),
                          kv_lens, row_ids, *scale, max_q_len=max_q_len,
                          **walk)
        o = by_row([a[0, first, None] for a in q], positions[0, first, None],
                   jnp.where(one, kv_lens, 0))               # [R, 1, h, dv]
        attn = attn.at[0, lone].set(o[:, 0], mode="drop")
    if cfg.mla:
        return _mla_out(cfg, blk, attn), pool
    return _attn_gate(cfg, blk, x, attn), pool


def _row_lines(addr: PoolAddr, T: int):
    """How the gated short convolution lays a packed step's tokens out a
    row a line: ``(lines, packed)``. ``lines(a)`` takes ``[1, T, ...]`` to
    ``[R, C, ...]`` (``C`` the longest row the step may hold; padding is
    dropped, what no token fills is zero) and ``packed(o)`` takes ``[R, C,
    ...]`` back to ``[1, T, ...]``. A step that is by row already gets
    identities. ``_conv_attention``'s alone: its lines are ``d`` wide and
    its state is a tail; the delta rule's packed step lays no line out
    (``_kda_packed``)."""
    from rbg_tpu.ops.ragged_paged_attention import _unpack_offsets

    if addr.row_ids is None:
        return (lambda a: a), (lambda o: o)
    R = addr.kv_lens.shape[0]
    C = T if addr.max_q_len is None else min(addr.max_q_len, T)
    col = _unpack_offsets(addr.row_ids)
    row = jnp.where(addr.token_mask[0], addr.row_ids, R)     # padding: dropped

    def lines(a):
        return jnp.zeros((R, C) + a.shape[2:], a.dtype).at[row, col].set(
            a[0], mode="drop")

    return lines, lambda o: o[addr.row_ids, col][None]


def _conv_attention(cfg: ModelConfig, blk, x, state, layer, addr: PoolAddr,
                    use_pallas: str):
    """The gated short convolution of one layer (LFM2; the walk is
    ``ops/short_conv.py``'s): ``[B, C, X] = x~ W_in``, a causal depthwise
    convolution of ``cfg.conv_kernel`` taps over ``u = B * X``, nothing
    activated, ``C *`` the result. ``state["tail"] [Lc, slots, (K-1) d]``
    holds each row's last ``K - 1`` values of ``u``, flat, and ``layer`` is
    this layer's ordinal in it; the rules of a slot are
    ``_kda_attention``'s: a row whose tokens start at position 0 starts
    from a zero tail whatever its slot held, every other row goes on from
    its slot, a row with no real token leaves it as it was. Plain XLA on
    every backend (``use_pallas`` is not read): a decode step moves 8 KB a
    row. Returns (``[B, T, d]`` before ``wo``; state)."""
    from rbg_tpu.ops.short_conv import gated_short_conv

    T, d = x.shape[1], cfg.hidden_size
    xa = rms_norm(x, blk["attn_norm"], cfg.rms_norm_eps)
    lines, packed = _row_lines(addr, T)
    b, c, xg, pos, mask = (lines(a) for a in (
        *jnp.split(xa @ blk["conv_in"], 3, axis=-1), addr.positions,
        addr.token_mask))
    lens = jnp.sum(mask, axis=1, dtype=jnp.int32)
    slots = addr.state_slots
    fresh = (pos[:, 0] == 0) & mask[:, 0]
    tail = state["tail"].at[layer, slots].get(mode="clip")
    tail = jnp.where(fresh[:, None], jnp.zeros_like(tail), tail)
    out, tail = gated_short_conv(b, c, xg, tail.reshape(tail.shape[0], -1, d),
                                 blk["conv_w"], lens)
    tail = tail.reshape(tail.shape[0], -1)
    state = {**state,
             "tail": state["tail"].at[layer, slots].set(tail, mode="drop")}
    return packed(out), state


def _kda_conv_qkv(h: int, dk: int, conv_w, qkv, tail, fresh, lens):
    """What a row's new tokens pass between the projection and the
    recurrence: ``qkv [R, C, 3 h dk]`` through the causal convolution
    (taps ``conv_w``) that goes on from ``tail [R, (K-1) 3 h dk]`` (the
    slot's, flat; zeros for a ``fresh`` row), split by head, ``q`` and
    ``k`` brought to unit length (``q`` scaled by ``dk ** -0.5``).
    Returns (``q, k, v [R, C, h, dk]`` float32, the new tail, flat)."""
    from rbg_tpu.ops import kda

    tail = jnp.where(fresh[:, None], jnp.zeros_like(tail), tail)
    qkv, tail = kda.short_conv(qkv, tail.reshape(tail.shape[0], -1, 3 * h * dk),
                               conv_w, lens)
    tail = tail.reshape(tail.shape[0], -1)
    q, k, v = (a.reshape(a.shape[:2] + (h, dk)).astype(jnp.float32)
               for a in jnp.split(qkv, 3, axis=-1))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
        * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    return q, k, v, tail


def _kda_rows(cfg: ModelConfig, blk, qkv, g, beta, state, layer,
              addr: PoolAddr, use_pallas: str):
    """The convolution and the recurrence of a step by row (``[B, T]``: a
    decode step, a chunk of the split prefill path): one token a row
    advances the states where they lie in the pool (``kda.kda_decode``),
    longer rows' states are gathered, walked in chunks and scattered back,
    every row's. Returns (``o [B, T, H, dk]`` float32, state)."""
    from rbg_tpu.ops import kda

    mask = addr.token_mask
    g = jnp.where(mask[..., None, None], g, 0.0)
    beta = jnp.where(mask[..., None], beta, 0.0)
    lens = jnp.sum(mask, axis=1, dtype=jnp.int32)
    slots = addr.state_slots
    fresh = (addr.positions[:, 0] == 0) & mask[:, 0]
    tail = state["conv"].at[layer, slots].get(mode="clip")
    q, k, v, tail = _kda_conv_qkv(cfg.kda_num_heads, cfg.kda_head_dim,
                                  blk["kda_conv"], qkv, tail, fresh, lens)
    if q.shape[1] == 1:
        o, s = kda.kda_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                              state["s"], layer, slots, fresh,
                              use_pallas=use_pallas)
        o = o[:, None]
    else:
        S = jnp.where(fresh[:, None, None, None], 0.0,
                      state["s"].at[layer, slots].get(mode="clip"))
        o, S = kda.kda_chunk(q, k, v, g, beta, S)
        s = state["s"].at[layer, slots].set(S, mode="drop")
    return o, {**state, "s": s, "conv": state["conv"].at[layer, slots].set(
        tail, mode="drop")}


def _kda_one_token_rows(h: int, dk: int, use_pallas: str, conv_w, qkv, g,
                        beta, s, conv, layer, slots, fresh):
    """The decode step's own path for a packed step's rows of one token:
    ``qkv [R, 3 h dk]``, ``g [R, h, dk]``, ``beta [R, h]`` each row's one
    token (zeros in ``g`` and ``beta`` for any other row), ``slots`` out
    of range for any other row. The pool's arrays ``s`` and ``conv``
    advance where they lie (``kda.kda_decode``). Returns (``o [R, h, dk]``
    float32, s, conv)."""
    from rbg_tpu.ops import kda

    live = (slots >= 0) & (slots < s.shape[1])
    tail = conv.at[layer, slots].get(mode="clip")
    q, k, v, tail = _kda_conv_qkv(h, dk, conv_w, qkv[:, None], tail, fresh,
                                  live.astype(jnp.int32))
    o, s = kda.kda_decode(q[:, 0], k[:, 0], v[:, 0], g, beta, s, layer,
                          slots, fresh, use_pallas=use_pallas)
    return o, s, conv.at[layer, slots].set(tail, mode="drop")


def _kda_chunk_row(h: int, dk: int, conv_w, qkv, g, beta, tail, S, fresh, n):
    """One row's chunk by the chunked form: ``qkv [C, 3 h dk]``, ``g [C,
    h, dk]``, ``beta [C, h]`` the ``C`` tokens from the row's first on, of
    which ``n`` are its own; ``tail [1, (K-1) 3 h dk]`` and ``S [1, h, dk,
    dk]`` its slot's; ``fresh`` a scalar. Returns (``o [C, h, dk]``
    float32, S, tail)."""
    from rbg_tpu.ops import kda

    real = jnp.arange(qkv.shape[0], dtype=jnp.int32) < n
    q, k, v, tail = _kda_conv_qkv(h, dk, conv_w, qkv[None], tail, fresh[None],
                                  n[None])
    o, S = kda.kda_chunk(
        q, k, v, jnp.where(real[:, None, None], g, 0.0)[None],
        jnp.where(real[:, None], beta, 0.0)[None], jnp.where(fresh, 0.0, S))
    return o[0], S, tail


def _kda_packed(cfg: ModelConfig, blk, qkv, g, beta, state, layer,
                addr: PoolAddr, use_pallas: str):
    """The convolution and the recurrence of a packed step (``[1, T]``,
    ``addr.row_ids``), whose cost follows the rows that hold a chunk and
    not the row bucket. A row's tokens lie side by side on the packed
    axis; how many it has, and where its first lies, is ``_row_spans``'.

    * A row of ONE token takes the decode step's path
      (``_kda_one_token_rows``): its token is gathered ``[R, 1]`` and
      advances its state where it lies in the pool (``kda.kda_decode``),
      every other row's slot named out of range, which that path treats
      as padding.
    * Rows of two tokens or more are walked by ``kda.kda_chunk``, they
      alone: ordered to the front, one a trip (``_kda_chunk_row``) of a
      loop whose length is their count (data, no static key). A trip reads
      its row's ``C`` tokens off the packed axis, its state and tail out
      of its slot, and writes state, tail and ``o`` back where they lie,
      so that no array holds every row's line or every row's state. One
      row a trip: a row's sub-chunk is some twenty small operations the
      chip runs one after another, and a trip of two or four rows cost
      the step of one chunk row more than it saved the ramp (PERF.md,
      PR 42).
    * A row with no token is in neither set: its slot is not touched.

    Returns (``o [1, T, H, dk]`` float32, state)."""
    T = qkv.shape[1]
    C = T if addr.max_q_len is None else min(addr.max_q_len, T)
    I32 = jnp.int32
    h, dk, conv_w = cfg.kda_num_heads, cfg.kda_head_dim, blk["kda_conv"]
    qkv, g, beta = qkv[0], g[0], beta[0]
    slots, n_slots = addr.state_slots, state["s"].shape[1]
    q_len, start = _row_spans(addr)
    first = jnp.minimum(start, T - 1)
    fresh = (addr.positions[0, first] == 0) & (q_len > 0)
    layer = jnp.asarray(layer, I32)

    # one token: the decode step's own path, in place on the pool
    one = q_len == 1
    o, s, conv = _kda_one_token_rows(
        h, dk, use_pallas, conv_w, qkv[first],
        jnp.where(one[:, None, None], g[first], 0.0),
        jnp.where(one[:, None], beta[first], 0.0), state["s"], state["conv"],
        layer, jnp.where(one, slots, n_slots), fresh)
    o = jnp.zeros((T,) + o.shape[1:], o.dtype).at[
        jnp.where(one, start, T)].set(o, mode="drop")

    # two tokens or more: the chunked form, a row a trip
    chunk = (q_len > 1) & (slots >= 0) & (slots < n_slots)
    order = jnp.argsort(~chunk, stable=True).astype(I32)         # they lead
    col = jnp.arange(C, dtype=I32)

    def entry(pool, slot):          # [1, ...]: the slot's own, of this layer
        return jax.lax.dynamic_slice(
            pool, (layer, slot) + (0,) * (pool.ndim - 2),
            (1, 1) + pool.shape[2:])[0]

    def put(pool, slot, new):
        return jax.lax.dynamic_update_slice(
            pool, new[None].astype(pool.dtype),
            (layer, slot) + (0,) * (pool.ndim - 2))

    def trip(i, carry):
        s, conv, o = carry
        row = order[i]
        at, where = slots[row], start[row] + col
        read = jnp.minimum(where, T - 1)
        o_row, S, tail = _kda_chunk_row(
            h, dk, conv_w, qkv[read], g[read], beta[read], entry(conv, at),
            entry(s, at), fresh[row], q_len[row])
        return (put(s, at, S), put(conv, at, tail), o.at[jnp.where(
            col < q_len[row], where, T)].set(o_row, mode="drop"))

    s, conv, o = jax.lax.fori_loop(0, jnp.sum(chunk, dtype=I32), trip,
                                   (s, conv, o))
    return o[None], {**state, "s": s, "conv": conv}


def _kda_attention(cfg: ModelConfig, blk, x, state, layer, addr: PoolAddr,
                   use_pallas: str):
    """The recurrent mixer of one layer (Kimi Delta Attention; the
    recurrence and its forms are ``ops/kda.py``'s). ``state`` is the pool's
    ``{"s": [Lk, slots, H, dk, dk] float32, "conv": [Lk, slots, (K-1) 3 H
    dk]}`` and ``layer`` this layer's ordinal in it; a row's slot is
    ``addr.state_slots``. A row whose tokens start at position 0 starts
    from a zero state and a zero convolution tail, whatever its slot held;
    every other row goes on from what its slot holds; a row with no real
    token leaves it as it was. The projections run on the step's tokens as
    they come, by row ``[B, T]`` or packed ``[1, T]``; the convolution and
    the recurrence are ``_kda_rows``'s for a step by row and
    ``_kda_packed``'s for a packed one, whose rows of one token take the
    decode step's path in place on the pool and whose rows that hold a
    chunk are walked by the chunked form, they alone. Returns (``[B, T, H,
    dk]``, normed by head and gated, before ``wo``; state)."""
    B, T, _ = x.shape
    h, dk, f32 = cfg.kda_num_heads, cfg.kda_head_dim, jnp.float32
    xa = rms_norm(x, blk["attn_norm"], cfg.rms_norm_eps)
    # The projections and what shapes them; ``wo`` lies under the same
    # path, ``attention/kda/proj`` (``_WO_SCOPES``).
    with jax.named_scope("proj"):
        qkv = xa @ blk["kda_qkv"]                            # [B, T, 3 ch]
        f = ((xa @ blk["kda_f_down"]) @ blk["kda_f_up"]).astype(f32)
        g = -jnp.exp(blk["kda_a_log"].astype(f32))[:, None] \
            * jax.nn.softplus(f + blk["kda_dt_bias"].astype(f32)).reshape(
                B, T, h, dk)
        beta = jax.nn.sigmoid((xa @ blk["kda_wb"]).astype(f32))  # [B, T, h]
        if cfg.kda_beta_scale != 1.0:   # 2: eigenvalues down to -1
            beta = cfg.kda_beta_scale * beta
        gate = jax.nn.sigmoid(
            ((xa @ blk["kda_g_down"]) @ blk["kda_g_up"]).astype(f32))

    walk = _kda_rows if addr.row_ids is None else _kda_packed
    o, state = walk(cfg, blk, qkv, g, beta, state, layer, addr, use_pallas)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg.rms_norm_eps) * blk["kda_o_norm"].astype(f32)
    return (o * gate.reshape(B, T, h, dk)).astype(x.dtype), state


def _mixer_program(kind: str):
    """``_pool_attention`` of a recurrent layer of ``kind`` as a program of
    its own, ``_<kind>_mixer``: ``_hybrid_layers`` walks a recurrent mixer
    in two places (the dense first layers alone, the expert layers in
    their loop), and a step program traces and lowers it once for both;
    the compiler inlines it. ``layer`` is an int32 array in both places,
    ``addr.max_q_len`` rides beside ``addr`` because it is static. The
    scope opens in here so that an operation's path holds
    ``attention/<kind>`` with nothing between."""
    def mixer(cfg: ModelConfig, use_pallas, max_q_len, blk, x, state, layer,
              addr: PoolAddr):
        with jax.named_scope("attention"):
            return _pool_attention(cfg, blk, x, state, layer,
                                   addr._replace(max_q_len=max_q_len),
                                   use_pallas)

    mixer.__name__ = mixer.__qualname__ = f"_{kind}_mixer"
    return jax.jit(mixer, static_argnums=(0, 1, 2))


_RECURRENT_MIXERS = {kind: _mixer_program(kind) for kind in ("kda", "conv")}


def _hybrid_plan(cfg: ModelConfig):
    """How ``_hybrid_layers`` walks a model whose mixers alternate, by KIND
    of layer, the pair ``(mixer's params key, MLP's params key)``:
    ``("run", kind, lo, hi)`` for a kind that stands in one run of layers,
    and ``("turns", kind A, kind B, rows)`` for a stretch in which two kinds
    take turns, a row ``(layers of A, layers of B, first layer)`` a turn.
    A dense first layer before the expert layers its mixer leads is a kind
    in one run, so a segment of its own, and the first turn is that much
    shorter. More than two kinds in turns (dense layers that reach into
    the second turn) are not walked."""
    kinds = [(h[1], h[3]) for h in cfg.layer_halves]
    runs = []                                   # [kind, lo, hi]
    for layer, kind in enumerate(kinds):
        if runs and runs[-1][0] == kind:
            runs[-1][2] = layer + 1
        else:
            runs.append([kind, layer, layer + 1])
    times = {kind: sum(r[0] == kind for r in runs) for kind in set(kinds)}
    plan, i = [], 0
    while i < len(runs):
        kind, lo, hi = runs[i]
        if times[kind] == 1:
            plan.append(("run", kind, lo, hi))
            i += 1
            continue
        a, b, rows = kind, None, []
        while i < len(runs) and times[runs[i][0]] > 1:
            kind, lo, hi = runs[i]
            if kind == a:
                rows.append([hi - lo, 0, lo])
            else:
                b = b or kind
                if kind != b:
                    name = "+".join
                    raise NotImplementedError(
                        f"{cfg.name}: layers of more than two kinds take "
                        f"turns ({name(a)}, {name(b)}, {name(kind)}) in the "
                        f"pattern " + " ".join(
                            f"{name(k)}:{hi - lo}" for k, lo, hi in runs))
                rows[-1][1] = hi - lo
            i += 1
        plan.append(("turns", a, b, rows))
    return plan


def _hybrid_layers(params: dict, cfg: ModelConfig, x, pool, addr: PoolAddr,
                   use_pallas: str, experts_whole: bool):
    """``paged_layers`` for a model whose layers differ in what mixes
    tokens (``cfg.mixer_kinds``): every layer, in order, each over its own
    cache: the page pool ``[attention layers, NP, ...]`` by the layer's
    ordinal among the attention layers, the state pool (``pool[4]``) by
    its ordinal among the recurrent ones, the window class's pools
    (``pool[5]``, ``(k, v) [window layers, NPw, ...]``, where the model has
    window layers) by its ordinal among those, under the rows' second
    table (``addr.window_table``). A compile follows the number of
    distinct loop bodies, so each KIND of layer (a mixer and an MLP) is
    traced once, not each of the runs: the parameters are stacked by
    half-layer (``cfg.param_groups``), kinds that take turns are walked a
    turn at a time, the layers of a turn in a loop of that turn's own
    length, and a layer's weights are read from its halves' stacks by
    their ordinals there. A loop carries one kind: a kind of a single
    layer (a dense first layer before expert layers) is walked on its
    own, its ordinals static. No body branches on the MLP's kind: the
    operands of a ``lax.cond`` in a loop are copied whole in every trip,
    whichever branch runs (a dense MLP's ``w_down`` and ``w_up``, 85 MB
    a trip; PERF.md, PR 38)."""
    *pages, state = pool[:5]
    window = pool[5] if len(pool) > 5 else None
    NP = pages[0].shape[1]
    flatten = functools.partial(
        jax.tree_util.tree_map, lambda p: p.reshape((-1,) + p.shape[2:]))
    flat = flatten(tuple(pages))
    wflat = None
    if window is not None:      # the window class, with no int8 form
        NPw, wflat = window[0].shape[1], (*flatten(tuple(window)), None, None)
    rows = x.shape[0] * x.shape[1]
    halves = cfg.layer_halves
    configs = {(h[1], h[3]): h[0] for h in halves}
    # A mixer reads its own fields of a layer's config, so one layer's
    # stands for every layer of that mixer: ``_mixer_program``'s static key.
    mixer_cfg = {h[1]: h[0] for h in reversed(halves)}
    # By absolute layer: the ordinal among its mixer kind's layers, in its
    # params and in its pool alike (a kind's layers are its pool's, in order).
    mixer_at = jnp.asarray([h[2] for h in halves], jnp.int32)
    mlp_at = jnp.asarray([h[4] for h in halves], jnp.int32)

    def layer(kind, li, carry):
        """Layer ``li`` of ``kind``: a loop's counter, or the layer's own
        number (an int) where it is walked alone."""
        h, flat, state, seen, wflat = carry
        (key, mlp), g = kind, configs[kind]
        m, n = ((halves[li][2], halves[li][4]) if isinstance(li, int)
                else (mixer_at[li], mlp_at[li]))
        blk = {k: v[m] for k, v in params[key].items()}
        if g.attention in _RECURRENT_MIXERS:
            attn, state = _RECURRENT_MIXERS[g.attention](
                mixer_cfg[key], use_pallas, addr.max_q_len, blk, h, state,
                jnp.asarray(m, jnp.int32), addr._replace(max_q_len=None))
        elif g.attention == "window":
            with jax.named_scope("attention"), jax.named_scope("window"):
                attn, wflat = _pool_attention(
                    g, blk, h, wflat, addr.window_table + m * NPw, addr,
                    use_pallas)
        else:
            with jax.named_scope("attention"):
                attn, flat = _pool_attention(
                    g, blk, h, flat, addr.page_table + m * NP, addr,
                    use_pallas)
        # The expert stacks stay whole for the hit form, which reads
        # ``(layer, expert)`` at each visit.
        hit_only = experts_whole and hit_experts_pay(g, rows)
        blk.update((k, v[n]) for k, v in params[mlp].items()
                   if not (hit_only and k in _EXPERT_STACKS))
        if not hit_only:
            return _post_attention(g, blk, h, attn), flat, state, seen, wflat
        stacks = {k: params[mlp][k] for k in _EXPERT_STACKS}
        h, visited = _post_attention(
            g, blk, h, attn,
            hit_experts=(stacks, n, addr.token_mask, use_pallas))
        return h, flat, state, seen + visited, wflat

    def loop(kind, count, l0, carry):
        return jax.lax.fori_loop(
            0, count, lambda i, c: layer(kind, l0 + i, c), carry)

    carry = (x, flat, state, jnp.zeros((), jnp.int32), wflat)
    for seg in _hybrid_plan(cfg):
        if seg[0] == "run":
            _, kind, lo, hi = seg
            carry = (layer(kind, lo, carry) if hi - lo == 1
                     else loop(kind, hi - lo, lo, carry))
            continue
        _, a, b, turns = seg

        def turn(carry, t):
            na, nb, l0 = t
            carry = loop(a, na, l0, carry)
            if b is not None:
                carry = loop(b, nb, l0 + na, carry)
            return carry, None

        carry, _ = jax.lax.scan(turn, carry, tuple(
            jnp.asarray(col, jnp.int32) for col in zip(*turns)))
    x, flat, state, seen, wflat = carry
    experts = experts_whole and any(
        hit_experts_pay(h[0], rows) for h in halves)
    unflatten = functools.partial(jax.tree_util.tree_map,
                                  lambda f, p: f.reshape(p.shape))
    pool = (*unflatten(flat, tuple(pages)), state)
    if window is not None:
        pool += (unflatten(wflat[:2], tuple(window)),)
    return x, pool, seen[None] if experts else None


def paged_layers(params: dict, cfg: ModelConfig, x, pool, addr: PoolAddr, *,
                 layers: Tuple[int, int], use_pallas: str = "auto", lora=None,
                 lora_ids=None, experts_whole: bool = False,
                 sharded: bool = False):
    """The one walk over cache-bearing layers (every pass of them, where
    ``cfg.loop_steps`` says the stack runs more than once): layers ``[lo,
    hi)`` (static)
    over the hidden states ``x [B, T, D]`` entering layer ``lo``, writing and
    attending those layers' pages of the FULL pool, the tuple ``(k_pages,
    v_pages, k_scales, v_scales)``, ``[L, NP, page, KV, hd]`` each (scales
    None unless int8; a model with recurrent layers adds a fifth, the state
    pool's arrays, and its pages are the attention layers' alone:
    ``_hybrid_layers``). A chain of windows covering every layer is the whole
    walk (``tests/test_layer_walk.py``): ``engine/pd.py`` chains them so that
    a first decode step starts when the leading layers' KV has arrived.
    Returns (x, pool, visited): ``visited`` counts the experts each expert
    layer of the window visited where the hit-experts form ran
    (``experts_whole`` and ``hit_experts_pay``), else None. ``sharded``:
    the parameters lie over a mesh, so the visits keep the XLA loop, whose
    F the compiler splits (the kernel would need a ``shard_map`` of its
    own)."""
    lo, hi = layers
    if cfg.by_kind:
        if (lo, hi) != (0, cfg.num_layers) or lora is not None:
            raise NotImplementedError(
                f"{cfg.name} {cfg.unbuilt_for}: its layers are walked "
                f"whole and without adapters (layers {layers}, or LoRA, "
                f"was asked for)")
        return _hybrid_layers(params, cfg, x, pool, addr, use_pallas,
                              experts_whole)
    if cfg.loop_steps > 1:
        # A looped model: the passes are one scan whose body is THIS walk
        # over the same stacked weights (the scan's invariants) as a model
        # that runs its layers once, the pool ``[T x L, NP, ...]`` its
        # carry beside ``x``, pass ``t`` of layer ``l`` on entry ``t L + l``
        # (``_pass_addr``), the final norm at the end of the body. A model
        # of one pass never comes here: its program is what it was.
        if (lo, hi) != (0, cfg.num_layers):
            raise NotImplementedError(
                f"{cfg.name} {cfg.looped_for}: its layers are walked whole, "
                f"every pass (layers {layers} was asked for)")
        once = cfg._kind(loop_steps=1)  # (its projections held as cfg's)
        entry_pages = cfg.num_layers * pool[0].shape[1]

        def one_pass(carry, t):
            h, pool = carry
            h, pool, _ = paged_layers(
                params, once, h, pool, _pass_addr(addr, t, entry_pages),
                layers=layers, use_pallas=use_pallas, lora=lora,
                lora_ids=lora_ids, experts_whole=experts_whole,
                sharded=sharded)
            return (_pass_norm(params, cfg, h), pool), None

        (x, pool), _ = jax.lax.scan(
            one_pass, (x, pool), jnp.arange(cfg.loop_steps, dtype=jnp.int32))
        return x, pool, None
    # The pool rides the layer scan as CARRY over a [L·NP, …] flat view,
    # with each layer addressing its pages as ``layer·NP + page_table``. As
    # a per-layer scan INPUT/OUTPUT (stacked ys) the entire pool would be
    # copied every step, though only [B·T] slots changed; the in-place carry
    # scatter keeps a step's KV traffic at the written slots.
    L_, NP = pool[0].shape[:2]
    flat = jax.tree_util.tree_map(
        lambda p: p.reshape((L_ * NP,) + p.shape[2:]), pool)

    # One scan a group of ``cfg.layer_groups`` that the window meets (a
    # one-kind model: one scan), each over its own stacked weights; the
    # pool is the one pool, addressed by absolute layer. The loop's body
    # stands here and not in a function of its own: one more Python frame
    # under everything the scan traces cost the server's warm-up 6 s of
    # CPU (CPython's 16 KiB frame chunks; PERF.md section 6, PR 32).
    visited = []
    visit_policy = "never" if sharded else use_pallas
    for name, g, first, end in cfg.layer_groups:
        glo, ghi = max(lo, first), min(hi, end)
        if glo >= ghi:
            continue
        blocks = params[name]
        # The hit-experts form takes the stacked expert weights as the
        # scan's invariants, addressed by (layer, expert) like the pool
        # above: as scanned inputs each layer's [E, D, F] slice would be
        # copied whole.
        hit_only = experts_whole and hit_experts_pay(
            g, x.shape[0] * x.shape[1])
        if hit_only:
            stacks = {k: blocks[k] for k in _EXPERT_STACKS}
            blocks = {k: v for k, v in blocks.items() if k not in stacks}

        def step(carry, xs):     # traced by this turn's scan, below
            hcur, flat = carry
            blk, li, lr = xs
            table = addr.page_table + li * NP
            with jax.named_scope("attention"):
                attn, flat = _pool_attention(g, blk, hcur, flat, table, addr,
                                             use_pallas, lr, lora_ids)
            # The stacks are the group's: its own layer index (no
            # subtraction where the group starts the model, so that a
            # one-group model's program is the one it was).
            hit = ((stacks, li - first if first else li, addr.token_mask,
                    visit_policy) if hit_only else None)
            out = _post_attention(g, blk, hcur, attn, lr, lora_ids, hit)
            out, seen = out if hit_only else (out, None)
            return (out, flat), seen

        # Block weights carry the group's layers, LoRA A/B every layer, on
        # the leading axis: scan-sliced per layer.
        blocks = jax.tree_util.tree_map(
            lambda a: a[glo - first:ghi - first], blocks)
        adapters = jax.tree_util.tree_map(lambda a: a[glo:ghi], lora)
        (x, flat), seen = jax.lax.scan(
            step, (x, flat),
            (blocks, jnp.arange(glo, ghi, dtype=jnp.int32), adapters))
        if seen is not None:
            visited.append(seen)
    visited = (None if not visited else visited[0] if len(visited) == 1
               else jnp.concatenate(visited))
    return x, jax.tree_util.tree_map(lambda f, p: f.reshape(p.shape), flat,
                                     pool), visited


def _all_pools(k_pages, v_pages, k_scales, v_scales, state, window_pages):
    """The pools a step walks, as ``paged_layers`` takes and returns them:
    the four page pools, then the state pool's arrays where the model has
    recurrent layers, then (after the state's place) the window class's
    pools where it has window layers."""
    pool = (k_pages, v_pages, k_scales, v_scales)
    if state is not None or window_pages is not None:
        pool += (state,)
    if window_pages is not None:
        pool += (tuple(window_pages),)
    return pool


def forward_paged(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,        # [B, T] int32
    positions: jnp.ndarray,     # [B, T] int32 absolute positions
    token_mask: jnp.ndarray,    # [B, T] bool — real (non-pad) tokens
    kv_lens: jnp.ndarray,       # [B] int32 — cache length AFTER this step
    page_table: jnp.ndarray,    # [B, P] int32 physical page ids
    k_pages: jnp.ndarray,       # [L, NP, page, KV, hd] (int8 when quantized)
    v_pages: jnp.ndarray,
    use_pallas: str = "auto",
    k_scales: Optional[jnp.ndarray] = None,  # [L, NP, page, KV, 1] (int8 KV)
    v_scales: Optional[jnp.ndarray] = None,
    lora: Optional[dict] = None,    # {w: (A [L,n,d,r], B [L,n,r,o]·alpha/r)}
    lora_ids: Optional[jnp.ndarray] = None,  # [B] int32 adapter slot per row
    experts_whole: bool = False,    # no mesh axis shards the expert dim
    state: Optional[dict] = None,   # recurrent layers: the state pool's arrays
    state_slots: Optional[jnp.ndarray] = None,   # [B] int32 slot per row
    sharded: bool = False,          # the parameters lie over a mesh
    window_pages: Optional[tuple] = None,   # window layers: their class's
                                            # (k, v) [Lw, NPw, page, KV, hd]
    window_table: Optional[jnp.ndarray] = None,  # [B, P] int32, that class's
):
    """Serving forward over the paged KV pool (prefill chunks and decode steps
    share this one traced program per (B, T) bucket). With scales, the pool
    is int8-quantized (per-vector absmax) — half the KV HBM.
    Returns (logits [B, T, V] f32, k_pages, v_pages, k_scales, v_scales).
    A caller whose experts are whole on every device says so with
    ``experts_whole`` and gets a sixth value: the experts visited, summed
    over the layers, where the step ran as ``_moe_mlp_hit`` (small enough
    for ``hit_experts_pay``); else None, and the program is the dense one.
    With ``state`` (a model with recurrent layers) the new state follows
    the pools, before that count; with ``window_pages`` (a model with
    window layers) the state's place (None without one) and then the
    window class's new pools do."""
    x = params["embed"].astype(cfg.jax_dtype)[tokens]
    pool = _all_pools(k_pages, v_pages, k_scales, v_scales, state,
                      window_pages)
    x, pool, visited = paged_layers(
        params, cfg, x, pool,
        PoolAddr(positions, token_mask, kv_lens, page_table,
                 state_slots=state_slots, window_table=window_table),
        layers=(0, cfg.num_layers), use_pallas=use_pallas, lora=lora,
        lora_ids=lora_ids, experts_whole=experts_whole, sharded=sharded)
    out = (_head(params, cfg, x), *pool)
    if experts_whole:
        out += (None if visited is None else visited.sum(),)
    return out


def forward_ragged(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,        # [1, T] int32 — ALL rows' tokens, packed
    positions: jnp.ndarray,     # [1, T] int32 absolute positions
    token_mask: jnp.ndarray,    # [1, T] bool — real (non-pad) tokens
    row_ids: jnp.ndarray,       # [T] int32 — token → batch row
    kv_lens: jnp.ndarray,       # [R] int32 — per-row cache length AFTER step
    page_table: jnp.ndarray,    # [R, P] int32 physical page ids per row
    k_pages: jnp.ndarray,       # [L, NP, page, KV, hd] (int8 when quantized)
    v_pages: jnp.ndarray,
    use_pallas: str = "auto",
    k_scales: Optional[jnp.ndarray] = None,
    v_scales: Optional[jnp.ndarray] = None,
    max_q_len: Optional[int] = None,  # static bound on a row's query len
                                      # (engine: prefill_chunk)
    state: Optional[dict] = None,     # recurrent layers: the state pool
    state_slots: Optional[jnp.ndarray] = None,   # [R] int32 slot per row
    window_pages: Optional[tuple] = None,    # window layers: their class
    window_table: Optional[jnp.ndarray] = None,  # [R, P] int32, its lines
    head_rows: Optional[jnp.ndarray] = None,  # [S] int32 packed offsets
):
    """Serving forward over a RAGGED packed batch: prefill chunks and decode
    steps of different rows ride ONE dispatch (tokens packed row-major on the
    flat token axis, ``row_ids`` naming each token's page table line and kv
    length). Only the KV scatter and the attention read the ragged metadata
    (``_pool_attention``). No LoRA: ``lora_delta`` gathers adapters per batch
    ROW and the packed batch axis is 1, so the engine gates such rows out.
    Returns (logits [1, T, V] f32, k_pages, v_pages, k_scales, v_scales),
    and the new state after them where ``state`` was given, the window
    class's pools after that where ``window_pages`` were
    (``forward_paged``'s order). With ``head_rows`` the head runs on those
    packed tokens alone and the logits are ``[1, S, V]``: a step samples at
    most a token a row, and ``[T, V]`` float32 is the largest array a
    packed step makes."""
    x = params["embed"].astype(cfg.jax_dtype)[tokens]
    pool = _all_pools(k_pages, v_pages, k_scales, v_scales, state,
                      window_pages)
    addr = PoolAddr(positions, token_mask, kv_lens, page_table, row_ids,
                    max_q_len, state_slots, window_table)
    x, pool, _ = paged_layers(
        params, cfg, x, pool, addr._replace(spans=_row_spans(addr)),
        layers=(0, cfg.num_layers), use_pallas=use_pallas)
    if head_rows is not None:
        x = x[:, head_rows]
    return (_head(params, cfg, x), *pool)


def forward_train(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,                       # [B, T] int32
    token_mask: Optional[jnp.ndarray] = None,  # [B, T] bool
    mesh=None,                                 # Mesh with an "sp" axis → ring
    remat: bool = False,                       # jax.checkpoint per block
) -> jnp.ndarray:
    """Cache-free causal forward for training. Returns logits [B, T, V] f32.

    With a mesh whose ``sp`` axis is > 1, attention runs as ring attention
    over sequence shards (exact; ICI neighbor exchange) instead of relying on
    XLA to all-gather the sequence dim. ``remat=True`` rematerializes each
    block's activations in the backward pass (trade FLOPs for HBM — the
    standard deep-stack training memory lever; activations per layer drop
    from O(B·T·(D+F+heads·T)) to the block boundary only).
    """
    return _head(params, cfg,
                 _encode_core(params, cfg, tokens, token_mask, mesh, remat,
                              final_norm=False))


def _encode_core(params, cfg, tokens, token_mask, mesh=None, remat=False,
                 final_norm=True):
    """Shared cache-free causal body (training AND embeddings paths — one
    copy of the embed → scan-over-blocks → norm pipeline)."""
    _no_recurrent(cfg, "the cache-free forward (training, embeddings)")
    B, T = tokens.shape
    if token_mask is None:
        token_mask = jnp.ones((B, T), bool)
    positions = jnp.broadcast_to(
        jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))

    use_ring = (
        mesh is not None
        and "sp" in mesh.axis_names
        and mesh.shape["sp"] > 1
        and T % mesh.shape["sp"] == 0
    )
    if use_ring:
        from rbg_tpu.parallel.ring import ring_attention
        # Pad K/V slots get a position beyond every query → never attended.
        kv_positions = jnp.where(token_mask, positions, jnp.int32(1 << 30))

    x = params["embed"].astype(cfg.jax_dtype)[tokens]

    for t in range(cfg.loop_steps):     # a looped model's passes, unrolled
        for name, g, _, _ in cfg.layer_groups:
            def body(h, blk, g=g):
                if use_ring:
                    q, k, vv = _qkv(g, blk, h, positions)
                    attn = ring_attention(q, k, vv, positions, kv_positions,
                                          mesh)
                    return _post_attention(g, blk, h,
                                           _attn_gate(g, blk, h, attn))
                h, _, _ = _block(g, h, blk, None, None, positions, token_mask)
                return h

            if remat:
                body = jax.checkpoint(body)

            def step(h, blk, body=body):
                return body(h, blk), None

            x, _ = jax.lax.scan(step, x, params[name])
        if cfg.loop_steps > 1:
            x = _pass_norm(params, cfg, x)
    if final_norm and cfg.loop_steps == 1:
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return x


def encode_hidden(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,                       # [B, T] int32
    token_mask: Optional[jnp.ndarray] = None,  # [B, T] bool
) -> jnp.ndarray:
    """Cache-free causal forward returning the FINAL-NORM hidden states
    [B, T, D] (no LM head) — the embeddings/representation path
    (/v1/embeddings pools these; reference engines expose the same)."""
    return _encode_core(params, cfg, tokens, token_mask)


def prefill_and_decode_greedy(params, cfg, prompt, steps: int):
    """Tiny reference loop used by tests/bench: greedy-decode ``steps`` tokens."""
    B, T = prompt.shape
    cache = KVCache.create(cfg, B, T + steps)
    logits, cache = forward(params, cfg, prompt, cache)
    tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
    out = [tok]
    for _ in range(steps - 1):
        logits, cache = forward(params, cfg, tok, cache)
        tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
        out.append(tok)
    return jnp.concatenate(out, axis=1)
