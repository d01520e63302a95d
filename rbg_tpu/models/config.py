"""Model configurations for the llama-family decoder.

One config dataclass covers the model families the reference's examples deploy
(reference: ``examples/inference/*.yaml`` deploy Qwen/Llama/DeepSeek via
SGLang). Presets below mirror the benchmark configs in BASELINE.md.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Hyperparameters of a llama-family (pre-norm, RoPE, GQA, SwiGLU) decoder."""

    name: str = "tiny"
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_layers: int = 16
    num_heads: int = 16
    num_kv_heads: int = 4
    head_dim: Optional[int] = None  # defaults to hidden_size // num_heads
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 8192
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    # Mixture-of-experts (0 = dense). DeepSeek/Mixtral-style sparse MLP with
    # top-k routing + optional always-on shared expert.
    num_experts: int = 0
    experts_per_token: int = 2
    moe_intermediate_size: int = 0      # 0 → intermediate_size
    moe_shared_expert: bool = False
    moe_shared_expert_size: int = 0     # 0 → intermediate_size
    # Leading layers whose MLP is the dense SwiGLU of intermediate_size
    # before the expert layers start (DeepSeek-V3 first_k_dense_replace).
    # Such a model's parameters come in two groups (``layer_groups``).
    first_dense_layers: int = 0
    # The router's rule (``llama._route``): how logits become scores
    # (softmax | sigmoid), a per-expert bias that is added for the top-k
    # selection only and never weighs (noaux_tc), whether the chosen
    # scores are renormalised to sum 1, and the factor they are scaled by.
    moe_scoring: str = "softmax"
    moe_select_bias: bool = False
    moe_renormalize: bool = True
    moe_routed_scale: float = 1.0
    # Multi-head latent attention (DeepSeek-V2/V3): the cache stores ONE
    # compressed latent (kv_lora_rank) + one shared RoPE key
    # (qk_rope_head_dim) per token instead of per-head K/V — an order of
    # magnitude less KV HBM, which is what makes long-context PD
    # disaggregation cheap to ship around. num_kv_heads is ignored.
    mla: bool = False
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # Low-rank query (DeepSeek-V3): wq_a → RMSNorm → wq_b in place of wq
    # (0 = one full-rank wq).
    q_lora_rank: int = 0
    # RoPE pairing: rotate-half pairs (x_i, x_{i+hd/2}); interleaved pairs
    # (x_2i, x_2i+1), the published DeepSeek convention.
    rope_interleave: bool = False

    @property
    def head_dim_(self) -> int:
        return self.head_dim if self.head_dim is not None else self.hidden_size // self.num_heads

    @property
    def jax_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def moe_f(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def moe_shared_f(self) -> int:
        return self.moe_shared_expert_size or self.intermediate_size

    @property
    def layer_groups(self):
        """``((params key, group config, lo, hi), ...)``: the runs of layers
        of one kind, in order. Each run's parameters are stacked under its
        key with a leading axis ``hi - lo``; ``lo``/``hi`` are absolute
        layer numbers (the KV pool is indexed by them). A model of one kind
        of layer is the one group ``blocks`` and the group config is this
        config itself."""
        n = self.num_layers - self.num_moe_layers if self.num_experts else 0
        if not n:
            return (("blocks", self, 0, self.num_layers),)
        dense = dataclasses.replace(self, num_experts=0, first_dense_layers=0)
        return (("dense_blocks", dense, 0, n),
                ("blocks", self, n, self.num_layers))

    @property
    def num_moe_layers(self) -> int:
        """Layers with routed experts: all but the leading dense ones."""
        if not self.num_experts:
            return 0
        return self.num_layers - min(self.first_dense_layers, self.num_layers)

    @property
    def num_params(self) -> int:
        """Approximate parameter count (embeddings + blocks + head)."""
        d, f, v = self.hidden_size, self.intermediate_size, self.vocab_size
        hd = self.head_dim_
        if self.mla:
            h, dc = self.num_heads, self.kv_lora_rank
            dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                          self.v_head_dim)
            rq = self.q_lora_rank
            wq = (d * rq + rq + rq * h * (dn + dr)) if rq else d * h * (dn + dr)
            attn = (wq                       # wq, or wq_a + q_norm + wq_b
                    + d * (dc + dr) + dc     # w_dkv + kv_norm
                    + dc * h * dn            # w_uk
                    + dc * h * dv            # w_uv
                    + h * dv * d)            # wo
        else:
            attn = d * self.num_heads * hd + 2 * d * self.num_kv_heads * hd + self.num_heads * hd * d
        dense_mlp = 3 * d * f
        moe_mlp = self.num_experts * 3 * d * self.moe_f + d * self.num_experts
        if self.moe_select_bias:
            moe_mlp += self.num_experts
        if self.moe_shared_expert:
            moe_mlp += 3 * d * self.moe_shared_f
        n_moe = self.num_moe_layers
        mlp = n_moe * moe_mlp + (self.num_layers - n_moe) * dense_mlp
        head = 0 if self.tie_word_embeddings else d * v
        return (v * d + self.num_layers * (attn + 2 * d) + mlp + d + head)


_PRESETS = {
    # Tiny config for tests — compiles in seconds on CPU.
    "tiny": ModelConfig(
        name="tiny", vocab_size=256, hidden_size=128, intermediate_size=384,
        num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=256,
        rope_theta=10000.0, dtype="float32",
    ),
    # Small config for single-chip benching — fits v5e-1 HBM easily.
    "qwen2-0.5b": ModelConfig(
        name="qwen2-0.5b", vocab_size=151936, hidden_size=896,
        intermediate_size=4864, num_layers=24, num_heads=14, num_kv_heads=2,
        head_dim=64, max_seq_len=32768, rope_theta=1000000.0,
        tie_word_embeddings=True,
    ),
    "llama3-1b": ModelConfig(
        name="llama3-1b", vocab_size=128256, hidden_size=2048,
        intermediate_size=8192, num_layers=16, num_heads=32, num_kv_heads=8,
        max_seq_len=131072, rope_theta=500000.0, tie_word_embeddings=True,
    ),
    "llama3-8b": ModelConfig(
        name="llama3-8b", vocab_size=128256, hidden_size=4096,
        intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
        max_seq_len=131072, rope_theta=500000.0,
    ),
    "llama3-70b": ModelConfig(
        name="llama3-70b", vocab_size=128256, hidden_size=8192,
        intermediate_size=28672, num_layers=80, num_heads=64, num_kv_heads=8,
        max_seq_len=131072, rope_theta=500000.0,
    ),
    # MoE family (DeepSeek/Mixtral-style) — the reference's config 5 deploys
    # DeepSeek-V3 multi-host (BASELINE.md).
    "tiny-moe": ModelConfig(
        name="tiny-moe", vocab_size=256, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=256,
        rope_theta=10000.0, dtype="float32",
        num_experts=4, experts_per_token=2, moe_intermediate_size=96,
        moe_shared_expert=True,
    ),
    "mixtral-8x7b": ModelConfig(
        name="mixtral-8x7b", vocab_size=32000, hidden_size=4096,
        intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
        max_seq_len=32768, rope_theta=1000000.0,
        num_experts=8, experts_per_token=2,
    ),
    "deepseek-v2-lite": ModelConfig(
        name="deepseek-v2-lite", vocab_size=102400, hidden_size=2048,
        intermediate_size=10944, num_layers=27, num_heads=16, num_kv_heads=16,
        max_seq_len=163840, rope_theta=10000.0,
        num_experts=64, experts_per_token=6, moe_intermediate_size=1408,
        moe_shared_expert=True, moe_shared_expert_size=2816,
        mla=True, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128,
        # From memory of the published config.json (assumed): one dense
        # layer first, full-rank query, softmax scores that are not
        # renormalised, interleaved rotary pairs.
        first_dense_layers=1, moe_renormalize=False, rope_interleave=True,
    ),
    "deepseek-v3": ModelConfig(
        name="deepseek-v3", vocab_size=129280, hidden_size=7168,
        intermediate_size=18432, num_layers=61, num_heads=128,
        num_kv_heads=128, max_seq_len=163840, rope_theta=10000.0,
        num_experts=256, experts_per_token=8, moe_intermediate_size=2048,
        moe_shared_expert=True, moe_shared_expert_size=2048,
        mla=True, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128,
        # From memory of the published config.json (assumed): three dense
        # layers first, low-rank query, sigmoid scores with the noaux_tc
        # selection bias, renormalised and scaled by 2.5. Its group-limited
        # selection (n_group 8, topk_group 4) is not modeled.
        first_dense_layers=3, q_lora_rank=1536, rope_interleave=True,
        moe_scoring="sigmoid", moe_select_bias=True, moe_routed_scale=2.5,
    ),
    # Tiny MLA config for tests — compiles in seconds on CPU.
    "tiny-mla": ModelConfig(
        name="tiny-mla", vocab_size=256, hidden_size=128,
        intermediate_size=384, num_layers=2, num_heads=4, num_kv_heads=4,
        max_seq_len=256, rope_theta=10000.0, dtype="float32",
        mla=True, kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
        v_head_dim=32,
    ),
    # Tiny DeepSeek-V3-shaped block for tests (the layer of the benchmark's
    # joyai-llm-flash): a dense layer before expert layers, latent attention
    # with a low-rank query and interleaved rotary pairs, sigmoid routing
    # with a selection bias and a scaling factor, a shared expert.
    "tiny-joyai": ModelConfig(
        name="tiny-joyai", vocab_size=256, hidden_size=128,
        intermediate_size=320, num_layers=3, num_heads=4, num_kv_heads=4,
        max_seq_len=256, rope_theta=10000.0, rms_norm_eps=1e-6,
        dtype="float32",
        num_experts=16, experts_per_token=4, moe_intermediate_size=48,
        moe_shared_expert=True, moe_shared_expert_size=48,
        first_dense_layers=1, moe_scoring="sigmoid", moe_select_bias=True,
        moe_routed_scale=2.5,
        mla=True, kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
        v_head_dim=32, q_lora_rank=96, rope_interleave=True,
    ),
}


def get_config(name: str, **overrides) -> ModelConfig:
    if name not in _PRESETS:
        raise KeyError(f"unknown model preset {name!r}; have {sorted(_PRESETS)}")
    cfg = _PRESETS[name]
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def list_presets():
    return sorted(_PRESETS)
