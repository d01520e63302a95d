"""Checkpointing: orbax save/restore + HuggingFace Llama weight import.

Serving engines need real weights; the plane's warmup jobs prefetch them to
slice hosts. Two formats:

* **orbax** — the native format (sharding-aware restore; what multi-host
  slices use).
* **HF safetensors** — import path for the model families the reference's
  examples deploy (Llama-3/Qwen2 checkpoints on local disk; this
  environment is zero-egress so nothing downloads). Weights are transposed
  into our ``[in, out]`` matmul layout and stacked along the layer axis for
  the scan.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from rbg_tpu.models.config import ModelConfig


def save_checkpoint(path: str, params: dict) -> None:
    import orbax.checkpoint as ocp

    with ocp.PyTreeCheckpointer() as ckptr:
        ckptr.save(os.path.abspath(path), params)


def load_checkpoint(path: str, like: Optional[dict] = None) -> dict:
    import orbax.checkpoint as ocp

    with ocp.PyTreeCheckpointer() as ckptr:
        if like is not None:
            target = jax.tree_util.tree_map(ocp.utils.to_shape_dtype_struct, like)
            return ckptr.restore(os.path.abspath(path), item=target)
        return ckptr.restore(os.path.abspath(path))


def is_hf_checkpoint(path: str) -> bool:
    return os.path.isdir(path) and (
        os.path.exists(os.path.join(path, "model.safetensors"))
        or os.path.exists(os.path.join(path, "model.safetensors.index.json"))
        or os.path.exists(os.path.join(path, "pytorch_model.bin"))
    )


def _hf_state_dict(path: str) -> dict:
    """Load all tensors from a local HF checkpoint dir as numpy arrays."""
    single = os.path.join(path, "model.safetensors")
    index = os.path.join(path, "model.safetensors.index.json")
    out = {}
    if os.path.exists(single) or os.path.exists(index):
        from safetensors import safe_open

        files = []
        if os.path.exists(index):
            import json
            with open(index) as f:
                files = sorted(set(json.load(f)["weight_map"].values()))
        else:
            files = ["model.safetensors"]
        for fname in files:
            with safe_open(os.path.join(path, fname), framework="np") as f:
                for k in f.keys():
                    out[k] = f.get_tensor(k)
        return out
    import torch

    sd = torch.load(os.path.join(path, "pytorch_model.bin"), map_location="cpu",
                    weights_only=True)
    return {k: v.float().numpy() for k, v in sd.items()}


def load_hf_llama(path: str, cfg: ModelConfig) -> dict:
    """Map a HF llama-family checkpoint (LlamaForCausalLM/Qwen2ForCausalLM
    layout) into our stacked-scan param tree."""
    if cfg.num_experts:
        raise NotImplementedError(
            "HF import currently covers dense llama-family layouts only; "
            "MoE checkpoints (Mixtral block_sparse_moe / DeepSeek experts) "
            "need a dedicated mapping — load via orbax instead.")
    if cfg.mla:
        raise NotImplementedError(
            "HF import does not map MLA layouts yet (kv_a/kv_b projections "
            "→ w_dkv/w_uk/w_uv) — load via orbax instead.")
    if cfg.attn_gate:
        raise NotImplementedError(
            "HF import does not map attn_gate's projection (wg): no "
            "dense llama-family checkpoint publishes one — load via orbax "
            "instead.")
    if cfg.sliding_window or cfg.rope_scaling \
            or cfg.partial_rotary_factor != 1.0:
        raise NotImplementedError(
            "HF import does not map a model with window layers "
            "(sliding_window: per-kind head counts, wq / wo stacked by "
            "kind) or with scaled or partial rotary settings: no dense "
            "llama-family checkpoint has them — load via orbax instead.")
    if cfg.loop_steps > 1 or cfg.post_norms or cfg.exit_gate:
        raise NotImplementedError(
            "HF import does not map a looped model (model_type ouro: "
            "total_ut_steps, the two norms after a sub-layer, "
            "model.early_exit_gate) until a checkpoint's index is in the "
            "repository to map the names from — load via orbax instead.")
    sd = _hf_state_dict(path)
    dt = cfg.jax_dtype
    L = cfg.num_layers

    def get(name):
        return np.asarray(sd[name], np.float32)

    def stack(fmt, transpose=True):
        ws = [get(fmt.format(i)) for i in range(L)]
        ws = [w.T if transpose else w for w in ws]
        return jnp.asarray(np.stack(ws), dt)

    p = "model.layers.{}."
    blocks = {
        "attn_norm": stack(p + "input_layernorm.weight", transpose=False),
        "wq": stack(p + "self_attn.q_proj.weight"),
        "wk": stack(p + "self_attn.k_proj.weight"),
        "wv": stack(p + "self_attn.v_proj.weight"),
        "wo": stack(p + "self_attn.o_proj.weight"),
        "mlp_norm": stack(p + "post_attention_layernorm.weight", transpose=False),
        "w_gate": stack(p + "mlp.gate_proj.weight"),
        "w_up": stack(p + "mlp.up_proj.weight"),
        "w_down": stack(p + "mlp.down_proj.weight"),
    }
    if p.format(0) + "self_attn.q_proj.bias" in sd:  # Qwen2 attention bias
        blocks["bq"] = stack(p + "self_attn.q_proj.bias", transpose=False)
        blocks["bk"] = stack(p + "self_attn.k_proj.bias", transpose=False)
        blocks["bv"] = stack(p + "self_attn.v_proj.bias", transpose=False)
    params = {
        "embed": jnp.asarray(get("model.embed_tokens.weight"), dt),
        "blocks": blocks,
        "final_norm": jnp.asarray(get("model.norm.weight"), dt),
    }
    if not cfg.tie_word_embeddings and "lm_head.weight" in sd:
        params["lm_head"] = jnp.asarray(get("lm_head.weight").T, dt)
    return params


def load_params(path: str, cfg: ModelConfig, like: Optional[dict] = None) -> dict:
    """Auto-detect format (HF dir vs orbax dir) and load."""
    if is_hf_checkpoint(path):
        return load_hf_llama(path, cfg)
    return load_checkpoint(path, like=like)
