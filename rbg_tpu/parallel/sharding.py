"""Sharding rules: map the model's param/activation pytrees to PartitionSpecs.

Megatron-style layout expressed declaratively; XLA inserts the collectives:

* column-parallel projections (wq/wk/wv/w_gate/w_up): output dim on ``tp``
* row-parallel projections (wo/w_down): input dim on ``tp`` (XLA emits the
  psum on the residual add)
* embedding + lm_head: vocab dim on ``tp``
* activations: batch on ``dp``, sequence on ``sp`` (ring attention path)
* KV cache: kv-head dim on ``tp``, batch on ``dp``

Everything goes through ``jax.jit``'s in_shardings/out_shardings — no manual
collectives on this path (shard_map kernels live in rbg_tpu.parallel.ring).
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from rbg_tpu.models.config import ModelConfig


def param_specs(cfg: ModelConfig, params: Optional[dict] = None) -> dict:
    """PartitionSpec pytree matching ``rbg_tpu.models.llama.init_params``.

    Leading axis of every block param is the scan/layer axis (unsharded).
    Pass ``params`` to align with optional checkpoint-dependent keys
    (Qwen2 attention biases) that the config alone can't predict.
    """
    specs = {
        "embed": P("tp", None),
        "final_norm": P(None),
    }
    for name, g, _, _ in cfg.layer_groups:
        specs[name] = _block_specs(g)
    if params is not None and "bq" in params.get("blocks", {}):
        # QKV bias columns follow their projection's output sharding.
        specs["blocks"].update(bq=P(None, "tp"), bk=P(None, "tp"),
                               bv=P(None, "tp"))
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, "tp")
    return specs


def _block_specs(cfg: ModelConfig) -> dict:
    """Specs of one group's stacked block weights (``llama._init_blocks``)."""
    blocks = {
        "attn_norm": P(None, None),
        "wo": P(None, "tp", None),
        "mlp_norm": P(None, None),
    }
    if cfg.mla and cfg.q_lora_rank:
        # The low-rank query: the down-projection and its norm replicate,
        # the up-projection shards over heads.
        blocks.update({
            "wq_a": P(None, None, None),
            "q_norm": P(None, None),
            "wq_b": P(None, None, "tp"),
        })
    else:
        blocks["wq"] = P(None, None, "tp")
    if cfg.mla:
        # MLA: query-side weights shard over heads (tp); the latent
        # down-projection and its norm replicate (no head axis — the latent
        # cache is shared by every head, which is the whole point).
        blocks.update({
            "w_dkv": P(None, None, None),
            "kv_norm": P(None, None),
            "w_uk": P(None, None, "tp"),
            "w_uv": P(None, None, "tp"),
        })
    else:
        blocks.update({
            "wk": P(None, None, "tp"),
            "wv": P(None, None, "tp"),
        })
        if cfg.attn_gate:
            # The output gate's columns are the heads' channels, as wq's.
            blocks["wg"] = P(None, None, "tp")
    if cfg.num_experts == 0 or cfg.moe_shared_expert:
        blocks["w_gate"] = P(None, None, "tp")
        blocks["w_up"] = P(None, None, "tp")
        blocks["w_down"] = P(None, "tp", None)
    if cfg.num_experts:
        # Experts split over ep; inside each expert, Megatron tp as usual.
        blocks["router"] = P(None, None, None)
        if cfg.moe_select_bias:
            blocks["router_bias"] = P(None, None)
        blocks["moe_gate"] = P(None, "ep", None, "tp")
        blocks["moe_up"] = P(None, "ep", None, "tp")
        blocks["moe_down"] = P(None, "ep", "tp", None)
    return blocks


def cache_specs() -> dict:
    """Specs for KVCache fields (k/v: [L, B, S, KV, hd])."""
    kv = P(None, "dp", None, "tp", None)
    return {"k": kv, "v": kv, "length": P("dp")}


def tokens_spec() -> P:
    return P("dp", None)


def logits_spec() -> P:
    return P("dp", None, "tp")


def shard_pytree(tree, specs, mesh: Mesh):
    """Device-put a pytree according to a spec pytree."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs
    )


def named(mesh: Mesh, spec_tree):
    """Map a PartitionSpec pytree to a NamedSharding pytree."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda s: isinstance(s, P),
    )
