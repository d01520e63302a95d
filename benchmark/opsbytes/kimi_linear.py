"""Counts of the latent-attention kernels (``_mla_decode_call``,
``_block_ragged_mla_call``) in a model of which only some layers attend
(Kimi-Linear: ``linear_attn_config.full_attn_layers``; the others keep a
recurrent state and call no kernel)."""


def mla_absorbed_attention_hybrid(cfg: dict, rows: list) -> tuple:
    """(FLOPs, bytes) of absorbed latent attention for one step of the
    whole model: ``opsbytes/mla.py::mla_absorbed_attention``'s count a
    layer (``2 h (2 dc + dr)`` FLOPs a (query, cached token) pair; the
    cache read once a row, ``dc + dr`` values a token; queries in as ``h
    (dc + dr)`` and latent outputs out as ``h dc`` a query token), times
    the latent-attention layers among the layers served, not every
    layer. ``rows`` are ``(q, kv)`` of the live rows."""
    h = cfg["num_attention_heads"]
    layers = sum(1 for n in cfg["linear_attn_config"]["full_attn_layers"]
                 if n <= cfg["num_hidden_layers"])
    dc, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    itemsize = 4 if cfg.get("torch_dtype") == "float32" else 2
    pairs = sum(q * kv - q * (q - 1) // 2 for q, kv in rows)
    flops = 2 * h * (2 * dc + dr) * pairs
    cache = sum(kv for _, kv in rows) * (dc + dr) * itemsize
    qo = sum(q for q, _ in rows) * h * (2 * dc + dr) * itemsize
    return layers * flops, layers * (cache + qo)
