"""Counts of the latent-attention kernels (``_mla_decode_call``,
``_block_ragged_mla_call``): attention in the absorbed form over a cache
that holds, per token and layer, one latent and one rotary key."""


def mla_absorbed_attention(cfg: dict, rows: list) -> tuple:
    """(FLOPs, bytes) of absorbed latent attention for one step of the
    whole model. ``rows`` are ``(q, kv)`` of the live rows: query tokens in
    the step and cache length after it. A query of each of ``h`` heads
    meets, per cached token it may see, the latent twice (``kv_lora_rank``:
    the score, then the value) and the rotary key once
    (``qk_rope_head_dim``): ``2 h (2 dc + dr)`` FLOPs a pair. The cache is
    read once a row, ``dc + dr`` values a token; the queries come in as
    ``h (dc + dr)`` and the latent outputs leave as ``h dc`` a query token.
    Every layer attends."""
    h, layers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    dc, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    itemsize = 4 if cfg.get("torch_dtype") == "float32" else 2
    pairs = sum(q * kv - q * (q - 1) // 2 for q, kv in rows)
    flops = 2 * h * (2 * dc + dr) * pairs
    cache = sum(kv for _, kv in rows) * (dc + dr) * itemsize
    qo = sum(q for q, _ in rows) * h * (2 * dc + dr) * itemsize
    return layers * flops, layers * (cache + qo)
