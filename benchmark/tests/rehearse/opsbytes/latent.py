"""The rehearsal's kernel count: attention over a cache of latents."""


def latent_attention(cfg: dict, rows: list) -> tuple:
    """(FLOPs, bytes) of absorbed latent attention for one step of the
    whole model: a query of every head meets, per cached token, one latent
    of ``kv_lora_rank`` and one rotary key of ``qk_rope_head_dim``."""
    p = cfg["preset"]
    h, layers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    dc, dr = p["kv_lora_rank"], p["qk_rope_head_dim"]
    itemsize = 4 if cfg["torch_dtype"] == "float32" else 2
    pairs = sum(q * kv - q * (q - 1) // 2 for q, kv in rows)
    flops = 2 * h * (2 * dc + dr) * pairs          # scores, then values
    cache = sum(kv for _, kv in rows) * (dc + dr) * itemsize
    qo = sum(q for q, _ in rows) * h * (2 * dc + dr) * itemsize
    return layers * flops, layers * (cache + qo)
