"""Count of the grouped-query attention kernels (``_decode_call``,
``_block_ragged_call``) in a model of which only some layers attend
(LFM2: ``layer_types`` ``full_attention``; the others keep a convolution's
tail and call no kernel)."""


def paged_attention_hybrid(cfg: dict, rows: list) -> tuple:
    """(FLOPs, bytes) of attention over a paged cache for one step of the
    whole model: ``harness/opsbytes.py::paged_attention``'s count a layer
    (``4 h hd`` FLOPs a (query, cached token) pair; K and V read once a
    row, ``2 kv hd`` values a token; queries in and outputs out, ``2 h
    hd`` a query token), times the attention layers among the layers
    served, not every layer. The head size is the published one (hidden /
    heads where the file gives none): heads of 64 lie two to a lane tile
    in the pool and are counted as held, 64 values each, not as a padded
    tile. ``rows`` are ``(q, kv)`` of the live rows."""
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    layers = cfg["layer_types"][:cfg["num_hidden_layers"]].count(
        "full_attention")
    itemsize = 4 if cfg.get("preset", {}).get("dtype") == "float32" else 2
    pairs = sum(q * kv - q * (q - 1) // 2 for q, kv in rows)
    flops = 4 * h * hd * pairs
    cache = sum(kv for _, kv in rows) * 2 * kvh * hd * itemsize
    qo = sum(q for q, _ in rows) * 2 * h * hd * itemsize
    return layers * flops, layers * (cache + qo)
