"""Count of the grouped-query attention kernels (``_decode_call``,
``_block_ragged_call``) in a looped model (Ouro: ``total_ut_steps`` passes
over ``num_hidden_layers`` layers of weights), whose every pass of every
layer attends a cache entry of its own."""


def paged_attention_looped(cfg: dict, rows: list) -> tuple:
    """(FLOPs, bytes) of attention over a paged cache for one step of the
    whole model: ``harness/opsbytes.py::paged_attention``'s count a layer
    (``4 h hd`` FLOPs a (query, cached token) pair; K and V read once a
    row, ``2 kv hd`` values a token; queries in and outputs out, ``2 h
    hd`` a query token), times ``num_hidden_layers x total_ut_steps``: a
    token keeps keys and values of its own in every pass, and a step
    walks each (pass, layer) entry once. Live work only. ``rows`` are
    ``(q, kv)`` of the live rows."""
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    walks = cfg["num_hidden_layers"] * cfg["total_ut_steps"]
    itemsize = 4 if cfg.get("torch_dtype") == "float32" else 2
    pairs = sum(q * kv - q * (q - 1) // 2 for q, kv in rows)
    flops = 4 * h * hd * pairs
    cache = sum(kv for _, kv in rows) * 2 * kvh * hd * itemsize
    qo = sum(q for q, _ in rows) * 2 * h * hd * itemsize
    return walks * flops, walks * (cache + qo)
