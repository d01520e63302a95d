"""Count of the grouped-query attention kernels (``_decode_call``,
``_block_ragged_call``) in a model whose attention layers are named by
number (Solar-Open2: ``gqa_layers``, from 0; the others keep a delta-rule
state and call ``_kda_decode_call``, counted in ``opsbytes/kda.py``)."""


def paged_attention_gqa_layers(cfg: dict, rows: list) -> tuple:
    """(FLOPs, bytes) of attention over a paged cache for one step of the
    whole model: ``harness/opsbytes.py::paged_attention``'s count a layer
    (``4 h hd`` FLOPs a (query, cached token) pair; K and V read once a
    row, ``2 kv hd`` values a token, 4096 B at 8 heads of 128 in bf16;
    queries in and outputs out, ``2 h hd`` a query token), times the
    ``gqa_layers`` among the layers served, not every layer. The output
    gate is no part of the kernels and is not counted. ``rows`` are ``(q,
    kv)`` of the live rows."""
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    layers = sum(1 for n in cfg["gqa_layers"] if n < cfg["num_hidden_layers"])
    itemsize = 4 if cfg.get("torch_dtype") == "float32" else 2
    pairs = sum(q * kv - q * (q - 1) // 2 for q, kv in rows)
    flops = 4 * h * hd * pairs
    cache = sum(kv for _, kv in rows) * 2 * kvh * hd * itemsize
    qo = sum(q for q, _ in rows) * 2 * h * hd * itemsize
    return layers * flops, layers * (cache + qo)
