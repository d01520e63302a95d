"""Count of the grouped-query attention kernels (``_decode_call``,
``_block_ragged_call``) in a model whose layers attend in two kinds
(Laguna: ``layer_types`` ``full_attention`` and ``sliding_attention``, with a
head count a layer in ``num_attention_heads_per_layer``)."""


def paged_attention_window_layers(cfg: dict, rows: list) -> tuple:
    """(FLOPs, bytes) of attention over a paged cache for one step of the
    whole model, by layer KIND among the layers served:
    ``harness/opsbytes.py::paged_attention``'s count a layer (``4 h hd``
    FLOPs a (query, attended token) pair; K and V read once a row, ``2 kv
    hd`` values a token; queries in and outputs out, ``2 h hd`` a query
    token) with the layer's own head count. A full layer's query attends
    everything up to itself and the row reads its ``kv`` cached tokens; a
    window layer's query at position ``p`` attends ``min(p + 1,
    sliding_window)`` tokens and the row reads the ``min(kv, sliding_window
    + q - 1)`` its ``q`` queries' windows cover. Live work only: what lies
    below a window is not read, and is not counted. The output gate is no
    part of the kernels. ``rows`` are ``(q, kv)`` of the live rows."""
    L, W = cfg["num_hidden_layers"], cfg["sliding_window"]
    kvh, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    itemsize = 4 if cfg.get("torch_dtype") == "float32" else 2
    queries = sum(q for q, _ in rows)
    full_pairs = sum(q * kv - q * (q - 1) // 2 for q, kv in rows)
    full_read = sum(kv for _, kv in rows)
    window_pairs = sum(min(p + 1, W) for q, kv in rows
                       for p in range(kv - q, kv))
    window_read = sum(min(kv, W + q - 1) for q, kv in rows)
    flops = nbytes = 0
    for kind, h in zip(cfg["layer_types"][:L],
                       cfg["num_attention_heads_per_layer"][:L]):
        pairs, read = ((window_pairs, window_read)
                       if kind == "sliding_attention"
                       else (full_pairs, full_read))
        flops += 4 * h * hd * pairs
        nbytes += (read * 2 * kvh * hd + queries * 2 * h * hd) * itemsize
    return flops, nbytes
