"""Count of the Kimi Delta Attention decode kernel (``_kda_decode_call``:
one token a row, each live row's state read out of its slot of the pool
and written back to it once a recurrent layer)."""


def kda_decode_step(cfg: dict, rows: list) -> tuple:
    """(FLOPs, bytes) of the recurrent layers' state update for one step
    of the whole model in which every live row has ONE query token: the
    recurrent layers among the layers served x live rows x (the float32
    state ``[H, dk, dv]`` read once and written once, plus the row's ``q,
    k, g`` along ``dk``, ``v`` in and ``o`` out along ``dv`` and ``b`` a
    head, float32), and ``8 H dk dv`` FLOPs a row a layer (the decay, two
    products and sums each for ``S^T k``, the rank-one update and ``S^T
    q``). A step that holds a row of more tokens counts nothing: it walks
    its states in chunks (``kda_chunk``) and calls no kernel. ``rows`` are
    ``(q, kv)`` of the live rows; the state alone is what the wire counter
    ``state_bytes_moved`` counts, less the convolution's tails."""
    if not rows or any(q != 1 for q, _ in rows):
        return 0, 0
    lin = cfg["linear_attn_config"]
    layers = sum(1 for n in lin["kda_layers"]
                 if n <= cfg["num_hidden_layers"])
    h, dk = lin["num_heads"], lin["head_dim"]
    dv = dk
    state = 2 * h * dk * dv * 4
    vectors = (3 * h * dk + 2 * h * dv + h) * 4
    calls = layers * len(rows)
    return calls * 8 * h * dk * dv, calls * (state + vectors)
