"""Operations and bytes a kernel call needs, from the live work alone.

The functions here are the benchmark's count of what the algorithm must
do for the rows a step really ran: never the grid, never ``max_seq_len``,
never padding. ``least_seconds`` turns them into the least time the chip
could take (the larger of operations over peak FLOP/s and bytes over peak
bytes/s), which a roofline share divides by the traced time.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peak_for(device_kind: str) -> dict:
    """The published peaks of a device; one that is not listed is an
    error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if device_kind not in peaks:
        raise KeyError(f"device kind {device_kind!r} has no entry in "
                       f"peaks.json; have {sorted(peaks)}")
    return peaks[device_kind]


def paged_attention(cfg: dict, rows: list) -> tuple:
    """(FLOPs, bytes) of attention over a paged cache for one step of the
    whole model. ``rows`` are ``(q, kv)``: a row's query tokens in this
    step and its cache length after it; its queries sit at positions
    ``kv - q .. kv - 1`` and each attends to everything up to itself."""
    h = cfg["num_attention_heads"]
    kvh = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    layers = cfg["num_hidden_layers"]
    itemsize = 2                                   # bf16 cache and queries
    pairs = sum(q * kv - q * (q - 1) // 2 for q, kv in rows)
    flops = 4 * h * hd * pairs                     # QK^T and PV, 2 per MAC
    kv_bytes = sum(kv for _, kv in rows) * 2 * kvh * hd * itemsize
    qo_bytes = sum(q for q, _ in rows) * 2 * h * hd * itemsize
    return layers * flops, layers * (kv_bytes + qo_bytes)


MODELS = {"paged_attention": paged_attention}


def least_seconds(model: str, cfg: dict, rows: list, peak: dict) -> float:
    flops, nbytes = MODELS[model](cfg, rows)
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
