"""Operations and bytes a kernel call needs, from the live work alone.

The functions here are the benchmark's count of what the algorithm must
do for the rows a step really ran: never the grid, never ``max_seq_len``,
never padding. ``least_seconds`` turns them into the least time the chip
could take (the larger of operations over peak FLOP/s and bytes over peak
bytes/s), which a roofline share divides by the traced time.

A kernel's count is a file: every public function of every
``benchmark/opsbytes/*.py`` is a model, ``(cfg, rows) -> (flops, bytes)``
with ``cfg`` the configuration file and ``rows`` the ``(q, kv)`` of one
recorded step, under the function's own name. ``paged_attention`` below
is the one that was here first.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
MODELS_DIR = os.path.join(os.path.dirname(_HERE), "opsbytes")


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


def models(extra_dirs=()) -> dict:
    """``{name: function}``: ``paged_attention`` and the public functions
    of the files in ``benchmark/opsbytes`` and in ``extra_dirs`` (the
    tests' own). A name defined twice is an error."""
    out = {"paged_attention": paged_attention}
    where = {"paged_attention": __file__}
    for d in (MODELS_DIR, *extra_dirs):
        for path in sorted(glob.glob(os.path.join(d, "*.py"))):
            stem = os.path.splitext(os.path.basename(path))[0]
            spec = importlib.util.spec_from_file_location(
                f"bench_opsbytes_{stem}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            for name, fn in vars(mod).items():
                if (name.startswith("_") or not callable(fn)
                        or getattr(fn, "__module__", None) != mod.__name__):
                    continue
                if name in out:
                    raise ValueError(f"opsbytes model {name!r} is defined in "
                                     f"{where[name]} and in {path}")
                out[name], where[name] = fn, path
    return out


MODELS = models()


def least_seconds(model, cfg: dict, rows: list, peak: dict) -> float:
    """``model`` is one of the functions of ``models()``."""
    flops, nbytes = model(cfg, rows)
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
