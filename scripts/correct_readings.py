"""The readings a configuration's ``correct`` limits are set between.

Run on the chip, from the root of a checkout, in one process:

    python scripts/correct_readings.py --config benchmark/configs/<c>.json \
        --weights 1 2 --prompts 11 12 13 [--controls int8 fp8] [--faults]

For each weight seed it builds the program's engine on the benchmark's
weights (``benchmark/harness/serve.py``'s own preset mapping and reference
module), serves the reference check's sample (``benchmark/run.py::
check_sample``'s: one prompt alone, then ``max_batch`` side by side) for each
prompt seed, and prints the three numbers ``run.py`` compares (the median and
the 75th percentile of |served - reference| log-probability over all
positions, the worst request's own median):

* ``sound``: the program against the reference;
* ``int8`` (and whatever else ``--controls`` names of the reference's
  ``CONTROLS``): the reference computed in that precision against the
  reference, on the same served tokens (int8 is the precision one step
  below bfloat16);
* with ``--faults``, for a model with recurrent layers, the program with
  its recurrent mixer patched: ``not carried`` (every chunk of a prompt
  starts from a zero state) and ``not zeroed`` (no row starts from zeros,
  served on slots another sample has used);
* with ``--faults``, for a model whose preset sets them, each of its rules
  left out of the served path, one at a time (``left_out``): the attention
  layers' output gate dropped, the delta rule's write strength not scaled,
  attention without positions rotated; YaRN dropped for plain RoPE, the
  whole head rotated, the window not kept, a window's page given back one
  page early, the window layers served with the full layers' head count;
  for a looped model the last pass dropped, the final norm not applied
  between passes, every pass reading and writing pass 0's pages, and the
  norm after the mixer or after the MLP dropped (the last four are no
  field of a preset: ``model_contract.patched`` patches the model's code).

The serving loop, the faults and ``left_out`` are ``tests/model_contract.py``'s:
the controls tier-1 holds every tiny configuration to are the ones run here
(that module imports ``pytest``, so the machine that runs this needs it).

A limit lies between the largest ``sound`` and the smallest control
(``PERF.md`` section 2). Nothing here is a timing.
"""

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark"),
                os.path.join(ROOT, "tests")]

import numpy as np  # noqa: E402


def sample(cfg, seed):
    """``run.py::check_sample``'s prompts: (alone, side by side)."""
    spec, vocab = cfg["correct"], cfg["vocab_size"]
    rng = np.random.default_rng([seed, 99])
    a = rng.integers(1, vocab, spec["first_len"]).tolist()
    rest = [a[:len(a) // 2] + rng.integers(1, vocab,
                                           spec["tail_len"]).tolist(), a]
    rest += [rng.integers(1, vocab, n).tolist() for n in spec["other_lens"]]
    return [a], rest


def numbers(diffs):
    """(median, 75th percentile, worst request's median) of per-request
    lists of absolute differences."""
    pooled = sorted(d for ds in diffs for d in ds)
    return (statistics.median(pooled),
            float(np.percentile(pooled, 75)),
            max(statistics.median(ds) for ds in diffs))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--weights", type=int, nargs="+", default=[3000000019])
    ap.add_argument("--prompts", type=int, nargs="+", default=[1, 2, 3])
    ap.add_argument("--controls", nargs="+", default=["int8"],
                    help="the reference's lower precisions to read beside "
                         "the sound program (its CONTROLS)")
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args(argv)

    import jax
    from harness import serve as harness
    from model_contract import (FAULTS, broken_preset, faulty, fitted,
                                left_out, patched, serve)
    from rbg_tpu.engine import Engine, EngineConfig
    from rbg_tpu.models import config as presets
    from rbg_tpu.utils.chipenv import configure_compile_cache
    configure_compile_cache()
    with open(os.path.join(ROOT, args.config)) as f:
        cfg = json.load(f)
    name = "correct-readings"
    presets._PRESETS[name] = harness.model_config(cfg, name)
    reference = harness.load_reference(cfg)
    new = cfg["correct"]["new_tokens"]
    print(f"device: {jax.devices()[0].platform} "
          f"{jax.devices()[0].device_kind}", flush=True)

    def engine(params, model=name):
        return Engine(EngineConfig(model=model, **cfg["server"]),
                      params=params)

    def against_reference(params, prompts, served, quant=None):
        diffs = []
        for prompt, (toks, lps) in zip(prompts, served):
            ref = np.asarray(reference.chosen_logprobs(cfg, params, prompt,
                                                       toks))
            got = lps if quant is None else np.asarray(
                reference.chosen_logprobs(cfg, params, prompt, toks, quant))
            diffs.append(np.abs(np.asarray(got) - ref).tolist())
        return diffs

    def say(kind, w, p, diffs, served=None):
        med, p75, worst = numbers(diffs)
        line = {"kind": kind, "weights": w, "prompts": p,
                "median": round(med, 5), "p75": round(p75, 5),
                "request": round(worst, 5)}
        if served is not None:      # how independent the positions are
            line["distinct"] = min(len(set(toks)) for toks, _ in served)
        print(json.dumps(line), flush=True)

    for w in args.weights:
        params = reference.make_params(cfg, w)
        eng = engine(params)
        for p in args.prompts:
            alone, rest = sample(cfg, p)
            served = serve(eng, alone, new) + serve(eng, rest, new)
            say("sound", w, p, against_reference(params, alone + rest,
                                                 served), served)
            for quant in args.controls:
                say(quant, w, p, against_reference(params, alone + rest,
                                                   served, quant))
        del eng
        if not args.faults:
            continue
        for fault, fields in left_out(presets._PRESETS[name],
                                      cfg["server"]["page_size"]).items():
            broken = presets._PRESETS[name + "-fault"] = broken_preset(
                presets._PRESETS[name], name + "-fault", fields)
            with patched(fault):    # a rule that no field of the preset is
                eng = engine(fitted(broken, params), name + "-fault")
                alone, rest = sample(cfg, args.prompts[0])
                served = serve(eng, alone, new) + serve(eng, rest, new)
            say(fault, w, args.prompts[0],
                against_reference(params, alone + rest, served))
            del eng
        if not presets._PRESETS[name].recurrent:
            continue
        for fault in FAULTS:
            with faulty(fault):
                eng = engine(params)
                # another sample first, so that every slot has been used
                other = sample(cfg, args.prompts[0] + 1000)
                serve(eng, other[0], new)
                serve(eng, other[1], new)
                alone, rest = sample(cfg, args.prompts[0])
                served = serve(eng, alone, new) + serve(eng, rest, new)
                say(fault, w, args.prompts[0],
                    against_reference(params, alone + rest, served))
                del eng
    return 0


if __name__ == "__main__":
    sys.exit(main())
