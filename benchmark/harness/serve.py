"""The server process of a benchmark run: the program's normal server,
started on the benchmark's configuration and the benchmark's weights.

    python benchmark/harness/serve.py --config benchmark/configs/<c>.json \
        --name <preset> --port P --ctl-port C --seed N [--record-steps] \
        [--platform tpu|cpu]

What runs is ``rbg_tpu.engine.server.main``: the program's entry point,
scheduler, cache and kernels. This launcher does three things around it,
all from outside the program:

* registers the configuration file's sizes as a model preset
  (``rbg_tpu.models.config._PRESETS``: the published keys, then the file's
  ``preset`` by field name) and hands the engine its weights: the
  ``make_params`` of the configuration's reference module from ``--seed``,
  one jitted call, instead of the program's leaf-by-leaf eager initialiser;
* answers a second, benchmark-only port (``--ctl-port``): the plain
  reference on those same weights (a chip belongs to one process, so the
  reference has to run here), the profiler's start and stop with the
  recorded steps, the one-op programs the program's warm-up leaves out,
  the names of what compiled;
* with ``--record-steps``, notes beside each engine step which rows it ran
  (query tokens and cache length per row) from the engine's host state, and
  wraps the step in a ``TraceAnnotation`` so that the device trace's idle
  gaps can be named. Nothing is recorded without the flag.
"""

from __future__ import annotations

import argparse
import importlib.util
import inspect
import json
import os
import socketserver
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

DEFAULT_REFERENCE = "benchmark/harness/reference.py"

STATE = {"cfg": None, "reference": None, "params": None, "steps": [],
         "recording": False, "tracing": False, "compiles": []}


def log_compiles() -> None:
    """Name every program this process compiles (or loads from the
    persistent cache) with its time, so that a compile inside a measured
    window can be named. ``jax.monitoring``'s public compile event."""
    import jax.monitoring as monitoring
    from rbg_tpu.utils.chipenv import COMPILE_EVENT

    def on_duration(event, seconds, **kw):
        if event == COMPILE_EVENT:
            STATE["compiles"].append(
                (time.monotonic(), str(kw.get("fun_name")), seconds))

    monitoring.register_event_duration_secs_listener(on_duration)


# ---------------------------------------------------------------------------
# what a configuration file names: its reference module, its preset
# ---------------------------------------------------------------------------


def reference_path(cfg: dict) -> str:
    """The configuration's reference module: ``reference`` in its file, a
    path from the repo's root; the default where the file names none."""
    path = os.path.join(ROOT, cfg.get("reference", DEFAULT_REFERENCE))
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"the configuration names the reference module "
            f"{cfg.get('reference', DEFAULT_REFERENCE)!r}: no such file "
            f"under {ROOT}")
    return path


# name -> the parameters it is called with, in this order
REFERENCE_CONTRACT = {
    "make_params": ("cfg", "seed"),
    "chosen_logprobs": ("cfg", "params", "prompt", "served", "quant"),
}


def load_reference(cfg: dict):
    """The reference module, held to its contract (``README.md``):
    ``make_params(cfg, seed)``, ``chosen_logprobs(cfg, params, prompt,
    served, quant=None)`` and ``CONTROLS``, the values of ``quant`` it
    knows. Anything missing is an error that names it."""
    path = reference_path(cfg)
    stem = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(f"bench_reference_{stem}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for name, want in REFERENCE_CONTRACT.items():
        fn = getattr(mod, name, None)
        if not callable(fn):
            raise TypeError(f"{path} defines no function {name!r}")
        have = tuple(inspect.signature(fn).parameters)
        if have[:len(want)] != want:
            raise TypeError(f"{path}: {name}{have} does not start with the "
                            f"contract's parameters {want}")
    controls = getattr(mod, "CONTROLS", None)
    if (not isinstance(controls, (tuple, list)) or not controls
            or not all(isinstance(c, str) for c in controls)):
        raise TypeError(f"{path} lists no CONTROLS (the values of `quant` "
                        f"its chosen_logprobs knows)")
    return mod


# ModelConfig field -> (published key, default where a published config may
# leave the key out; REQUIRED where it may not)
REQUIRED = object()
PUBLISHED = {
    "vocab_size": ("vocab_size", REQUIRED),
    "hidden_size": ("hidden_size", REQUIRED),
    "intermediate_size": ("intermediate_size", REQUIRED),
    "num_layers": ("num_hidden_layers", REQUIRED),
    "num_heads": ("num_attention_heads", REQUIRED),
    "num_kv_heads": ("num_key_value_heads", REQUIRED),
    "head_dim": ("head_dim", None),
    "rope_theta": ("rope_theta", REQUIRED),
    "rms_norm_eps": ("rms_norm_eps", REQUIRED),
    "max_seq_len": ("max_position_embeddings", REQUIRED),
    "tie_word_embeddings": ("tie_word_embeddings", False),
    "dtype": ("torch_dtype", "bfloat16"),
    "num_experts": ("num_local_experts", 0),
    "experts_per_token": ("num_experts_per_tok", 2),
}


def model_config(cfg: dict, name: str):
    """The program's preset of a configuration file: its published keys
    (``PUBLISHED``), then its ``preset``, ``{ModelConfig field: value}``,
    on top of them. A ``preset`` name that is no field of the program's
    ``ModelConfig`` is an error that names it, and so is a published key
    that the file leaves out and the ``preset`` does not give."""
    import dataclasses
    from rbg_tpu.models.config import ModelConfig
    known = {f.name for f in dataclasses.fields(ModelConfig)}
    preset = cfg.get("preset", {})
    unknown = sorted(set(preset) - known)
    if unknown:
        raise ValueError(
            f"preset names {unknown}: no field of rbg_tpu.models.config."
            f"ModelConfig, which has {sorted(known)}")
    fields = {"name": name}
    for field, (key, default) in PUBLISHED.items():
        if key in cfg:
            fields[field] = cfg[key]
        elif default is not REQUIRED:
            fields[field] = default
        elif field not in preset:
            raise KeyError(f"the configuration has no {key!r} and its preset "
                           f"no {field!r}")
    fields.update(preset)
    for field in ("rope_theta", "rms_norm_eps"):
        fields[field] = float(fields[field])
    fields["tie_word_embeddings"] = bool(fields["tie_word_embeddings"])
    return ModelConfig(**fields)


def install(cfg: dict, name: str, seed: int) -> None:
    """Preset and weights, before the server builds its engine. Replacing
    ``rbg_tpu.engine.engine.init_params`` is the one seam by which the
    benchmark's weights reach the engine: whatever family's model code the
    engine imports, it has to ask that name for them."""
    from rbg_tpu.engine import engine as engine_mod
    from rbg_tpu.models import config as model_presets
    model_presets._PRESETS[name] = model_config(cfg, name)
    reference = STATE["reference"] = load_reference(cfg)

    def benchmark_params(_mcfg, _key):
        import jax
        t0 = time.monotonic()
        params = reference.make_params(cfg, seed)
        jax.block_until_ready(params)
        STATE["params"] = params
        print(f"bench: weights from seed {seed} in "
              f"{time.monotonic() - t0:.2f}s", flush=True)
        return params

    engine_mod.init_params = benchmark_params


# ---------------------------------------------------------------------------
# step records (only with --record-steps)
# ---------------------------------------------------------------------------


def _rows_of_unified(eng) -> list:
    """(query tokens, cache length after the step) per row of the unified
    step about to run, from the engine's host state (no device read)."""
    rows = []
    chunk = eng.cfg.prefill_chunk
    for r in eng.running:
        if r.state == "prefill":
            end = min(r.prefill_pos + chunk, len(r.prompt))
            rows.append((end - r.prefill_pos, end))
        elif r.state == "running":
            rows.append((1, r.seq_len + 1))
    return rows


def record_steps() -> None:
    """Wrap the engine's two step methods: rows in, annotation around."""
    import jax
    from rbg_tpu.engine.engine import Engine

    def wrap(method_name, kind, rows_of):
        inner = getattr(Engine, method_name)

        def wrapped(self, *a, **kw):
            if not STATE["recording"]:
                return inner(self, *a, **kw)
            rows = rows_of(self)
            t0 = time.monotonic()
            with jax.profiler.TraceAnnotation(f"bench.{kind}"):
                out = inner(self, *a, **kw)
            if rows:
                STATE["steps"].append((t0, time.monotonic(), kind, rows))
            return out

        wrapped.__name__ = inner.__name__
        setattr(Engine, method_name, wrapped)

    wrap("_unified_step", "unified_step", _rows_of_unified)
    wrap("_fused_decode_step", "decode_step",
         lambda eng: [(1, r.seq_len + 1) for r in eng.running
                      if r.state == "running"])


# ---------------------------------------------------------------------------
# what the program's own warm-up leaves out
# ---------------------------------------------------------------------------


def warm_eager() -> int:
    """The unified step picks its sampling rows out of the packed logits
    with an eager ``logits[0][idx]``: three one-op programs (dynamic_slice,
    squeeze, gather) for every pair of packed-token bucket and sampling-row
    bucket. The server's ``warmup`` op compiles none of them and traffic
    meets a new pair at any time, so they are run here once on zeros of the
    same shapes; the engine's later calls find them in the process's
    executable cache. Listed in PERF.md for a later PR to move into the
    program's own warm-up."""
    import jax.numpy as jnp
    import numpy as np
    s, vocab = STATE["cfg"]["server"], STATE["cfg"]["vocab_size"]
    rows = [b for b in (1, 2, 4, 8, 16, 32, 64) if b < 2 * s["max_batch"]]
    n, t = len(STATE["compiles"]), 8
    while True:
        logits = jnp.zeros((1, t, vocab), jnp.float32)
        for b in rows:
            logits[0][jnp.asarray(np.zeros(b, np.int32))].block_until_ready()
        if t >= s["max_batch"] * s["prefill_chunk"]:
            break
        t *= 2
    return len(STATE["compiles"]) - n


# ---------------------------------------------------------------------------
# the benchmark's control port
# ---------------------------------------------------------------------------


def _ctl(obj: dict) -> dict:
    import jax
    import numpy as np
    op = obj.get("op")
    if op == "reference":
        if STATE["params"] is None:
            return {"error": "no weights yet"}
        lp = STATE["reference"].chosen_logprobs(
            STATE["cfg"], STATE["params"], obj["prompt"], obj["served"],
            obj.get("quant"))
        return {"logprobs": [float(x) for x in np.asarray(lp)]}
    if op == "trace_start":
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 1     # host frames, to name idle gaps
        opts.host_tracer_level = 2
        STATE["steps"].clear()
        STATE["recording"] = True
        jax.profiler.start_trace(obj["dir"], profiler_options=opts)
        STATE["tracing"] = True
        return {"ok": True, "t": time.monotonic()}
    if op == "trace_stop":
        t = time.monotonic()
        STATE["recording"] = False
        if STATE["tracing"]:
            jax.profiler.stop_trace()
            STATE["tracing"] = False
        return {"ok": True, "t": t, "stop_s": time.monotonic() - t,
                "steps": STATE["steps"][:]}
    if op == "warm_eager":
        return {"programs": warm_eager()}
    if op == "compiles":
        return {"compiles": [c for c in STATE["compiles"]
                             if obj["since"] <= c[0] < obj["until"]]}
    return {"error": f"unknown ctl op {op!r}"}


class _CtlHandler(socketserver.StreamRequestHandler):
    def handle(self):
        for line in self.rfile:
            try:
                reply = _ctl(json.loads(line))
            except Exception as e:  # noqa: BLE001 - reported to the caller
                import traceback
                traceback.print_exc()
                reply = {"error": f"{type(e).__name__}: {e}"}
            self.wfile.write(json.dumps(reply).encode() + b"\n")
            self.wfile.flush()


class _CtlServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--name", required=True, help="preset name to register")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--ctl-port", type=int, required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--record-steps", action="store_true")
    ap.add_argument("--platform", default="tpu",
                    help="what JAX must find here; anything else is fatal")
    args = ap.parse_args(argv)

    import jax
    found = jax.devices()[0].platform
    if found != args.platform:
        print(f"bench: JAX found platform {found!r}, the run needs "
              f"{args.platform!r}", flush=True)
        return 3
    with open(args.config) as f:
        cfg = json.load(f)
    STATE["cfg"] = cfg
    install(cfg, args.name, args.seed)
    log_compiles()
    if args.record_steps:
        record_steps()

    ctl = _CtlServer(("127.0.0.1", args.ctl_port), _CtlHandler)
    threading.Thread(target=ctl.serve_forever, daemon=True,
                     name="bench-ctl").start()

    from rbg_tpu.engine import server
    s = cfg["server"]
    return server.main([
        "--model", args.name, "--port", str(args.port),
        "--page-size", str(s["page_size"]),
        "--num-pages", str(s["num_pages"]),
        "--max-seq-len", str(s["max_seq_len"]),
        "--max-batch", str(s["max_batch"]),
        "--prefill-chunk", str(s["prefill_chunk"])])


if __name__ == "__main__":
    sys.exit(main())
