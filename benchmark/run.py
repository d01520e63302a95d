#!/usr/bin/env python3
"""Run one cell of the benchmark once and print the contract's last line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Everything about a cell is data: ``BENCHMARK.json`` names its
configuration file, its traffic file (``benchmark/traffic/<traffic>.json``)
and its metrics (``benchmark/end_to_end_metrics/<name>.json``,
``benchmark/layer_metrics/<name>.json``). This file knows none of them by
name. It starts the program's server (``harness/serve.py``; four of them
pinned to a chip each behind the program's router where the cell asks for
four chips), warms it up, checks the served path against the plain
reference, rehearses the cell's own traffic until nothing compiles any
more, then lets ``harness/loadgen.py`` offer the load and reads counters,
client records and (``--trace 1``) the device trace.

This process never imports JAX: a chip belongs to the server process.
It fails, and prints no result, unless the servers hold TPUs.
``--rehearse`` (never passed by the driver) walks the same flow on the CPU
for the tests; its line names the CPU and carries no device metric.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import statistics
import subprocess
import sys
import threading
import time

T_START = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

import numpy as np  # noqa: E402

from harness import client, opsbytes, serve, window  # noqa: E402

REHEARSAL_SEED = 20260927      # token contents of the rehearsal passes
LEAD_S = 3.0                   # loadgen start-up before the ramp is due
TRACE_S = 4.0                  # the stretch of the window the profiler sees
SAMPLED = ("waiting", "queue_depth")   # wire counters polled in a traced run


def say(msg: str) -> None:
    print(msg, flush=True)


COMPARED = []      # what `correct` compared, each number beside its limit


def say_compared(msg: str) -> None:
    """A line of the reference check: said now, and again as the run's
    last lines on standard error."""
    COMPARED.append(msg)
    say(msg)


class Children:
    """Every process this run starts; all are stopped whatever happens."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.procs = []

    def spawn(self, argv, env, log_name):
        log_path = os.path.join(self.out_dir, log_name)
        with open(log_path, "w") as log:
            proc = subprocess.Popen(argv, env=env, cwd=ROOT, stdout=log,
                                    stderr=subprocess.STDOUT)
        proc.log_path = log_path
        self.procs.append(proc)
        return proc

    def stop_all(self):
        for p in self.procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in self.procs:
            try:
                p.wait(timeout=45)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


def tail(path: str, lines: int = 30) -> str:
    with open(path, errors="replace") as f:
        return "".join(f.readlines()[-lines:])


def alive_check(proc):
    def check():
        if proc.poll() is not None:
            raise RuntimeError(f"{proc.args[1:3]} exited {proc.returncode}:\n"
                               f"{tail(proc.log_path)}")
    return check


def load_json(path: str) -> dict:
    with open(os.path.join(ROOT, path) if not os.path.isabs(path) else path) as f:
        return json.load(f)


def in_parallel(fn, items):
    """``fn(item)`` for every item side by side; the first error is
    raised after all have ended."""
    errors, threads = [], []

    def run(x):
        try:
            fn(x)
        except Exception as e:  # noqa: BLE001 - re-raised below
            errors.append(e)

    for x in items:
        threads.append(threading.Thread(target=run, args=(x,)))
        threads[-1].start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


def child_env(rehearse: bool) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("RBG_SERVE_PORT", "RBG_PORT_SERVE", "RBG_DATA_TOKEN")}
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    # Every program, however small, goes to the persistent cache: a later
    # run of the cell then loads the one-op eager programs too.
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    env.setdefault("TPU_LOG_DIR", "disabled")
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    return env


def start_servers(ch: Children, cell: dict, cfg_entry: dict, seed: int,
                  trace: bool, rehearse: bool) -> list:
    from rbg_tpu.utils import chipenv          # imports no JAX
    base = child_env(rehearse)
    n = cell["chips"]
    servers = []
    for i in range(n):
        port, ctl = client.free_port(), client.free_port()
        argv = [sys.executable, os.path.join(HERE, "harness", "serve.py"),
                "--config", os.path.join(ROOT, cfg_entry["file"]),
                "--name", cfg_entry["name"], "--port", str(port),
                "--ctl-port", str(ctl), "--seed", str(seed),
                "--platform", "cpu" if rehearse else "tpu"]
        if trace:
            argv.append("--record-steps")
        env = base if (n == 1 or rehearse) else chipenv.chip_env(i, n, base)
        proc = ch.spawn(argv, env, f"server{i}.log")
        servers.append({"addr": f"127.0.0.1:{port}",
                        "ctl": f"127.0.0.1:{ctl}", "proc": proc})
    return servers


def start_router(ch: Children, servers: list, rehearse: bool) -> dict:
    port = client.free_port()
    env = child_env(rehearse)
    env["JAX_PLATFORMS"] = "cpu"        # the router computes nothing
    proc = ch.spawn(
        [sys.executable, "-m", "rbg_tpu.engine.router", "--port", str(port),
         "--backends", json.dumps({"unified": [s["addr"] for s in servers]})],
        env, "router.log")
    addr = f"127.0.0.1:{port}"
    client.wait_healthy(addr, alive_check(proc), 120)
    return {"addr": addr, "proc": proc}


def server_metrics(servers: list) -> list:
    return [window.flatten(client.checked(s["addr"], {"op": "metrics"},
                                          30)["metrics"]) for s in servers]


def router_metrics(router) -> dict:
    if router is None:
        return {}
    h = client.checked(router["addr"], {"op": "health"}, 30)
    return window.flatten(h.get("metrics", {}))


def compiled(servers: list) -> int:
    return sum(m.get("compile.programs", 0) for m in server_metrics(servers))


# ---------------------------------------------------------------------------
# correct: the served path against the plain reference
# ---------------------------------------------------------------------------


def check_sample(server: dict, cfg: dict, seed: int) -> tuple:
    """The reference check's requests through the served path: alone, a
    prompt of two prefill chunks with cached decode steps after it; then
    side by side, as many as ``max_batch`` holds, a prompt that shares its
    first half (prefix-cache hit), the first prompt again (a fully cached
    turn) and fresh prompts of ``other_lens``, so that the full-batch
    unified and decode programs are compared too. Returns (prompts,
    replies) by name."""
    spec = cfg["correct"]
    rng = np.random.default_rng([seed, 99])
    new, vocab = spec["new_tokens"], cfg["vocab_size"]
    a = rng.integers(1, vocab, spec["first_len"]).tolist()
    prompts = {"first": a, "cached_again": a, "shared_prefix":
               a[:len(a) // 2] + rng.integers(1, vocab,
                                              spec["tail_len"]).tolist()}
    for i, n in enumerate(spec["other_lens"]):
        prompts[f"other{i}"] = rng.integers(1, vocab, n).tolist()

    got = {}

    def serve(key):
        got[key] = client.checked(server["addr"], {
            "op": "generate", "prompt": prompts[key], "max_new_tokens": new,
            "logprobs": True}, 900)

    serve("first")
    in_parallel(serve, [k for k in prompts if k != "first"])
    return prompts, got


def check_correct(server: dict, cfg: dict, seed: int) -> bool:
    """Each served token's log-probability against the reference's for
    the same position (teacher forcing on the served tokens). Over all
    positions the median has to be within ``limit`` and the 75th
    percentile within ``limit_p75``; every request's own median within
    ``limit_request`` (one request served from wrong pages moves neither
    of the first two far enough). The cached turn is one of the requests:
    held to the reference, it is held to the uncached turn. Prints every
    number compared beside its limit."""
    spec = cfg["correct"]
    new = spec["new_tokens"]
    t0 = time.monotonic()
    prompts, got = check_sample(server, cfg, seed)
    t_served = time.monotonic() - t0
    ok, pooled = True, []
    for key in prompts:
        toks, lps = got[key]["tokens"], got[key].get("logprobs") or []
        if len(toks) != new or len(lps) != new:
            say_compared(f"correct[{key}]: {len(toks)} tokens and "
                         f"{len(lps)} logprobs of {new} asked: truncated")
            ok = False
            continue
        ref = client.checked(server["ctl"], {
            "op": "reference", "prompt": prompts[key], "served": toks},
            900)["logprobs"]
        d = [abs(x - y) for x, y in zip(lps, ref)]
        pooled += d
        med = statistics.median(d)
        say_compared(
            f"correct[{key}]: median |served - reference| logprob over "
            f"{new} positions = {med:.5f} (limit {spec['limit_request']}); "
            f"max = {max(d):.5f}")
        ok = ok and med <= spec["limit_request"]
    if pooled:
        med, p75 = statistics.median(pooled), window.percentile(pooled, 75)
        say_compared(
            f"correct: over {len(pooled)} positions median = {med:.5f} "
            f"(limit {spec['limit']}), 75th percentile = {p75:.5f} (limit "
            f"{spec['limit_p75']}); 90th = "
            f"{window.percentile(pooled, 90):.5f}, not compared; served in "
            f"{t_served:.1f}s, reference in "
            f"{time.monotonic() - t0 - t_served:.1f}s")
        ok = ok and med <= spec["limit"] and p75 <= spec["limit_p75"]
    return ok


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------


def run_loadgen(ch: Children, traffic_path: str, vocab: int, addr: str,
                seed: int, t_open: float, seconds: float, out: str, tag: str,
                ramp=None, drain=None):
    argv = [sys.executable, os.path.join(HERE, "harness", "loadgen.py"),
            "--traffic", traffic_path, "--vocab", str(vocab), "--addr", addr,
            "--seed", str(seed), "--t-open", repr(t_open),
            "--seconds", repr(seconds), "--out", out]
    if ramp is not None:
        argv += ["--ramp", repr(ramp)]
    if drain is not None:
        argv += ["--drain", repr(drain)]
    return ch.spawn(argv, child_env(True), f"loadgen_{tag}.log")


def wait_loadgen(proc, budget: float) -> None:
    try:
        rc = proc.wait(timeout=budget)
    except subprocess.TimeoutExpired:
        raise RuntimeError(f"load generator still running after "
                           f"{budget:.0f}s:\n{tail(proc.log_path)}")
    if rc != 0:
        raise RuntimeError(f"load generator exited {rc}:\n"
                           f"{tail(proc.log_path)}")


def rehearse_traffic(ch, servers, traffic, traffic_path, vocab, addr,
                     seconds, out_dir) -> None:
    """The cell's own generator with other token contents, until a pass
    compiles nothing new (at most three passes)."""
    length = max(seconds * traffic["rehearse_share"], 1.0)
    drain = float(traffic.get("rehearse_drain_s", traffic["drain_s"]))
    before = compiled(servers)
    for i in range(3):
        t0 = time.monotonic()
        t_open = t0 + LEAD_S
        out = os.path.join(out_dir, f"rehearsal{i}.json")
        proc = run_loadgen(ch, traffic_path, vocab, addr,
                           REHEARSAL_SEED + i, t_open, length, out,
                           f"rehearsal{i}", ramp=0.0, drain=drain)
        wait_loadgen(proc, LEAD_S + length + drain + 30)
        after = compiled(servers)
        say(f"setup: rehearsal pass {i} ({time.monotonic() - t0:.1f}s) "
            f"compiled {after - before} programs")
        if after == before:
            return
        before = after


class Sampler(threading.Thread):
    """Polls a wire counter of every server each 100 ms (traced runs)."""

    def __init__(self, servers, names, t_open, seconds):
        super().__init__(daemon=True)
        self.servers, self.names = servers, names
        self.t_open, self.seconds = t_open, seconds
        self.samples = {n: [] for n in names}

    def run(self):
        while time.monotonic() < self.t_open + self.seconds:
            tick = time.monotonic()
            if tick >= self.t_open:
                try:
                    ms = server_metrics(self.servers)
                except (OSError, RuntimeError, ValueError):
                    ms = []
                for n in self.names:
                    if ms:
                        self.samples[n].append(sum(m.get(n, 0) for m in ms))
            time.sleep(max(0.0, 0.1 - (time.monotonic() - tick)))


def sleep_until(t: float) -> None:
    d = t - time.monotonic()
    if d > 0:
        time.sleep(d)


def trace_window(servers, out_dir, t_open, seconds) -> dict:
    """Bracket a short stretch of the window with the profiler in every
    server process, then reduce the traces in a process of their own."""
    span = min(seconds / 3.0, TRACE_S)
    sleep_until(t_open + seconds / 3.0)
    dirs = []
    for i, s in enumerate(servers):
        d = os.path.join(out_dir, f"trace{i}")
        shutil.rmtree(d, ignore_errors=True)
        dirs.append(d)
    starts = [None] * len(servers)

    def start(i):
        starts[i] = client.checked(servers[i]["ctl"], {
            "op": "trace_start", "dir": dirs[i]}, 60)["t"]

    in_parallel(start, range(len(servers)))
    sleep_until(max(starts) + span)
    stops = [None] * len(servers)

    def stop(i):
        stops[i] = client.checked(servers[i]["ctl"], {"op": "trace_stop"},
                                  300)

    in_parallel(stop, range(len(servers)))
    return {"dirs": dirs,
            "window_s": sum(b["t"] - a for a, b in zip(starts, stops))
            / len(servers),
            "steps": [[st for st in b["steps"]] for b in stops],
            "stop_s": max(b["stop_s"] for b in stops)}


def reduce_traces(traced: dict) -> dict:
    env = child_env(True)
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "harness", "trace_reduce.py"),
         *traced["dirs"]], env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=600)
    if r.returncode != 0:
        raise RuntimeError(f"trace reduction failed:\n{r.stderr[-3000:]}")
    out = json.loads(r.stdout.strip().splitlines()[-1])
    out["window_s"] = window.traced_stretch(traced["window_s"],
                                            out["devices"])
    out["steps"] = traced["steps"]
    with open(os.path.join(os.path.dirname(traced["dirs"][0]),
                           "trace_reduced.json"), "w") as f:
        json.dump(out, f)
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def metric_specs(bench: dict, group: str, directories: list, cell: str,
                 models: dict) -> list:
    """(entry, metric file) of the cell's metrics of one group; a metric's
    file is looked for in each of ``directories`` in turn. What a file
    names and nothing defines (a reader kind, a roofline's model) stops
    the run here, before any server starts."""
    out = []
    for m in bench[group]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        paths = [os.path.join(ROOT, d, m["name"] + ".json")
                 for d in directories]
        found = next((p for p in paths if os.path.exists(p)), None)
        if found is None:
            raise SystemExit(f"metric {m['name']!r} has no file: {paths}")
        spec = load_json(found)
        try:
            window.check_spec(spec, models)
        except ValueError as e:
            raise SystemExit(f"metric {m['name']!r} ({found}): {e}")
        out.append((m, spec))
    return out


def run(args, ch: Children) -> dict:
    bench = load_json(args.benchmark)
    cell = next((w for w in bench["workloads"] if w["name"] == args.workload),
                None)
    if cell is None:
        raise SystemExit(f"no workload {args.workload!r} in {args.benchmark}")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(cfg_entry["file"])
    try:
        serve.reference_path(cfg)
    except FileNotFoundError as e:
        raise SystemExit(f"configuration {cfg_entry['name']!r} "
                         f"({cfg_entry['file']}): {e}")
    # ``dirs`` is the tests' own: their cells file names directories that
    # are searched before the benchmark's.
    dirs = bench.get("dirs", {})
    models = opsbytes.models([os.path.join(ROOT, d)
                              for d in dirs.get("opsbytes", [])])
    traffic_path = os.path.join(
        ROOT, dirs.get("traffic", "benchmark/traffic"),
        cell["traffic"] + ".json")
    traffic = load_json(traffic_path)
    trace = bool(args.trace)
    group = "per_layer" if trace else "end_to_end"
    specs = metric_specs(
        bench, group, dirs.get(group, []) + [
            {"per_layer": "benchmark/layer_metrics",
             "end_to_end": "benchmark/end_to_end_metrics"}[group]],
        cell["name"], models)

    # -- servers, warm-up ---------------------------------------------------
    servers = start_servers(ch, cell, cfg_entry, args.seed, trace,
                            args.rehearse)
    devices = []
    for s in servers:
        h = client.wait_healthy(s["addr"], alive_check(s["proc"]), 1100)
        devices.append(h["device"])
    say(f"setup: {len(servers)} server(s) ready at "
        f"{time.monotonic() - T_START:.1f}s: " + "; ".join(
            f"{d['platform']} {d['kind']!r} x{d['count']}" for d in devices))
    want = "cpu" if args.rehearse else "tpu"
    if any(d["platform"] != want for d in devices):
        raise RuntimeError(f"servers hold {devices}, not {want}")
    count = sum(d["count"] for d in devices)
    if not args.rehearse and count != cell["chips"]:
        raise RuntimeError(f"the cell asks for {cell['chips']} chip(s), the "
                           f"servers hold {count}")
    in_parallel(lambda s: client.checked(s["addr"], {"op": "warmup"}, 1100),
                servers)
    in_parallel(lambda s: client.checked(s["ctl"], {"op": "warm_eager"}, 600),
                servers)
    say(f"setup: warm-up done at {time.monotonic() - T_START:.1f}s, "
        f"{compiled(servers)} programs so far")
    router = start_router(ch, servers, args.rehearse) \
        if len(servers) > 1 else None
    front = router["addr"] if router else servers[0]["addr"]

    # -- correct, rehearsal -------------------------------------------------
    correct = check_correct(servers[0], cfg, args.seed)
    say(f"setup: reference check done at {time.monotonic() - T_START:.1f}s")
    rehearse_traffic(ch, servers, traffic, traffic_path, cfg["vocab_size"],
                     front, args.seconds, ch.out_dir)

    # -- the window ---------------------------------------------------------
    ramp, drain = float(traffic["ramp_s"]), float(traffic["drain_s"])
    t_open = time.monotonic() + LEAD_S + ramp
    setup_s = (t_open - ramp) - T_START
    records_path = os.path.join(ch.out_dir, "records.json")
    gen = run_loadgen(ch, traffic_path, cfg["vocab_size"], front, args.seed,
                      t_open, args.seconds, records_path, "window")
    sleep_until(t_open)
    at_open = server_metrics(servers)
    r_open = router_metrics(router)
    sampler = traced = None
    if trace:
        sampler = Sampler(servers, SAMPLED, t_open, args.seconds)
        sampler.start()
        traced = trace_window(servers, ch.out_dir, t_open, args.seconds)
    sleep_until(t_open + args.seconds)
    at_close = server_metrics(servers)
    r_close = router_metrics(router)
    wait_loadgen(gen, drain + 60)
    after = server_metrics(servers)

    # -- readings -----------------------------------------------------------
    records = load_json(records_path)["records"]
    scalars, series = window.client_readings(records, args.seconds)
    per_server = [{k: c[k] - o.get(k, 0) for k in c}
                  for o, c in zip(at_open, at_close)]
    for k in {k for d in per_server for k in d}:
        scalars[k] = sum(d.get(k, 0) for d in per_server)
    for k in {k for d in at_open for k in d}:
        scalars["open." + k] = sum(d.get(k, 0) for d in at_open)
    for k, v in r_close.items():
        scalars["router." + k] = v - r_open.get(k, 0)
    scalars["setup_s"] = setup_s
    if sampler is not None:
        sampler.join()
        for n, xs in sampler.samples.items():
            series["samples." + n] = xs
    peak_bytes = max((m.get("device_memory.peak_bytes_in_use", 0)
                      for m in after), default=0)
    ctx = {"scalars": scalars, "series": series, "per_server": per_server,
           "seconds": args.seconds, "cfg": cfg, "trace": None,
           "models": models,
           "cap_ms": 1000.0 * (ramp + args.seconds + drain),
           "memory_peak_bytes": peak_bytes}
    device = {"platform": devices[0]["platform"], "kind": devices[0]["kind"],
              "count": count, "memory_peak_bytes": peak_bytes}
    result = {}
    if traced is not None and not args.rehearse:
        ctx["trace"] = reduce_traces(traced)
        ctx["peak"] = opsbytes.peak_for(devices[0]["kind"])
        if not ctx["trace"]["devices"] or ctx["trace"]["busy_s"] <= 0:
            raise RuntimeError("the trace holds no device operation: "
                               f"{json.dumps(ctx['trace']['structure'])[:2000]}")
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
        n = len(ctx["trace"]["devices"])
        ops, gaps = {}, {}
        for d in ctx["trace"]["devices"]:
            for name, s in d["ops_self"].items():
                ops[name] = ops.get(name, 0.0) + s / n
            for name, s in d["gaps"]:
                gaps[name] = gaps.get(name, 0.0) + s / n
        top = lambda d: [[k[:64], v] for k, v in  # noqa: E731
                         sorted(d.items(), key=lambda kv: -kv[1])[:10]]
        result["breakdown"] = {"device_ops": top(ops), "idle_gaps": top(gaps)}
        say(f"trace: {ctx['trace']['file_bytes']} bytes, profiler stop "
            f"{traced['stop_s']:.1f}s, {len(traced['steps'][0])} steps "
            f"recorded on server 0")

    metrics = {}
    for m, spec in specs:
        v = window.read_metric(spec, ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # -- what a reader of the line should know first --------------------------
    in_window = scalars.get("compile.programs", 0)
    if in_window:
        names = [c[1] for s in servers for c in client.checked(
            s["ctl"], {"op": "compiles", "since": t_open,
                       "until": t_open + args.seconds}, 30)["compiles"]]
        say(f"warning: {in_window} program(s) compiled inside the window: "
            f"{names}")
    late = window.percentile(series["client.lateness_ms"], 99)
    itl = window.percentile(series["client.itl_ms"], 50)
    if late is not None and itl is not None and late > itl / 10.0:
        say(f"warning: the load generator ran {late:.2f} ms late at p99, "
            f"over a tenth of the median token gap ({itl:.2f} ms)")
    truncated = [r for r in records if (r["error"] or "").startswith("trunc")]
    if truncated:
        say_compared(f"correct: {len(truncated)} request(s) of the window "
                     f"came back truncated (limit 0)")
        correct = False
    say(f"window: {scalars['client.attempted']} requests due, "
        f"{scalars['client.failed']} failed, "
        f"{scalars['client.tokens_in_window']} tokens received, "
        f"{scalars.get('steps', 0)} engine steps, compiles in window "
        f"{in_window}, lateness p99 {late} ms")
    # A sum over the window takes a stall whole and a median none of it:
    # every run says where its longest silences were.
    say("window: token gap p25/p50/p75/p99 " + "/".join(
        f"{window.percentile(series['client.itl_ms'], p) or 0:.2f}"
        for p in (25, 50, 75, 99)) + " ms; longest "
        "stretches with no token from any request: " + ", ".join(
            f"{1e3 * d:.0f} ms at {at:.1f}s" for d, at in
            window.silences(records, args.seconds)[:3]))
    result.update(correct=bool(correct),
                  attempted=scalars["client.attempted"],
                  failed=scalars["client.failed"], metrics=metrics,
                  device=device)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU walk-through for the tests; never a result "
                         "of the chip")
    ap.add_argument("--benchmark", default="BENCHMARK.json",
                    help="the cells file (tests point this at their own)")
    args = ap.parse_args(argv)

    out_dir = os.path.join(ROOT, ".bench_out", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    ch = Children(out_dir)
    try:
        result = run(args, ch)
    except Exception:  # noqa: BLE001 - reported, then the run fails
        import traceback
        traceback.print_exc()
        for p in ch.procs:
            if p.poll() not in (None, 0):
                sys.stderr.write(f"--- {p.log_path}\n{tail(p.log_path)}\n")
        return 1
    finally:
        ch.stop_all()
    sys.stderr.write("\n".join(COMPARED) + "\n")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
