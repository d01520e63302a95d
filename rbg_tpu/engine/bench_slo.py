"""North-star SLO suite: PD-disaggregated vs unified serving, measured.

BASELINE.md north star: PD-disagg throughput >= 50% of co-located, p50
TTFT < 200 ms (on TPU v5e-64 for Llama-3-70B). On this machine the suite
runs the same topology as a CPU proxy (tiny model, real processes, real
wire) so the ratio is a *tracked number* across rounds rather than an
aspiration; ``--platform tpu`` runs the identical command on a TPU host,
each engine process pinned to a chip of its own (a chip belongs to one
process; ``utils/chipenv.chip_env``) and everything else kept on the CPU.

Topologies (all real subprocesses over the wire protocol):

* ``unified`` — one engine server, requests hit it directly.
* ``pd``      — router + prefill + decode (+ shared KV pool wired to the
  prefill), the BASELINE config-3/4 shape; requests hit the router, KV
  bundles cross the wire (Mooncake-style DCN transfer).

Both are offered the SAME Poisson arrival schedule at each rate via
``bench_serving`` (open-loop), after a warmup that exercises every decode
batch bucket so XLA compilation never lands in a measured TTFT.

Usage:
    python -m rbg_tpu.engine.bench_slo --rates 8,16,24 --requests 96 \
        --json-out SLO_r05.json

Emits a markdown table (stdout) and, with --json-out, a BENCH-style JSON
artifact carrying the exact per-run command equivalents and the 1-min
load average observed before each measurement (docs/benchmarks.md
reproducibility rule: no number without its command + load note).
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time
from typing import Dict, List

from rbg_tpu.engine import bench_serving


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_ready(port: int, timeout: float = 240.0) -> None:
    from rbg_tpu.engine.protocol import request_once
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            h, _, _ = request_once(f"127.0.0.1:{port}", {"op": "health"},
                                   timeout=5)
            if h and h.get("ok"):
                return
        except OSError:
            pass
        time.sleep(0.5)
    raise TimeoutError(f"server on {port} never became ready")


class _Topology:
    """Spawn + tear down one serving topology. ``env`` is the CPU
    environment every process without an engine gets; with ``on_tpu`` the
    topology's engine servers each get one chip of this host instead."""

    def __init__(self, kind: str, engine_args: List[str], env: dict,
                 max_batch: int, decode_replicas: int = 1,
                 on_tpu: bool = False):
        self.kind = kind
        self.procs: List[subprocess.Popen] = []
        self.max_batch = max_batch
        self.engine_ports: List[int] = []
        self._on_tpu = on_tpu
        self._engines = 1 if kind == "unified" else 1 + decode_replicas
        self._engines_started = 0
        ports: Dict[str, int] = {}
        try:
            if kind == "unified":
                ports["front"] = _free_port()
                self._spawn_engine(["--mode", "unified",
                                    "--port", str(ports["front"])]
                                   + engine_args, env)
                _wait_ready(ports["front"])
                self.engine_ports = [ports["front"]]
            elif kind == "pd":
                for name in ("pool", "prefill", "front"):
                    ports[name] = _free_port()
                decode_ports = [_free_port() for _ in range(decode_replicas)]
                page = _flag(engine_args, "--page-size", "16")
                self._spawn(["-m", "rbg_tpu.engine.kvpool",
                             "--port", str(ports["pool"]),
                             "--page-size", page], env)
                self._spawn_engine(["--mode", "prefill",
                                    "--port", str(ports["prefill"]),
                                    "--kv-pool",
                                    f"127.0.0.1:{ports['pool']}"]
                                   + engine_args, env)
                for dp in decode_ports:
                    self._spawn_engine(["--mode", "decode",
                                        "--port", str(dp)] + engine_args, env)
                backends = {"prefill": [f"127.0.0.1:{ports['prefill']}"],
                            "decode": [f"127.0.0.1:{dp}"
                                       for dp in decode_ports]}
                self._spawn(["-m", "rbg_tpu.engine.router",
                             "--port", str(ports["front"]),
                             "--backends", json.dumps(backends)], env)
                for port in [ports["prefill"], ports["front"]] + decode_ports:
                    _wait_ready(port)
                self.engine_ports = [ports["prefill"]] + decode_ports
            else:
                raise ValueError(kind)
        except BaseException:
            self.stop()
            raise
        self.addr = f"127.0.0.1:{ports['front']}"

    def _spawn(self, argv: List[str], env: dict) -> None:
        self.procs.append(subprocess.Popen([sys.executable] + argv, env=env))

    def _spawn_engine(self, argv: List[str], env: dict) -> None:
        """An engine server: on a TPU host, engine i of n on chip i."""
        if self._on_tpu:
            from rbg_tpu.utils.chipenv import chip_env
            env = chip_env(self._engines_started, self._engines,
                           {**env, "JAX_PLATFORMS": "tpu"})
        self._engines_started += 1
        self._spawn(["-m", "rbg_tpu.engine.server"] + argv, env)

    def warmup(self, input_len: int) -> None:
        """Compile every jit bucket variant on every engine in the
        topology via the server's ``warmup`` op (a variant first hit
        mid-measurement shows up as a seconds-long stall — observed as a
        9x swing between identical runs), then a short full-batch wave
        through the FRONT door so the router / PD-transfer / pool paths
        are exercised end to end too."""
        import threading

        from rbg_tpu.engine.protocol import request_once
        import numpy as np
        token = os.environ.get("RBG_DATA_TOKEN") or None

        def req(extra):
            # Token-gated deployments (RBG_DATA_TOKEN set) must be
            # benchmarkable — attach the same credential the topology's
            # own processes inherited from this environment.
            return {**extra, "token": token} if token else extra

        for port in self.engine_ports:
            resp, _, _ = request_once(
                f"127.0.0.1:{port}",
                req({"op": "warmup", "input_len": input_len}), timeout=900)
            if not (resp or {}).get("ok"):
                raise RuntimeError(f"warmup failed on :{port}: {resp}")
        rng = np.random.default_rng(987)
        threads = []
        for _ in range(self.max_batch):
            prompt = rng.integers(200, 250, size=input_len).tolist()
            t = threading.Thread(
                target=lambda p=prompt: request_once(
                    self.addr, req({"op": "generate", "prompt": p,
                                    "max_new_tokens": 4}), timeout=600),
                daemon=True)
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=600)

    def stop(self) -> None:
        for p in self.procs:
            p.terminate()
        for p in self.procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


def _flag(args: List[str], name: str, default: str) -> str:
    return args[args.index(name) + 1] if name in args else default


def measure(kind: str, rates: List[float], args, env) -> List[dict]:
    engine_args = ["--model", args.model,
                   "--page-size", str(args.page_size),
                   "--num-pages", str(args.num_pages),
                   "--max-seq-len", str(args.max_seq_len),
                   "--max-batch", str(args.max_batch),
                   "--prefill-chunk", str(args.prefill_chunk),
                   "--use-pallas", args.use_pallas]
    topo = _Topology(kind, engine_args, env, args.max_batch,
                     decode_replicas=args.pd_decode_replicas,
                     on_tpu=args.platform == "tpu")
    rows = []
    try:
        topo.warmup(args.input_len)
        for rate in rates:
            bargs = argparse.Namespace(
                requests=args.requests, rate=rate,
                input_len=args.input_len, output_len=args.output_len,
                model=args.model, page_size=args.page_size,
                num_pages=args.num_pages, max_seq_len=args.max_seq_len,
                max_batch=args.max_batch, use_pallas=args.use_pallas,
                multi_step=1, speculative="off", addr=topo.addr,
                token=os.environ.get("RBG_DATA_TOKEN", ""),
                slo_ttft_s=args.slo_ttft_s, slo_tpot_s=args.slo_tpot_s,
                seed=args.seed, json=True)
            load1 = os.getloadavg()[0]
            out = bench_serving.run(bargs)
            out["setup"] = kind
            out["load1_before"] = round(load1, 2)
            replicas = (f" [pd topology: --pd-decode-replicas "
                        f"{args.pd_decode_replicas}]"
                        if kind == "pd" else "")
            out["command"] = (
                f"python -m rbg_tpu.engine.bench_serving --addr <{kind}> "
                f"--requests {args.requests} --rate {rate} "
                f"--input-len {args.input_len} --output-len {args.output_len} "
                f"--model {args.model} --max-batch {args.max_batch}"
                f"{replicas}")
            rows.append(out)
    finally:
        topo.stop()
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("rbg-tpu SLO suite (PD-disagg vs unified)")
    ap.add_argument("--rates", default="8,16,24",
                    help="comma-separated offered rates (req/s)")
    ap.add_argument("--requests", type=int, default=96)
    ap.add_argument("--input-len", type=int, default=32)
    ap.add_argument("--output-len", type=int, default=32)
    ap.add_argument("--model", default="tiny")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=512)
    ap.add_argument("--max-seq-len", type=int, default=512)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--use-pallas", default="never")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slo-ttft-s", type=float, default=0.2,
                    help="TTFT target for windowed goodput (default: the "
                         "BASELINE north-star 200 ms; 0 disables)")
    ap.add_argument("--slo-tpot-s", type=float, default=0.1,
                    help="per-output-token target for goodput (0 disables)")
    ap.add_argument("--json-out", default="",
                    help="write the BENCH-style artifact here")
    ap.add_argument("--setups", default="unified,pd")
    def _positive(v):
        n = int(v)
        if n < 1:
            raise argparse.ArgumentTypeError("must be >= 1")
        return n

    ap.add_argument("--pd-decode-replicas", type=_positive, default=1,
                    help="decode replicas in the pd topology (the router "
                         "least-loads across them) — the knob the "
                         "saturation ratio scales with")
    ap.add_argument("--platform", default="cpu", choices=["cpu", "tpu"],
                    help="cpu = CPU-proxy subprocesses (default); tpu = "
                         "each engine server holds one chip of this host "
                         "(unified needs one chip, pd one per engine), the "
                         "router and kv-pool stay on the CPU")
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",") if r]

    # The executor's env contract (RBG_SERVE_PORT & co) must not leak into
    # spawned topologies — it would override every --port with ONE value.
    from rbg_tpu.utils import scrubbed_cpu_env
    env = scrubbed_cpu_env(extra={"RBG_SERVE_PORT": None,
                                  "RBG_PORT_SERVE": None,
                                  "RBG_KV_POOL_ADDR": None})

    results: Dict[str, List[dict]] = {}
    for kind in args.setups.split(","):
        results[kind] = measure(kind, rates, args, env)

    # The north-star ratio at each matched rate.
    ratios = []
    if "unified" in results and "pd" in results:
        for u, p in zip(results["unified"], results["pd"]):
            ratios.append({
                "rate_rps": u["offered_rate_rps"],
                "pd_over_unified_throughput": round(
                    p["output_tok_per_s"] / u["output_tok_per_s"], 3)
                    if u["output_tok_per_s"] else None,
                "pd_ttft_p50_s": p["ttft_s"]["p50"],
                "unified_ttft_p50_s": u["ttft_s"]["p50"],
                # Attainment, not just latency quantiles: req/s that met
                # BOTH SLO targets — the trajectory SLO_r*.json tracks.
                "pd_goodput_rps": p.get("goodput_rps"),
                "unified_goodput_rps": u.get("goodput_rps"),
            })

    hdr = (f"| setup | rate rps | done | tok/s | ttft p50/p99 s | "
           f"itl p50/p99 ms | e2e p50/p99 s | load1 |")
    print(hdr)
    print("|" + "---|" * 8)
    for kind, rows in results.items():
        for r in rows:
            print(f"| {kind} | {r['offered_rate_rps']} "
                  f"| {r['completed']}/{r['requests']} "
                  f"| {r['output_tok_per_s']} "
                  f"| {r['ttft_s']['p50']}/{r['ttft_s']['p99']} "
                  f"| {r['itl_ms']['p50']}/{r['itl_ms']['p99']} "
                  f"| {r['e2e_s']['p50']}/{r['e2e_s']['p99']} "
                  f"| {r['load1_before']} |")
    for rt in ratios:
        print(f"ratio @ {rt['rate_rps']} rps: PD/unified throughput = "
              f"{rt['pd_over_unified_throughput']}  "
              f"(PD ttft p50 {rt['pd_ttft_p50_s']}s, PD goodput "
              f"{rt['pd_goodput_rps']} rps vs unified "
              f"{rt['unified_goodput_rps']} rps)")

    if args.json_out:
        artifact = {
            "suite": "pd_vs_unified_slo",
            "model": args.model,
            "hardware": "cpu-proxy" if args.platform == "cpu" else "tpu",
            "input_len": args.input_len, "output_len": args.output_len,
            "pd_decode_replicas": args.pd_decode_replicas,
            "slo_targets": {"ttft_s": args.slo_ttft_s,
                            "tpot_s": args.slo_tpot_s},
            "results": results, "north_star_ratios": ratios,
        }
        with open(args.json_out, "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"wrote {args.json_out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
