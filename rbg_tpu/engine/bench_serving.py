"""Serving benchmark: Poisson open-loop load against an engine, measuring
the serving SLOs the north star is written in (BASELINE.json: tokens/sec
AND p50 TTFT) — TTFT / inter-token latency / throughput percentiles.

Reference context: the reference's only perf apparatus is the
control-plane stress harness (``test/stress``); engine-side serving SLOs
are delegated to the engines it orchestrates. This harness closes that
gap for ours: an sglang.bench_serving analog that drives EITHER an
in-process ``EngineService`` (default — measures the engine itself) or a
remote server over the wire (``--addr``; measures the full role stack).

Open-loop (arrivals don't wait for completions) so the measured latencies
reflect queueing at the offered rate — the honest serving-SLO
methodology; a closed loop understates latency at saturation.

Usage:
    python -m rbg_tpu.engine.bench_serving --requests 64 --rate 16 \
        --model tiny --input-len 32 --output-len 32 [--addr host:port]

Prints one human table and, with ``--json``, one JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import threading
import time
from typing import List, Optional


def _percentile(xs: List[float], p: float) -> float:
    if not xs:
        return float("nan")
    ys = sorted(xs)
    i = min(len(ys) - 1, max(0, int(round(p / 100 * (len(ys) - 1)))))
    return ys[i]


class _Result:
    __slots__ = ("ttft_s", "itl_s", "n_tokens", "latency_s", "ok")

    def __init__(self):
        self.ttft_s: Optional[float] = None
        self.itl_s: List[float] = []
        self.n_tokens = 0
        self.latency_s = 0.0
        self.ok = False


def _drive_inprocess(args, prompts, arrivals):
    """Submit through an EngineService; per-token timing via step events."""
    from rbg_tpu.engine.config import EngineConfig, SamplingParams
    from rbg_tpu.engine.service import EngineService
    from rbg_tpu.utils import chipenv

    chipenv.configure_compile_cache()
    svc = EngineService(EngineConfig(
        model=args.model, page_size=args.page_size, num_pages=args.num_pages,
        max_seq_len=args.max_seq_len, max_batch=args.max_batch,
        use_pallas=args.use_pallas, multi_step=args.multi_step,
        speculative=args.speculative,
        # Honest prefills: warmup prompts must not seed a prefix cache the
        # measured requests then hit.
        enable_radix_cache=False))
    # Compile every jit bucket variant up front (prefill B, finish-sample
    # Bs, decode B — one wave per bucket size), so measured TTFT/ITL
    # excludes XLA compilation. Full-batch draining alone is NOT enough:
    # a bucket first hit mid-measurement was observed as a 9x throughput
    # swing between identical runs.
    svc.warmup(args.input_len)

    results = [_Result() for _ in prompts]
    lock = threading.Lock()
    done = threading.Event()
    outstanding = [len(prompts)]

    def one(i):
        res = results[i]
        t0 = time.perf_counter()
        p = svc.submit_async(prompts[i],
                             SamplingParams(max_new_tokens=args.output_len))
        try:
            last = [t0]

            # Poll tokens for ITL (the service appends as events arrive).
            while not p.done.wait(0.002):
                now = time.perf_counter()
                n = len(p.tokens)
                if n > res.n_tokens:
                    if res.ttft_s is None:
                        res.ttft_s = now - t0
                    else:
                        res.itl_s.append((now - last[0]) / (n - res.n_tokens))
                    res.n_tokens = n
                    last[0] = now
            res.n_tokens = len(p.tokens)
            if res.ttft_s is None and p.t_first:
                res.ttft_s = p.t_first - p.t_submit
            res.latency_s = time.perf_counter() - t0
            res.ok = p.error is None
        finally:
            with lock:
                outstanding[0] -= 1
                if not outstanding[0]:
                    done.set()

    t_start = time.perf_counter()
    for i, at in enumerate(arrivals):
        delay = t_start + at - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        threading.Thread(target=one, args=(i,), daemon=True).start()
    done.wait()
    wall = time.perf_counter() - t_start
    svc.stop()
    return results, wall


def _drive_remote(args, prompts, arrivals):
    """Streamed requests over the wire protocol against --addr."""
    import socket

    from rbg_tpu.engine.protocol import recv_msg, send_msg

    results = [_Result() for _ in prompts]
    done = threading.Event()
    lock = threading.Lock()
    outstanding = [len(prompts)]

    def one(i):
        res = results[i]
        t0 = time.perf_counter()
        try:
            host, port = args.addr.rsplit(":", 1)
            req = {"op": "generate", "prompt": prompts[i],
                   "max_new_tokens": args.output_len, "stream": True}
            token = getattr(args, "token", None)
            if token:
                req["token"] = token
            with socket.create_connection((host, int(port)),
                                          timeout=300) as s:
                send_msg(s, req)
                last = t0
                while True:
                    frame, _, _ = recv_msg(s)
                    if frame is None or "error" in (frame or {}):
                        break
                    toks = frame.get("tokens", [])
                    now = time.perf_counter()
                    if toks:
                        if res.ttft_s is None:
                            res.ttft_s = now - t0
                        else:
                            res.itl_s.append((now - last) / len(toks))
                        res.n_tokens += len(toks)
                        last = now
                    if frame.get("done"):
                        res.ok = True
                        break
            res.latency_s = time.perf_counter() - t0
        except OSError:
            pass
        finally:
            with lock:
                outstanding[0] -= 1
                if not outstanding[0]:
                    done.set()

    t_start = time.perf_counter()
    for i, at in enumerate(arrivals):
        delay = t_start + at - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        threading.Thread(target=one, args=(i,), daemon=True).start()
    done.wait()
    return results, time.perf_counter() - t_start


def run(args) -> dict:
    import numpy as np

    rng = np.random.default_rng(args.seed)
    # Synthetic prompts: random ids in a safe sub-vocab range.
    prompts = [rng.integers(1, 200, size=args.input_len).tolist()
               for _ in range(args.requests)]
    # Poisson process: exponential gaps at the offered rate.
    gaps = rng.exponential(1.0 / args.rate, size=args.requests)
    gaps[0] = 0.0
    arrivals = np.cumsum(gaps).tolist()

    if args.addr:
        results, wall = _drive_remote(args, prompts, arrivals)
    else:
        results, wall = _drive_inprocess(args, prompts, arrivals)

    ok = [r for r in results if r.ok]
    ttfts = [r.ttft_s for r in ok if r.ttft_s is not None]
    itls = [x for r in ok for x in r.itl_s]
    lats = [r.latency_s for r in ok]
    total_tokens = sum(r.n_tokens for r in ok)

    # SLO judgment (optional): per-request TTFT + TPOT ((e2e - ttft) /
    # (n - 1), the decode-side per-token latency) against the targets —
    # goodput is completions/s that met BOTH. The met-rules (0 disables
    # a dimension, a missing measurement fails an enabled one) live in
    # ONE place: SLOTargets.verdict, the same rules the serving plane's
    # rbg_slo_* series stand on.
    from rbg_tpu.obs.slo import SLOTargets
    ttft_target = float(getattr(args, "slo_ttft_s", 0.0) or 0.0)
    tpot_target = float(getattr(args, "slo_tpot_s", 0.0) or 0.0)
    targets = SLOTargets(ttft_s=ttft_target, tpot_s=tpot_target)

    def _tpot(r):
        if r.n_tokens > 1 and r.ttft_s is not None:
            return (r.latency_s - r.ttft_s) / (r.n_tokens - 1)
        return 0.0 if r.ttft_s is not None else None

    def _verdict(r):
        return targets.verdict(r.ttft_s, _tpot(r))

    out = {
        "requests": args.requests,
        "completed": len(ok),
        "offered_rate_rps": args.rate,
        "duration_s": round(wall, 3),
        "output_tok_per_s": round(total_tokens / wall, 1) if wall else 0.0,
        "ttft_s": {"p50": round(_percentile(ttfts, 50), 4),
                   "p90": round(_percentile(ttfts, 90), 4),
                   "p99": round(_percentile(ttfts, 99), 4)},
        "itl_ms": {"p50": round(_percentile(itls, 50) * 1e3, 2),
                   "p90": round(_percentile(itls, 90) * 1e3, 2),
                   "p99": round(_percentile(itls, 99) * 1e3, 2)},
        "e2e_s": {"p50": round(_percentile(lats, 50), 3),
                  "p99": round(_percentile(lats, 99), 3)},
    }
    if ttft_target > 0 or tpot_target > 0:
        verdicts = [_verdict(r) for r in ok]
        good = sum(1 for t_ok, p_ok in verdicts if t_ok and p_ok)
        out["slo"] = {
            "ttft_target_s": ttft_target, "tpot_target_s": tpot_target,
            "ttft_attainment": round(
                sum(1 for t_ok, _ in verdicts if t_ok) / len(ok), 4)
                if ok else None,
            "tpot_attainment": round(
                sum(1 for _, p_ok in verdicts if p_ok) / len(ok), 4)
                if ok else None,
            "goodput_fraction": round(good / len(ok), 4) if ok else None,
        }
        out["goodput_rps"] = round(good / wall, 3) if wall else 0.0
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("rbg-tpu serving benchmark")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--rate", type=float, default=16.0,
                    help="offered request rate (Poisson), req/s")
    ap.add_argument("--input-len", type=int, default=32)
    ap.add_argument("--output-len", type=int, default=32)
    ap.add_argument("--model", default="tiny")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=512)
    ap.add_argument("--max-seq-len", type=int, default=512)
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--use-pallas", default="auto")
    ap.add_argument("--multi-step", type=int, default=1)
    ap.add_argument("--speculative", default="off")
    ap.add_argument("--addr", default="",
                    help="benchmark a remote engine/router instead of "
                         "in-process (host:port)")
    ap.add_argument("--token", default=os.environ.get("RBG_DATA_TOKEN", ""),
                    help="data-plane bearer token for --addr targets "
                         "(default: $RBG_DATA_TOKEN)")
    ap.add_argument("--slo-ttft-s", type=float, default=0.0,
                    help="TTFT target: emit goodput_rps + attainment "
                         "(0 = no TTFT judgment)")
    ap.add_argument("--slo-tpot-s", type=float, default=0.0,
                    help="per-output-token latency target for goodput "
                         "(0 = no TPOT judgment)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", action="store_true",
                    help="print one JSON line instead of the table")
    args = ap.parse_args(argv)
    out = run(args)
    if args.json:
        print(json.dumps(out))
        return 0
    print(f"completed {out['completed']}/{out['requests']} requests "
          f"in {out['duration_s']}s @ offered {out['offered_rate_rps']} rps")
    print(f"throughput  {out['output_tok_per_s']} output tok/s")
    print(f"ttft        p50 {out['ttft_s']['p50']}s   p90 "
          f"{out['ttft_s']['p90']}s   p99 {out['ttft_s']['p99']}s")
    print(f"itl         p50 {out['itl_ms']['p50']}ms  p90 "
          f"{out['itl_ms']['p90']}ms  p99 {out['itl_ms']['p99']}ms")
    print(f"e2e         p50 {out['e2e_s']['p50']}s   p99 "
          f"{out['e2e_s']['p99']}s")
    if "goodput_rps" in out:
        slo = out["slo"]
        print(f"goodput     {out['goodput_rps']} req/s meeting ttft<="
              f"{slo['ttft_target_s']}s tpot<={slo['tpot_target_s']}s "
              f"(fraction {slo['goodput_fraction']})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
