"""The one traffic generator: reads a traffic file, offers its load.

    python benchmark/harness/loadgen.py --traffic benchmark/traffic/<t>.json \
        --vocab V --addr host:port --seed N --t-open T --seconds S --out F

A process of its own that never imports JAX. ``--t-open`` is a reading of
``time.monotonic()`` (one clock for every process of the machine): traffic
starts ``ramp_s`` before it, the measured window is ``[t_open, t_open +
seconds)``, nothing new is due after it, and what was due inside it is
followed for at most ``drain_s`` more.

Stratified, so that two seeds offer the same work. The expected number of
requests of each segment (ramp, window) is fixed by the rate; each length
distribution gives that many evenly spaced quantiles as a fixed multiset;
the seed decides their order, their pairing, the token contents and the
arrival times. Arrivals are a Poisson process conditioned on that count
(uniform order statistics).

Kinds: ``open`` (independent arrivals in one or more streams, a stream may
share prefixes in groups), ``closed`` (``clients`` callers, each taking
the next planned request when its last one ends), ``sessions`` (sessions arrive
open-loop, the turns of one session chain with think time and carry the
served answers forward).

Every time in the output is in seconds relative to ``t_open``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import statistics
import sys
import time

import numpy as np

_NORMAL = statistics.NormalDist()


# ---------------------------------------------------------------------------
# the plan: everything the seed decides, before any request is sent
# ---------------------------------------------------------------------------


def quantiles(dist: dict, n: int) -> list:
    """``n`` evenly spaced quantiles of ``dist``: the fixed multiset."""
    qs = [(i + 0.5) / n for i in range(n)]
    kind = dist["dist"]
    if kind == "uniform":
        xs = [dist["min"] + q * (dist["max"] - dist["min"]) for q in qs]
    elif kind == "lognormal":
        mu = math.log(dist["median"])
        xs = [math.exp(mu + dist["sigma"] * _NORMAL.inv_cdf(q)) for q in qs]
    elif kind == "exponential":
        xs = [-dist["mean"] * math.log(1.0 - q) for q in qs]
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    lo, hi = dist.get("min", -math.inf), dist.get("max", math.inf)
    xs = [min(max(x, lo), hi) for x in xs]
    return xs if dist.get("real") else [int(round(x)) for x in xs]


def arrivals(n: int, start: float, length: float, rng) -> list:
    """``n`` arrival times in ``[start, start + length)``, sorted: a
    Poisson process conditioned on its count (uniform order statistics)."""
    return [start + float(x) * length for x in np.sort(rng.random(n))]


def _tokens(rng, n: int, vocab: int) -> list:
    return rng.integers(1, vocab, int(n)).tolist()


def _spread_labels(groups: int, per_group: int, min_gap: int, rng) -> list:
    """A seeded order of ``groups * per_group`` labels in which equal
    labels sit at least ``min_gap`` places apart where that is possible."""
    labels = np.repeat(np.arange(groups), per_group)
    rng.shuffle(labels)
    labels = labels.tolist()
    for i in range(len(labels)):
        recent = set(labels[max(0, i - min_gap):i])
        if labels[i] in recent:
            for j in range(i + 1, len(labels)):
                if labels[j] not in recent:
                    labels[i], labels[j] = labels[j], labels[i]
                    break
    return labels


def _in_blocks(xs: list, block: int, rng) -> list:
    """The sorted multiset ``xs`` reordered so that every run of ``block``
    consecutive entries holds one value from each of ``block`` equal
    strata (smallest .. largest), in seeded order: whatever stretch of the
    list a window consumes, it gets the same mix."""
    xs = sorted(xs)
    per = len(xs) // block
    strata = [xs[j * per:(j + 1) * per] for j in range(block)]
    for st in strata:
        rng.shuffle(st)
    out = []
    for i in range(per):
        row = [st[i] for st in strata]
        rng.shuffle(row)
        out += row
    return out + xs[block * per:]


def _request_list(spec: dict, n: int, rng, vocab: int) -> list:
    """``n`` requests of one stream (or of a closed loop) in their seeded
    order: lengths from the fixed multisets, prefixes shared in groups
    where the spec says so. ``n`` is rounded to whole groups."""
    shared = spec.get("shared")
    if shared:
        per = shared["per_group"]
        groups = max(1, int(round(n / per)))
        n = groups * per
    prompts = quantiles(spec["prompt"], n)
    outputs = quantiles(spec["output"], n)
    if spec.get("block") and not shared:
        prompts = _in_blocks(prompts, int(spec["block"]), rng)
        outputs = _in_blocks(outputs, int(spec["block"]), rng)
    else:
        rng.shuffle(prompts)
        rng.shuffle(outputs)
    name = spec.get("name", "closed")
    if not shared:
        return [{"prompt": _tokens(rng, p, vocab), "want": o,
                 "class": "unshared", "stream": name}
                for p, o in zip(prompts, outputs)]
    prefix_len = quantiles(shared["prefix"], groups)
    rng.shuffle(prefix_len)
    prefixes = [_tokens(rng, m, vocab) for m in prefix_len]
    labels = _spread_labels(groups, per, shared["min_gap"], rng)
    reqs, seen = [], set()
    for g, p, o in zip(labels, prompts, outputs):
        cls = "shared_later" if g in seen else "shared_first"
        seen.add(g)
        reqs.append({"prompt": prefixes[g] + _tokens(rng, p, vocab),
                     "want": o, "class": cls, "stream": name})
    return reqs


def _open_segment(stream: dict, si: int, seg: int, start: float,
                  length: float, seed: int, vocab: int) -> list:
    rng = np.random.default_rng([seed, si, seg])
    n = int(round(stream["rate"] * length))
    if n == 0:
        return []
    reqs = _request_list(stream, n, rng, vocab)
    times = arrivals(len(reqs), start, length, rng)
    for r, t in zip(reqs, times):
        r["due"] = t
    return reqs


def plan(traffic: dict, seed: int, seconds: float, vocab: int) -> dict:
    """What will be offered, with times relative to the window's opening."""
    ramp = float(traffic["ramp_s"])
    kind = traffic["kind"]
    if kind == "open":
        reqs = []
        for si, stream in enumerate(traffic["streams"]):
            reqs += _open_segment(stream, si, 0, -ramp, ramp, seed, vocab)
            reqs += _open_segment(stream, si, 1, 0.0, seconds, seed, vocab)
        reqs.sort(key=lambda r: r["due"])
        return {"kind": kind, "requests": reqs}
    if kind == "closed":
        rng = np.random.default_rng([seed, 0, 0])
        reqs = _request_list(traffic, int(traffic["requests"]), rng, vocab)
        return {"kind": kind, "clients": int(traffic["clients"]),
                "requests": reqs}
    if kind == "sessions":
        sessions = []
        turns = int(traffic["turns"])
        for seg, (start, length) in enumerate(((-ramp, ramp), (0.0, seconds))):
            rng = np.random.default_rng([seed, 0, seg])
            n = int(round(traffic["rate"] * length))
            if n == 0:
                continue
            times = arrivals(n, start, length, rng)
            prefix = quantiles(traffic["prefix"], n)
            user = quantiles(traffic["user"], n * turns)
            answer = quantiles(traffic["answer"], n * turns)
            think = quantiles({**traffic["think"], "real": True}, n * turns)
            for xs in (prefix, user, answer, think):
                rng.shuffle(xs)
            for i, t in enumerate(times):
                sl = slice(i * turns, (i + 1) * turns)
                sessions.append({
                    "due": t, "prefix": _tokens(rng, prefix[i], vocab),
                    "user": [_tokens(rng, m, vocab) for m in user[sl]],
                    "want": answer[sl], "think": think[sl]})
        return {"kind": kind, "sessions": sessions}
    raise ValueError(f"unknown traffic kind {kind!r}")


def offered_tokens(p: dict) -> dict:
    """Prompt and output tokens the plan offers (sessions: the parts the
    plan fixes; the answers carried forward are the server's)."""
    if p["kind"] in ("open", "closed"):
        reqs = p["requests"]
    else:
        return {"requests": sum(len(s["user"]) for s in p["sessions"]),
                "prompt": sum(len(s["prefix"]) + sum(map(len, s["user"]))
                              for s in p["sessions"]),
                "output": sum(sum(s["want"]) for s in p["sessions"])}
    return {"requests": len(reqs),
            "prompt": sum(len(r["prompt"]) for r in reqs),
            "output": sum(r["want"] for r in reqs)}


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------


class Driver:
    def __init__(self, addr: str, t_open: float, seconds: float,
                 drain_s: float):
        self.host, port = addr.rsplit(":", 1)
        self.port = int(port)
        self.t_open, self.seconds, self.drain_s = t_open, seconds, drain_s
        self.records = []

    def now(self) -> float:
        return time.monotonic() - self.t_open

    async def until(self, t: float) -> None:
        delay = t - self.now()
        if delay > 0:
            await asyncio.sleep(delay)

    async def one(self, req: dict, due: float) -> dict:
        """Send one streamed generate at ``due``; record every frame."""
        rec = {"due": due, "class": req["class"], "stream": req["stream"],
               "prompt_len": len(req["prompt"]), "want": req["want"],
               "sent": None, "times": [], "counts": [], "tokens": [],
               "ok": False, "error": None, "code": None}
        self.records.append(rec)
        await self.until(due)
        writer = None
        try:
            reader, writer = await asyncio.open_connection(self.host,
                                                           self.port)
            rec["sent"] = self.now()
            writer.write(json.dumps({
                "op": "generate", "prompt": req["prompt"], "stream": True,
                "max_new_tokens": req["want"]}).encode() + b"\n")
            await writer.drain()
            while True:
                line = await reader.readline()
                t = self.now()
                if not line:
                    rec["error"] = "connection closed mid-stream"
                    break
                frame = json.loads(line)
                if frame.get("error"):
                    rec["error"] = str(frame["error"])
                    rec["code"] = frame.get("code")
                    break
                if frame.get("tokens"):
                    rec["times"].append(t)
                    rec["counts"].append(len(frame["tokens"]))
                    rec["tokens"] += frame["tokens"]
                if frame.get("done"):
                    break
            rec["ok"] = (rec["error"] is None
                         and len(rec["tokens"]) == req["want"])
            if rec["error"] is None and not rec["ok"]:
                rec["error"] = (f"truncated: {len(rec['tokens'])} of "
                                f"{req['want']} tokens")
        except asyncio.CancelledError:
            rec["error"] = rec["error"] or "not finished when the drain ended"
            raise
        except (OSError, ValueError) as e:
            rec["error"] = f"{type(e).__name__}: {e}"
        finally:
            rec["end"] = self.now()
            if writer is not None:
                writer.close()
        return rec

    # -- kinds ------------------------------------------------------------

    async def run_open(self, p: dict) -> None:
        await asyncio.gather(*(self.one(r, r["due"]) for r in p["requests"]))

    async def run_closed(self, p: dict, ramp: float) -> None:
        queue = iter(p["requests"])     # one seeded order, taken in turn

        async def client():
            due = -ramp
            while due < self.seconds:
                req = next(queue, None)
                if req is None:
                    raise RuntimeError(
                        "the closed loop ran out of planned requests: "
                        "raise `requests` in the traffic file")
                rec = await self.one(req, due)
                due = max(rec["end"], due)
        await asyncio.gather(*(client() for _ in range(p["clients"])))

    async def run_sessions(self, p: dict) -> None:
        async def session(s):
            history, due = list(s["prefix"]), s["due"]
            for k, user in enumerate(s["user"]):
                if due >= self.seconds:
                    return
                history = history + user
                rec = await self.one({
                    "prompt": history, "want": s["want"][k],
                    "class": "turn_first" if k == 0 else "turn_later",
                    "stream": "sessions"}, due)
                if not rec["ok"]:
                    return
                history = history + rec["tokens"]
                due = rec["end"] + s["think"][k]
        await asyncio.gather(*(session(s) for s in p["sessions"]))


async def _drive(driver: Driver, p: dict, ramp: float) -> None:
    if p["kind"] == "open":
        work = driver.run_open(p)
    elif p["kind"] == "closed":
        work = driver.run_closed(p, ramp)
    else:
        work = driver.run_sessions(p)
    task = asyncio.ensure_future(work)
    budget = driver.seconds + driver.drain_s - driver.now()
    try:
        await asyncio.wait_for(task, timeout=max(budget, 0.0))
    except asyncio.TimeoutError:
        pass        # the records say which requests were cut off


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--addr", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--t-open", type=float, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--ramp", type=float, default=None,
                    help="instead of the traffic file's ramp_s (rehearsals)")
    ap.add_argument("--drain", type=float, default=None,
                    help="instead of the traffic file's drain_s (rehearsals)")
    args = ap.parse_args(argv)
    with open(args.traffic) as f:
        traffic = json.load(f)
    if args.ramp is not None:
        traffic["ramp_s"] = args.ramp
    if args.drain is not None:
        traffic["drain_s"] = args.drain
    p = plan(traffic, args.seed, args.seconds, args.vocab)
    ramp = float(traffic["ramp_s"])
    if time.monotonic() > args.t_open - ramp:
        print("loadgen: the plan was ready "
              f"{time.monotonic() - (args.t_open - ramp):.3f}s after the "
              "ramp was due to start", file=sys.stderr, flush=True)
    driver = Driver(args.addr, args.t_open, args.seconds,
                    float(traffic["drain_s"]))
    asyncio.run(_drive(driver, p, ramp))
    for rec in driver.records:
        rec.pop("tokens")
        rec.setdefault("end", None)
    with open(args.out, "w") as f:
        json.dump({"offered": offered_tokens(p), "records": driver.records},
                  f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
