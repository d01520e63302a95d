"""From what a run observed to the numbers it prints.

``client_readings`` reduces the load generator's records to named scalars
and series; ``READERS`` are the reader kinds a metric file may name. A
metric file (``benchmark/end_to_end_metrics/<name>.json`` or
``benchmark/layer_metrics/<name>.json``) is ``{"kind": <reader>, ...its
parameters}``; a reader that finds nothing to read returns ``None`` and
the metric is left out of the line.

Readings (``ctx``):

* ``scalars``  - ``<counter>`` is a wire counter's difference over the
  window summed over servers (nested ones dotted: ``compile.programs``);
  ``open.<counter>`` its value when the window opened; ``router.<c>`` the
  router's; ``client.<c>`` the load generator's; ``setup_s``.
* ``per_server`` - the same differences, one entry per server.
* ``series``   - ``client.ttft_ms`` (failed requests are ``inf``),
  ``client.ttft_ms.<class>``, ``client.itl_ms``, ``client.lateness_ms``,
  ``samples.<counter>`` (polled over the wire in a traced run).
* ``trace``    - ``trace_reduce``'s output, or ``None`` without a trace.
* ``models``   - ``opsbytes.models()`` with the run's directories (absent:
  ``opsbytes.MODELS``), for ``trace_roofline``.
"""

from __future__ import annotations

import math
import re

from harness import opsbytes


def percentile(xs: list, p: float):
    """Nearest-rank percentile; ``inf`` entries (failures) rank last."""
    if not xs:
        return None
    ys = sorted(xs)
    return ys[max(0, math.ceil(p / 100.0 * len(ys)) - 1)]


def client_readings(records: list, seconds: float) -> tuple:
    """(scalars, series) of one window. Latencies belong to requests *due*
    in the window, tokens to the window they were *received* in."""
    due_in = [r for r in records if 0.0 <= r["due"] < seconds]
    series = {"client.ttft_ms": [], "client.itl_ms": [],
              "client.lateness_ms": []}
    tokens_in = 0
    for r in records:
        tokens_in += sum(c for t, c in zip(r["times"], r["counts"])
                         if 0.0 <= t < seconds)
    refused = 0
    for r in due_in:
        ttft = ((r["times"][0] - r["due"]) * 1000.0
                if r["ok"] and r["times"] else math.inf)
        series["client.ttft_ms"].append(ttft)
        series.setdefault(f"client.ttft_ms.{r['class']}", []).append(ttft)
        if r["sent"] is not None:
            series["client.lateness_ms"].append((r["sent"] - r["due"]) * 1e3)
        if r["code"] in ("overloaded", "rejected", "draining"):
            refused += 1
        for i in range(1, len(r["times"])):
            gap = (r["times"][i] - r["times"][i - 1]) * 1e3 / r["counts"][i]
            series["client.itl_ms"] += [gap] * r["counts"][i]
    scalars = {
        "client.attempted": len(due_in),
        "client.failed": sum(1 for r in due_in if not r["ok"]),
        "client.refused": refused,
        "client.tokens_in_window": tokens_in,
        "client.prompt_tokens": sum(r["prompt_len"] for r in due_in),
    }
    return scalars, series


def silences(records: list, seconds: float) -> list:
    """(length, start) in seconds of the stretches of the window in which
    no request received a token, longest first."""
    stamps = sorted(t for r in records for t in r["times"]
                    if 0.0 <= t < seconds)
    edges = [0.0] + stamps + [seconds]
    return sorted(((b - a, a) for a, b in zip(edges, edges[1:])),
                  reverse=True)


def flatten(d: dict, prefix: str = "") -> dict:
    """Numeric leaves of a (nested) counter dict, dotted."""
    out = {}
    for k, v in d.items():
        if isinstance(v, dict):
            out.update(flatten(v, f"{prefix}{k}."))
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            out[f"{prefix}{k}"] = v
    return out


# ---------------------------------------------------------------------------
# reader kinds
# ---------------------------------------------------------------------------


def _sum(ctx, names):
    vals = [ctx["scalars"].get(n) for n in names]
    return None if any(v is None for v in vals) else sum(vals)


def r_counter_delta(m, ctx):
    return ctx["scalars"].get(m["counter"])


def r_counter_at_open(m, ctx):
    return ctx["scalars"].get("open." + m["counter"])


def r_counter_ratio(m, ctx):
    num, den = _sum(ctx, m["num"]), _sum(ctx, m["den"])
    if num is None or not den:
        return None
    return m.get("scale", 1.0) * num / den


def r_per_second(m, ctx):
    v = ctx["scalars"].get(m["counter"])
    return None if v is None else v / ctx["seconds"]


def r_client_percentile(m, ctx):
    xs = ctx["series"].get(m["series"])
    if not xs:
        return None
    v = percentile(xs, m["p"])
    # A failed request ranks last; where the percentile lands on one, the
    # longest any request can have been waited for stands in its place.
    return ctx["cap_ms"] if math.isinf(v) else v


def r_series_mean(m, ctx):
    xs = ctx["series"].get(m["series"])
    return sum(xs) / len(xs) if xs else None


def r_server_max_over_mean(m, ctx):
    xs = [s.get(m["counter"]) for s in ctx["per_server"]]
    if len(xs) < 2 or any(x is None for x in xs) or not sum(xs):
        return None
    return max(xs) / (sum(xs) / len(xs))


def _op_seconds(ctx, patterns, field="ops_total"):
    """Device seconds of the operations whose names match, averaged over
    the traced devices."""
    tr = ctx.get("trace")
    if not tr or not tr["devices"]:
        return None
    rx = [re.compile(p) for p in patterns]
    tot = 0.0
    for dev in tr["devices"]:
        tot += sum(s for name, s in dev[field].items()
                   if any(r.search(name) for r in rx))
    return tot / len(tr["devices"])


def r_trace_op_share(m, ctx):
    s = _op_seconds(ctx, m["ops"])
    if s is None or not ctx["trace"]["busy_s"]:
        return None
    return 100.0 * s / ctx["trace"]["busy_s"]


def r_trace_scope_share(m, ctx):
    """Device self time of the operations whose ``jax.named_scope`` path
    matches, over busy time. Nothing where no path matches: never 0 for a
    block the program does not have."""
    s = _op_seconds(ctx, m["scopes"], "scopes_self")
    if not s or not ctx["trace"]["busy_s"]:
        return None
    return 100.0 * s / ctx["trace"]["busy_s"]


def traced_stretch(host_window_s: float, devices: list) -> float:
    """Seconds the profiler covered. It goes on recording while it is being
    stopped, so that is the host's window or the devices' own span of
    operations (their mean), whichever is longer: busy time, which lies
    inside the span, never exceeds it."""
    spans = [(d["last_ns"] - d["first_ns"]) / 1e9 for d in devices
             if d["first_ns"] is not None]
    return max(host_window_s, sum(spans) / len(spans) if spans else 0.0)


def r_trace_idle(m, ctx):
    tr = ctx.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def r_trace_roofline(m, ctx):
    """Least time the chip could take for the calls' live work over the
    time the trace gives them. Above 100 the operations or bytes are
    counted too high or the time leaves out part of the work: an error."""
    s = _op_seconds(ctx, m["ops"])
    steps = (ctx.get("trace") or {}).get("steps")
    if not s or not steps:
        return None
    model = ctx.get("models", opsbytes.MODELS)[m["model"]]
    least = 0.0
    for server_steps in steps:
        for _t0, _t1, _kind, rows in server_steps:
            least += opsbytes.least_seconds(model, ctx["cfg"], rows,
                                            ctx["peak"])
    least /= len(steps)
    share = 100.0 * least / s
    if share > 100.0:
        raise ValueError(
            f"roofline share {share:.1f}% of {m['ops']} is above 100: least "
            f"time {least:.4f}s against {s:.4f}s in the trace")
    return share


def r_memory_peak(m, ctx):
    b = ctx.get("memory_peak_bytes")
    return None if not b else b / m["divide"]


READERS = {
    "counter_delta": r_counter_delta,
    "counter_at_open": r_counter_at_open,
    "counter_ratio": r_counter_ratio,
    "per_second": r_per_second,
    "client_percentile": r_client_percentile,
    "series_mean": r_series_mean,
    "server_max_over_mean": r_server_max_over_mean,
    "trace_op_share": r_trace_op_share,
    "trace_scope_share": r_trace_scope_share,
    "trace_idle": r_trace_idle,
    "trace_roofline": r_trace_roofline,
    "memory_peak": r_memory_peak,
}


def check_spec(spec: dict, models: dict) -> None:
    """Refuse at start what would fail at read: a reader kind that is not
    one of ``READERS``, a roofline whose ``model`` no file defines."""
    kind = spec.get("kind")
    if kind not in READERS:
        raise ValueError(f"unknown reader kind {kind!r}; have "
                         f"{sorted(READERS)}")
    if kind == "trace_roofline" and spec.get("model") not in models:
        raise ValueError(
            f"roofline model {spec.get('model')!r} is defined by no file of "
            f"benchmark/opsbytes; have {sorted(models)}")


def read_metric(spec: dict, ctx: dict):
    check_spec(spec, ctx.get("models", opsbytes.MODELS))
    return READERS[spec["kind"]](spec, ctx)
