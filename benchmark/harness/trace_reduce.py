"""From a profiler trace to busy time, per-operation time and named gaps.

    python benchmark/harness/trace_reduce.py <trace_dir> [<trace_dir>...]

prints one JSON object. Each ``<trace_dir>`` is what
``jax.profiler.start_trace`` was given by one server process; its
``.xplane.pb`` is read by ``harness/xplane.py`` (``google.protobuf``, no
JAX), in a process that holds no chip.

The reduction itself (``reduce_events``) works on plain tuples
``(start_ns, end_ns, name[, scope])``, so that a hand-built event list
checks it:

* busy       - the union of the intervals in which an operation ran on the
               device; idle is the traced window minus that;
* ops_total  - seconds per operation name, every event whole;
* ops_self   - the same less the time of the operations nested inside
               (a ``while`` holds its body), so the column sums to busy;
* scopes_self - that self time by the ``jax.named_scope`` path the
               profiler recorded for the operation (``""`` where it
               recorded none), so this column sums to busy too;
* gaps       - the longest idle gaps, each named by the innermost host
               events (Python frames, ``bench.*`` annotations) that cover
               its middle.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _BENCH not in sys.path:          # run as a script: ``harness`` is a sibling
    sys.path.insert(0, _BENCH)

DEVICE_PREFIX = "/device:TPU"
OPS_LINE = "XLA Ops"
MAX_GAPS = 300
MIN_GAP_NS = 2000


def merge(intervals: list) -> list:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def self_times(events: list, key: int = 2) -> dict:
    """Seconds per ``event[key]`` (2 the name, 3 the scope), nested
    events' time taken out of their parents. ``events`` are ``(start_ns,
    end_ns, name[, scope])`` of one line (they nest)."""
    out = {}
    stack = []                                # [end, label, child_ns, start]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, label, child, start = stack.pop()
            out[label] = out.get(label, 0.0) + (end - start - child) / 1e9
            if stack:
                stack[-1][2] += end - start

    for ev in sorted(events, key=lambda x: (x[0], -x[1])):
        close(ev[0])
        stack.append([ev[1], ev[key] if key < len(ev) else "", 0, ev[0]])
    close(float("inf"))
    return out


def name_gaps(gaps: list, host_events: list) -> list:
    """``[(name, seconds)]`` summed by name, longest first. A gap is named
    by the two innermost host events covering its middle."""
    import numpy as np
    if host_events:
        hs = np.asarray([h[0] for h in host_events], np.int64)
        he = np.asarray([h[1] for h in host_events], np.int64)
    by_name = {}
    ranked = sorted(gaps, key=lambda g: g[0] - g[1])
    for s, e in ranked[:MAX_GAPS]:
        name = "unattributed"
        if host_events:
            mid = (s + e) // 2
            idx = np.nonzero((hs <= mid) & (he >= mid))[0]
            if len(idx):
                inner = idx[np.argsort(he[idx] - hs[idx])[:2]]
                name = " < ".join(host_events[i][2] for i in inner)
        by_name[name] = by_name.get(name, 0.0) + (e - s) / 1e9
    rest = sum(e - s for s, e in ranked[MAX_GAPS:]) / 1e9
    if rest:
        by_name[f"gaps beyond the longest {MAX_GAPS}"] = rest
    return sorted(by_name.items(), key=lambda kv: -kv[1])


def reduce_events(device_events: list, host_events: list) -> dict:
    """One device's reduction. Both lists hold ``(start_ns, end_ns,
    name)``; a device event may carry its scope as a fourth entry."""
    busy = merge([ev[:2] for ev in device_events])
    totals = {}
    for s, e, name, *_ in device_events:
        totals[name] = totals.get(name, 0.0) + (e - s) / 1e9
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:])
            if b[0] - a[1] >= MIN_GAP_NS]
    return {
        "busy_s": sum(e - s for s, e in busy) / 1e9,
        "first_ns": busy[0][0] if busy else None,
        "last_ns": busy[-1][1] if busy else None,
        "events": len(device_events),
        "ops_total": totals,
        "ops_self": self_times(device_events),
        "scopes_self": self_times(device_events, key=3),
        "gaps": name_gaps(gaps, host_events),
    }


# ---------------------------------------------------------------------------
# reading the profiler's file
# ---------------------------------------------------------------------------


_HLO = re.compile(r"^%?([\w.\-]+) = \(?(\w+\[[\d,]*\])?")


def op_name(name: str) -> str:
    """A device event is named by its whole HLO instruction
    (``%fusion.4 = bf16[8,4096]{...} fusion(...)``): keep the
    instruction's name and its first result shape."""
    m = _HLO.match(name)
    if not m:
        return name[:64]
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


def read_xplane(path: str) -> tuple:
    """(device planes' op events by plane name, host events, structure).
    A device event is ``(start_ns, end_ns, op_name(name), scope)``, a host
    event ``(start_ns, end_ns, name)``."""
    from harness import xplane
    devices, host, structure = {}, [], []
    for plane in xplane.read_planes(path):
        name, lines = plane["name"], plane["lines"]
        structure.append({"plane": name, "lines": [
            {"name": ln["name"], "events": len(ln["events"])}
            for ln in lines]})
        if name.startswith(DEVICE_PREFIX):
            for ln in lines:
                if ln["name"] == OPS_LINE:
                    devices[name] = [(s, e, op_name(n), scope)
                                     for s, e, n, scope in ln["events"]]
        elif name.startswith("/host:"):
            for ln in lines:
                host += [ev[:3] for ev in ln["events"]]
    return devices, host, structure


def reduce_dir(trace_dir: str) -> dict:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    devices, host, structure = read_xplane(paths[-1])
    return {"file_bytes": os.path.getsize(paths[-1]),
            "structure": structure,
            "devices": [dict(plane=name, **reduce_events(evs, host))
                        for name, evs in sorted(devices.items())]}


def main(argv=None) -> int:
    dirs = (argv if argv is not None else sys.argv[1:])
    out = {"devices": [], "structure": [], "file_bytes": 0}
    for d in dirs:
        r = reduce_dir(d)
        out["devices"] += r["devices"]
        out["structure"].append(r["structure"])
        out["file_bytes"] += r["file_bytes"]
    n = len(out["devices"])
    out["busy_s"] = (sum(d["busy_s"] for d in out["devices"]) / n
                     if n else 0.0)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
