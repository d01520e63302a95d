"""The device's idle time in a profile, by what the loop thread was doing.

    python scripts/idle_by_phase.py .bench_out/<cell>/trace0 [...]

Each ``<trace_dir>`` is what a traced run of the benchmark left behind (the
directory ``jax.profiler.start_trace`` was given by one server). The
device's idle time is the gaps between its operations. The loop thread's
``engine.*`` and ``service.*`` annotations (``rbg_tpu/obs/names.py``;
``engine/engine.py::_Phase``) lie on one line of the host's plane, on the
device trace's own clock: every instant of a gap is put down to the
innermost of them that covers it, and to ``unattributed`` where none does.
The stretch is the one both saw: from the device's first operation or the
loop thread's first span, whichever is later, to the earlier of their
last (the profiler goes on recording the device while it is being
stopped; what the device idled outside is printed and left out). One line
a span name: idle seconds, and their share of the stretch's idle time.

This is the check of the engine's starved-time probes (``t_starved_s`` and
its split, ``docs/observability.md`` "How long the device starved") on the
stretch both saw; the last lines give the stretch's idle time a step for
that comparison. Reads the file with the benchmark's own reader
(``benchmark/harness/xplane.py``); needs no JAX and no chip.
"""

import glob
import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"))

from harness import xplane                                   # noqa: E402
from harness.trace_reduce import DEVICE_PREFIX, OPS_LINE, merge  # noqa: E402

LOOP_SPAN = "engine.step"          # names the loop thread's line
DISPATCH_SPAN = "engine.dispatch"  # one a step that ran
PREFIXES = ("engine.", "service.")
UNATTRIBUTED = "unattributed"


def span_name(name: str) -> str:
    """An annotation's name without the attributes the tracer may have
    left on it (``name#key=value#``)."""
    return name.split("#", 1)[0]


def innermost_segments(events: list) -> list:
    """Disjoint ``(start, end, name)``, in order: each stretch of one
    line's nested ``(start, end, name)`` events under the name of the
    innermost event that covers it."""
    segs, stack = [], []               # stack of (end, name), outermost first
    cur = 0

    def advance(to):
        nonlocal cur
        if stack and to > cur:
            segs.append((cur, to, stack[-1][1]))
        cur = max(cur, to)

    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            advance(stack[-1][0])
            stack.pop()
        advance(s)
        stack.append((e, name))
    while stack:
        advance(stack[-1][0])
        stack.pop()
    return segs


def loop_line(planes: list) -> list:
    """The loop thread's annotations, ``(start, end, name)``: the host
    line that holds the most ``engine.step`` events, its ``engine.*`` and
    ``service.*`` events alone."""
    best, most = [], 0
    for plane in planes:
        if not plane["name"].startswith("/host:"):
            continue
        for ln in plane["lines"]:
            evs = [(s, e, span_name(n)) for s, e, n, *_ in ln["events"]]
            n = sum(1 for ev in evs if ev[2] == LOOP_SPAN)
            if n > most:
                best, most = evs, n
    return [ev for ev in best if ev[2].startswith(PREFIXES)]


def attribute(planes: list) -> list:
    """One dict a device plane of ``xplane.read_planes``' list: ``plane``,
    ``stretch_s`` (the stretch the device's operations and the loop
    thread's spans both cover), ``idle_s`` inside it, ``outside_s`` (the
    device's idle time outside it), ``steps`` (the loop thread's
    ``engine.dispatch`` spans that began inside the stretch) and
    ``by_span``: ``[(name, idle seconds)]``, most first, summing to
    ``idle_s``."""
    loop = loop_line(planes)
    segs = innermost_segments(loop)
    seen = ((min(s for s, _, _ in loop), max(e for _, e, _ in loop))
            if loop else None)
    out = []
    for plane in planes:
        if not plane["name"].startswith(DEVICE_PREFIX):
            continue
        ops = [ev[:2] for ln in plane["lines"] if ln["name"] == OPS_LINE
               for ev in ln["events"]]
        busy = merge(ops)
        if not busy:
            continue
        first, last = busy[0][0], busy[-1][1]
        if seen is not None:
            first, last = max(first, seen[0]), min(last, seen[1])
        by_span, i, outside = {}, 0, 0
        for (_, s), (e, _) in zip(busy, busy[1:]):       # the gap s..e
            outside += e - s
            s, e = max(s, first), min(e, last)
            if e <= s:
                continue
            outside -= e - s
            left = e - s
            while i < len(segs) and segs[i][1] <= s:
                i += 1
            j = i
            while j < len(segs) and segs[j][0] < e:
                a, b, name = segs[j]
                part = min(b, e) - max(a, s)
                by_span[name] = by_span.get(name, 0) + part
                left -= part
                j += 1
            if left:
                by_span[UNATTRIBUTED] = by_span.get(UNATTRIBUTED, 0) + left
        out.append({
            "plane": plane["name"],
            "stretch_s": max(last - first, 0) / 1e9,
            "idle_s": sum(by_span.values()) / 1e9,
            "outside_s": outside / 1e9,
            "steps": sum(1 for s, _, n in loop
                         if n == DISPATCH_SPAN and first <= s < last),
            "by_span": sorted(((n, ns / 1e9) for n, ns in by_span.items()),
                              key=lambda kv: -kv[1])})
    return out


def render(dev: dict) -> str:
    idle, steps = dev["idle_s"], dev["steps"]
    def share(part, whole):
        return 100 * part / whole if whole else 0.0

    lines = [f"{dev['plane']}: stretch {dev['stretch_s']:.4f} s, idle "
             f"{idle:.4f} s ({share(idle, dev['stretch_s']):.2f} %); "
             f"outside it idle {dev['outside_s']:.4f} s"]
    for name, s in dev["by_span"]:
        lines.append(f"{name:<24} {s:10.4f} s {share(s, idle):6.2f} %")
    named = idle - dict(dev["by_span"]).get(UNATTRIBUTED, 0.0)
    lines.append(f"{'named':<24} {named:10.4f} s {share(named, idle):6.2f} %")
    if steps:
        lines.append(f"steps {steps}: {1e3 * dev['stretch_s'] / steps:.3f} ms"
                     f" a step, of them idle {1e3 * idle / steps:.3f} ms")
    return "\n".join(lines)


def main(argv=None) -> int:
    dirs = argv if argv is not None else sys.argv[1:]
    if not dirs:
        print(__doc__, file=sys.stderr)
        return 2
    for d in dirs:
        paths = sorted(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                                 recursive=True))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {d}")
        for dev in attribute(xplane.read_planes(paths[-1])):
            print(render(dev))
    return 0


if __name__ == "__main__":
    sys.exit(main())
