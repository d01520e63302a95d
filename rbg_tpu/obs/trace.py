"""Zero-dependency request tracing for the serving plane.

A request crosses http_frontend → router (retries/failover) → prefill →
KV handoff → decode → ``_BatchService`` queue/scan; the counters in
``obs/metrics.py`` say *that* p99 degraded, never *which hop* ate the
budget. This module is the per-request, per-hop timeline layer (the
Mooncake / "Taming the Chaos" trace-driven-analysis analog):

* :class:`Span` — trace_id / span_id / parent linkage, monotonic start +
  duration, structured attrs. Spans of one trace share a bounded
  ``_TraceState`` (``MAX_SPANS_PER_TRACE``; overflow is counted, never
  unbounded).
* ambient *current span* (thread-local stack, :func:`use_span` /
  :func:`current` / :func:`child`) so deep callees attach children
  without parameter plumbing;
* wire propagation: ``span.wire()`` rides request objects as
  ``obj["trace"] = {"trace_id", "parent_id", "sampled"}``;
  :func:`from_wire` continues an incoming context (joining the SAME
  in-process trace state when the hop shares the process — the stress
  drills see one rooted tree) and :func:`ingress_span` accepts a W3C
  ``traceparent`` header at the HTTP edge;
* a process-wide :class:`TraceSink` (``SINK``) holding two ring
  buffers — recent traces and slowest-N by root duration — pulled from a
  live plane via the admin / engine-server ``traces`` op;
* head-based sampling: the decision is made ONCE at ingress
  (``RBG_TRACE_SAMPLE``, default 1%) and rides the wire, so the hot
  decode loop is never perturbed for unsampled requests. Tracing off
  (``RBG_TRACE`` unset, the production default) means every entry point
  returns the falsy ``NULL_SPAN`` — same near-zero-overhead contract as
  locktrace.

``RBG_TRACE_STRICT=1`` is the runtime complement of the
``span-name-registry`` lint rule: a span name missing from the
``obs/names.py`` catalog raises at creation time.

:func:`annotation` is the bridge to the *device's* clock: the phases of
the serving loop (``engine.*`` / ``service.*`` in the catalog) are not
per-request spans but ``jax.profiler`` annotations, which cost under a
microsecond without a profiler session and land in the ``.xplane.pb``
beside the device ops with one (docs/observability.md "Step timeline").
"""

from __future__ import annotations

import gc
import os
import threading
import time
import uuid
from typing import Dict, List, Optional

from rbg_tpu.obs import names
from rbg_tpu.obs.metrics import REGISTRY

MAX_SPANS_PER_TRACE = 128
MAX_ACTIVE_TRACES = 512


def _env_flag(var: str) -> bool:
    v = (os.environ.get(var) or "").strip().lower()
    return bool(v) and v not in ("0", "false", "off")


class _Config:
    def __init__(self):
        self.enabled = _env_flag("RBG_TRACE")
        try:
            self.sample = float(os.environ.get("RBG_TRACE_SAMPLE", "0.01"))
        except ValueError:
            self.sample = 0.01
        self.strict = _env_flag("RBG_TRACE_STRICT")


_CFG = _Config()


def configure(enabled: Optional[bool] = None,
              sample: Optional[float] = None,
              strict: Optional[bool] = None) -> None:
    """Programmatic arming (the stress harness / tests; production uses the
    RBG_TRACE* env vars). ``None`` leaves a knob unchanged."""
    if enabled is not None:
        _CFG.enabled = bool(enabled)
    if sample is not None:
        _CFG.sample = float(sample)
    if strict is not None:
        _CFG.strict = bool(strict)


def enabled() -> bool:
    return _CFG.enabled


def _check_name(name: str) -> None:
    if _CFG.strict and name not in names.SPANS:
        raise ValueError(
            f"span name {name!r} is not cataloged in rbg_tpu/obs/names.py "
            f"SPANS (RBG_TRACE_STRICT is set)")


_profiler = None    # jax.profiler, once annotation() has been called


def annotation(name: str, **attrs):
    """The ``jax.profiler`` annotation (a context manager) for one phase
    of the serving loop, always on: without a profiler session entering
    it is a flag test. With ``step_num`` among the attributes it is a
    ``StepTraceAnnotation``, which the profile viewer lays out as one
    step. Attributes known only later go in through the annotation's own
    ``set_metadata(**attrs)`` before it exits. jax is imported at the
    first call, not at module load: the control plane traces requests
    without it."""
    global _profiler
    _check_name(name)
    if _profiler is None:
        from jax import profiler as _profiler
    if "step_num" in attrs:
        return _profiler.StepTraceAnnotation(name, **attrs)
    return _profiler.TraceAnnotation(name, **attrs)


class _GcClock:
    """Collections and seconds of Python's collector in this process
    (it runs on whichever thread allocates, holding the interpreter, so
    its pause is every thread's): one ``gc.callbacks`` hook, installed
    by the first serving loop that starts and left in place."""

    def __init__(self):
        self.collections = 0
        self.pause_s = 0.0
        self._t0: Optional[float] = None
        self._lock = threading.Lock()

    def install(self) -> "_GcClock":
        with self._lock:
            if self._on_gc not in gc.callbacks:
                gc.callbacks.append(self._on_gc)
        return self

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.monotonic()
        elif self._t0 is not None:
            self.pause_s += time.monotonic() - self._t0
            self.collections += 1
            self._t0 = None


GC_CLOCK = _GcClock()


def new_trace_id() -> str:
    return uuid.uuid4().hex            # 32 hex chars (traceparent-sized)


def new_span_id() -> str:
    return uuid.uuid4().hex[:16]       # 16 hex chars


class _NullSpan:
    """Falsy no-op span: the disabled/unsampled path. Every method is a
    cheap constant so call sites stay unconditional."""

    __slots__ = ()
    trace_id = ""
    span_id = ""
    parent_id = None
    sampled = False

    def __bool__(self):
        return False

    def child(self, name, **attrs):
        return self

    def end(self, **attrs):
        return None

    def wire(self):
        return None

    # Same context-manager contract as Span so the two stay interchangeable
    # on the ``with span.child(...):`` form.
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


NULL_SPAN = _NullSpan()


class _TraceState:
    """Shared bookkeeping for the spans of one in-process trace. The lock
    is a plain (untraced) threading.Lock — spans are recorded from handler
    AND loop threads, and the tracer must never feed back into the
    detectors it helps debug."""

    __slots__ = ("trace_id", "root", "spans", "dropped", "finalized", "lock")

    def __init__(self, trace_id: str, root: "Span"):
        self.trace_id = trace_id
        self.root = root
        self.spans: List[Span] = [root]
        self.dropped = 0
        self.finalized = False
        self.lock = threading.Lock()

    def add(self, span: "Span") -> bool:
        with self.lock:
            if self.finalized or len(self.spans) >= MAX_SPANS_PER_TRACE:
                self.dropped += 1
                REGISTRY.inc(names.TRACE_SPANS_DROPPED_TOTAL)
                return False
            self.spans.append(span)
            return True


class Span:
    __slots__ = ("trace_id", "span_id", "parent_id", "name", "t0",
                 "duration_s", "attrs", "_state")

    sampled = True

    def __init__(self, name: str, trace_id: str, parent_id: Optional[str],
                 state: Optional[_TraceState], attrs: Optional[dict] = None):
        _check_name(name)
        self.name = name
        self.trace_id = trace_id
        self.span_id = new_span_id()
        self.parent_id = parent_id
        self.t0 = time.monotonic()
        self.duration_s: Optional[float] = None
        self.attrs: Dict[str, object] = dict(attrs) if attrs else {}
        self._state = state

    def child(self, name: str, **attrs) -> "Span | _NullSpan":
        state = self._state
        if state is None:
            return NULL_SPAN
        sp = Span(name, self.trace_id, self.span_id, state, attrs)
        if not state.add(sp):
            return NULL_SPAN           # per-trace bound hit: drop, count
        return sp

    def end(self, **attrs) -> None:
        """Idempotent: the first end wins (error paths may double-end)."""
        if self.duration_s is not None:
            return
        self.duration_s = time.monotonic() - self.t0
        if attrs:
            self.attrs.update(attrs)
        state = self._state
        if state is not None and state.root is self:
            SINK._finalize(state)

    def wire(self) -> dict:
        """The context a downstream hop continues from (this span becomes
        the parent)."""
        return {"trace_id": self.trace_id, "parent_id": self.span_id,
                "sampled": True}

    # Context-manager form: ``with span.child(...) as sp:`` ends on exit.
    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.end()


# ---- ambient current-span context (per-thread) ----

_AMBIENT = threading.local()


def _stack() -> list:
    st = getattr(_AMBIENT, "stack", None)
    if st is None:
        st = _AMBIENT.stack = []
    return st


def current() -> "Span | _NullSpan":
    st = getattr(_AMBIENT, "stack", None)
    return st[-1] if st else NULL_SPAN


class use_span:
    """``with use_span(sp):`` makes ``sp`` the ambient current span for
    this thread. Pushing NULL_SPAN is legal (and cheap) so call sites
    never branch on sampling."""

    __slots__ = ("_span",)

    def __init__(self, span):
        self._span = span

    def __enter__(self):
        _stack().append(self._span)
        return self._span

    def __exit__(self, *exc):
        st = _stack()
        if st:
            st.pop()


def child(name: str, **attrs) -> "Span | _NullSpan":
    """Child of the ambient current span (NULL when nothing is ambient)."""
    return current().child(name, **attrs)


# ---- trace creation: ingress sampling + wire continuation ----


def start_trace(name: str, sample: Optional[bool] = None,
                **attrs) -> "Span | _NullSpan":
    """Root span for a NEW trace. The head-based sampling decision happens
    here, once; ``sample=True`` forces (the stress drills), ``None`` rolls
    the configured rate."""
    if not _CFG.enabled:
        return NULL_SPAN
    if sample is None:
        import random
        sample = random.random() < _CFG.sample
    if not sample:
        return NULL_SPAN
    tid = new_trace_id()
    root = Span(name, tid, None, None, attrs)
    root._state = SINK._open(tid, root)
    return root


def from_wire(ctx, name: str, **attrs) -> "Span | _NullSpan":
    """Continue an incoming wire context (``obj["trace"]``): the upstream
    hop already made the sampling decision. When the context names a trace
    whose state lives in THIS process (in-process multi-hop: router and
    service in one drill), the new span joins that state so the sink sees
    one rooted tree. No usable context ⇒ this hop IS ingress:
    :func:`start_trace` semantics."""
    if not (isinstance(ctx, dict) and ctx.get("sampled")
            and ctx.get("trace_id")):
        return start_trace(name, **attrs)
    if not _CFG.enabled:
        return NULL_SPAN
    tid = str(ctx["trace_id"])
    parent = ctx.get("parent_id")
    parent = str(parent) if parent else None
    state = SINK._lookup(tid)
    if state is not None:
        sp = Span(name, tid, parent, state, attrs)
        if not state.add(sp):
            return NULL_SPAN
        return sp
    sp = Span(name, tid, parent, None, attrs)
    sp._state = SINK._open(tid, sp)
    return sp


def ingress_span(name: str, traceparent: Optional[str] = None,
                 **attrs) -> "Span | _NullSpan":
    """HTTP-edge ingress: accept a W3C ``traceparent`` header
    (``00-<32 hex trace id>-<16 hex span id>-<flags>``; flags bit 0 =
    sampled). A valid sampled header continues that trace; a valid
    UNsampled one suppresses tracing for the request (the client made the
    head decision); anything else falls back to a local decision."""
    if not _CFG.enabled:
        return NULL_SPAN
    if traceparent:
        parts = traceparent.strip().split("-")
        if len(parts) >= 4 and len(parts[1]) == 32 and len(parts[2]) == 16:
            try:
                tid = parts[1].lower()
                parent = parts[2].lower()
                sampled = bool(int(parts[3], 16) & 1)
                int(tid, 16)
            except ValueError:
                pass
            else:
                if not sampled:
                    return NULL_SPAN
                return from_wire({"trace_id": tid, "parent_id": parent,
                                  "sampled": True}, name, **attrs)
    return start_trace(name, **attrs)


def inject(obj: dict, span=None) -> dict:
    """Attach the (ambient or given) span's wire context to a request
    object in place; no-op for unsampled requests."""
    sp = span if span is not None else current()
    if sp:
        obj["trace"] = sp.wire()
    return obj


# ---- the sink: recent + slowest ring buffers ----


class TraceSink:
    """Process-wide trace store. Two bounded buffers of *finalized* trace
    records — ``recent`` (last N roots to end) and ``slowest`` (top N by
    root duration) — plus the registry of active (not yet finalized)
    states. Active states are bounded too: past ``MAX_ACTIVE_TRACES`` the
    oldest is force-finalized as leaked, so a hop that never ends its
    root cannot grow memory without bound (and the leak is visible in
    ``rbg_trace_traces_total{result="leaked"}``)."""

    def __init__(self, recent: int = 64, slowest: int = 16):
        self._lock = threading.Lock()
        self._recent_cap = recent
        self._slowest_cap = slowest
        self._recent: List[dict] = []
        self._slowest: List[dict] = []
        self._active: "Dict[str, _TraceState]" = {}

    # -- active-state registry (module-internal) --

    def _open(self, trace_id: str, root: Span) -> _TraceState:
        state = _TraceState(trace_id, root)
        evict = None
        with self._lock:
            self._active[trace_id] = state
            if len(self._active) > MAX_ACTIVE_TRACES:
                oldest = next(iter(self._active))
                if oldest != trace_id:
                    evict = self._active.pop(oldest)
        if evict is not None:
            self._finalize(evict, leaked=True)
        return state

    def _lookup(self, trace_id: str) -> Optional[_TraceState]:
        with self._lock:
            return self._active.get(trace_id)

    def _finalize(self, state: _TraceState, leaked: bool = False) -> None:
        with state.lock:
            if state.finalized:
                return
            state.finalized = True
            spans = list(state.spans)
            dropped = state.dropped
        record = _record(state.trace_id, spans, dropped, leaked)
        REGISTRY.inc(names.TRACE_TRACES_TOTAL,
                     result=("leaked" if leaked else
                             "complete" if record["complete"] else
                             "incomplete"))
        with self._lock:
            self._active.pop(state.trace_id, None)
            self._recent.append(record)
            if len(self._recent) > self._recent_cap:
                del self._recent[0]
            self._slowest.append(record)
            self._slowest.sort(key=lambda r: -(r["duration_ms"] or 0.0))
            del self._slowest[self._slowest_cap:]

    # -- operator surface --

    def recent(self, n: int = 10) -> List[dict]:
        with self._lock:
            return list(self._recent[-n:])

    def slowest(self, n: int = 10) -> List[dict]:
        with self._lock:
            return list(self._slowest[:n])

    def active_count(self) -> int:
        with self._lock:
            return len(self._active)

    def snapshot(self, n: int = 10) -> dict:
        return {"recent": self.recent(n), "slowest": self.slowest(n),
                "active": self.active_count()}

    def reset(self) -> None:
        with self._lock:
            self._recent.clear()
            self._slowest.clear()
            self._active.clear()


SINK = TraceSink()


def _record(trace_id: str, spans: List[Span], dropped: int,
            leaked: bool) -> dict:
    """Finalized, JSON-able trace record. ``complete`` = the spans form
    one rooted tree (exactly one local root; every other parent resolves
    in-trace) and every span ended — the ``trace_complete`` invariant the
    stress drills assert. Dropped spans (per-trace bound) are counted
    separately; they are a bounding choice, not an orphan."""
    root = spans[0]
    t0 = root.t0
    ids = {s.span_id for s in spans}
    local_roots = [s for s in spans
                   if s.parent_id is None or s.parent_id not in ids]
    out_spans = []
    for s in sorted(spans, key=lambda s: s.t0):
        out_spans.append({
            "name": s.name, "span_id": s.span_id, "parent_id": s.parent_id,
            "start_ms": round((s.t0 - t0) * 1000.0, 3),
            "duration_ms": (round(s.duration_s * 1000.0, 3)
                            if s.duration_s is not None else None),
            "attrs": dict(s.attrs),
        })
    complete = (not leaked and len(local_roots) == 1
                and all(s.duration_s is not None for s in spans))
    return {
        "trace_id": trace_id,
        "root": root.name,
        "duration_ms": (round(root.duration_s * 1000.0, 3)
                        if root.duration_s is not None else None),
        "spans": out_spans,
        "dropped_spans": dropped,
        "complete": complete,
        "leaked": leaked,
    }


def complete(record: dict) -> bool:
    return bool(record.get("complete"))


def waterfall(record: dict) -> List[str]:
    """Human-readable waterfall for one trace record: tree-indented spans
    with start offset, duration, and attrs — what the stress report and
    the ``traces`` op print for the slowest request."""
    spans = record.get("spans") or []
    by_parent: Dict[Optional[str], List[dict]] = {}
    ids = {s["span_id"] for s in spans}
    for s in spans:
        parent = s["parent_id"] if s["parent_id"] in ids else None
        by_parent.setdefault(parent, []).append(s)
    lines = [f"trace {record.get('trace_id', '?')} "
             f"({record.get('duration_ms')} ms"
             f"{', INCOMPLETE' if not record.get('complete') else ''})"]

    def emit(parent: Optional[str], depth: int) -> None:
        for s in sorted(by_parent.get(parent, ()),
                        key=lambda s: s["start_ms"]):
            attrs = " ".join(f"{k}={v}" for k, v in
                             sorted(s.get("attrs", {}).items()))
            dur = (f"{s['duration_ms']:.1f}ms"
                   if s["duration_ms"] is not None else "UNFINISHED")
            lines.append(f"{'  ' * depth}{s['name']:<22} "
                         f"+{s['start_ms']:.1f}ms {dur}"
                         + (f"  {attrs}" if attrs else ""))
            emit(s["span_id"], depth + 1)

    emit(None, 1)
    return lines


def hop_coverage(record: dict) -> Optional[float]:
    """Fraction of the root span's duration covered by the union of its
    DIRECT children's intervals — the "hop durations sum to the root"
    acceptance check, overlap-safe. None when it cannot be computed."""
    spans = record.get("spans") or []
    if not spans or record.get("duration_ms") in (None, 0):
        return None
    root = spans[0]
    kids = [s for s in spans
            if s["parent_id"] == root["span_id"]
            and s["duration_ms"] is not None]
    if not kids:
        return 0.0
    iv = sorted((s["start_ms"], s["start_ms"] + s["duration_ms"])
                for s in kids)
    covered, lo, hi = 0.0, iv[0][0], iv[0][1]
    for a, b in iv[1:]:
        if a > hi:
            covered += hi - lo
            lo, hi = a, b
        else:
            hi = max(hi, b)
    covered += hi - lo
    return covered / record["duration_ms"]


def traces_response(n) -> dict:
    """The operator `traces` op payload, shared by the admin plane and the
    engine server: sink snapshot (recent + slowest ring buffers), the
    slowest request's rendered waterfall, and the histogram exemplars that
    link a bad quantile to a trace_id. ``n`` is clamped to [1, 64] and
    tolerates malformed input (wire-facing)."""
    from rbg_tpu.obs.metrics import REGISTRY
    try:
        n = int(n)
    except (TypeError, ValueError):
        n = 10
    resp = SINK.snapshot(max(1, min(n, 64)))
    slowest = resp.get("slowest") or []
    resp["waterfall"] = waterfall(slowest[0]) if slowest else []
    resp["exemplars"] = REGISTRY.exemplars_snapshot()
    return resp
