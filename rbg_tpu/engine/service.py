"""Engine services: background continuous-batching loops + blocking APIs.

Requests arriving on different connections batch together on the device —
server threads only enqueue and wait; ONE loop thread owns each engine
(single-writer, no engine locking on the hot path). ``EngineService`` serves
unified generate; ``DecodeService`` serves the disaggregated decode role
(KV-bundle injection). Both share the same loop machinery: locked queue
swap, admission capped at the engine's max_batch, cancel routing (timeouts
recycle batch slots + KV pages), and the event pump.
"""

from __future__ import annotations

import collections
import resource
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from rbg_tpu.engine.config import EngineConfig, SamplingParams
from rbg_tpu.engine.engine import _SYNC, Engine, _Phase
# Re-exported here for callers that think in service terms; defined in
# protocol.py so jax-free processes (server startup) can import them.
from rbg_tpu.engine.protocol import (CODE_DEADLINE, DeadlineExceeded,
                                     Overloaded, Rejected)
from rbg_tpu.obs import names, trace
from rbg_tpu.obs.metrics import REGISTRY
from rbg_tpu.obs.slo import SLOTargets, SLOTracker
from rbg_tpu.utils import chipenv, jitwatch, pystack
from rbg_tpu.utils.locktrace import named_lock
from rbg_tpu.utils.racetrace import guard as _race_guard


class _Pending:
    __slots__ = ("tokens", "stamps", "logprobs", "done", "t_submit",
                 "t_admit", "t_first", "error", "code", "deadline",
                 "span_parent", "span_queue", "span_scan", "stream_rx")

    def __init__(self, deadline: Optional[float] = None):
        self.tokens: List[int] = []
        # 1:1 with tokens, appended first: the time.monotonic() of the
        # ``_deliver`` that handed each on (one read a delivery), so that
        # the relay finds the stamp of its first unsent index.
        self.stamps: List[float] = []
        self.logprobs: List[float] = []   # 1:1 with tokens when requested
        self.done = threading.Event()
        self.t_submit = time.perf_counter()
        # When the engine first gave the request a batch row (None until
        # then; a preempted request's later admissions do not move it).
        self.t_admit: Optional[float] = None
        self.t_first: Optional[float] = None
        self.error: Optional[str] = None
        self.code: Optional[str] = None   # structured rejection code
        self.deadline = deadline          # absolute time.monotonic() budget
        # Tracing (obs/trace.py): parent span of this request plus the
        # queue-wait / scan child spans — NULL_SPAN when unsampled, so
        # every lifecycle site below ends them unconditionally.
        self.span_parent = trace.NULL_SPAN
        self.span_queue = trace.NULL_SPAN
        self.span_scan = trace.NULL_SPAN
        # KV stream receiver backing this request (decode_stream path) —
        # its t_first_step is stamped at the first decode token, the
        # kv_stream_overlap invariant's input.
        self.stream_rx = None


DEFAULT_TIMEOUT_S = 600.0
# Completion timestamps kept for the estimated-wait admission gate.
_RATE_WINDOW = 64
# Prefill-throughput EMA expiry for the early-reject predictor: past
# this, the rate is absence-of-signal, not a measurement (a shed does
# no prefill, so a stale-slow rate could otherwise never re-learn).
_PF_RATE_TTL_S = 30.0
# Fallback backpressure hint when no throughput estimate exists yet.
_RETRY_AFTER_FLOOR_S = 0.5
# A turn of the loop whose wall time outside ``engine.sync`` and
# ``service.idle`` passes this is a late step: five times the longest host
# part of any step on record (10 ms of a unified step), under half the
# shortest pause on record (105 ms; PERF.md section 5).
LATE_STEP_S = 0.05
# A sync wait this long is a turn late by itself: the longest device step
# on record is 56 ms, so a second of it is a device or a runtime that
# stands, or the process stood while this thread waited.
SYNC_STALL_S = 1.0
# The watchdog's period: a late turn's stack is taken within one period
# of its becoming late, and 50 wake-ups a second cost a core nothing.
WATCHDOG_PERIOD_S = 0.02
# The loop reads its thread's rusage every so many turns, and at the end
# of every late one: the call costs 6 us on the chip's host (30 in place,
# between a sync wait and the next turn), and the CPU clock it reads
# ticks in steps of 10 ms there, so a reading a turn would buy nothing.
RUSAGE_EVERY = 8
# Most CPU seconds a turn may leave owing to later turns' off-CPU time:
# two ticks of the coarsest thread CPU clock met (10 ms, the chip's host).
_CPU_DEBT_S = 0.02
# Innermost frames of the loop thread kept in a late-step record.
STACK_FRAMES = 12
# The host phases of a turn, in the order a late-step record weighs them,
# and the sync wait, which it names only where no host phase was late.
_HOST_PHASES = ((names.SPAN_SERVICE_INTAKE,) + tuple(
    n for i, n in enumerate(_Phase.SPANS) if i != _SYNC)
    + (names.SPAN_SERVICE_DELIVER,))
_LATE_PHASES = _HOST_PHASES + (names.SPAN_ENGINE_SYNC,)


def embed_prompts(engine: Engine, prompts: List[List[int]]) -> List[List[float]]:
    """Mean-pooled final-norm hidden states, ONE batched forward for the
    whole list (encode_hidden is [B, T]-shaped; a per-string forward would
    cost B serial dispatches). Pads (B, T) to (chunk-multiple) buckets and
    caches one jitted program per bucket on the engine. Safe to call from
    server threads — reads engine.params only (jit dispatch is
    thread-safe; no queue state is touched)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    for p in prompts:
        engine._check_prompt(p)
        if len(p) > engine.cfg.max_seq_len:
            raise ValueError(f"prompt ({len(p)} tokens) exceeds "
                             f"max_seq_len {engine.cfg.max_seq_len}")
    out: List[List[float]] = []
    for lo in range(0, len(prompts), EMBED_MAX_BATCH):
        out.extend(_embed_batch(engine, prompts[lo:lo + EMBED_MAX_BATCH]))
    return out


# Per-forward row cap: bounds activation memory and the (B, T) compile
# variety to the same order as the serving path (engine batches are capped
# by cfg); larger request lists chunk through this.
EMBED_MAX_BATCH = 32


# bucket_fn
def _chunk_bucket(n: int, chunk: int = 1) -> int:
    """Round ``n`` up to ``chunk`` × a power of two: log-many compiled
    shapes per axis instead of one per chunk multiple (chunk=1 is a plain
    pow2 bucket). Extra padding is masked out downstream."""
    m = 1
    while m * chunk < n:
        m *= 2
    return m * chunk


def _embed_batch(engine: Engine, prompts: List[List[int]]) -> List[List[float]]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    chunk = engine.cfg.prefill_chunk
    longest = max(len(p) for p in prompts)
    # Both axes bucketed (log compile variety): T to chunk × pow2 — the
    # old chunk-multiple rounding compiled one program per multiple.
    T = _chunk_bucket(longest, chunk)
    B = _chunk_bucket(len(prompts))
    cache = getattr(engine, "_embed_cache", None)
    if cache is None:
        cache = engine._embed_cache = {}
    fn = cache.get((B, T))
    if fn is None:
        from rbg_tpu.models.llama import encode_hidden
        mcfg = engine.mcfg

        def pooled(params, toks, mask):
            # Pool in f32: bf16 models would accumulate the D-sum AND the
            # token count in bf16 (counts are exact only to 256 — long
            # prompts would mean-pool with the wrong divisor).
            h = encode_hidden(params, mcfg, toks, mask).astype(jnp.float32)
            m = mask[:, :, None].astype(jnp.float32)
            return (h * m).sum(1) / jnp.maximum(m.sum(1), 1.0)

        pooled.__name__ = names.PROGRAM_EMBED_POOLED   # jitwatch catalog
        fn = cache[(B, T)] = jax.jit(pooled)
    toks = np.zeros((B, T), np.int32)
    mask = np.zeros((B, T), bool)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
        mask[i, :len(p)] = True
    vecs = np.asarray(fn(engine.params, jnp.asarray(toks),
                         jnp.asarray(mask)), np.float32)
    return [vecs[i].tolist() for i in range(len(prompts))]


@_race_guard
class _BatchService:
    """Shared loop: subclasses implement ``_admit(item, sampling) -> rid``
    (raising on bad input fails just that request) and expose ``engine``.

    Overload protection (``max_queue``): submission into a full queue — or
    one whose estimated wait (from recent completion throughput) already
    exceeds the request's deadline budget — raises ``Overloaded`` with a
    ``retry_after_s`` hint instead of queueing unboundedly. Deadlines:
    queued entries whose budget expires before admission are dropped
    without ever touching the engine, and admitted rows past deadline are
    aborted ON the loop thread (slot + KV pages recycle immediately), so
    abandoned work never burns device steps."""

    engine: Engine
    # Role label the SLO judgments carry (per-role attainment aggregates
    # over it); DecodeService overrides.
    slo_role = "unified"

    def __init__(self, max_queue: Optional[int] = None):
        self.max_queue = max_queue
        # Per-request SLO judgment at finish (obs/slo.py): targets come
        # from the engine config; one judgment per FINISHED request —
        # blocking and streaming both finish through the loop below, so
        # this is the single site (the slo_accounted invariant).
        cfg = self.engine.cfg
        self.slo = SLOTracker(
            SLOTargets(ttft_s=cfg.slo_ttft_s, tpot_s=cfg.slo_tpot_s),
            component=type(self).__name__.lower())
        # guarded_by[engine.service_queue]
        self.counters = {"shed_total": 0, "deadline_queue_drops": 0,
                         "deadline_running_aborts": 0, "early_rejects": 0}
        # Predictive early rejection (Mooncake overload story): armed by
        # cfg.early_reject="auto" with a TTFT SLO target — admission
        # predicts TTFT (queue wait + prefill net of the prefix hit this
        # request would get) and sheds at INGRESS, before any prefill
        # compute is spent.
        self._early_reject = (cfg.early_reject == "auto"
                              and cfg.slo_ttft_s > 0)
        self._er_gate_s = cfg.slo_ttft_s * cfg.early_reject_factor
        # Measured prefill throughput (tokens/s EMA) — written by the
        # loop thread between steps, read racily by submitter threads
        # (a float read; staleness only skews one prediction). The
        # rate expires after _PF_RATE_TTL_S without a prefill window:
        # rejected requests do no prefill, so a stale-slow rate (e.g.
        # compile stalls on an unwarmed service) would otherwise shed
        # everything FOREVER — the rate could never re-learn.
        self._prefill_rate: Optional[float] = None
        self._pf_rate_t = 0.0
        self._pf_tokens = self.engine.metrics.get("prefill_tokens", 0)
        self._pf_t = time.monotonic()
        # Loop-thread-confined (admitted rows); deliberately NOT guarded.
        self._pending: Dict[int, _Pending] = {}
        # The loop thread's share of the step timeline
        # (docs/observability.md): cumulative seconds and counts, never
        # reset, written by the loop thread only and copied whole by
        # service_stats(). t_loop_s is the thread's wall time; intake,
        # the engine's t_step_s, deliver and idle tile it.
        self.timeline = {"t_loop_s": 0.0, "t_intake_s": 0.0,
                         "t_deliver_s": 0.0, "t_idle_s": 0.0,
                         "queue_wait_s": 0.0, "queue_waited": 0,
                         "ttft_s": 0.0, "first_tokens": 0}
        # The relay's clocks (``server.Handler._stream_pending``, one
        # thread a streaming request), folded under a plain lock of their
        # own, never the queue's: token frames sent and their tokens,
        # seconds inside the socket write, and per frame the write's start
        # less the loop thread's stamp of the frame's oldest token.
        self.relay = {"relay_frames": 0, "relay_tokens": 0,
                      "t_relay_send_s": 0.0, "relay_lag_s": 0.0}
        self._relay_lock = threading.Lock()
        # What a late-step record differences over its turn beside the
        # thread's own rusage: the collector's clock, programs compiled.
        self._gc = trace.GC_CLOCK.install()
        self._compiles = chipenv.compile_counter()
        # The turn in progress, for the watchdog: (start, number, the sync
        # clock as it started), and whether the loop is in its idle wait.
        # What the watchdog leaves for the loop thread: the number of the
        # turn whose stack it took with the stack; when it last woke; and
        # each of its sleeps that overran by a period or more, (asleep
        # since, seconds late), for the turn that ends next to take.
        # ``_wd_since`` is the loop thread's own: a sleep begun before it
        # is accounted for (the end of the last late turn, or of the turn
        # that took a sleep as overdue), whenever its report comes.
        self._turn = (time.monotonic(), 0, 0.0)
        self._idling = False
        self._wd_stack: Tuple[int, tuple] = (0, ())
        self._wd_wake = time.monotonic()
        self._wd_lates: collections.deque = collections.deque(maxlen=64)
        self._wd_since = 0.0
        self._wd_stop = threading.Event()
        self._lock = named_lock("engine.service_queue")
        self._wake = threading.Event()
        self._stopped = False
        # guarded_by[engine.service_queue]
        self._queue: List[Tuple[object, SamplingParams, _Pending]] = []
        self._cancels: List[_Pending] = []  # guarded_by[engine.service_queue]
        # Inbound KV stream receivers awaiting loop-thread adoption
        # (DecodeService.watch_stream fills it; _pump drains it).
        self._new_streams: List[object] = []  # guarded_by[engine.service_queue]
        self._done_times = collections.deque(maxlen=_RATE_WINDOW)
        # The loop traces the programs a warm wave first meets: on a roomy
        # stack, as the warm-up's own thread is (``pystack``).
        self._thread = threading.Thread(
            target=pystack.on_roomy_stack, args=(self._loop,), daemon=True,
            name=type(self).__name__.lower())
        self._thread.start()
        self._watchdog = threading.Thread(
            target=self._watch, daemon=True,
            name=type(self).__name__.lower() + "-watchdog")
        self._watchdog.start()

    # -- subclass hooks --
    def _admit(self, item, sampling: SamplingParams) -> Optional[int]:
        raise NotImplementedError

    def _pump(self) -> None:
        """Loop-thread hook before each iteration's engine work —
        DecodeService commits inbound KV stream chunks here."""

    def _ingress_prompt(self, item) -> Optional[List[int]]:
        """Prompt tokens of a submission, for the TTFT predictor — None
        when the item carries no prefill work this engine would run
        (e.g. a decode leg, whose prefill was already paid upstream)."""
        return None

    # -- admission control --

    def _completion_rate(self) -> Optional[float]:
        """Recent request completions per second (None = no estimate yet).
        Span is measured between the completions themselves — anchoring it
        to "now" would decay the rate through idle periods and make the
        estimated-wait gate shed the first requests after a lull."""
        d = self._done_times
        if len(d) < 2:
            return None
        span = d[-1] - d[0]
        if span <= 0:
            return None
        return (len(d) - 1) / span

    def estimated_wait_s(self, depth: Optional[int] = None) -> Optional[float]:
        """Expected queueing delay for a NEW submission, from the recent
        completion rate. None until enough history exists."""
        if depth is None:
            with self._lock:
                depth = len(self._queue)
        rate = self._completion_rate()
        if rate is None or rate <= 0:
            return None
        eng = self.engine
        backlog = depth + len(eng.running) + len(eng.waiting)
        return backlog / rate

    def _retry_after_hint(self, depth: int) -> float:
        est = self.estimated_wait_s(depth)
        return max(_RETRY_AFTER_FLOOR_S, est if est is not None else 1.0)

    def _note_prefill_progress(self) -> None:
        """Loop-thread sampling of prefill throughput between steps —
        only windows that actually prefilled update the EMA (idle
        windows must not decay the estimate toward zero and blind the
        predictor after a lull, the _completion_rate lesson)."""
        now = time.monotonic()
        dt = now - self._pf_t
        if dt < 0.2:
            return
        tp = self.engine.metrics.get("prefill_tokens", 0)
        if tp > self._pf_tokens:
            rate = (tp - self._pf_tokens) / dt
            stale = now - self._pf_rate_t > _PF_RATE_TTL_S
            self._prefill_rate = (
                rate if self._prefill_rate is None or stale
                else 0.7 * self._prefill_rate + 0.3 * rate)
            self._pf_rate_t = now
        self._pf_tokens, self._pf_t = tp, now

    def predicted_ttft_s(self, item,
                         depth: Optional[int] = None) -> Optional[float]:
        """Predicted TTFT for a NEW submission: measured queue wait plus
        this request's prefill time NET of the prefix-cache hit (device
        radix + host tier) it would get. None while either rate lacks
        history — the predictor never sheds on a guess."""
        est = self.estimated_wait_s(depth)
        prompt = self._ingress_prompt(item)
        rate = self._prefill_rate
        if (prompt is None or rate is None or rate <= 0
                or time.monotonic() - self._pf_rate_t > _PF_RATE_TTL_S):
            # No (or expired) throughput history: predict queue wait
            # only — the gate must never shed on a rate it cannot
            # re-measure.
            return est
        hit = self.engine.prefix_peek(list(prompt))
        prefill_s = max(0, len(prompt) - hit) / rate
        return prefill_s if est is None else est + prefill_s

    def _shed(self, msg: str, depth: int) -> None:
        self.counters["shed_total"] += 1
        REGISTRY.inc(names.SERVING_SHED_TOTAL,
                     service=type(self).__name__.lower())
        raise Overloaded(msg, retry_after_s=self._retry_after_hint(depth))

    # -- public --
    def submit_async(self, item, sampling: SamplingParams,
                     deadline: Optional[float] = None,
                     span=None, stream_rx=None) -> _Pending:
        """Enqueue one request. ``deadline`` is absolute ``time.monotonic()``
        seconds; raises ``Overloaded`` / ``DeadlineExceeded`` instead of
        queueing work that cannot be served. ``span`` (or the ambient
        current span) parents this request's queue-wait/scan spans; shed
        and deadline rejections still close their span — a refused request
        must leave a complete trace, not an orphan."""
        parent = span if span is not None else trace.current()
        qspan = parent.child(names.SPAN_SERVICE_QUEUE_WAIT)
        now = time.monotonic()
        if deadline is not None and now >= deadline:
            with self._lock:
                self.counters["deadline_queue_drops"] += 1
            REGISTRY.inc(names.SERVING_DEADLINE_EXCEEDED_TOTAL, stage="queue")
            qspan.end(outcome="deadline")
            raise DeadlineExceeded("deadline already expired at submission")
        p = _Pending(deadline=deadline)
        p.span_parent = parent
        p.span_queue = qspan
        p.stream_rx = stream_rx
        try:
            with self._lock:
                # estimated_wait_s with an explicit depth never re-takes the
                # lock, so both gates may raise from inside it.
                depth = len(self._queue)
                if self.max_queue is not None and depth >= self.max_queue:
                    self._shed(f"service queue full ({self.max_queue})", depth)
                if deadline is not None:
                    est = self.estimated_wait_s(depth)
                    if est is not None and now + est >= deadline:
                        self._shed(
                            f"estimated wait {est:.2f}s exceeds remaining "
                            f"deadline budget {deadline - now:.2f}s", depth)
                if self._early_reject:
                    pred = self.predicted_ttft_s(item, depth)
                    if pred is not None:
                        svc = type(self).__name__.lower()
                        REGISTRY.observe(
                            names.SERVING_PREDICTED_TTFT_SECONDS, pred,
                            service=svc)
                        if pred > self._er_gate_s:
                            self.counters["early_rejects"] += 1
                            REGISTRY.inc(names.SERVING_EARLY_REJECTS_TOTAL,
                                         service=svc)
                            self._shed(
                                f"predicted TTFT {pred:.2f}s exceeds the "
                                f"early-reject gate {self._er_gate_s:.2f}s",
                                depth)
                self._queue.append((item, sampling, p))
                REGISTRY.observe(names.SERVING_QUEUE_DEPTH, depth + 1)
        except Rejected as e:
            qspan.end(outcome=e.code)
            raise
        self._wake.set()
        return p

    def submit_wave(self, items) -> List[_Pending]:
        """Atomically enqueue ``[(item, sampling), ...]`` so one loop
        iteration admits them together (up to max_batch) — warmup needs a
        deterministic batch composition, not whatever interleaving the
        wake races produce."""
        ps = []
        with self._lock:
            for item, sampling in items:
                p = _Pending()
                self._queue.append((item, sampling, p))
                ps.append(p)
        self._wake.set()
        return ps

    def _bucket_sizes(self) -> List[int]:
        eng = self.engine
        return sorted({eng._bucket(b)
                       for b in range(1, eng.cfg.max_batch + 1)},
                      reverse=True)

    def warmup(self, input_len: int = 32, out_len: int = 2) -> float:
        """Compile every decode/prefill bucket jit variant before traffic.

        A variant first hit mid-serving stalls EVERY in-flight request for
        the compile (seconds on CPU, tens of seconds at real model sizes) —
        measured here as a 9x throughput swing between identical bench
        runs, and the gap the reference closes with warmup pods
        (``rolebasedgroup_controller.go`` buildWarmupPod:535; our control
        plane's warmup controller readies images, this readies the jit
        cache). One wave per bucket size, largest first, through the
        normal submit path. Returns elapsed seconds. Most of a warm
        start is JAX tracing and lowering in this thread, so it runs on a
        roomy stack (``pystack``)."""
        return pystack.on_roomy_stack(self._warmup, input_len, out_len)

    def _warmup(self, input_len: int, out_len: int) -> float:
        t0 = time.monotonic()
        took = {}

        def timed(name, warmer):
            t = time.monotonic()
            warmer()
            took[name + "_s"] = round(time.monotonic() - t, 3)

        # Ragged unified shapes first (all-pad dispatches, cache
        # untouched) — the prompt waves below only hit the packed-token
        # buckets their own composition happens to produce.
        timed("ragged", self.engine.warm_ragged)
        t_waves = time.monotonic()
        for B in self._bucket_sizes():
            items = [(self._warm_item(input_len, B, i),
                      SamplingParams(max_new_tokens=out_len))
                     for i in range(B)]
            for p in self.submit_wave(items):
                self.wait(p, 600.0)
        took["waves_s"] = round(time.monotonic() - t_waves, 3)
        # The waves only compiled the fused-decode and sampler variants
        # their own composition hit (default sampling, wave-sized
        # buckets); warm_decode/warm_samplers cover the full plain
        # bucket × top-p grid — the gap the jitwatch sentry surfaced.
        timed("decode", self.engine.warm_decode)
        # The waves compiled the K=multi_step fused programs; the K=1
        # early-exit twins (_decode_window's join shortening) would
        # otherwise first compile MID-SERVING, on the join-latency path.
        timed("join_windows", self.engine.warm_join_windows)
        timed("samplers", self.engine.warm_samplers)
        timed("place", self.engine.warm_place)
        # Arm the jitwatch gate (no-op unless RBG_JITWATCH armed the
        # hooks): everything compiled above is the blessed warmup set;
        # any cataloged program compiling after this is a violation.
        jitwatch.warmup_complete()
        # The warm waves were compile-laden: their token throughput is
        # not serving throughput, and an early-reject predictor trained
        # on it would shed the first real traffic. Reset so the EMA
        # learns from warm steps only.
        self._prefill_rate = None
        self._pf_tokens = self.engine.metrics.get("prefill_tokens", 0)
        self._pf_t = time.monotonic()
        elapsed = time.monotonic() - t0
        # Wall time of the last warm-up, whole and by warmer, on the wire
        # (metrics op: ``warmup_s``, ``warmup.<warmer>_s``).
        with self._lock:
            self.counters["warmup_s"] = round(elapsed, 3)
            self.counters["warmup"] = took
        return elapsed

    def _warm_item(self, input_len: int, wave: int, row: int):
        raise NotImplementedError

    def wait(self, p: _Pending, timeout: float) -> List[int]:
        if not p.done.wait(timeout):
            self.cancel(p)  # recycle batch slot + KV pages, don't orphan
            raise TimeoutError("generation timed out")
        if p.error:
            if p.code == CODE_DEADLINE:
                raise DeadlineExceeded(p.error)
            raise ValueError(p.error)
        return p.tokens

    def submit_wait(self, item, sampling: SamplingParams,
                    timeout: float = DEFAULT_TIMEOUT_S,
                    deadline: Optional[float] = None,
                    span=None) -> _Pending:
        """Blocking submit; returns the completed _Pending (tokens,
        logprobs, ttft timestamps). The one blocking-wait/timeout contract
        every caller — server ops included — goes through. ``deadline``
        (absolute monotonic) bounds the whole stay: admission gate, queue
        drop, AND engine-side abort, not just this thread's wait."""
        p = self.submit_async(item, sampling, deadline=deadline, span=span)
        if deadline is not None:
            timeout = min(timeout, max(0.0, deadline - time.monotonic()) + 1.0)
        self.wait(p, timeout)
        return p

    @staticmethod
    def ttft(p: _Pending) -> float:
        return (p.t_first - p.t_submit) if p.t_first else 0.0

    def service_stats(self) -> dict:
        """Admission-control / lifecycle counters (merged into the metrics
        op by every serving mode, scraped by the stress harness)."""
        with self._lock:
            depth = len(self._queue)
            out = dict(self.counters)
        out.update(self.timeline)
        with self._relay_lock:
            out.update(self.relay)
        out["gc_collections"] = self._gc.collections
        out["gc_pause_s"] = self._gc.pause_s
        est = self.estimated_wait_s(depth)
        out["queue_depth"] = depth
        out["max_queue"] = self.max_queue
        out["estimated_wait_s"] = round(est, 4) if est is not None else None
        out["slo_judged_total"] = self.slo.judged_total()
        out["early_reject_armed"] = self._early_reject
        return out

    def cancel(self, pending: _Pending) -> None:
        """Abort an in-flight request (routed through the loop thread)."""
        with self._lock:
            self._cancels.append(pending)
        self._wake.set()

    def note_relay(self, tokens: int, send_s: float, lag_s: float) -> None:
        """One token frame the relay sent (a connection thread)."""
        with self._relay_lock:
            r = self.relay
            r["relay_frames"] += 1
            r["relay_tokens"] += tokens
            r["t_relay_send_s"] += send_s
            r["relay_lag_s"] += lag_s

    def stop(self):
        self._stopped = True
        self._wake.set()
        self._wd_stop.set()
        if threading.current_thread() is not self._watchdog:
            self._watchdog.join(timeout=5.0)
        # Join so stop() actually frees the CPU: a "stopped" service whose
        # loop thread lingers keeps polling (and in a test suite, dozens of
        # leaked loops become ambient load that starves later tests).
        if threading.current_thread() is not self._thread:
            self._thread.join(timeout=30.0)

    # -- loop --
    def _expire_queue_locked(self, now: float) -> List[_Pending]:
        """Drop queued entries whose deadline passed before admission.
        Caller holds the lock; the dropped pendings are failed OUTSIDE it."""
        if not any(p.deadline is not None for _, _, p in self._queue):
            return []
        live, dead = [], []
        for entry in self._queue:
            p = entry[2]
            if p.deadline is not None and now >= p.deadline:
                dead.append(p)
            else:
                live.append(entry)
        self._queue = live
        return dead

    def _abort_expired_running(self, now: float) -> None:
        """Abort admitted rows past deadline (loop thread — the only thread
        allowed to touch the engine): the slot and KV pages recycle NOW
        instead of burning device steps to max_new_tokens."""
        expired = [(rid, p) for rid, p in self._pending.items()
                   if p.deadline is not None and now >= p.deadline]
        if expired:
            with self._lock:
                self.counters["deadline_running_aborts"] += len(expired)
        for rid, p in expired:
            self.engine.cancel_request(rid)
            del self._pending[rid]
            REGISTRY.inc(names.SERVING_DEADLINE_EXCEEDED_TOTAL,
                         stage="running")
            p.error = "deadline exceeded mid-generation (aborted)"
            p.code = CODE_DEADLINE
            p.span_scan.end(outcome="deadline_abort",
                            tokens=len(p.tokens))
            p.done.set()

    def _judge_finished(self, pending: _Pending, t_done: float) -> None:
        """SLO-judge ONE finished request (loop thread). TTFT measures
        submission → first token; TPOT is the mean per-token latency
        after the first (0 for single-token outputs — trivially met).
        Every finished request passes here exactly once, and only
        finished requests do (deadline aborts, cancels, and admit errors
        are accounted under their own counters, not judged)."""
        n = len(pending.tokens)
        if pending.t_first is not None:
            ttft = pending.t_first - pending.t_submit
            tpot = ((t_done - pending.t_first) / (n - 1)) if n > 1 else 0.0
        else:
            # Finished without a streamed token (e.g. a decode bundle
            # completed at inject): its whole stay is the TTFT.
            ttft = t_done - pending.t_submit
            tpot = 0.0
        self.slo.judge(ttft, tpot, role=self.slo_role)
        svc = type(self).__name__.lower()
        REGISTRY.inc(names.SERVING_REQUESTS_FINISHED_TOTAL, service=svc)
        if n:
            REGISTRY.inc(names.SERVING_TOKENS_TOTAL, float(n), service=svc)

    def _loop(self):
        """One turn: intake, then either an idle wait or one engine step
        and the delivery of what it emitted. Each part is a phase of the
        step timeline: a profiler annotation and a cumulative clock.
        Every ``RUSAGE_EVERY`` turns, and at a late turn's end, the loop
        reads its thread's rusage: the turns' wall time outside
        ``engine.sync`` and ``service.idle`` since the last reading goes
        to ``t_host_s``, and that less the thread's CPU time to
        ``t_host_off_s``. A turn is late, and leaves a record of what it
        can know of the cause (``_note_late``), when that host time
        passes ``LATE_STEP_S``; and a turn that ran a step also when the
        watchdog woke that late in it (the process stood, wherever this
        thread waited) or its sync wait passed ``SYNC_STALL_S``."""
        eng = self.engine
        tl = self.timeline
        m = eng.metrics
        clocks = _Phase.CLOCKS
        t_start = time.monotonic()
        counts = self._counts()
        seq = 0
        host_acc = 0.0      # host seconds of the turns since that reading
        # CPU seconds read beyond the turns' host time, set against later
        # readings (never above 0): a CPU clock that ticks in steps of
        # 10 ms gives most readings none and some a whole tick. Bounded,
        # so that CPU burnt inside a sync wait cannot hide a later pause.
        carry = 0.0
        while not self._stopped:
            now = time.monotonic()
            seq += 1
            # The engine's phase clocks as the turn starts: the turn's
            # own phases are their differences at its end.
            phase0 = [m[c] for c in clocks]
            self._turn = (now, seq, phase0[_SYNC])
            with trace.annotation(names.SPAN_SERVICE_INTAKE):
                self._intake(now)
            t = time.monotonic()
            eng.probe(t, turn_began=now)
            intake = t - now
            tl["t_intake_s"] += intake
            if eng.has_work():
                stepped = True
                events = eng.step()
                t = time.monotonic()
                with trace.annotation(names.SPAN_SERVICE_DELIVER):
                    self._deliver(events, t)
                t_end = time.monotonic()
                eng.probe(t_end)
                deliver = t_end - t
                tl["t_deliver_s"] += deliver
                sync_s = m["t_sync_s"] - phase0[_SYNC]
                host_s = late_s = t_end - now - sync_s
                wd_late = self._watchdog_late(t_end)
                if wd_late > late_s:
                    late_s = wd_late
                if sync_s > SYNC_STALL_S and sync_s > late_s:
                    late_s = sync_s
            else:
                stepped = False
                deliver = sync_s = 0.0
                with self._lock:
                    empty = not self._queue and not self._cancels
                if empty:
                    # Idle time must not enter the prefill-rate window:
                    # the first active window after a lull would
                    # otherwise measure chunk_tokens / lull_length, and
                    # (past the TTL) REPLACE the EMA with that near-zero
                    # rate — shedding the whole next burst.
                    self._pf_t = time.monotonic()
                    self._pf_tokens = m.get("prefill_tokens", 0)
                    self._idling = True
                    with trace.annotation(names.SPAN_SERVICE_IDLE):
                        self._wake.wait(0.01)
                        self._wake.clear()
                    self._idling = False
                    tl["t_idle_s"] += time.monotonic() - t
                t_end = time.monotonic()
                host_s = late_s = intake
                # A sleep the watchdog overran while the loop idled is
                # nobody's late step: taken here, and left out.
                wd_late = self._watchdog_late(t_end)
            tl["t_loop_s"] = t_end - t_start
            host_acc += host_s
            late = late_s > LATE_STEP_S
            if late or seq % RUSAGE_EVERY == 0:
                was, counts = counts, self._counts()
                carry += host_acc - (counts[0] - was[0])
                m["t_host_s"] += host_acc
                host_acc = 0.0
                if carry > 0.0:
                    m["t_host_off_s"] += carry
                    carry = 0.0
                elif carry < -_CPU_DEBT_S:
                    carry = -_CPU_DEBT_S
            if late:
                self._wd_since = t_end
                walls = [m[c] - w for c, w in zip(clocks, phase0)]
                sync_s = walls.pop(_SYNC)
                walls = [intake] + walls + [deliver, 0.0]
                if host_s <= LATE_STEP_S:
                    # Late while this thread waited for the device.
                    walls = [0.0] * 6 + [sync_s]
                self._note_late(now, t_end, late_s, seq, stepped, walls,
                                tuple(b - a for a, b in zip(was, counts)),
                                wd_late)

    def _watchdog_late(self, t_end: float) -> float:
        """How late the watchdog ran in the turn that ends now: the
        longest of the late sleeps it has reported since the last turn
        ended, or, if it has not woken since the process stood still, how
        overdue it is (once that alone makes the turn late). A sleep is
        one turn's: one begun before ``_wd_since`` is left out."""
        late = 0.0
        since = self._wd_since
        lates = self._wd_lates
        while lates:
            asleep_since, overran = lates.popleft()
            if overran > late and asleep_since >= since:
                late = overran
        woke = self._wd_wake
        overdue = t_end - woke - WATCHDOG_PERIOD_S
        if overdue > LATE_STEP_S and overdue > late and woke >= since:
            self._wd_since = t_end
            late = overdue
        return late

    def _counts(self) -> tuple:
        """What the loop differences between two readings: its thread's
        CPU seconds, voluntary and involuntary context switches, major and
        minor faults (one ``getrusage``, the costly call); the collector's
        runs and seconds; programs compiled or loaded; token frames the
        relay sent. A late turn's record holds the differences since the
        last reading, at most ``RUSAGE_EVERY`` - 1 turns before it."""
        ru = resource.getrusage(resource.RUSAGE_THREAD)
        return (ru.ru_utime + ru.ru_stime, ru.ru_nvcsw, ru.ru_nivcsw,
                ru.ru_majflt, ru.ru_minflt, self._gc.collections,
                self._gc.pause_s, self._compiles.programs,
                self.relay["relay_frames"])

    def _note_late(self, t0: float, t_end: float, late_s: float, seq: int,
                   stepped: bool, walls: list, counts: tuple,
                   wd_late: float) -> None:
        """The record of one late turn (``Engine.note_late`` has the
        fields): the phase that took most of it (of ``walls``, in
        ``_LATE_PHASES``' order), the thread's CPU seconds and the other
        counts over the turn, how late the watchdog itself ran and the
        stack it took of this thread."""
        eng = self.engine
        kind, step_num = "idle", eng.metrics["steps_run"]
        if stepped:
            kind = "none"
            if eng._dispatched is not None:
                kind, step_num = eng._dispatched[0], step_num - 1
        worst = max(range(len(walls)), key=walls.__getitem__)
        wd_seq, stack = self._wd_stack
        eng.note_late(
            (t0, t_end, step_num, kind, _LATE_PHASES[worst], walls[worst])
            + counts + (wd_late, stack if wd_seq == seq else ()), late_s)

    def _watch(self) -> None:
        """The watchdog: wakes every ``WATCHDOG_PERIOD_S``, reports a
        sleep of its own that overran by a period or more, and takes the
        loop thread's Python stack once in a turn that is past
        ``LATE_STEP_S`` outside ``engine.sync`` and ``service.idle``, or
        ``SYNC_STALL_S`` into a sync wait. A stack says in what the loop
        thread stood; a watchdog as late as the turn says that every
        Python thread of the process stood."""
        eng = self.engine
        loop_ident = self._thread.ident
        last = self._wd_wake
        while not self._wd_stop.wait(WATCHDOG_PERIOD_S):
            now = time.monotonic()
            late = now - last - WATCHDOG_PERIOD_S
            if late > WATCHDOG_PERIOD_S:
                # Reported by the sleep's start: a turn that was late
                # meanwhile, or took the sleep as overdue, leaves it out.
                self._wd_lates.append((last, late))
            self._wd_wake = last = now
            t0, seq, sync0 = self._turn
            if seq == self._wd_stack[0] or self._idling:
                continue
            phase = eng._phase_now
            if phase is not None and phase.idx == _SYNC:
                if now - phase.t0 <= SYNC_STALL_S:
                    continue
            elif now - t0 - (eng.metrics["t_sync_s"] - sync0) <= LATE_STEP_S:
                continue
            frame = sys._current_frames().get(loop_ident)
            stack = []
            while frame is not None and len(stack) < STACK_FRAMES:
                code = frame.f_code
                stack.append("%s:%d %s" % (
                    "/".join(code.co_filename.rsplit("/", 2)[-2:]),
                    frame.f_lineno, code.co_name))
                frame = frame.f_back
            self._wd_stack = (seq, tuple(stack))

    def _intake(self, now: float) -> None:
        """The turn's bookkeeping before the engine runs: expired and
        cancelled requests leave, new submissions enter the engine's
        queue (up to its batch ceiling), inbound KV streams are pumped."""
        eng = self.engine
        with self._lock:
            cancels = self._cancels
            self._cancels = []
            expired = self._expire_queue_locked(now)
            # Admission control: never exceed the engine's batch ceiling —
            # excess items stay queued for later rounds.
            budget = max(0, eng.cfg.max_batch
                         - len(eng.running) - len(eng.waiting))
            newly = self._queue[:budget]
            self._queue = self._queue[budget:]
            # Continuous batching: submissions still queued beyond this
            # step's budget shorten the engine's fused decode window so
            # the next free slot absorbs them at step granularity.
            eng.join_hint = bool(self._queue)
        if expired:
            with self._lock:
                self.counters["deadline_queue_drops"] += len(expired)
        for pending in expired:
            REGISTRY.inc(names.SERVING_DEADLINE_EXCEEDED_TOTAL,
                         stage="queue")
            pending.error = "deadline expired before admission"
            pending.code = CODE_DEADLINE
            pending.span_queue.end(outcome="deadline_dropped")
            pending.done.set()
        for item, sampling, pending in newly:
            pending.span_queue.end(outcome="admitted")
            scan = pending.span_scan = pending.span_parent.child(
                names.SPAN_SERVICE_SCAN)
            try:
                if pending.span_parent:
                    # Ambient span so hop internals (e.g. the decode
                    # bundle KV-import in pd.py) attach their own
                    # children without signature plumbing.
                    with trace.use_span(pending.span_parent):
                        rid = self._admit(item, sampling)
                else:
                    rid = self._admit(item, sampling)
            except Exception as e:
                # A bad request must fail ITSELF, never the loop thread.
                scan.end(outcome="admit_error")
                pending.error = str(e)
                # Structured failure classes (e.g. a dead KV stream's
                # kv_stream_failed) keep their wire code so the router
                # can recognize and recover instead of passing a raw
                # error to the client.
                pending.code = getattr(e, "wire_code", None)
                pending.done.set()
                continue
            if rid is None:
                scan.end(outcome="done_at_admit")
                self._judge_finished(pending, time.perf_counter())
                pending.done.set()  # completed at admission
                self._done_times.append(time.monotonic())
                continue
            self._pending[rid] = pending
        self._abort_expired_running(now)
        self._pump()
        for pending in cancels:
            rid = next((r for r, p in self._pending.items() if p is pending),
                       None)
            if rid is not None:
                eng.cancel_request(rid)
                del self._pending[rid]
                pending.span_scan.end(outcome="cancelled")
                pending.done.set()
            else:
                # Still queued (never admitted) — drop it from the queue.
                with self._lock:
                    self._queue = [q for q in self._queue if q[2] is not pending]
                pending.span_queue.end(outcome="cancelled")
                pending.done.set()

    def _deliver(self, events, now: float) -> None:
        """What one engine step produced, handed on: admissions and the
        step's occupancy observed, tokens to their ``_Pending``s (each
        stamped ``now``, the delivery's one time.monotonic() read),
        finished requests judged and released."""
        eng = self.engine
        tl = self.timeline
        self._note_prefill_progress()
        # Batch-occupancy / join-latency observability (one occupancy
        # sample per step; join waits are recorded by the engine at
        # admission and drained here — both loop-thread-confined).
        REGISTRY.observe(names.SERVING_BATCH_OCCUPANCY,
                         len(eng.running) / max(1, eng.cfg.max_batch),
                         service=type(self).__name__.lower())
        if eng.last_join_waits:
            for wait, rid, t_admit in eng.last_join_waits:
                REGISTRY.observe(names.SERVING_JOIN_LATENCY_SECONDS, wait,
                                 service=type(self).__name__.lower())
                pending = self._pending.get(rid)
                if pending is not None and pending.t_admit is None:
                    # Queue wait as the request felt it: both queues, from
                    # submit_async to its batch row.
                    pending.t_admit = t_admit
                    tl["queue_wait_s"] += t_admit - pending.t_submit
                    tl["queue_waited"] += 1
            eng.last_join_waits.clear()
        for ev in events:
            pending = self._pending.get(ev.request_id)
            if pending is None:
                continue
            if pending.t_first is None:
                pending.t_first = time.perf_counter()
                tl["ttft_s"] += pending.t_first - pending.t_submit
                tl["first_tokens"] += 1
                if pending.stream_rx is not None \
                        and pending.stream_rx.t_first_step is None:
                    # First DECODE step of a streamed row — the
                    # kv_stream_overlap invariant compares this
                    # against the stream's FIN arrival.
                    pending.stream_rx.t_first_step = time.monotonic()
            pending.stamps.append(now)
            pending.tokens.append(ev.token)
            if ev.logprob is not None:
                pending.logprobs.append(ev.logprob)
            if ev.finished:
                pending.span_scan.end(outcome="ok",
                                      tokens=len(pending.tokens))
                t_done = time.perf_counter()
                REGISTRY.observe(
                    names.SERVING_REQUEST_DURATION_SECONDS,
                    t_done - pending.t_submit,
                    exemplar=pending.span_scan.trace_id or None,
                    service=type(self).__name__.lower())
                self._judge_finished(pending, t_done)
                pending.done.set()
                del self._pending[ev.request_id]
                # Completion history feeds the estimated-wait gate.
                self._done_times.append(time.monotonic())


class EngineService(_BatchService):
    def __init__(self, cfg: EngineConfig, params=None, mesh=None,
                 max_queue: Optional[int] = None):
        self.engine = Engine(cfg, params=params, mesh=mesh)
        super().__init__(max_queue=max_queue)

    def _admit(self, prompt, sampling: SamplingParams) -> Optional[int]:
        return self.engine.add_request(prompt, sampling)

    def _ingress_prompt(self, item) -> Optional[List[int]]:
        return item if isinstance(item, (list, tuple)) else None

    def _warm_item(self, input_len: int, wave: int, row: int):
        from rbg_tpu.engine.config import warm_prompt
        return warm_prompt(input_len, wave, row)

    def submit(self, prompt: List[int], sampling: SamplingParams,
               timeout: float = DEFAULT_TIMEOUT_S,
               deadline: Optional[float] = None) -> Tuple[List[int], float]:
        """Blocking generate. Returns (tokens, ttft_seconds)."""
        p = self.submit_wait(prompt, sampling, timeout, deadline=deadline)
        return p.tokens, self.ttft(p)

    def embed(self, prompt: List[int]) -> List[float]:
        """Mean-pooled final-norm hidden state for one prompt."""
        return embed_prompts(self.engine, [prompt])[0]

    def stats(self) -> dict:
        out = dict(self.engine.metrics)
        out["running"] = len(self.engine.running)
        out["waiting"] = len(self.engine.waiting)
        out["free_pages"] = self.engine.allocator.free_pages
        out["radix_nodes"] = (self.engine.radix.num_nodes
                              if self.engine.radix is not None else 0)
        out.update(self.service_stats())
        return out


class DecodeService(_BatchService):
    """Disaggregated decode role: KV bundles from many router connections
    decode TOGETHER on the device instead of serializing per connection."""

    slo_role = "decode"

    def __init__(self, cfg, params=None, mesh=None,
                 max_queue: Optional[int] = None):
        from rbg_tpu.engine.pd import DecodeWorker
        from rbg_tpu.kvtransfer.stream import StreamRegistry

        self.worker = DecodeWorker(cfg, params=params, mesh=mesh)
        self.engine = self.worker.engine
        # Inbound KV chunk streams (the decode server's kv_stream op feeds
        # these; decode_stream requests consume them).
        self.kv_streams = StreamRegistry()
        super().__init__(max_queue=max_queue)

    def watch_stream(self, receiver) -> None:
        """Ask the loop thread to start committing this stream's chunks
        into the page table AS THEY ARRIVE (before admission) — callable
        from any connection thread."""
        with self._lock:
            self._new_streams.append(receiver)
        self._wake.set()

    def _pump(self) -> None:
        with self._lock:
            new, self._new_streams = self._new_streams, []
        for rx in new:
            self.worker.begin_stream(rx)
        self.worker.pump_streams()

    def submit_stream(self, receiver, sampling: SamplingParams,
                      deadline: Optional[float] = None,
                      span=None) -> _Pending:
        """Admit a coverage-complete KV stream (caller waited on
        ``receiver.wait_ready``) into the decode batch."""
        return self.submit_async(receiver, sampling, deadline=deadline,
                                 span=span, stream_rx=receiver)

    def _admit(self, item, sampling: SamplingParams) -> Optional[int]:
        from rbg_tpu.kvtransfer.stream import KVStreamReceiver

        if isinstance(item, KVStreamReceiver):
            rid = self.worker.finalize_stream(item, sampling)
            self.kv_streams.pop(item.stream_id)
        else:
            rid = self.worker.inject(item, sampling)
        req = self.engine.requests.get(rid)
        if req is None or req.state == "finished":
            return None  # completed at inject (max_new_tokens == 1 / stop)
        return rid

    def _warm_item(self, input_len: int, wave: int, row: int):
        """A zero-KV bundle with the serving page count: compiles the
        inject scatter (keyed on n_pages) and the decode buckets. Numerics
        are irrelevant to compilation; the zero pages are released when
        the warm request finishes."""
        import numpy as np

        from rbg_tpu.engine.kvcache import pages_for_tokens
        from rbg_tpu.engine.pd import KVBundle

        from rbg_tpu.engine.config import warm_prompt

        eng = self.engine
        n_pages = pages_for_tokens(input_len, eng.cfg.page_size)
        # k and v bundle halves take their OWN pool's shape/dtype: under
        # MLA the v pool holds the shared RoPE key (different channel dim
        # than the k latent) — deriving both from k_pages made every MLA
        # decode replica fail its {"op": "warmup"}.
        kshape = eng.cache.k_pages.shape
        vshape = eng.cache.v_pages.shape
        return KVBundle(
            prompt=warm_prompt(input_len, wave, row), first_token=1,
            k_data=np.zeros((kshape[0], n_pages) + kshape[2:],
                            np.dtype(eng.cache.k_pages.dtype)),
            v_data=np.zeros((vshape[0], n_pages) + vshape[2:],
                            np.dtype(eng.cache.v_pages.dtype)))

    def submit_bundle(self, bundle, sampling: SamplingParams,
                      timeout: float = DEFAULT_TIMEOUT_S) -> List[int]:
        """Blocking decode of an injected bundle (first token included)."""
        p = self.submit_wait(bundle, sampling, timeout)
        return [bundle.first_token] + p.tokens
