"""Engine server process — what a role pod runs.

Reference analog: the SGLang server container in RBG's role templates
(``examples/inference/*.yaml``); here the engine is ours and the rendezvous
contract is the one the control plane injects (RBG_* envs, see
rbg_tpu.discovery.env_builder).

Modes (= PD-disagg roles): ``unified`` serves generate; ``prefill`` answers
prefill ops with KV bundles; ``decode`` accepts bundles and decodes.

Env contract consumed: ``RBG_SERVE_PORT`` (from the executor or the port
allocator's ``RBG_PORT_SERVE``), ``RBG_JAX_NUM_PROCESSES``/``RBG_JAX_PROCESS_ID``/
``RBG_JAX_COORDINATOR_ADDRESS`` (multi-host slice init), ``RBG_TPU_*``.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socketserver
import sys
import threading
import time

from rbg_tpu.engine.config import EngineConfig, SamplingParams
from rbg_tpu.engine.protocol import (CODE_DRAINING, DeadlineExceeded,
                                     Rejected, bundle_from_wire,
                                     bundle_to_wire, recv_msg, send_msg)
from rbg_tpu.obs import names, trace
from rbg_tpu.obs.metrics import REGISTRY
from rbg_tpu.utils import chipenv
from rbg_tpu.utils.locktrace import named_lock


# Blocking decode_stream wait bound when the client sent no deadline —
# the same legacy contract as service.DEFAULT_TIMEOUT_S.
DEFAULT_WAIT_S = 600.0


def _deadline_of(obj: dict):
    """Absolute monotonic deadline from a wire ``timeout_s`` (None = the
    legacy unbounded contract). The router stamps the REMAINING client
    budget here per hop, so engine-side enforcement composes with its."""
    t = obj.get("timeout_s")
    if t is None:
        return None
    t = float(t)
    if t <= 0:
        raise ValueError(f"timeout_s must be > 0, got {t}")
    return time.monotonic() + t


def build_config(args) -> EngineConfig:
    return EngineConfig(
        model=args.model, mode=args.mode, page_size=args.page_size,
        num_pages=args.num_pages, max_batch=args.max_batch,
        max_seq_len=args.max_seq_len, prefill_chunk=args.prefill_chunk,
        use_pallas=args.use_pallas,
        checkpoint_path=args.checkpoint_path,
        kv_dtype=args.kv_dtype,
        multi_step=args.multi_step,
        ragged=args.ragged,
        vocab_size=args.vocab_size,
        speculative=args.speculative,
        spec_k=args.spec_k,
        spec_ngram=args.spec_ngram,
        grammar_table=args.grammar_table,
        grammar_state_budget=args.grammar_state_budget,
        slo_ttft_s=args.slo_ttft_s,
        slo_tpot_s=args.slo_tpot_s,
        host_tier_bytes=args.host_tier_bytes,
        early_reject=args.early_reject,
        early_reject_factor=args.early_reject_factor,
    )


class Handler(socketserver.BaseRequestHandler):
    def handle(self):
        srv = self.server
        while True:
            try:
                obj, k, v = recv_msg(self.request)
            except (ConnectionError, json.JSONDecodeError):
                return
            if obj is None:
                return
            try:
                self._dispatch(srv, obj, k, v)
            except ConnectionError:
                return      # client went away; generation already cancelled
            except Exception as e:
                try:
                    send_msg(self.request, {"error": str(e)})
                except OSError:
                    return

    def _stream_pending(self, service, pending, first_tokens=(),
                        with_logprobs=False, deadline=None):
        """Relay a pending generation as incremental token-batch messages:
        ``{"tokens": [...], "done": false}``* then a final ``done`` frame
        with ttft. The transport framing the SSE front end rides on. With
        logprobs, frames carry an aligned ``"logprobs"`` slice (emission
        waits for both lists — the loop thread appends tokens first).
        ``deadline`` (absolute monotonic) caps the relay; the service loop
        aborts the generation itself at the same deadline."""
        import time as _time

        from rbg_tpu.engine.service import DEFAULT_TIMEOUT_S
        try:
            if first_tokens:
                frame = {"tokens": list(first_tokens), "done": False}
                if with_logprobs:
                    # PD first token is sampled prefill-side (no logprob) —
                    # null keeps the 1:1 alignment (OpenAI's convention for
                    # tokens without a logprob).
                    frame["logprobs"] = [None] * len(first_tokens)
                send_msg(self.request, frame)
            sent = 0
            if deadline is None:
                # lint: allow[deadline-hygiene] ingress fallback: the client sent no timeout_s, so THIS is the one stamp the legacy contract gets
                deadline = _time.monotonic() + DEFAULT_TIMEOUT_S
            while True:
                done = pending.done.is_set()
                if done and pending.error:
                    frame = {"error": pending.error, "done": True}
                    if pending.code:
                        frame["code"] = pending.code
                    send_msg(self.request, frame)
                    return
                tokens = list(pending.tokens)
                n = len(tokens)
                frame = None
                if with_logprobs:
                    lps = list(pending.logprobs)
                    if not done:
                        n = min(n, len(lps))
                    if n > sent:
                        frame = {"tokens": tokens[sent:n],
                                 "logprobs": lps[sent:n], "done": False}
                elif n > sent:
                    frame = {"tokens": tokens[sent:], "done": False}
                if frame is not None:
                    # The relay's span and clocks: the socket write on the
                    # profiler's clock, and how long the frame's oldest
                    # token waited for this thread since its delivery.
                    t0 = _time.monotonic()
                    with trace.annotation(names.SPAN_SERVER_RELAY_SEND):
                        send_msg(self.request, frame)
                    service.note_relay(n - sent, _time.monotonic() - t0,
                                       t0 - pending.stamps[sent])
                    sent = n
                if done and sent == len(pending.tokens):
                    break
                if _time.monotonic() > deadline:
                    from rbg_tpu.engine.protocol import CODE_DEADLINE
                    service.cancel(pending)  # recycle slot + pages
                    send_msg(self.request, {"error": "generation timed out",
                                            "code": CODE_DEADLINE,
                                            "done": True})
                    return
                _time.sleep(0.005)
            ttft = (pending.t_first - pending.t_submit) if pending.t_first else 0.0
            send_msg(self.request, {"tokens": [], "done": True, "ttft_s": ttft})
        except (BrokenPipeError, ConnectionResetError, OSError):
            # Client went away mid-stream (e.g. the HTTP edge cut at a stop
            # string): abort the generation so it stops occupying a batch
            # slot and KV pages for the rest of its max_new_tokens budget.
            service.cancel(pending)
            raise ConnectionError("client closed stream")

    _DATA_OPS = frozenset({"generate", "generate_text", "embed",
                           "prefill", "decode_bundle", "kv_stream",
                           "decode_stream"})

    def _dispatch(self, srv, obj, k, v):
        op = obj.get("op")
        if op == "health":
            ready = srv.service is not None or srv.prefill is not None or srv.decode is not None
            resp = {"ok": ready, "mode": srv.mode, "draining": srv.draining,
                    "device": srv.device}
            if srv.draining:
                resp["draining_for_s"] = round(
                    time.monotonic() - srv.drain_started, 3)
            send_msg(self.request, resp)
            return
        if srv.draining and op in self._DATA_OPS:
            # Drain contract: in-flight work finishes, NEW work is refused
            # with a structured code the router treats as
            # route-around-without-evicting. "done" terminates stream
            # clients that won't look past the first frame. The
            # retry_after_s hint is the remaining drain budget (capped):
            # by then either the replacement serves or this address is
            # gone — under a ROLLING drain the router surfaces the fleet's
            # smallest hint to the client.
            REGISTRY.inc(names.SERVING_DRAIN_REFUSALS_TOTAL)
            budget = getattr(srv, "drain_deadline_s", 30.0)
            remaining = max(0.0, budget - (time.monotonic()
                                           - srv.drain_started))
            send_msg(self.request, {
                "error": "server is draining (SIGTERM received)",
                "code": CODE_DRAINING, "done": True,
                "retry_after_s": round(min(5.0, max(0.5, remaining)), 3)})
            return
        if op in self._DATA_OPS:
            srv.note_inflight(+1)
            try:
                self._dispatch_data(srv, obj, k, v)
            finally:
                srv.note_inflight(-1)
            return
        self._dispatch_data(srv, obj, k, v)

    def _dispatch_data(self, srv, obj, k, v):
        """Auth gate + trace wrapper around :meth:`_serve_data`: every data
        op continues the request's wire trace context (or starts one when
        this server IS the ingress) as an ``engine.op`` span, ambient for
        the op's duration so the service queue/scan spans and the PD
        KV-handoff span parent under it."""
        op = obj.get("op")
        if srv.auth_token and op not in ("metrics", "slo"):
            # Data-plane token gate (VERDICT r4 #6): prefill/decode_bundle
            # carry KV activations, generate carries prompts — none of it
            # for unauthenticated peers. health (above) stays open for
            # probes; metrics too (scrape-friendly, numbers only).
            from rbg_tpu.engine.protocol import token_ok
            if not token_ok(obj.get("token"), srv.auth_token):
                send_msg(self.request, {"error": "unauthorized"})
                return
        if op == "slo":
            # Operator pull of SLO attainment + windowed signals (the
            # serving-plane sibling of the admin `slo` op; numbers only,
            # so it stays scrape-open like `metrics`). Same clamped-
            # response contract as `traces`.
            from rbg_tpu.obs.slo import slo_response
            send_msg(self.request, slo_response(obj.get("window")))
            return
        if op == "traces":
            # Operator pull of the trace sink (the serving-plane sibling of
            # the admin `traces` op): recent + slowest ring buffers, the
            # slowest request's waterfall, and the histogram exemplars
            # linking a bad quantile to a trace_id.
            from rbg_tpu.obs.trace import traces_response
            resp = traces_response(obj.get("n", 10))
            if "steps_since" in obj:
                # The step timeline's rings (docs/observability.md): the
                # engine's step records, and its late-step records, later
                # than the caller's cursor.
                svc = srv.service or srv.decode
                eng = svc.engine if svc is not None else (
                    srv.prefill.engine if srv.prefill is not None else None)
                try:
                    since = float(obj["steps_since"] or 0.0)
                except (TypeError, ValueError):
                    since = 0.0
                resp.update(eng.steps_since(since) if eng is not None
                            else {"steps": [], "steps_dropped": 0,
                                  "late_steps": []})
            send_msg(self.request, resp)
            return
        if op in self._DATA_OPS:
            span = trace.from_wire(obj.get("trace"), names.SPAN_ENGINE_OP,
                                   op=op, mode=srv.mode)
            if not span:
                return self._serve_data(srv, obj, k, v)
            try:
                with trace.use_span(span):
                    return self._serve_data(srv, obj, k, v)
            finally:
                span.end()
        return self._serve_data(srv, obj, k, v)

    def _serve_data(self, srv, obj, k, v):
        op = obj.get("op")
        if op == "warmup":
            # Compile every jit bucket variant NOW (one blocking op per
            # serving pod, before it takes traffic) instead of stalling
            # live requests at first variant hit. The serving-SLO analog
            # of the control plane's warmup pods (SURVEY #9).
            import time as _time
            t0 = _time.perf_counter()
            n = int(obj.get("input_len", 32))
            if srv.service is not None:
                srv.service.warmup(n)
            elif srv.prefill is not None:
                with srv.pd_lock:
                    srv.prefill.warmup(n)
            elif srv.decode is not None:
                srv.decode.warmup(n)
            else:
                send_msg(self.request, {"error": "engine not ready"})
                return
            send_msg(self.request, {
                "ok": True,
                "elapsed_s": round(_time.perf_counter() - t0, 2)})
            return
        if op == "metrics":
            stats = {}
            eng = None
            if srv.service is not None:
                stats = srv.service.stats()
                eng = srv.service.engine
            elif srv.prefill is not None:
                stats = {**srv.prefill.engine.metrics, **srv.prefill.metrics}
                eng = srv.prefill.engine
            elif srv.decode is not None:
                eng = srv.decode.engine
                stats = {**eng.metrics, **srv.decode.worker.metrics,
                         **srv.decode.service_stats(),
                         "running": len(eng.running),
                         "waiting": len(eng.waiting),
                         "free_pages": eng.allocator.free_pages}
            if eng is not None and getattr(eng, "host_tier", None) is not None:
                stats["host_tier"] = eng.host_tier.stats()
                stats["device_tier_pages"] = (
                    eng.radix.cached_pages if eng.radix is not None else 0)
            stats["draining"] = srv.draining
            # Set-up cost and device memory, so a client on the wire can
            # tell compile time from serving time and see what the pool
            # and the step programs really hold.
            stats["compile"] = srv.compile_counter.snapshot()
            stats["device_memory"] = chipenv.memory_summary()
            send_msg(self.request, {"metrics": stats, "mode": srv.mode})
            return
        if op == "generate_text" and srv.service is not None:
            tok = srv.tokenizer
            vocab = srv.service.engine.mcfg.vocab_size
            if tok.vocab_size > vocab:
                send_msg(self.request, {"error": (
                    f"tokenizer vocab {tok.vocab_size} exceeds model vocab "
                    f"{vocab}; pass --tokenizer-path matching the model")})
                return
            try:
                sampling = SamplingParams.from_wire(
                    obj, default_max_tokens=64, stop_token=tok.eos_id)
                deadline = _deadline_of(obj)
            except (ValueError, TypeError) as e:
                send_msg(self.request, {"error": f"bad sampling params: {e}"})
                return
            prompt_ids = tok.encode(obj["text"])
            limit = srv.service.engine.cfg.max_seq_len
            if len(prompt_ids) + sampling.max_new_tokens > limit:
                send_msg(self.request, {"error": (
                    f"prompt ({len(prompt_ids)} tokens) + max_new_tokens "
                    f"({sampling.max_new_tokens}) exceeds max_seq_len {limit}")})
                return
            try:
                ids, ttft = srv.service.submit(prompt_ids, sampling,
                                               deadline=deadline)
            except Rejected as e:
                send_msg(self.request, e.to_wire())
                return
            send_msg(self.request, {"text": tok.decode(ids), "tokens": ids,
                                    "ttft_s": ttft})
            return
        if op == "generate" and srv.service is not None:
            try:
                sampling = SamplingParams.from_wire(obj)
                deadline = _deadline_of(obj)
            except (ValueError, TypeError) as e:
                send_msg(self.request, {"error": f"bad sampling params: {e}"})
                return
            if obj.get("stream"):
                try:
                    pending = srv.service.submit_async(obj["prompt"], sampling,
                                                       deadline=deadline)
                except Rejected as e:
                    send_msg(self.request, {**e.to_wire(), "done": True})
                    return
                self._stream_pending(srv.service, pending,
                                     with_logprobs=sampling.logprobs,
                                     deadline=deadline)
                return
            try:
                p = srv.service.submit_wait(obj["prompt"], sampling,
                                            deadline=deadline)
            except Rejected as e:
                send_msg(self.request, e.to_wire())
                return
            except (TimeoutError, ValueError) as e:
                send_msg(self.request, {"error": str(e)})
                return
            resp = {"tokens": p.tokens, "ttft_s": srv.service.ttft(p)}
            if sampling.logprobs:
                resp["logprobs"] = p.logprobs
            send_msg(self.request, resp)
            return
        if op == "embed":
            # Any engine mode serves embeddings — prefill/decode roles hold
            # the same weights, so a PD group's edge works too.
            eng = None
            if srv.service is not None:
                eng = srv.service.engine
            elif srv.prefill is not None:
                eng = srv.prefill.engine
            elif srv.decode is not None:
                eng = srv.decode.engine
            if eng is None:
                send_msg(self.request, {"error": "engine not ready"})
                return
            tok = srv.tokenizer
            if "prompts" in obj:
                prompts = [list(p) for p in obj["prompts"]]
            elif "text" in obj:
                prompts = [tok.encode(obj["text"], add_bos=False)]
            else:
                prompts = [list(obj.get("prompt") or [])]
            from rbg_tpu.engine.service import embed_prompts
            try:
                vecs = embed_prompts(eng, prompts)
            except ValueError as e:
                send_msg(self.request, {"error": str(e)})
                return
            send_msg(self.request, {
                "embeddings": vecs, "dim": len(vecs[0]),
                "prompt_tokens": sum(len(p) for p in prompts),
                # single-prompt back-compat field
                "embedding": vecs[0]})
            return
        if op == "prefill" and srv.prefill is not None:
            try:
                sampling = SamplingParams.from_wire(obj)
                deadline = _deadline_of(obj)
            except (ValueError, TypeError) as e:
                send_msg(self.request, {"error": f"bad sampling params: {e}"})
                return
            # The prefill engine serializes behind pd_lock: a deadline-
            # carrying request bounds its wait for the lock (the implicit
            # queue here), and a budget spent while queued is refused
            # BEFORE any prefill compute burns chip time.
            qspan = trace.child(names.SPAN_SERVICE_QUEUE_WAIT)
            if deadline is not None:
                remaining = deadline - time.monotonic()
                if remaining <= 0 or not srv.pd_lock.acquire(timeout=remaining):
                    REGISTRY.inc(names.SERVING_DEADLINE_EXCEEDED_TOTAL,
                                 stage="prefill_queue")
                    qspan.end(outcome="deadline")
                    send_msg(self.request, DeadlineExceeded(
                        "deadline spent waiting for the prefill engine"
                    ).to_wire())
                    return
            else:
                srv.pd_lock.acquire()
            qspan.end(outcome="admitted")
            t_lock = time.perf_counter()
            pspan = trace.child(names.SPAN_PD_PREFILL,
                                prompt_tokens=len(obj.get("prompt") or ()))
            push_to = obj.get("push_to")
            push = None
            try:
                if push_to and srv.kv_push is not None:
                    # KVCache-centric path: chunks stream DIRECTLY to the
                    # decode peer as prefill chunks complete; the sends
                    # ride a sender thread, so the pd_lock critical
                    # section covers compute only, never the link.
                    push = srv.prefill.prefill_stream(
                        obj["prompt"], sampling, transport=srv.kv_push,
                        peer=push_to,
                        stream_id=obj.get("stream_id"),
                        deadline=deadline)
                else:
                    bundle = srv.prefill.prefill(obj["prompt"], sampling,
                                                 deadline=deadline)
            except DeadlineExceeded as e:
                pspan.end(outcome="deadline_abort")
                send_msg(self.request, e.to_wire())
                return
            except Exception:
                pspan.end(outcome="error")
                raise
            finally:
                srv.pd_lock.release()
                REGISTRY.observe(names.PD_LOCK_HOLD_SECONDS,
                                 time.perf_counter() - t_lock,
                                 lock="server_pd")
            if push is not None:
                pspan.end(outcome="pushed", bytes=push.meta.nbytes())
                # Reply the moment COMPUTE is done — the chunk tail drains
                # to the decode peer while the router sets up the decode
                # leg. An already-failed push (connect refused surfaces
                # during compute) is reported so the router falls back to
                # the bundle path instead of a doomed decode_stream.
                send_msg(self.request, {
                    "pushed": push.error() is None,
                    "stream_id": push.stream_id,
                    "first_token": push.first_token,
                    "prompt": list(obj["prompt"]),
                    "kv_bytes": push.meta.nbytes(),
                    "push_error": push.error(),
                    # Measured prefill→decode link rates from COMPLETED
                    # pushes — the router folds them into its
                    # transfer-cost-aware decode scoring.
                    "link_rates": srv.kv_push.stats.snapshot()})
                return
            pspan.end(outcome="ok", bytes=bundle.nbytes)
            header, kb, vb = bundle_to_wire(bundle)
            send_msg(self.request, header, kb, vb)
            return
        if op == "kv_stream" and srv.decode is not None:
            self._serve_kv_stream(srv, obj)
            return
        if op == "decode_stream" and srv.decode is not None:
            self._serve_decode_stream(srv, obj)
            return
        if op == "decode_bundle" and srv.decode is not None:
            bundle = bundle_from_wire(obj, k, v)
            try:
                sampling = SamplingParams.from_wire(obj)
                deadline = _deadline_of(obj)
            except (ValueError, TypeError) as e:
                send_msg(self.request, {"error": f"bad sampling params: {e}"})
                return
            # Continuous batching: bundles from concurrent connections decode
            # together on the device (no per-connection serialization).
            if obj.get("stream"):
                # A bundle finished at inject (max_new_tokens == 1 / stop
                # token) resolves with done set and no tokens — the stream
                # then carries only the first_token frame.
                try:
                    pending = srv.decode.submit_async(bundle, sampling,
                                                      deadline=deadline)
                except Rejected as e:
                    send_msg(self.request, {**e.to_wire(), "done": True})
                    return
                self._stream_pending(srv.decode, pending,
                                     first_tokens=[bundle.first_token],
                                     with_logprobs=sampling.logprobs,
                                     deadline=deadline)
                return
            try:
                p = srv.decode.submit_wait(bundle, sampling,
                                           deadline=deadline)
            except Rejected as e:
                send_msg(self.request, e.to_wire())
                return
            except (TimeoutError, ValueError) as e:
                send_msg(self.request, {"error": str(e)})
                return
            resp = {"tokens": [bundle.first_token] + p.tokens}
            if sampling.logprobs:
                # First token sampled prefill-side — null placeholder.
                resp["logprobs"] = [None] + p.logprobs
            send_msg(self.request, resp)
            return
        send_msg(self.request, {"error": f"unsupported op {op!r} in mode {srv.mode}"})

    def _serve_kv_stream(self, srv, obj):
        """Ingest one inbound KV chunk stream on THIS connection (the
        prefill peer opened it): frames land in the decode service's
        stream registry; the loop thread commits them into the page table
        as they arrive. Replies an ack after FIN (the sender's drain
        barrier). A broken connection fails the stream with a structured
        error — never a wedge."""
        from rbg_tpu.kvtransfer.chunks import StreamFin
        from rbg_tpu.kvtransfer.transport import frame_from_wire

        sid = obj.get("stream_id") or ""
        rx = srv.decode.kv_streams.get_or_create(sid)
        srv.decode.watch_stream(rx)
        nbytes = 0
        while True:
            try:
                fobj, fk, fv = recv_msg(self.request)
            except (ConnectionError, json.JSONDecodeError) as e:
                rx.fail(f"kv stream connection broke: {e}")
                return
            if fobj is None:
                rx.fail("kv stream EOF before FIN")
                return
            try:
                frame = frame_from_wire(fobj, fk, fv)
            except Exception as e:  # noqa: BLE001 — fail the stream, not the handler
                rx.fail(f"bad kv frame: {e}")
                send_msg(self.request, {"error": str(e)})
                return
            nbytes += len(fk or b"") + len(fv or b"")
            rx.feed(frame)
            if isinstance(frame, StreamFin):
                REGISTRY.inc(names.KVT_BYTES_TOTAL, float(nbytes),
                             direction="recv", transport="tcp")
                send_msg(self.request, {"ok": True, "bytes": nbytes})
                return

    def _serve_decode_stream(self, srv, obj):
        """Decode a previously (or concurrently) pushed KV stream: wait
        for admission coverage, then decode exactly like decode_bundle.
        The row is admitted the moment layer coverage for the prompt is
        complete — the stream's FIN may still be in flight."""
        from rbg_tpu.engine.protocol import CODE_KV_STREAM
        from rbg_tpu.kvtransfer.chunks import StreamError

        try:
            sampling = SamplingParams.from_wire(obj)
            deadline = _deadline_of(obj)
        except (ValueError, TypeError) as e:
            send_msg(self.request, {"error": f"bad sampling params: {e}"})
            return
        sid = obj.get("stream_id") or ""
        rx = srv.decode.kv_streams.get_or_create(sid)
        srv.decode.watch_stream(rx)
        wait_s = 30.0
        if deadline is not None:
            wait_s = max(0.0, min(wait_s, deadline - time.monotonic()))
        try:
            rx.wait_ready(wait_s)
        except StreamError as e:
            # Mark the receiver failed so the loop thread's pump releases
            # any pages it pre-allocated — an abandoned stream must not
            # hold KV capacity.
            rx.fail(f"abandoned: {e}")
            srv.decode.kv_streams.pop(sid)
            send_msg(self.request, {"error": f"kv stream: {e}",
                                    "code": CODE_KV_STREAM, "done": True})
            return
        first_token = rx.assembler.first_token
        if obj.get("stream"):
            try:
                pending = srv.decode.submit_stream(rx, sampling,
                                                   deadline=deadline)
            except Rejected as e:
                send_msg(self.request, {**e.to_wire(), "done": True})
                return
            self._stream_pending(srv.decode, pending,
                                 first_tokens=[first_token],
                                 with_logprobs=sampling.logprobs,
                                 deadline=deadline)
            return
        p = None
        try:
            p = srv.decode.submit_stream(rx, sampling, deadline=deadline)
            srv.decode.wait(p, DEFAULT_WAIT_S if deadline is None
                            else max(0.0, deadline - time.monotonic()) + 1.0)
        except Rejected as e:
            send_msg(self.request, e.to_wire())
            return
        except (TimeoutError, ValueError) as e:
            frame = {"error": str(e)}
            # Admit-time stream failures (dead kv_stream connection,
            # no pages for the pushed KV) keep their wire code so the
            # router re-routes in bundle mode instead of surfacing them.
            if p is not None and p.code:
                frame["code"] = p.code
            send_msg(self.request, frame)
            return
        resp = {"tokens": [first_token] + p.tokens}
        if sampling.logprobs:
            resp["logprobs"] = [None] + p.logprobs
        send_msg(self.request, resp)
        return


class EngineServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True

    def note_inflight(self, delta: int) -> None:
        with self._inflight_lock:
            self._inflight += delta

    def inflight(self) -> int:
        with self._inflight_lock:
            return self._inflight


def start_drain(server: EngineServer, drain_deadline_s: float) -> None:
    """Flip the server into draining and schedule the clean exit.

    The state machine (reference: RBG's group-level drain contract —
    ANN_DRAIN_DEADLINE / PreparingDelete, api/constants.py): serving →
    (SIGTERM) → draining — health reports it, every NEW data op is refused
    with code "draining", in-flight requests keep running — → all in-flight
    done OR drain deadline passed → listener shutdown → process exit 0.
    Idempotent: a second SIGTERM neither resets the clock nor stacks
    drainer threads."""
    if server.draining:
        return
    server.draining = True
    server.drain_started = time.monotonic()
    REGISTRY.inc(names.SERVING_DRAINS_TOTAL)
    REGISTRY.set_gauge(names.SERVING_DRAINING, 1.0)
    # A draining prefill replica's prefix-directory entries go stale the
    # moment it exits — invalidate them NOW so no router routes a prefix
    # hit at a pod that is about to refuse it.
    pf = server.prefill
    if pf is not None and pf.directory is not None and pf.advertise_addr:
        try:
            pf.directory.invalidate_backend(pf.advertise_addr,
                                            reason="drain")
        except Exception:  # noqa: BLE001 — drain must never fail on this
            pass
    print(f"draining: finishing in-flight work "
          f"(deadline {drain_deadline_s:.1f}s)", flush=True)

    def drainer():
        deadline = server.drain_started + drain_deadline_s
        while time.monotonic() < deadline:
            busy = server.inflight() > 0
            for s in (server.service, server.decode):
                if s is not None and (s.engine.has_work() or s._queue):
                    busy = True
            if not busy:
                break
            time.sleep(0.05)
        drained = time.monotonic() - server.drain_started
        aborted = server.inflight()
        print(f"drain {'complete' if not aborted else 'deadline'} after "
              f"{drained:.2f}s ({aborted} in-flight aborted)", flush=True)
        server.shutdown()

    threading.Thread(target=drainer, daemon=True, name="drainer").start()


def serve(args) -> None:
    cfg = build_config(args)
    cfg.validate()  # fail fast on bad CLI values, before the port binds
    port = int(os.environ.get("RBG_SERVE_PORT")
               or os.environ.get("RBG_PORT_SERVE")
               or args.port)

    # Multi-host slice init (the control plane injected the contract).
    nproc = int(os.environ.get("RBG_JAX_NUM_PROCESSES", "1"))
    if nproc > 1 and os.environ.get("RBG_DISTRIBUTED", "0") == "1":
        import jax
        jax.distributed.initialize(
            os.environ["RBG_JAX_COORDINATOR_ADDRESS"],
            num_processes=nproc,
            process_id=int(os.environ["RBG_JAX_PROCESS_ID"]),
        )

    # Windowed-signal sampler (obs/timeseries.py): the `slo` data op and
    # `rbg-tpu top` read rates/means over its ring buffer — start it with
    # the process so the first operator pull already has history.
    from rbg_tpu.obs import timeseries
    timeseries.ensure_started()

    server = EngineServer(("127.0.0.1", port), Handler)
    server.mode = cfg.mode
    server.service = server.prefill = server.decode = None
    server.device = None           # set by init_engine, reported by health
    chipenv.configure_compile_cache()
    server.compile_counter = chipenv.compile_counter()
    server.auth_token = (args.auth_token
                         or os.environ.get("RBG_DATA_TOKEN") or None)
    server.pd_lock = named_lock("engine.server_pd")
    server.kv_push = None          # TCPTransport, prefill mode only
    server.draining = False
    server.drain_started = 0.0
    server._inflight = 0
    server._inflight_lock = named_lock("engine.server_inflight")
    max_queue = args.max_queue if args.max_queue > 0 else None
    drain_deadline_s = float(
        args.drain_deadline_s
        if args.drain_deadline_s is not None
        else os.environ.get("RBG_DRAIN_DEADLINE_S", "30"))
    server.drain_deadline_s = drain_deadline_s
    # SIGTERM = the rollout/scale-down signal (what the executor and k8s
    # send): graceful drain instead of dropping in-flight streams on the
    # floor. serve() runs on the main thread, where signal() is legal.
    try:
        signal.signal(signal.SIGTERM,
                      lambda *_: start_drain(server, drain_deadline_s))
    except ValueError:
        pass  # non-main-thread embedding (tests) — drain via start_drain()
    from rbg_tpu.engine.tokenizer import ByteTokenizer
    server.tokenizer = ByteTokenizer()  # replaced by init_engine if HF given

    # Bind the port FIRST (readiness probes connect), then load model and
    # tokenizer in the background — a slow HF load must not stall accepts.
    def init_engine():
        try:
            # One line a deployment's log can be searched for: which
            # device this process took (a chip belongs to one process).
            server.device = chipenv.device_summary()
            print("engine device platform={platform} kind={kind!r} id={id} "
                  "count={count}".format(**server.device), flush=True)
            if args.tokenizer_path:
                from rbg_tpu.engine.tokenizer import load_tokenizer
                server.tokenizer = load_tokenizer(args.tokenizer_path)
            def load_adapters(engine):
                import numpy as np
                for spec in args.lora:
                    name, _, path = spec.partition("=")
                    if not path:
                        raise ValueError(f"--lora expects NAME=PATH, got "
                                         f"{spec!r}")
                    z = np.load(path)
                    targets = sorted({k.rsplit(".", 1)[0] for k in z.files
                                      if k.endswith(".A")})
                    adapter = {t: (z[f"{t}.A"], z[f"{t}.B"])
                               for t in targets}
                    alpha = float(z["alpha"]) if "alpha" in z.files else 16.0
                    engine.load_lora(name, adapter, alpha=alpha)

            # Fully wire each engine (grammar table, adapters) BEFORE
            # publishing it on the server object: health reports ready the
            # moment the attribute is set, and a json_mode request racing
            # the grammar wiring used to get a spurious admission error.
            if cfg.mode == "prefill":
                from rbg_tpu.engine.pd import PrefillWorker
                pool = None
                directory = None
                pool_addr = args.kv_pool or os.environ.get(
                    "RBG_KV_POOL_ADDR", "")
                if pool_addr:
                    from rbg_tpu.engine.kvpool import KVPoolClient
                    pool = KVPoolClient(
                        pool_addr,
                        token=server.auth_token,
                        ca_path=(args.kv_pool_ca
                                 or os.environ.get("RBG_KV_POOL_CA")
                                 or None))
                    # The pool server hosts the cluster prefix directory
                    # (dir_* ops): computed prefixes register under this
                    # replica's serving address so the router can steer
                    # prefix-sharing requests to ANY holder.
                    from rbg_tpu.kvtransfer.directory import DirectoryClient
                    directory = DirectoryClient(
                        pool_addr, token=server.auth_token,
                        page_size=cfg.page_size)
                advertise = (args.advertise_addr
                             or os.environ.get("RBG_ADVERTISE_ADDR")
                             or f"127.0.0.1:{port}")
                prefill = PrefillWorker(cfg, pool=pool,
                                        directory=directory,
                                        advertise_addr=advertise)
                if prefill.engine.host_tier is not None and directory:
                    # Host-tier spills register in the cluster directory
                    # under this replica's serving address (tier="host"),
                    # so the router's tier-fetch-cost scoring sees them.
                    prefill.engine.host_tier.wire_directory(
                        directory, advertise)
                prefill.engine.enable_json_grammar(server.tokenizer)
                load_adapters(prefill.engine)
                if args.kv_stream != "off":
                    from rbg_tpu.kvtransfer.transport import TCPTransport
                    server.kv_push = TCPTransport(token=server.auth_token)
                server.prefill = prefill
            elif cfg.mode == "decode":
                from rbg_tpu.engine.service import DecodeService
                decode = DecodeService(cfg, max_queue=max_queue)
                decode.engine.enable_json_grammar(server.tokenizer)
                load_adapters(decode.engine)
                server.decode = decode
            else:
                from rbg_tpu.engine.service import EngineService
                service = EngineService(cfg, max_queue=max_queue)
                service.engine.enable_json_grammar(server.tokenizer)
                load_adapters(service.engine)
                server.service = service
        except Exception:
            # A pod that cannot build its engine must CRASH (so the restart
            # policy sees it), not linger as a never-ready zombie listener.
            import traceback
            traceback.print_exc()
            os._exit(1)
        eng = (server.service or server.decode or server.prefill).engine
        print(f"engine ready mode={cfg.mode} model={cfg.model} port={port} "
              "decode_walk_kernel_copies="
              f"{eng.metrics['decode_walk_kernel_copies']}", flush=True)

    threading.Thread(target=init_engine, daemon=True).start()
    print(f"engine listening on 127.0.0.1:{port}", flush=True)
    server.serve_forever()
    # serve_forever returns only via the drainer's shutdown(): close the
    # listener and fall out of main() with exit code 0 — a clean rollout.
    server.server_close()
    print("engine exited cleanly after drain", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="rbg-tpu-engine")
    ap.add_argument("--model", default=os.environ.get("RBG_MODEL", "tiny"))
    ap.add_argument("--mode", default="unified",
                    choices=["unified", "prefill", "decode"])
    ap.add_argument("--port", type=int, default=9000)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=512)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-seq-len", type=int, default=1024)
    ap.add_argument("--prefill-chunk", type=int, default=64)
    ap.add_argument("--use-pallas", default="auto")
    ap.add_argument("--kv-dtype", default="model", choices=["model", "int8"],
                    help="int8 halves KV HBM (unified mode only)")
    ap.add_argument("--checkpoint-path",
                    default=os.environ.get("RBG_CHECKPOINT_PATH", ""),
                    help="orbax dir or local HF dir (else random init)")
    ap.add_argument("--tokenizer-path",
                    default=os.environ.get("RBG_TOKENIZER_PATH", ""),
                    help="local HF tokenizer dir (else byte-level fallback)")
    ap.add_argument("--kv-pool",
                    default=os.environ.get("RBG_KV_POOL_ADDR", ""),
                    help="host:port of the shared KV pool (prefill mode; "
                         "Mooncake-store analog, rbg_tpu.engine.kvpool)")
    ap.add_argument("--kv-pool-ca", default="",
                    help="CA cert path for a TLS kv-pool (default: "
                         "$RBG_KV_POOL_CA; empty = plaintext)")
    ap.add_argument("--kv-stream", choices=("auto", "off"), default="auto",
                    help="chunked layer-overlapped prefill→decode KV "
                         "streaming (the router passes push_to and this "
                         "prefill pushes chunks as they compute); 'off' "
                         "keeps the whole-bundle wire path")
    ap.add_argument("--advertise-addr", default="",
                    help="address this replica registers in the cluster "
                         "prefix directory (default: $RBG_ADVERTISE_ADDR "
                         "or 127.0.0.1:<port>)")
    ap.add_argument("--auth-token", default="",
                    help="require this bearer token on every data op "
                         "(default: $RBG_DATA_TOKEN; empty = open wire). "
                         "The same token authenticates this server's own "
                         "kv-pool client calls.")
    ap.add_argument("--multi-step", type=int, default=1,
                    help="decode steps fused per device dispatch (lax.scan "
                         "window; higher = throughput, burstier streaming)")
    ap.add_argument("--ragged", choices=("auto", "off"), default="auto",
                    help="ragged unified prefill/decode dispatch "
                         "(continuous batching); 'off' keeps the split "
                         "phase paths — the bit-identical baseline")
    ap.add_argument("--lora", action="append", default=[],
                    metavar="NAME=PATH.npz",
                    help="load a LoRA adapter (repeatable). The npz holds "
                         "'{target}.A' [L,d,r] / '{target}.B' [L,r,o] "
                         "arrays (targets wq/wk/wv/wo/w_gate/w_up/w_down) "
                         "and optional scalar 'alpha'")
    ap.add_argument("--vocab-size", type=int, default=0,
                    help="override the preset's vocab size (0 = keep; lets "
                         "demo models cover the byte tokenizer's 259 ids)")
    ap.add_argument("--speculative", choices=("off", "ngram"), default="off",
                    help="prompt-lookup speculative decoding (bit-identical "
                         "output; wins on repetitive/structured text)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max drafted tokens per speculative verify step")
    ap.add_argument("--spec-ngram", type=int, default=3,
                    help="trailing n-gram length for prompt lookup")
    ap.add_argument("--grammar-table", choices=("auto", "off"),
                    default="auto",
                    help="device-resident grammar tables: constrained "
                         "(regex/json_schema) rows decode inside the fused "
                         "multi-step window; 'off' keeps the host-synced "
                         "per-token mask path")
    ap.add_argument("--grammar-state-budget", type=int, default=512,
                    help="max token-level automaton states per grammar "
                         "table (S x V x 5 bytes each); grammars over "
                         "budget fall back to the host-synced path")
    ap.add_argument("--slo-ttft-s", type=float, default=2.0,
                    help="per-request TTFT target the serving loop judges "
                         "every finished request against (rbg_slo_* "
                         "attainment/goodput series; 0 disables the "
                         "dimension)")
    ap.add_argument("--slo-tpot-s", type=float, default=0.5,
                    help="per-output-token latency target (time per token "
                         "after the first; 0 disables the dimension)")
    ap.add_argument("--host-tier-bytes", type=int, default=0,
                    help="host-DRAM KV spill tier budget in bytes: device "
                         "page-pool evictions spill prefix pages here and "
                         "admission promotes them back on a hit (0 = off; "
                         "needs the radix cache; Mooncake's 'more storage "
                         "for less computation' level)")
    ap.add_argument("--early-reject", choices=("off", "auto"),
                    default="off",
                    help="predictive early rejection: admission predicts "
                         "TTFT (measured queue wait + prefill net of the "
                         "prefix hit this request would get) and sheds at "
                         "ingress with retry_after_s when it exceeds "
                         "--early-reject-factor x --slo-ttft-s")
    ap.add_argument("--early-reject-factor", type=float, default=1.5,
                    help="early-rejection gate as a multiple of the TTFT "
                         "SLO target")
    ap.add_argument("--max-queue", type=int, default=256,
                    help="admission-control bound on the service queue: "
                         "submissions past it are shed with a structured "
                         "'overloaded' error + retry_after_s hint instead "
                         "of queueing unboundedly (0 = unbounded)")
    ap.add_argument("--drain-deadline-s", type=float, default=None,
                    help="graceful-drain budget after SIGTERM: in-flight "
                         "requests may finish for this long before the "
                         "process exits (default: $RBG_DRAIN_DEADLINE_S "
                         "or 30)")
    args = ap.parse_args(argv)
    serve(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
