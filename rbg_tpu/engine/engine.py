"""Serving engine: continuous batching over a paged KV pool.

The data plane the control plane orchestrates — the SGLang-on-JAX-equivalent
(the reference deploys SGLang in its role pods; BASELINE.md configs). One
Engine = one model replica on one JAX program (single chip or a whole slice
via the tp/sp mesh).

Design (TPU-first):
* **Bucketed static shapes** — one compiled program per (batch, chunk)
  bucket; prefill chunks and decode steps reuse the same ``forward_paged``.
* **Host-side logistics, device-side math** — page tables/lengths are plain
  numpy handed to jit as arrays; the graph never sees Python branching.
* **Chunked prefill** — long prompts stream through a fixed-size chunk
  program, so TTFT for short prompts never waits behind a long compile.
* **Radix prefix cache** — page-granular prefix sharing with LRU eviction.
* **Preemption** — page exhaustion preempts the youngest request back to the
  waiting queue (its pages recycle; the radix cache softens the re-prefill).

Modes: ``unified`` (prefill+decode co-located), ``prefill`` (produces KV
pages + first token for a peer), ``decode`` (imports KV pages) — see
rbg_tpu.engine.pd for the disaggregated pair.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import json
import logging
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from rbg_tpu.engine.config import EngineConfig, SamplingParams
from rbg_tpu.engine.kvcache import (PageAllocator, PagedKVCache, StatePool,
                                    window_pool_pages,
                                    pages_for_tokens)
from rbg_tpu.engine.radix_cache import RadixCache
from rbg_tpu.engine.sampler import NEG_INF, row_keys, sample, step_keys
from rbg_tpu.obs.names import (PROGRAM_FUSED_DECODE, PROGRAM_PAGED_FWD,
                               PROGRAM_PLACE_TOKENS, PROGRAM_RAGGED_FWD,
                               PROGRAM_SAMPLER, PROGRAM_SPEC_VERIFY)
from rbg_tpu.models.llama import forward_paged, forward_ragged, init_params
from rbg_tpu.obs import names as obs_names
from rbg_tpu.obs import trace
from rbg_tpu.obs.metrics import REGISTRY

log = logging.getLogger("rbg_tpu.engine")

# Step records kept for the ``traces`` op (about a minute of 14 ms steps).
STEP_RING = 4096
# Late-step records kept beside them: they are rare by definition, so 64
# hold hours of a sound server and every one of a bad minute.
LATE_RING = 64


class _Phase:
    """One phase of a step: its ``jax.profiler`` annotation, the stamp of
    when it last began (for the step record), and its wall time added to
    a cumulative clock in ``Engine.metrics``. A phase entered inside
    another suspends the outer one's clock (a read of the pending step
    inside ``engine.pack`` is sync and emit time, not pack time),
    so the clocks never overlap and sum to no more than the step.

    ``engine.pack`` and ``engine.dispatch`` of a unified and of a fused
    decode step (``kind``) are tiled by sub-phases: flat, one open at a
    time, each an annotation inside the phase's and a clock of its own by
    the kind of step (``SUB_CLOCKS``). The first opens on the phase's
    entry stamp and the last closes on its exit stamp; ``mark`` closes
    one and opens the next on ONE reading of the clock. Every stretch that
    goes to the phase's clock goes to the open sub-phase's too, a stretch
    cut short by an inner phase included, so the sub-phase clocks sum to
    the two phases' to float rounding."""

    __slots__ = ("eng", "idx", "ann", "outer", "t0", "subs", "sub",
                 "sub_ann")
    SPANS = (obs_names.SPAN_ENGINE_ADMIT, obs_names.SPAN_ENGINE_PACK,
             obs_names.SPAN_ENGINE_DISPATCH, obs_names.SPAN_ENGINE_SYNC,
             obs_names.SPAN_ENGINE_EMIT)
    CLOCKS = ("t_admit_s", "t_pack_s", "t_dispatch_s", "t_sync_s",
              "t_emit_s")
    SUB_SPANS = (obs_names.SPAN_ENGINE_PACK_ROWS,
                 obs_names.SPAN_ENGINE_PACK_FILL,
                 obs_names.SPAN_ENGINE_PACK_UPLOAD,
                 obs_names.SPAN_ENGINE_DISPATCH_CALL,
                 obs_names.SPAN_ENGINE_DISPATCH_BOOK,
                 obs_names.SPAN_ENGINE_DISPATCH_SAMPLE)
    # In ``SUB_SPANS``' order; a decode step has no ``sample``.
    SUB_CLOCKS = {
        "unified": ("t_unified_rows_s", "t_unified_fill_s",
                    "t_unified_upload_s", "t_unified_call_s",
                    "t_unified_book_s", "t_unified_sample_s"),
        "decode": ("t_decode_rows_s", "t_decode_fill_s",
                   "t_decode_upload_s", "t_decode_call_s",
                   "t_decode_book_s")}

    def __init__(self, eng: "Engine", idx: int, kind: Optional[str] = None):
        self.eng = eng
        self.idx = idx
        self.ann = trace.annotation(self.SPANS[idx])
        self.subs = self.SUB_CLOCKS[kind] if kind is not None else None
        self.sub = self.sub_ann = None

    def __enter__(self):
        eng = self.eng
        self.ann.__enter__()
        now = self.t0 = eng._marks[self.idx] = time.monotonic()
        outer = self.outer = eng._phase_now
        if outer is not None:
            outer._stop(now)
        eng._phase_now = self
        if self.idx == _DISPATCH:
            eng.probe(now)
        if self.subs is not None:
            self._open(_ROWS if self.idx == _PACK else _CALL)
        return self

    def __exit__(self, *exc):
        now = time.monotonic()
        self._stop(now)
        if self.sub_ann is not None:
            self.sub_ann.__exit__(*exc)
        eng = self.eng
        outer = eng._phase_now = self.outer
        if outer is not None:
            outer.t0 = now
        self.ann.__exit__(*exc)
        idx = self.idx
        if idx == _SYNC and eng._pending is None:
            # The read that left nothing in flight: from here on the
            # device has nothing of this engine's to run.
            eng._t_emptied = now
        if idx != _PACK and idx != _DISPATCH:
            eng.probe(now)

    def _stop(self, now: float) -> None:
        took = now - self.t0
        m = self.eng.metrics
        m[self.CLOCKS[self.idx]] += took
        if self.sub is not None:
            m[self.sub] += took

    def _open(self, sub: int) -> None:
        self.sub = self.subs[sub]
        self.sub_ann = trace.annotation(self.SUB_SPANS[sub])
        self.sub_ann.__enter__()

    def mark(self, sub: int) -> float:
        """Close the open sub-phase and open ``sub``; the stamp."""
        now = time.monotonic()
        self._stop(now)
        self.t0 = now
        self.sub_ann.__exit__(None, None, None)
        self._open(sub)
        return now


_ADMIT, _PACK, _DISPATCH, _SYNC, _EMIT = range(5)
# The sub-phases of ``engine.pack`` (the first three) and of
# ``engine.dispatch``, in ``_Phase.SUB_SPANS``' order.
_ROWS, _FILL, _UPLOAD, _CALL, _BOOK, _SAMPLE = range(6)


@dataclasses.dataclass
class StepEvent:
    request_id: int
    token: int
    finished: bool
    text_done: bool = False
    logprob: Optional[float] = None


class _Unread(NamedTuple):
    """A step whose sampled tokens are still on the device: the engine's
    one pending read, whichever kind of step left it. ``rows[j]`` owns
    column ``j`` of ``toks`` (``[K, B]`` of a fused window, one line of a
    unified step) and of ``lps``, ``valid[j]`` of its tokens count, and
    entry ``j`` of ``last`` is the token its next step reads
    (``Engine._unread_tokens``): the window's carried ``tok``, a unified
    step's ``toks`` itself."""
    rows: list
    toks: object
    lps: object
    visited: object             # a fused window's count of expert visits
    valid: List[int]
    first: Optional[List[bool]]  # a unified step: the row's first token
    last: object


def _place_tokens(tok, take, prev):
    """``tok`` where ``take`` is negative, entry ``take`` of ``prev``
    elsewhere: how a step reads a row's input token off the step before
    it while the host has not seen that token."""
    return jnp.where(take >= 0, prev[jnp.maximum(take, 0)], tok)


_place_tokens.__name__ = PROGRAM_PLACE_TOKENS   # jitwatch catalog name
_place = jax.jit(_place_tokens)


class Request:
    _ids = itertools.count()

    def __init__(self, prompt: List[int], sampling: SamplingParams):
        self.id = next(Request._ids)
        self.prompt = list(prompt)
        # _preempt folds generated output into prompt for re-prefill;
        # everything past this index is OUTPUT for penalty accounting
        # (presence/frequency act on generated tokens only).
        self.orig_prompt_len = len(prompt)
        self.sampling = sampling
        self.output: List[int] = []
        self.state = "waiting"          # waiting | prefill | running | finished
        self.pages: List[int] = []
        self.state_slot: Optional[int] = None   # recurrent-state pool slot
        # The window class's pages (a model with window layers): those of
        # the row's line from column ``window_lo`` on; what lay below was
        # given back (``Engine._window_pages_for``).
        self.window_pages: List[int] = []
        self.window_lo = 0
        self.shared_tokens = 0          # radix-matched prefix (page-aligned)
        self.prefill_pos = 0            # next prompt index to prefill
        self.seq_len = 0                # tokens materialized in KV
        self.last_token: Optional[int] = None
        self.ngram = None                   # NGramIndex, speculative mode
        self.gstate = None                  # grammar state (json_mode/regex)
        self.grammar = None                 # this request's TokenGrammar
        self.lora_idx = 0                   # adapter slot (0 = base model)
        self.t_submit = time.perf_counter()
        self.t_first: Optional[float] = None
        # Continuous-admission accounting: the engine step at which the
        # request entered `waiting`, and how many admission attempts it sat
        # out because capacity (a batch slot or KV pages) was unavailable.
        # The difference (wait − blocked) is the request's EXCESS wait —
        # steps it queued beyond what resource availability forced — and
        # the continuous-batching invariant bounds it at one step.
        self.enqueue_step = 0
        self.blocked_steps = 0
        # Wall-clock twin of enqueue_step: when the request last entered
        # `waiting` (submit or preemption) — the join-latency metric
        # measures from here, not t_submit, so a preempted-then-readmitted
        # request's running time never reads as queue wait.
        self.t_enqueue = self.t_submit

    @property
    def total_len(self) -> int:
        return len(self.prompt) + len(self.output)

    def max_len(self) -> int:
        return len(self.prompt) + self.sampling.max_new_tokens


class Engine:
    def __init__(self, cfg: EngineConfig, params: Optional[dict] = None,
                 mesh=None):
        cfg.validate()
        self.cfg = cfg
        self.mcfg = cfg.model_config
        self.mesh = mesh
        key = jax.random.key(cfg.seed)
        if params is not None:
            self.params = params
        elif cfg.checkpoint_path:
            from rbg_tpu.models.checkpoint import load_params
            self.params = load_params(cfg.checkpoint_path, self.mcfg)
        else:
            self.params = init_params(self.mcfg, key)
        # Base for per-row sampling streams: a request's randomness is
        # fold_in(row_key, position) — row_key from its seed (reproducible)
        # or from this base + request id (distinct streams). See sampler.py.
        self._sample_base = jax.random.key(cfg.seed + 1)

        if self.mcfg.unbuilt_for:
            self._refuse_unbuilt()
        if self.mcfg.looped_for:
            self._refuse_looped()
        # A model with window layers keeps a second class of page for
        # them, sized by the rows' windows, with an allocator of its own.
        self.window_allocator: Optional[PageAllocator] = None
        if self.mcfg.sliding_window:
            self.window_allocator = PageAllocator(window_pool_pages(
                self.mcfg, cfg.page_size, cfg.max_batch,
                max(cfg.prefill_chunk, cfg.multi_step)))
        self.cache = PagedKVCache.create(
            self.mcfg, cfg.num_pages, cfg.page_size,
            quantize=(cfg.kv_dtype == "int8"),
            tp=1 if mesh is None else mesh.shape.get("tp", 1),
            window_num_pages=(self.window_allocator.num_pages
                              if self.window_allocator else 0))
        self.allocator = PageAllocator(cfg.num_pages)
        # A model with recurrent layers keeps a state slot a row beside
        # the pages (of its attention layers alone).
        self.state: Optional[StatePool] = None
        if self.mcfg.recurrent:
            self.state = StatePool(self.mcfg, cfg.max_batch)
        self.radix = RadixCache(self.allocator, cfg.page_size) if cfg.enable_radix_cache else None
        # Host-DRAM spill tier under the device pool (engine/kvtier.py):
        # radix evictions spill into it, admission promotes out of it.
        self.host_tier = None
        if cfg.host_tier_bytes and self.radix is not None \
                and not self.cache.quantized:
            from rbg_tpu.engine.kvtier import HostKVTier
            self.host_tier = HostKVTier(cfg.page_size, cfg.host_tier_bytes)

        if mesh is not None:
            self._shard_state(mesh)
        # A decode step may read hit experts only where every device holds
        # every expert (parallel/sharding.py splits them over ``ep``).
        self._experts_whole = mesh is None or mesh.shape.get("ep", 1) == 1

        # Step programs take the state pool and the window class's pools
        # by keyword and give them back.
        self._donate_state = (("state",) if self.state is not None else ()) \
            + (("window",) if self.window_allocator is not None else ())

        self.waiting: List[Request] = []
        self.running: List[Request] = []
        self.requests: Dict[int, Request] = {}
        self._fwd_cache: Dict[Tuple[int, int], object] = {}
        self._samplers: Dict[Tuple[bool, bool], object] = {}
        # Fused decode path: device-resident (tok, pos, kvl, table, …) state
        # of the window's rows, built anew when the batch changes.
        self._dec: Optional[dict] = None
        # The one-step emission lag of every step kind: the step whose
        # tokens are still on the device. The step after it is dispatched
        # first and takes its rows' input tokens from ``last``, then the
        # host fetches and emits these (``_emit_pending``), so its
        # bookkeeping for step N+1 overlaps the device computing it.
        self._pending: Optional[_Unread] = None
        # What a step that reads nothing off the step before it is given
        # in ``last``'s place: as wide as the widest line of rows.
        self._rows_max = self._bucket(cfg.max_batch)
        self._no_tokens = jnp.zeros(self._rows_max, jnp.int32)
        self._dec_fn_cache: Dict[Tuple[int, bool, bool], object] = {}
        self._spec_fn_cache: Dict[Tuple[int, bool, bool, bool, bool], object] = {}
        # Ragged unified prefill/decode dispatch: one compiled program per
        # (row bucket, packed-token bucket).
        self._ragged_fn_cache: Dict[Tuple[int, int], object] = {}
        # Set by the serving loop when submissions are waiting beyond this
        # step's admissions — the fused decode scan shortens its window so
        # the join is absorbed next step instead of a full multi_step
        # window later. Loop-thread-confined (single-writer, like all
        # engine state); cleared at the end of every step.
        self.join_hint = False
        # ``(seconds, request id, time.perf_counter())`` of each admission:
        # how long the request waited between entering the engine queue
        # and joining the running batch, and when it joined — drained by
        # the service loop into rbg_serving_join_latency_seconds and its
        # own queue-wait clock.
        self.last_join_waits: List[Tuple[float, int, float]] = []
        self.grammar = None     # TokenGrammar — enable_json_grammar()
        self._token_bytes = None
        self._grammar_eos = None
        self._token_trie = None
        self._regex_grammars = collections.OrderedDict()
        # Device-resident grammar tables: one upload per batch grammar
        # combination (per-grammar np tables cached on the TokenGrammar
        # itself; see _device_grammar_tables).
        self._gtable_dev = collections.OrderedDict()
        # Events drained outside step() (e.g. a runtime load_lora must
        # flush the fused pipeline) surface on the NEXT step() call.
        self._deferred_events: List[StepEvent] = []
        # Multi-LoRA: name → slot (0 = reserved no-adapter slot); stacked
        # arrays rebuilt on load (rank-padded so one program serves all).
        self._lora_slots: Dict[str, int] = {}
        self._lora_raw: List[Tuple[dict, float]] = []
        self.lora_stack: Optional[dict] = None
        self.metrics = {"steps": 0, "decode_tokens": 0, "prefill_tokens": 0,
                        "radix_hit_tokens": 0, "host_hit_tokens": 0,
                        "preemptions": 0,
                        "spec_drafted": 0, "spec_accepted": 0,
                        "spec_steps": 0, "unified_steps": 0, "joins": 0,
                        "join_wait_steps_max": 0, "join_excess_steps_max": 0,
                        # The step timeline (docs/observability.md):
                        # cumulative seconds and counts, never reset.
                        # ``steps`` counts every turn of step();
                        # ``steps_run`` those that dispatched a program.
                        "t_step_s": 0.0, "t_admit_s": 0.0, "t_pack_s": 0.0,
                        "t_dispatch_s": 0.0, "t_sync_s": 0.0,
                        "t_emit_s": 0.0, "steps_run": 0,
                        "t_unified_s": 0.0, "unified_steps_run": 0,
                        "t_decode_s": 0.0, "decode_steps_run": 0,
                        "kv_live_token_steps": 0, "kv_held_slot_steps": 0,
                        # The window class of page (a model with window
                        # layers), per step that dispatched: the slots its
                        # rows' windows hold live (at most
                        # ``sliding_window`` a row) against the slots of
                        # the pages they hold; and the pages given back
                        # because every later window lies above them.
                        "kv_window_live_token_steps": 0,
                        "kv_window_held_slot_steps": 0,
                        "kv_window_pages_released": 0,
                        # What makes a step late, always on: the turns'
                        # seconds outside sync and idle, and those less
                        # the loop thread's CPU seconds; late turns and
                        # their late seconds (``_BatchService._loop`` has
                        # both rules); steps dispatched with nothing of
                        # this engine's left running on the device
                        # (``_note_dispatch``).
                        "t_host_s": 0.0, "t_host_off_s": 0.0,
                        "late_steps": 0, "t_late_s": 0.0,
                        "device_waited_steps": 0,
                        # Steps dispatched while the step before them
                        # was unread (``_pending``): the host's work
                        # for them ran beside the device's.
                        "lagged_steps": 0,
                        # Decode steps that visited hit experts only
                        # (llama._moe_mlp_hit): experts x layers a step,
                        # and how many of them the step read.
                        "moe_expert_slots": 0, "moe_experts_visited": 0,
                        "moe_routed_rows": 0,
                        # The recurrent-state pool (a model with recurrent
                        # layers), per step that dispatched: slots held
                        # and slots whose row the step advanced; slots
                        # handed out at admission; bytes those rows'
                        # states move if every recurrent layer reads and
                        # writes each once; admissions that skipped the
                        # prefix lookup (a hit would need the state at
                        # the prefix's end).
                        "state_slots_held": 0, "state_slots_live": 0,
                        "state_resets": 0, "state_bytes_moved": 0,
                        "prefix_skipped": 0,
                        # Runs of ``sample`` as dispatched (a fused window
                        # is one a step), and those of them in which some
                        # sampling row set top-k, top-p or min-p: the ones
                        # that sort the vocabulary.
                        "sampler_steps": 0, "sampler_sort_steps": 0,
                        # Rows of the unified steps that dispatched, and
                        # those of them with more than one query token
                        # (a prompt's chunk): how often a recurrent
                        # layer's walk over chunk rows engages
                        # (``llama._kda_packed``), from the pack.
                        "unified_rows": 0, "unified_chunk_rows": 0,
                        # A fact of the step programs, not a count of
                        # steps: the decode walks a step whose kernel
                        # copies its own pages.
                        "decode_walk_kernel_copies":
                            self._decode_walk_kernel_copies(),
                        # Host-to-device puts of the unified and the
                        # decode step paths (``_put``).
                        "uploads": 0,
                        # How long the device had nothing of this engine's
                        # to run before a step's program reached it, from
                        # probes on the loop thread (``probe``,
                        # ``_note_enqueued``): since the first probe that
                        # found the step in flight finished, split by
                        # where the loop was (before the pack, in it, in
                        # the dispatch), and since the last that found it
                        # running; the truth lies between the two.
                        "t_starved_s": 0.0, "t_starved_max_s": 0.0,
                        "t_starved_between_s": 0.0, "t_starved_pack_s": 0.0,
                        "t_starved_dispatch_s": 0.0}
        # What engine.pack and engine.dispatch are made of, by the kind
        # of step (``_Phase.mark``): cumulative seconds.
        for clocks in _Phase.SUB_CLOCKS.values():
            self.metrics.update(dict.fromkeys(clocks, 0.0))
        # The step being run: when each phase last began and which one
        # is running (``_Phase``), and what the step first dispatched
        # (``_note_dispatch``).
        self._marks: List[Optional[float]] = [None] * 5
        self._phase_now: Optional[_Phase] = None
        self._dispatched: Optional[tuple] = None
        # The step in flight as the probes have seen it (``probe``): which
        # one, the last probe that found it running and the first that
        # found it finished; when the last program call of a marked step
        # returned, when the read that left nothing in flight ended, and
        # the stamp before which the device idled for want of requests
        # (the service loop's idle turns), not for the host.
        self._probed: Optional[_Unread] = None
        self._t_running: Optional[float] = None
        self._t_ready: Optional[float] = None
        self._t_enq: Optional[float] = None
        self._t_emptied: Optional[float] = None
        self._starve_floor = 0.0
        self._out_of_work = False
        # One record per run step, ``(t0, t_pack, t_dispatch, t_sync,
        # t_emit, t_end, kind, rows, q_tokens, bucket, step_num)`` on
        # time.monotonic(); appended by the loop thread, read by the
        # ``traces`` op through steps_since().
        self.step_ring: collections.deque = collections.deque(
            maxlen=STEP_RING)
        self._ring_dropped = 0
        self._ring_dropped_t0 = 0.0
        # One record per late turn of the service loop (``note_late``
        # has the fields), same clock, same cursor rule.
        self.late_ring: collections.deque = collections.deque(
            maxlen=LATE_RING)

    def _refuse_unbuilt(self) -> None:
        """What is not built for a model with recurrent layers, or with
        window layers, refused here, in one place, by the mechanism that is
        missing (prefix reuse is bypassed in ``_admit`` and ``_finish``;
        LoRA is refused by ``load_lora``, a model of several groups)."""
        cfg, why = self.cfg, None
        recurrent = self.mcfg.recurrent
        if cfg.speculative != "off":
            why = ("speculative decoding: a rejected draft would have to "
                   "be taken out of the state again" if recurrent else
                   "speculative decoding: the verify step has no window "
                   "table, and a rejected draft's pages could already "
                   "have been given back")
        elif cfg.kv_dtype == "int8":
            why = ("kv_dtype int8: the state pool has no quantised form (a "
                   "delta-rule state is float32, a convolution's tail the "
                   "model's dtype)" if recurrent else
                   "kv_dtype int8: the window class of page has no "
                   "quantised form (no scales pool, no dequantising walk)")
        elif cfg.mode != "unified":
            why = (f"mode {cfg.mode!r}: a PD bundle carries pages, not the "
                   f"recurrent state" if recurrent else
                   f"mode {cfg.mode!r}: a PD bundle carries one class of "
                   f"page, not the window class and its table")
        elif cfg.host_tier_bytes:
            why = ("host_tier_bytes: the host tier keeps prefixes, which a "
                   "recurrent model cannot reuse" if recurrent else
                   "host_tier_bytes: the host tier keeps prefixes, and a "
                   "prefix's window pages were given back")
        elif self.mesh is not None:
            why = ("a device mesh: the state pool has no sharding"
                   if recurrent else
                   "a device mesh: the window class of page has no sharding")
        if why:
            raise ValueError(
                f"model {self.mcfg.name!r} {self.mcfg.unbuilt_for}, which "
                f"do not support {why}")

    def _refuse_looped(self) -> None:
        """What is not built for a model that runs its layers several
        times a token, refused by the mechanism that is missing. Such a
        model is served like any model of one kind of layer (the one walk,
        one class of page whose leading axis is a pass a layer, prefix
        reuse, preemption, the host tier, an int8 cache): only what walks
        layers or lays parameters out beside ``llama.paged_layers`` is
        refused (LoRA by ``load_lora``)."""
        cfg, why = self.cfg, None
        if cfg.speculative != "off":
            why = ("speculative decoding: no test holds a verify step's "
                   "accepted and rejected drafts over a cache of several "
                   "passes to the reference")
        elif cfg.mode != "unified":
            why = (f"mode {cfg.mode!r}: a PD bundle is sent and taken in "
                   f"windows of layers that the stack is walked through "
                   f"once (engine/pd.py)")
        elif self.mesh is not None:
            why = ("a device mesh: the parameters' sharding specs know "
                   "neither the norms after a sub-layer nor the exit gate")
        if why:
            raise ValueError(
                f"model {self.mcfg.name!r} {self.mcfg.looped_for}, which "
                f"does not support {why}")

    def _slot_rows(self, reqs, B: int):
        """``[B]`` state slots of ``reqs`` in row order, on the host; a
        row of padding names a slot out of range, so its write is
        dropped."""
        slots = np.full(B, self.state.slots, np.int32)
        for i, r in enumerate(reqs):
            slots[i] = r.state_slot
        return slots

    def _state_kw(self, reqs, B: int) -> dict:
        """The keyword arguments by which a step program gets what a model
        keeps beside its pages: the state pool and its rows' slots
        (recurrent layers), the window class's pools and its rows' lines
        (window layers)."""
        kw = {}
        if self.state is not None:
            kw.update(state=self.state.arrays,
                      slots=self._put(self._slot_rows(reqs, B)))
        if self.window_allocator is not None:
            kw.update(window=self.cache.window_pages,
                      wtable=self._put(self._window_table(reqs, B)))
        return kw

    def _put_pools(self, kp, vp, ksc, vsc, state=None, window=None) -> None:
        """Take back the pools a step program was given (donated), in
        ``forward_paged``'s order."""
        window = window or (None, None)
        self.cache = PagedKVCache(k_pages=kp, v_pages=vp,
                                  k_scales=ksc, v_scales=vsc,
                                  window_k=window[0], window_v=window[1])
        if state is not None:
            self.state.arrays = state

    # ---- the window class of page ----

    def _window_table(self, reqs, B: int) -> np.ndarray:
        """``[B, P]`` lines of the window class for ``reqs`` in row order:
        a row's pages at their absolute columns, 0 (the null page) wherever
        it holds none: below its live range, where they were given back,
        and beyond it."""
        table = np.zeros((B, self.cfg.max_pages_per_seq), np.int32)
        for i, r in enumerate(reqs):
            table[i, r.window_lo:r.window_lo + len(r.window_pages)] = \
                r.window_pages
        return table

    def _window_pages_for(self, req: "Request", first_query: int,
                          horizon: int) -> bool:
        """Bring ``req``'s pages of the window class to what the step about
        to be dispatched reads and writes: its oldest query stands at
        ``first_query`` and its slots end before ``horizon``. A page is
        given back once every slot of it lies below ``first_query -
        sliding_window + 1``, the oldest slot that query attends: no later
        query reaches lower, and the walk starts above it. The pool holds
        every row's most (``window_pool_pages``), so taking cannot fail.
        Returns whether the row's line changed."""
        ps = self.cfg.page_size
        lo = max(first_query - self.mcfg.sliding_window + 1, 0) // ps
        drop = min(max(lo - req.window_lo, 0), len(req.window_pages))
        if drop:
            self.window_allocator.release(req.window_pages[:drop])
            del req.window_pages[:drop]
            self.metrics["kv_window_pages_released"] += drop
        if not req.window_pages:
            req.window_lo = lo
        else:
            req.window_lo += drop
        need = (pages_for_tokens(horizon, ps) - req.window_lo
                - len(req.window_pages))
        if need > 0:
            pages = self.window_allocator.alloc(need)
            assert pages is not None, "the window class is sized per row"
            req.window_pages.extend(pages)
        return bool(drop) or need > 0

    def _release_window(self, req: "Request") -> None:
        """Every page ``req`` holds of the window class, back (a finished,
        cancelled or preempted request)."""
        if req.window_pages:
            self.window_allocator.release(req.window_pages)
        req.window_pages, req.window_lo = [], 0

    def _release_slot(self, req: "Request") -> None:
        if req.state_slot is not None:
            self.state.release(req.state_slot)
            req.state_slot = None

    def _shard_state(self, mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from rbg_tpu.parallel.sharding import param_specs, shard_pytree
        self.params = shard_pytree(
            self.params, param_specs(self.mcfg, self.params), mesh)
        # GQA pages shard over tp on the KV-head axis; the MLA latent pool
        # has no head axis and replicates (it is ~10x smaller).
        page_spec = NamedSharding(
            mesh, P() if self.mcfg.mla else P(None, None, None, "tp", None))
        self.cache = PagedKVCache(
            k_pages=jax.device_put(self.cache.k_pages, page_spec),
            v_pages=jax.device_put(self.cache.v_pages, page_spec),
            k_scales=(jax.device_put(self.cache.k_scales, page_spec)
                      if self.cache.quantized else None),
            v_scales=(jax.device_put(self.cache.v_scales, page_spec)
                      if self.cache.quantized else None),
        )

    # ---- public API ----

    def _check_prompt(self, prompt: List[int]) -> None:
        """Reject wire-supplied token ids outside the vocab — they would
        crash the single engine loop thread later (penalty mask indexing,
        embedding gather on some backends) instead of failing one request."""
        V = self.mcfg.vocab_size
        if not prompt:
            raise ValueError("empty prompt")
        lo, hi = min(prompt), max(prompt)   # C-speed; this runs per admission
        if lo < 0 or hi >= V:
            bad = lo if lo < 0 else hi
            raise ValueError(
                f"prompt token {bad} outside model vocab [0, {V})")

    def enable_json_grammar(self, tokenizer) -> None:
        """Wire grammar-constrained decoding (json_mode AND regex
        requests) to a tokenizer's token→bytes table. Callers that admit
        constrained requests without this get a per-request admission
        error."""
        from rbg_tpu.engine.grammar import (JsonGrammar, TokenGrammar,
                                            TokenTrie, token_bytes_for)
        self._token_bytes = token_bytes_for(tokenizer)
        self._grammar_eos = tokenizer.eos_id
        # ONE trie per tokenizer, shared by the JSON grammar and every
        # cached regex grammar (it depends only on the vocab).
        self._token_trie = TokenTrie(self._token_bytes)
        self.grammar = TokenGrammar(JsonGrammar(), self._token_bytes,
                                    self._grammar_eos,
                                    trie=self._token_trie)
        self._regex_grammars = collections.OrderedDict()
        self._gtable_dev = collections.OrderedDict()

    _REGEX_GRAMMAR_CACHE = 64

    def _regex_grammar(self, pattern: str):
        return self._compiled_grammar(("re", pattern))

    def _compiled_grammar(self, key, schema: Optional[dict] = None):
        """Per-pattern/per-schema compiled TokenGrammar (NFA + shared
        trie + mask cache), LRU-bounded — repeat constraints (the common
        case: one schema per client) pay compilation once. Raises
        ValueError on bad inputs (an admission error, never a loop
        failure)."""
        from rbg_tpu.engine.grammar import (JsonSchemaGrammar, RegexGrammar,
                                            TokenGrammar)
        tg = self._regex_grammars.get(key)
        if tg is not None:
            self._regex_grammars.move_to_end(key)  # LRU refresh
            return tg
        byte_grammar = (RegexGrammar(key[1]) if key[0] == "re"
                        else JsonSchemaGrammar(schema))
        tg = TokenGrammar(byte_grammar, self._token_bytes,
                          self._grammar_eos, trie=self._token_trie)
        if len(self._regex_grammars) >= self._REGEX_GRAMMAR_CACHE:
            self._regex_grammars.popitem(last=False)
        self._regex_grammars[key] = tg
        return tg

    def _grammar_for(self, sampling: SamplingParams):
        if sampling.json_mode:
            return self.grammar
        if sampling.regex is not None:
            return self._compiled_grammar(("re", sampling.regex))
        if sampling.json_schema is not None:
            if not sampling.json_schema:
                return self.grammar   # {} = "any JSON" (vLLM semantics)
            # Key preserves property ORDER (no sort_keys): compilation is
            # order-sensitive — properties emit in declaration order, so
            # order-differing schemas must not share a grammar.
            key = ("schema", json.dumps(sampling.json_schema))
            return self._compiled_grammar(key, sampling.json_schema)
        return None

    _LORA_ATTN_TARGETS = ("wq", "wk", "wv", "wo")
    _LORA_MLP_TARGETS = ("w_gate", "w_up", "w_down")

    def load_lora(self, name: str, adapter: dict, alpha: float = 16.0):
        """Register a LoRA adapter for per-request batched serving.

        ``adapter``: {target: (A [L, d_in, r], B [L, r, d_out])} for any of
        wq/wk/wv/wo (GQA) or wq/w_dkv/wo (MLA), plus w_gate/w_up/w_down on
        dense-MLP models. All loaded
        adapters are stacked (rank-padded, alpha/r folded into B
        per-target) into one [L, n, ...] array set so a single compiled
        program serves every batch mix — per-row adapter gather inside the
        jitted step (punica/S-LoRA), no recompile per adapter."""
        if not adapter:
            raise ValueError("empty adapter")
        if name in self._lora_slots:
            raise ValueError(f"adapter {name!r} already loaded")
        if self.mcfg.looped_for:
            raise ValueError(
                f"model {self.mcfg.name!r} {self.mcfg.looped_for}: LoRA "
                f"adapters are not supported on it (no test holds an "
                f"adapter that every pass applies to the reference)")
        if len(self.mcfg.layer_groups) > 1:
            # The [L, n, ...] adapter stack rides one scan over one kind
            # of layer; a dense prefix before expert layers is two.
            raise ValueError(
                f"model {self.mcfg.name!r} has "
                f"{len(self.mcfg.layer_groups)} groups of layers: LoRA "
                f"adapters are not supported on it")
        if self.mcfg.mla:
            # MLA: LoRA targets the PLAIN input projections + output;
            # the absorbed per-head up-projections (w_uk/w_uv) are not
            # adapter targets, nor are a low-rank query's two factors.
            allowed = {"w_dkv", "wo"} | (
                set() if self.mcfg.q_lora_rank else {"wq"})
        else:
            allowed = set(self._LORA_ATTN_TARGETS)
        if self.mcfg.num_experts == 0:
            allowed |= set(self._LORA_MLP_TARGETS)
        L = self.mcfg.num_layers
        base = self.params["blocks"]
        for tgt, (A, B) in adapter.items():
            if tgt not in allowed:
                # A typo'd/unsupported target would be a silent no-op —
                # _lora_proj matches exact names on the dense paths only.
                raise ValueError(
                    f"adapter {name!r}: unsupported target {tgt!r} "
                    f"(supported here: {sorted(allowed)})")
            if A.shape[0] != L or B.shape[0] != L or A.shape[2] != B.shape[1]:
                raise ValueError(
                    f"adapter {name!r} target {tgt!r}: bad shapes "
                    f"{A.shape} / {B.shape}")
            bw = base[tgt]
            if A.shape[1] != bw.shape[1] or B.shape[2] != bw.shape[2]:
                raise ValueError(
                    f"adapter {name!r} target {tgt!r}: dims {A.shape[1]}→"
                    f"{B.shape[2]} do not match base weight "
                    f"{bw.shape[1]}→{bw.shape[2]} (wrong base model?)")
        # Commit only after a successful rebuild — a half-registered slot
        # would resolve past the stack and JAX's clamped gather would
        # silently serve a DIFFERENT adapter.
        self._lora_raw.append((adapter, float(alpha)))
        try:
            self._rebuild_lora_stack()
        except Exception:
            self._lora_raw.pop()
            raise
        self._lora_slots[name] = len(self._lora_raw)

    def _rebuild_lora_stack(self):
        L = self.mcfg.num_layers
        n = len(self._lora_raw) + 1                     # + no-adapter slot 0
        targets = sorted({t for ad, _ in self._lora_raw for t in ad})
        rmax = max(A.shape[2] for ad, _ in self._lora_raw
                   for A, _B in ad.values())
        stack = {}
        dt = self.mcfg.jax_dtype
        for tgt in targets:
            d_in = next(A.shape[1] for ad, _ in self._lora_raw
                        if tgt in ad for A, _B in [ad[tgt]])
            d_out = next(B.shape[2] for ad, _ in self._lora_raw
                         if tgt in ad for _A, B in [ad[tgt]])
            As = np.zeros((L, n, d_in, rmax), np.float32)
            Bs = np.zeros((L, n, rmax, d_out), np.float32)
            for i, (ad, alpha) in enumerate(self._lora_raw):
                if tgt in ad:
                    A, B = ad[tgt]
                    r = A.shape[2]
                    As[:, i + 1, :, :r] = np.asarray(A, np.float32)
                    # Per-TARGET scaling: alpha/r with THIS target's rank
                    # (mixed-rank adapters would otherwise mis-scale).
                    Bs[:, i + 1, :r, :] = (np.asarray(B, np.float32)
                                           * (alpha / r))
            stack[tgt] = (jnp.asarray(As, dt), jnp.asarray(Bs, dt))
        self.lora_stack = stack
        # The compiled variants bind the stack shape — new adapters mean
        # new shapes, so old cached programs are stale. DRAIN the fused
        # pipeline first: discarding self._dec would lose the pending
        # window's tokens while seq_len already counts them (corrupting
        # every in-flight request on a runtime load).
        self._deferred_events.extend(self._drain_decode())
        self._fwd_cache.clear()
        self._dec_fn_cache.clear()
        self._spec_fn_cache.clear()

    def _resolve_lora(self, sampling: SamplingParams) -> int:
        if sampling.lora is None:
            return 0
        slot = self._lora_slots.get(sampling.lora)
        if slot is None:
            raise ValueError(
                f"unknown LoRA adapter {sampling.lora!r}; loaded: "
                f"{sorted(self._lora_slots) or 'none'}")
        return slot

    def _grammar_check(self, sampling: SamplingParams) -> None:
        constrained = (sampling.json_mode or sampling.regex is not None
                       or sampling.json_schema is not None)
        if constrained and self.grammar is None:
            raise ValueError(
                "json_mode/regex/json_schema require a grammar table — the "
                "server wires it from the tokenizer (enable_json_grammar)")
        if constrained:
            # Bad pattern/schema → admission error, never a loop failure.
            self._grammar_for(sampling)

    def _gmask(self, grammar, state) -> np.ndarray:
        """Grammar mask padded to MODEL vocab: ids beyond the tokenizer's
        vocab can never be legal constrained output."""
        V = self.mcfg.vocab_size
        m = grammar.mask(state)
        if len(m) == V:
            return m
        out = np.zeros(V, bool)
        out[:min(len(m), V)] = m[:V]
        return out

    # ---- device-resident grammar tables ----

    # Multi-grammar combination LRU: shallow on purpose — each entry
    # duplicates its grammars' device blocks, so hold only the current
    # composition plus one predecessor (ping-pong recompositions).
    _GTABLE_DEV_CACHE = 2

    def _grammar_table(self, tg):
        """The host-side GrammarTable for a TokenGrammar, or None when the
        grammar must stay on the host-synced path (tables disabled,
        pushdown JSON grammar, or state budget exceeded). The compile —
        and a budget failure — is cached on the grammar object, which is
        itself LRU-cached per pattern/schema, so each grammar pays BFS
        once per engine lifetime."""
        if self.cfg.grammar_table == "off":
            return None
        from rbg_tpu.engine.grammar import NfaGrammar, compile_token_table
        if not isinstance(tg.grammar, NfaGrammar):
            return None     # JsonGrammar: pushdown, no finite token table
        budget = self.cfg.grammar_state_budget
        cached = getattr(tg, "_table_cache", None)
        if cached is not None and cached[0] == budget:
            return cached[1]
        table = compile_token_table(tg, budget, self.mcfg.vocab_size)
        tg._table_cache = (budget, table)
        return table

    def _row_fusable(self, r: Request) -> bool:
        """True when the row can decode inside the fused scan: no grammar,
        or a grammar with a compiled device table."""
        return r.grammar is None or self._grammar_table(r.grammar) is not None

    def _grammar_dev_block(self, tg):
        """A grammar's table on device, offset-free, padded to the next
        POWER-OF-TWO state count (rows past the table are -1/False,
        unreachable) — ONE upload per (grammar, vocab), cached on the
        grammar object. Pow-2 buckets keep [S, V] shapes stable across
        similarly-sized grammars (compiled decode programs reuse within a
        bucket, ≤ log2(budget) shapes total) WITHOUT paying a full
        budget-sized block for a 3-state regex: blocks live as long as
        their grammar sits in the pattern/schema LRU, so the aggregate
        device retention is Σ pow2(S_g) × V × 5 bytes over cached
        grammars, not 64 × budget × V × 5."""
        budget = self.cfg.grammar_state_budget
        cached = getattr(tg, "_dev_block", None)
        if cached is not None and cached[0] == budget:
            return cached[1], cached[2]
        t = self._grammar_table(tg)
        V = self.mcfg.vocab_size
        S = 1
        while S < t.num_states:
            S *= 2
        nxt = np.full((S, V), -1, np.int32)
        leg = np.zeros((S, V), bool)
        nxt[:t.num_states] = t.next_state
        leg[:t.num_states] = t.legal
        nxt_dev, leg_dev = jnp.asarray(nxt), jnp.asarray(leg)
        tg._dev_block = (budget, nxt_dev, leg_dev)
        return nxt_dev, leg_dev

    def _device_grammar_tables(self, grammars):
        """(next_state_dev [S, V] int32, legal_dev [S, V] bool, offsets)
        for a batch's grammars: per-grammar device blocks concatenated
        with per-grammar state-id offsets so one array pair serves every
        row (a row's device gstate = offset + its table's local state
        id). The common single-grammar batch reuses the grammar's own
        block directly — no copy; multi-grammar combinations concatenate
        ON DEVICE (offsets applied with a where, no host re-upload) and
        are LRU-cached only shallowly: combinations are transient batch
        compositions, and each held entry duplicates its blocks' memory.
        Entries hold strong grammar refs so the id()-keys stay valid
        while cached."""
        uniq, seen = [], set()
        for g in grammars:
            if id(g) not in seen:
                seen.add(id(g))
                uniq.append(g)
        if len(uniq) == 1:
            nxt, leg = self._grammar_dev_block(uniq[0])
            return nxt, leg, {id(uniq[0]): 0}
        key = tuple(sorted(id(g) for g in uniq))
        hit = self._gtable_dev.get(key)
        if hit is not None:
            self._gtable_dev.move_to_end(key)
            return hit[0], hit[1], hit[2]
        offsets: Dict[int, int] = {}
        nexts, legals = [], []
        off = 0
        for g in uniq:
            nxt, leg = self._grammar_dev_block(g)
            offsets[id(g)] = off
            nexts.append(jnp.where(nxt >= 0, nxt + off, -1))
            legals.append(leg)
            off += nxt.shape[0]
        entry = (jnp.concatenate(nexts), jnp.concatenate(legals),
                 offsets, list(uniq))
        self._gtable_dev[key] = entry
        if len(self._gtable_dev) > self._GTABLE_DEV_CACHE:
            self._gtable_dev.popitem(last=False)
        return entry[0], entry[1], entry[2]

    def add_request(self, prompt: List[int],
                    sampling: Optional[SamplingParams] = None) -> int:
        sampling = sampling or SamplingParams()
        self._check_prompt(prompt)
        self._grammar_check(sampling)
        if len(prompt) + sampling.max_new_tokens > self.cfg.max_seq_len:
            raise ValueError(
                f"prompt+max_new_tokens {len(prompt)}+{sampling.max_new_tokens} "
                f"exceeds max_seq_len {self.cfg.max_seq_len}")
        req = Request(prompt, sampling)
        req.lora_idx = self._resolve_lora(sampling)
        req.enqueue_step = self.metrics["steps"]
        g = self._grammar_for(sampling)
        if g is not None:
            req.grammar = g
            req.gstate = g.initial()
        self.requests[req.id] = req
        self.waiting.append(req)
        return req.id

    def add_request_with_prefix(self, prompt: List[int],
                                sampling: Optional[SamplingParams],
                                prefix_len: int,
                                k_data, v_data) -> Optional[int]:
        """Admit a request whose first ``prefix_len`` tokens' KV arrives
        precomputed (fetched from the shared KV pool — the Mooncake-reuse
        path, keps/74): the pages are written into the local pool and
        prefill resumes at ``prefix_len``. ``prefix_len`` must be
        page-aligned and < len(prompt) (the last token always prefills for
        logits). Returns None when no pages are free (caller falls back to
        a cold prefill through the normal admission queue)."""
        if self.mcfg.unbuilt_for:
            raise ValueError(
                f"model {self.mcfg.name!r} {self.mcfg.unbuilt_for}: a "
                f"prefix's pages without the state at its end "
                f"(the window class's pages below it) cannot be resumed")
        sampling = sampling or SamplingParams()
        self._check_prompt(prompt)
        self._grammar_check(sampling)
        lora_idx = self._resolve_lora(sampling)  # before alloc: no page leak
        ps = self.cfg.page_size
        if prefix_len % ps or not 0 < prefix_len < len(prompt):
            raise ValueError(f"prefix_len {prefix_len} must be page-aligned "
                             f"and in (0, {len(prompt)})")
        if len(prompt) + sampling.max_new_tokens > self.cfg.max_seq_len:
            raise ValueError("prompt+max_new_tokens exceeds max_seq_len")
        need = pages_for_tokens(len(prompt) + 1, ps)
        pages = self._alloc(need)
        if pages is None:
            return None
        n_prefix = prefix_len // ps
        ids = jnp.asarray(pages[:n_prefix], jnp.int32)
        try:
            self.cache = PagedKVCache(
                k_pages=self.cache.k_pages.at[:, ids].set(
                    jnp.asarray(k_data, self.cache.k_pages.dtype)),
                v_pages=self.cache.v_pages.at[:, ids].set(
                    jnp.asarray(v_data, self.cache.v_pages.dtype)),
                k_scales=self.cache.k_scales, v_scales=self.cache.v_scales,
            )
        except (ValueError, TypeError) as e:
            # Foreign pool data (e.g. a replica with different model
            # geometry sharing the pool): the freshly allocated pages must
            # go back or every bad hit leaks them until admission wedges.
            self.allocator.release(pages)
            raise ValueError(f"prefix KV rejected: {e}") from e
        req = Request(prompt, sampling)
        req.lora_idx = lora_idx
        g = self._grammar_for(sampling)
        if g is not None:
            req.grammar = g
            req.gstate = g.initial()
        req.pages = pages
        req.prefill_pos = prefix_len
        req.seq_len = prefix_len
        req.state = "prefill"
        self.requests[req.id] = req
        self.running.append(req)
        self.metrics["pool_hit_tokens"] = (
            self.metrics.get("pool_hit_tokens", 0) + prefix_len)
        return req.id

    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    def step(self) -> List[StepEvent]:
        """One scheduler iteration: admit, then either the ragged UNIFIED
        dispatch (prefill chunks + decode steps of the whole batch in one
        program — continuous batching, no phase split; MLA rides it via
        the ragged latent path since round 16) or the legacy split
        prefill→decode paths (pure-decode batches always take the fused
        multi-step scan; cfg.ragged='off', speculative, and LoRA-mixed
        batches keep the split paths throughout)."""
        events: List[StepEvent] = []
        if self._deferred_events:
            events.extend(self._deferred_events)
            self._deferred_events = []
        m = self.metrics
        m["steps"] += 1
        self._marks = [None] * 5
        self._dispatched = None
        t0 = time.monotonic()
        with trace.annotation(obs_names.SPAN_ENGINE_STEP,
                              step_num=m["steps_run"]) as ann:
            with _Phase(self, _ADMIT):
                self._admit()
            if self._unified_eligible():
                m["unified_steps"] += 1
                events.extend(self._unified_step())
            else:
                events.extend(self._prefill_step())
                events.extend(self._decode_step())
            self.join_hint = False
            t_end = time.monotonic()
            m["t_step_s"] += t_end - t0
            if self._dispatched is not None:
                self._record_step(t0, t_end, ann)
        return events

    def _note_dispatch(self, kind: str, rows: int, q_tokens: int,
                       row_bucket: int, token_bucket: int) -> None:
        """What this step ran, noted where it dispatches its first device
        program; that one names the step's kind (a step of the split path
        that prefills and decodes is a ``prefill`` step)."""
        if self._dispatched is None:
            self._dispatched = (kind, rows, q_tokens,
                                (row_bucket, token_bucket))
            # Is a program this engine dispatched earlier still running?
            # A step is left unread between steps, whichever its kind,
            # unless its rows needed the host: with none pending, or its
            # tokens ready, the device has finished all it was given and
            # idles until this dispatch lands. No device read, no sync.
            unread = self._pending
            if unread is None or unread.toks.is_ready():
                self.metrics["device_waited_steps"] += 1
            if unread is not None:
                self.metrics["lagged_steps"] += 1
            if self.state is not None:
                # Held as the step starts: a row that finishes in this
                # step frees its slot before the step is recorded.
                self._slots_at_dispatch = self.state.held

    def probe(self, now: float, turn_began: Optional[float] = None) -> None:
        """Ask whether the step in flight has finished, at a point the
        loop thread passes anyway (``now``, a stamp it has just taken):
        the end of ``engine.admit``, ``engine.sync`` and ``engine.emit``,
        the entry of ``engine.dispatch``, every mark of a sub-phase, and
        the service loop's ends of ``service.intake`` and
        ``service.deliver``. Keeps the last stamp at which it was running
        and the first at which it was not, and asks no more after that:
        ``is_ready()`` reads no device memory and waits for nothing.

        The probe that ends ``service.intake`` also gives the stamp at
        which its turn began (``turn_began``): a turn that leaves the
        engine without work is followed by idle waits, and whatever the
        device idles until the turn in which work arrives again is no
        host's fault (``_starve_floor``)."""
        if turn_began is not None:
            if not (self.waiting or self.running):
                self._out_of_work = True
            elif self._out_of_work:
                self._out_of_work = False
                self._starve_floor = turn_began
        unread = self._pending
        if unread is not self._probed:
            self._probed = unread
            self._t_running = self._t_ready = None
        if unread is None or self._t_ready is not None:
            return
        if unread.toks.is_ready():
            self._t_ready = now
        else:
            self._t_running = now

    def _mark(self, sub: int) -> None:
        """Close the step's open sub-phase and open ``sub`` (no-op in a
        phase that has none: the split-path steps), and probe. The mark
        that closes ``engine.dispatch.call`` is where the step's program
        has reached the device."""
        phase = self._phase_now
        if phase is None or phase.subs is None:
            return
        now = phase.mark(sub)
        self.probe(now)
        if sub == _BOOK:
            self._note_enqueued(now)

    def _note_enqueued(self, now: float) -> None:
        """Account the time the device starved for the step whose program
        call has just returned (``now``; the probe of this mark has run).
        The lower bound runs from the first probe that found the step
        before it finished: the device went idle somewhere before that
        probe. The upper bound runs from the last probe that found it
        running, or from the call before this one where none did. With
        nothing in flight (rows that need the host, the step after a
        forced read, and a unified step that sampled no row, whose
        program may in truth still run: the rule of
        ``device_waited_steps``) the device has idled since the read that
        emptied ``_pending`` ended, and both bounds take that stamp. No
        bound reaches back before the call before this one, nor before
        ``_starve_floor``. The lower bound is split at the stamps at which
        this step's pack and dispatch began."""
        m = self.metrics
        floor = max(self._starve_floor, self._t_enq or 0.0)
        self._t_enq = now
        if self._pending is None:
            lo = hi = self._t_emptied
        else:
            lo = self._t_ready
            hi = self._t_running if self._t_running is not None else floor
        if hi is None:
            return
        m["t_starved_max_s"] += now - max(hi, floor)
        if lo is None:
            return
        lo = max(lo, floor)
        pack = min(max(self._marks[_PACK], lo), now)
        dispatch = min(max(self._marks[_DISPATCH], pack), now)
        m["t_starved_s"] += now - lo
        m["t_starved_between_s"] += pack - lo
        m["t_starved_pack_s"] += dispatch - pack
        m["t_starved_dispatch_s"] += now - dispatch

    def _put(self, a):
        """A host array put on the device: every put of the unified and
        the decode step paths is made here, and counted."""
        self.metrics["uploads"] += 1
        return jnp.asarray(a)

    def _record_step(self, t0: float, t_end: float, ann) -> None:
        """Account one step that dispatched: its wall time by kind, the
        cache it held against the cache it used, and its record in the
        ring (stamps of phases that did not run, or ran only before an
        earlier phase, read as the stamp that follows them)."""
        m = self.metrics
        kind, rows, q_tokens, bucket = self._dispatched
        ann.set_metadata(kind=kind, rows=rows, q_tokens=q_tokens,
                         row_bucket=bucket[0], token_bucket=bucket[1])
        step_num = m["steps_run"]
        m["steps_run"] = step_num + 1
        if kind == "unified":
            m["t_unified_s"] += t_end - t0
            m["unified_steps_run"] += 1
        elif kind == "decode":
            m["t_decode_s"] += t_end - t0
            m["decode_steps_run"] += 1
        live = held = 0
        for r in self.running:
            live += r.seq_len
            held += len(r.pages)
        m["kv_live_token_steps"] += live
        m["kv_held_slot_steps"] += held * self.cfg.page_size
        if self.window_allocator is not None:
            W = self.mcfg.sliding_window
            m["kv_window_live_token_steps"] += sum(
                min(r.seq_len, W) for r in self.running)
            m["kv_window_held_slot_steps"] += self.cfg.page_size * sum(
                len(r.window_pages) for r in self.running)
        if self.state is not None:
            # A fused window advances each row once a step, a unified step
            # each of its rows once: ``q_tokens`` of the former, ``rows`` of
            # the latter, are the row-steps whose state moved.
            moved = q_tokens if kind == "decode" else rows
            m["state_slots_held"] += self._slots_at_dispatch
            m["state_slots_live"] += rows
            m["state_bytes_moved"] += moved * self.state.row_bytes
        marks = self._marks
        prev = marks[_PACK]
        for i in (_DISPATCH, _SYNC, _EMIT):
            if marks[i] is not None:
                if marks[i] < prev:
                    marks[i] = None
                else:
                    prev = marks[i]
        nxt = t_end
        for i in (_EMIT, _SYNC, _DISPATCH, _PACK):
            if marks[i] is None:
                marks[i] = nxt
            nxt = marks[i]
        ring = self.step_ring
        if len(ring) == ring.maxlen:
            self._ring_dropped += 1
            self._ring_dropped_t0 = ring[0][0]
        ring.append((t0, marks[_PACK], marks[_DISPATCH], marks[_SYNC],
                     marks[_EMIT], t_end, kind, rows, q_tokens, bucket,
                     step_num))

    def note_late(self, rec: tuple, late_s: float) -> None:
        """Account one late turn of the service loop (late by ``late_s``
        seconds: its time outside ``engine.sync`` and ``service.idle``, the
        watchdog's lateness in it or a stalled sync wait) and keep its record,
        made by ``_BatchService._note_late``: ``(t0, t_end, step_num,
        kind, phase, phase_wall_s, cpu_s, nvcsw, nivcsw, majflt,
        minflt, gc_collections, gc_pause_s, compiles, relay_frames,
        watchdog_late_s, stack)`` on time.monotonic(). Rare by definition,
        so each is also a WARNING in the log and a zero-length
        ``engine.late_step`` annotation: a device trace that catches one
        shows it on its own clock."""
        self.metrics["late_steps"] += 1
        self.metrics["t_late_s"] += late_s
        self.late_ring.append(rec)
        with trace.annotation(obs_names.SPAN_ENGINE_LATE_STEP, phase=rec[4],
                              wall_ms=round(late_s * 1e3, 3)):
            pass
        log.warning("late step: %s", json.dumps(rec))

    def steps_since(self, since: float = 0.0) -> dict:
        """The ``traces`` op's view of the rings: the records that began
        after ``since`` (time.monotonic() seconds), oldest first, and
        ``steps_dropped``: 0 when the ring still held every such record,
        else how many it has overwritten so far (an upper bound on those
        missed; ``step_num``, the last field, counts them exactly).
        ``late_steps`` holds the late turns' records by the same cursor."""
        # Called from a connection thread while the loop thread appends:
        # each copy is one C call under the interpreter lock.
        records = list(self.step_ring)
        late = list(self.late_ring)
        dropped = self._ring_dropped if self._ring_dropped_t0 > since else 0
        return {"steps": [r for r in records if r[0] > since],
                "steps_dropped": dropped,
                "late_steps": [r for r in late if r[0] > since]}

    def generate(self, prompts: List[List[int]],
                 sampling: Optional[SamplingParams] = None) -> List[List[int]]:
        ids = [self.add_request(p, sampling) for p in prompts]
        outputs = {i: [] for i in ids}
        while self.has_work():
            for ev in self.step():
                if ev.request_id in outputs:
                    outputs[ev.request_id].append(ev.token)
        return [outputs[i] for i in ids]

    # ---- admission ----

    def _admit(self):
        blocked = False
        while self.waiting:
            if len(self.running) >= self.cfg.max_batch:
                blocked = True   # a batch slot is the unavailable resource
                break
            req = self.waiting[0]
            matched, shared_pages = 0, []
            radix_matched = host_matched = 0
            if self.mcfg.unbuilt_for:
                # No prefix reuse for a model with recurrent layers: a hit
                # would have to restore the state at the prefix's end, and
                # nothing keeps it (``_finish`` inserts nothing either).
                # Nor for one with window layers: a cached prefix's pages
                # of the window class were given back as it was served.
                if self.radix is not None:
                    self.metrics["prefix_skipped"] += 1
            elif (self.radix is not None and req.state == "waiting"
                    and req.lora_idx == 0):
                # Keep at least the prompt's last token for prefill (logits).
                # Adapter requests skip the prefix cache: their KV differs
                # from base-model KV for the same tokens.
                matched, shared_pages = self.radix.match(req.prompt[:-1])
                radix_matched = matched
                if self.host_tier is not None:
                    matched, shared_pages = self._promote_host(
                        req, matched, shared_pages)
                    host_matched = matched - radix_matched
            # Admit with pages for the PROMPT + first token only — decode
            # grows page-by-page (memory oversubscription; preemption
            # reclaims on exhaustion). Reserving max_len up front would
            # forfeit continuous batching's throughput.
            need = (pages_for_tokens(len(req.prompt) + 1, self.cfg.page_size)
                    - len(shared_pages))
            pages = self._alloc(need)
            if pages is None:
                if shared_pages:
                    self.allocator.release(shared_pages)
                blocked = True
                break  # no capacity — stay queued
            self.waiting.pop(0)
            # Join accounting for the continuous-admission invariant: a
            # request admitted at the first step after enqueue waited 0.
            wait = max(0, self.metrics["steps"] - req.enqueue_step - 1)
            excess = max(0, wait - req.blocked_steps)
            self.metrics["joins"] += 1
            self.metrics["join_wait_steps_max"] = max(
                self.metrics["join_wait_steps_max"], wait)
            self.metrics["join_excess_steps_max"] = max(
                self.metrics["join_excess_steps_max"], excess)
            now = time.perf_counter()
            self.last_join_waits.append((now - req.t_enqueue, req.id, now))
            # Bounded: only the service loop drains this (PD workers and
            # generate() step the engine directly) — cap so an undrained
            # engine never leaks; the loop drains every step, so real
            # serving never comes near the cap.
            del self.last_join_waits[:-1024]
            req.blocked_steps = 0
            req.pages = shared_pages + pages
            if self.state is not None:
                # Its rows start at position 0, which is what zeroes it.
                req.state_slot = self.state.take()
                self.metrics["state_resets"] += 1
            req.shared_tokens = matched
            req.prefill_pos = matched
            req.seq_len = matched
            req.state = "prefill"
            self.running.append(req)
            # Hit accounting happens HERE, on admission success, and the
            # two tiers' counters sum to the request's total hit. A
            # promotion whose request then fails its remaining alloc
            # must count NOTHING: the promoted pages entered the radix,
            # so the retry's radix.match re-finds them — charging the
            # promotion too would double-count the same tokens. Same
            # rule for the registry tier counters: a blocked request
            # re-attempts every step and must not inflate the panel.
            self.metrics["radix_hit_tokens"] += radix_matched
            self.metrics["host_hit_tokens"] += host_matched
            if self.host_tier is not None and req.lora_idx == 0:
                if host_matched:
                    REGISTRY.inc(obs_names.KVC_TIER_HITS_TOTAL,
                                 tier="host")
                elif radix_matched:
                    REGISTRY.inc(obs_names.KVC_TIER_HITS_TOTAL,
                                 tier="device")
                else:
                    REGISTRY.inc(obs_names.KVC_TIER_MISSES_TOTAL)
        if blocked:
            # Every still-queued request sat this step out for a capacity
            # reason — the excess-wait metric must not count it.
            for r in self.waiting:
                r.blocked_steps += 1

    # hot_path
    def _promote_host(self, req: "Request", matched: int,
                      shared_pages: List[int]):
        """Extend a radix hit from the host spill tier: promoted pages
        move onto freshly allocated device pages and enter the radix
        cache, so this request — and every later one — device-hits
        them. Tier hit/miss accounting lives here (the one admission
        site where both tiers are consulted)."""
        h_tokens, h_pages, new_cache = self.host_tier.promote_to_device(
            req.prompt[:-1], matched, self._alloc, self.cache,
            release_fn=self.allocator.release)
        if h_tokens:
            self.cache = new_cache
            # The radix insert takes the cache's own reference on the
            # promoted pages (share()) — the request's ref stays
            # separate, exactly like a radix hit. Token accounting is
            # the CALLER's, on admission success only.
            self.radix.insert(req.prompt[:matched + h_tokens],
                              shared_pages + h_pages)
            shared_pages = shared_pages + h_pages
            matched += h_tokens
        self._publish_tier_gauges()
        return matched, shared_pages

    def _alloc(self, n: int) -> Optional[List[int]]:
        if n <= 0:
            return []
        pages = self.allocator.alloc(n)
        if pages is None and self.radix is not None:
            self.radix.evict(
                n - self.allocator.free_pages,
                on_evict=(self._spill_evicted if self.host_tier is not None
                          else None))
            pages = self.allocator.alloc(n)
            if self.host_tier is not None:
                self._publish_tier_gauges()
        return pages

    def _spill_evicted(self, prefix_tokens: List[int],
                       page_ids: List[int]) -> None:
        """Radix eviction hook: copy the evicted leaf's device pages into
        the host tier BEFORE their allocator release (device contents are
        still valid here; ids may recycle right after).

        Pages a RUNNING request still pins (refcount > 1: the cache's
        ref plus the request's) are NOT spilled: they stay device-
        resident and re-enter the radix when that request finishes —
        spilling a copy would leave the same content resident in both
        tiers, breaking the exactly-one-tier contract. A request's
        match/share always takes a PREFIX of a node's pages, so the
        pinned region is a prefix of ``page_ids`` and the free tail is
        contiguous."""
        k = 0
        while k < len(page_ids) \
                and self.allocator.refcount(page_ids[k]) > 1:
            k += 1
        if k == len(page_ids):
            return
        self.host_tier.spill_from_device(prefix_tokens, page_ids[k:],
                                         self.cache)

    def _publish_tier_gauges(self) -> None:
        if self.radix is None or self.host_tier is None:
            return
        pages = self.radix.cached_pages
        per_page = ((self.cache.k_pages.nbytes + self.cache.v_pages.nbytes)
                    / max(1, self.cache.num_pages))
        REGISTRY.set_gauge(obs_names.KVC_TIER_PAGES, float(pages),
                           tier="device")
        REGISTRY.set_gauge(obs_names.KVC_TIER_BYTES,
                           float(pages * per_page), tier="device")

    def prefix_peek(self, prompt: List[int]) -> int:
        """Advisory total prefix-hit depth (device radix + host tier)
        this prompt would get at admission. Read cross-thread by the
        admission TTFT predictor — pure dict walks, best-effort: a stale
        or zero answer only skews one prediction, never correctness."""
        if self.radix is None or len(prompt) < 2:
            return 0
        try:
            m = self.radix.peek(prompt[:-1])
            if self.host_tier is not None:
                m += self.host_tier.peek(prompt[:-1], m)
            return m
        except Exception:  # noqa: BLE001 — racy read, degrade to miss
            return 0

    # ---- ragged unified prefill/decode step ----

    def _unified_eligible(self) -> bool:
        """True when this step should run ONE ragged dispatch serving the
        whole batch (prefill chunks + decode steps together). Pure-decode
        batches return False — the fused multi-step scan (zero host syncs
        per window) beats a host-synced ragged step there."""
        if self.cfg.ragged == "off" or self.cfg.speculative != "off":
            return False
        if not any(r.state == "prefill" for r in self.running):
            return False
        if any(r.lora_idx for r in self.running):
            # lora_delta gathers adapters per batch ROW; the packed batch
            # axis is 1, so adapter-mixed batches keep the split paths.
            return False
        return True

    # bucket_fn
    def _token_bucket(self, n: int) -> int:
        """Packed-token bucket: next power of two (≥ 8), so compile
        variety stays at log2(max_batch × prefill_chunk) programs."""
        b = 8
        while b < n:
            b *= 2
        return b

    def _get_ragged_fn(self, R: int, T: int):
        """One jitted ragged forward per (row bucket, packed-token
        bucket). The cache key carries the kernel's grid revision so a
        cache warmed for one grid (the PR-7 token grid, the block-ragged
        tiles over the whole table, the tiles over live pages) can never
        alias programs compiled for another."""
        from rbg_tpu.ops.pallas.ragged_attention_kernel import \
            RAGGED_GRID_REV
        fn = self._ragged_fn_cache.get((R, T, RAGGED_GRID_REV))
        if fn is None:
            import functools
            base = functools.partial(forward_ragged, cfg=self.mcfg,
                                     use_pallas=self.cfg.use_pallas,
                                     max_q_len=self.cfg.prefill_chunk)

            def wrapped(params, tokens, positions, token_mask, row_ids,
                        kv_lens, page_table, k_pages, v_pages, k_scales,
                        v_scales, take, prev, rows, state=None, slots=None,
                        window=None, wtable=None):
                # A decode row's token may still be on the device, in the
                # unread step before this one (``_unread_tokens``).
                tokens = _place_tokens(tokens, take, prev)
                logits, *pools = base(
                    params, tokens=tokens, positions=positions,
                    token_mask=token_mask, row_ids=row_ids,
                    kv_lens=kv_lens, page_table=page_table,
                    k_pages=k_pages, v_pages=v_pages,
                    k_scales=k_scales, v_scales=v_scales,
                    state=state, state_slots=slots,
                    window_pages=window, window_table=wtable,
                    head_rows=rows)
                # The sampling rows' logits alone, [rows, V]: with two
                # steps in flight a whole packed line's would be held twice.
                return (logits[0], *pools)

            wrapped.__name__ = PROGRAM_RAGGED_FWD   # jitwatch catalog name
            donate = (7, 8, 9, 10) if self.cache.quantized else (7, 8)
            fn = jax.jit(wrapped, donate_argnums=donate,
                         donate_argnames=self._donate_state)
            self._ragged_fn_cache[(R, T, RAGGED_GRID_REV)] = fn
        return fn

    def warm_ragged(self) -> int:
        """Pre-compile every ragged unified program shape (row bucket ×
        packed-token bucket) with an all-pad dispatch: token_mask is all
        False so every KV write drops and the pool round-trips through
        the donated buffers unchanged. A shape first hit mid-serving
        stalls every in-flight request for the compile — same rationale
        as _BatchService.warmup, which calls this. Must run while the
        engine is IDLE (no in-flight requests): the warm dispatches
        mutate the cache from the calling thread, outside the loop
        thread's single-writer discipline. Returns the number of
        programs compiled."""
        if (self.cfg.ragged == "off" or self.cfg.speculative != "off"
                or self.cfg.mode == "decode"):
            return 0
        P = self.cfg.max_pages_per_seq
        n = 0
        buckets = sorted({self._bucket(b)
                          for b in range(1, self.cfg.max_batch + 1)})
        for i, R in enumerate(buckets):
            # A step of this row bucket holds more rows than the bucket
            # below, and a token a row at least: no smaller pack exists.
            t = self._token_bucket(buckets[i - 1] + 1 if i else 1)
            t_max = self._token_bucket(R * self.cfg.prefill_chunk)
            while True:
                fn = self._get_ragged_fn(R, t)
                _, *pools = fn(
                    self.params,
                    jnp.zeros((1, t), jnp.int32),
                    jnp.full((1, t), -1, jnp.int32),       # all pad
                    jnp.zeros((1, t), bool),
                    jnp.zeros((t,), jnp.int32),
                    jnp.zeros((R,), jnp.int32),
                    jnp.zeros((R, P), jnp.int32),
                    self.cache.k_pages, self.cache.v_pages,
                    self.cache.k_scales, self.cache.v_scales,
                    jnp.full((1, t), -1, jnp.int32), self._no_tokens,
                    self._no_tokens, **self._state_kw([], R))
                self._put_pools(*pools)
                n += 1
                if t >= t_max:
                    break
                t *= 2
        return n

    def warm_join_windows(self) -> int:
        """Pre-compile the K=1 'early-exit' variant of every PLAIN fused
        decode program compiled so far (same bucket and sampling flags,
        window length 1). _decode_window shortens to 1 exactly on the
        join-latency path, so a mid-serving compile there would stall
        every in-flight request — the hazard warm_ragged documents —
        right when this feature is trying to cut latency. Exotic
        variants (penalties/logprobs/LoRA/grammar) stay lazy, as they do
        for every other program. Same idle-engine requirement as
        warm_ragged (the dispatches mutate the cache from the calling
        thread). Returns the number of programs compiled."""
        if self.cfg.multi_step == 1 or self.cfg.ragged == "off":
            return 0   # the window never shortens (see _decode_window)
        P = self.cfg.max_pages_per_seq
        n = 0
        for (B, pen, lp, la, gr, K) in list(self._dec_fn_cache):
            if K == 1 or pen or lp or la or gr:
                continue
            if (B, pen, lp, la, gr, 1) in self._dec_fn_cache:
                continue
            temps, ks, tps, mps, seeds, rids, _, _, _ = \
                self._sampling_rows([], B)
            fn = self._get_decode_fn(B, pen, lp, la, gr, K=1)
            # mask all-False: write_ok is False everywhere, so no KV slot
            # is written and pos/kvl never advance — the donated pool
            # buffers round-trip unchanged (tok/pos/kvl/limit are
            # separate arrays: pos and kvl are donated, tok is not).
            out = fn(
                self.params, jnp.zeros(B, jnp.int32),
                jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32),
                jnp.zeros((B, P), jnp.int32), jnp.zeros((B, 1), bool),
                jnp.zeros(B, jnp.int32),
                self.cache.k_pages, self.cache.v_pages,
                self.cache.k_scales, self.cache.v_scales,
                row_keys(seeds, self._sample_base, rids),
                jnp.asarray(temps), jnp.asarray(ks), jnp.asarray(tps),
                jnp.asarray(mps), **self._state_kw([], B))
            self._put_pools(*out[6:10], *out[12:])
            n += 1
        return n

    def warm_decode(self) -> int:
        """Pre-compile the PLAIN fused decode program (no penalties /
        logprobs / LoRA / grammar) for every decode bucket at the full
        multi_step window. The jitwatch sentry
        surfaced this gap: warm_ragged covers the unified forward and
        warm_join_windows the K=1 variants, but the full-window decode
        program itself compiled lazily on the first pure-decode batch —
        stalling every in-flight request mid-serving. Exotic variants
        stay lazy (same policy as warm_join_windows). Same idle-engine
        requirement as warm_ragged (the warm dispatches mutate the cache
        from the calling thread). Returns the number of programs
        compiled."""
        if self.cfg.mode == "prefill" or self.cfg.speculative != "off":
            return 0   # no fused decode path to warm
        P = self.cfg.max_pages_per_seq
        K = self.cfg.multi_step
        n = 0
        buckets = sorted({self._bucket(b)
                          for b in range(1, self.cfg.max_batch + 1)})
        for B in buckets:
            if (B, False, False, False, False, K) in self._dec_fn_cache:
                continue
            temps, ks, tps, mps, seeds, rids, _, _, _ = \
                self._sampling_rows([], B)
            fn = self._get_decode_fn(B, False, False, False, False, K=K)
            # mask all-False, [B, 1] as _build_decode_state makes it (any
            # other shape is another program, and this one would compile
            # mid-serving): no KV slot is written and pos/kvl never
            # advance — the donated pool buffers round-trip unchanged
            # (see warm_join_windows).
            out = fn(
                self.params, jnp.zeros(B, jnp.int32),
                jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32),
                jnp.zeros((B, P), jnp.int32), jnp.zeros((B, 1), bool),
                jnp.zeros(B, jnp.int32),
                self.cache.k_pages, self.cache.v_pages,
                self.cache.k_scales, self.cache.v_scales,
                row_keys(seeds, self._sample_base, rids),
                jnp.asarray(temps), jnp.asarray(ks), jnp.asarray(tps),
                jnp.asarray(mps), **self._state_kw([], B))
            self._put_pools(*out[6:10], *out[12:])
            n += 1
        return n

    def warm_samplers(self) -> int:
        """Pre-compile the host-path sampler (prefill finish + unified
        emission) for every sample-row bucket. One jitted
        program per (pen, lp) — but XLA compiles per SHAPE under
        that wrapper, so each bucket is its own compile; a first-hit
        mid-serving stalls the step exactly like an unwarmed forward.
        Penalties/logprobs variants stay lazy (warm_join_windows
        rationale). Returns the number of programs compiled."""
        if self.cfg.mode == "decode":
            return 0   # decode-only workers sample inside the fused scan
        V = self.mcfg.vocab_size
        n = 0
        buckets = sorted({self._bucket(b)
                          for b in range(1, self.cfg.max_batch + 1)})
        for B in buckets:
            temps, ks, tps, mps, seeds, rids, _, _, _ = \
                self._sampling_rows([], B)
            keys = step_keys(row_keys(seeds, self._sample_base, rids),
                             jnp.zeros(B, jnp.int32))
            toks, _ = self._get_sampler(False, False)(
                jnp.zeros((B, V), jnp.float32), keys,
                jnp.asarray(temps), jnp.asarray(ks),
                jnp.asarray(tps), jnp.asarray(mps))
            toks.block_until_ready()
            n += 1
        return n

    def warm_place(self) -> int:
        """Pre-compile the two small programs by which a step reads its
        rows' tokens off the unread step before it, outside the ragged
        program (which holds its own placement): a decode state built
        while a step is unread (``_build_decode_state``, after a unified
        step or when the batch changed: a line of a decode bucket, taken
        from the widest line), and a fused window's carried tokens
        widened to that line (``_unread_tokens``). One program a decode
        bucket each. Returns the number of programs compiled."""
        if self.cfg.mode == "prefill" or self.cfg.speculative != "off":
            return 0   # no fused window: every step is read as it ends
        n = 0
        wide = self._rows_max
        for B in sorted({self._bucket(b)
                         for b in range(1, self.cfg.max_batch + 1)}):
            none = jnp.full(B, -1, jnp.int32)
            _place(jnp.zeros(B, jnp.int32), none, self._no_tokens)
            n += 1
            if B != wide:
                _place(self._no_tokens, jnp.full(wide, -1, jnp.int32),
                       jnp.zeros(B, jnp.int32))
                n += 1
        return n

    def _grow_decode_pages(self, rows: List[Request],
                           events: List[StepEvent]) -> None:
        """Ensure every decode row has a page for its next token (the
        unified step advances decode rows by exactly one). Preempts the
        youngest on exhaustion, mirroring the fused path: the tokens in
        flight are read first (their events go to ``events``), so that a
        preempted request is sent no stale token and host bookkeeping
        sees every page it releases consistently; a finish among them may
        free enough on its own."""
        for req in sorted(rows, key=lambda r: r.t_submit):
            if req.state != "running":
                continue  # preempted or finished earlier in this very loop
            need = (pages_for_tokens(req.seq_len + 1, self.cfg.page_size)
                    - len(req.pages))
            if need <= 0:
                continue
            extra = self._alloc(need)
            if extra is None and self._pending is not None:
                events.extend(self._read_pending())
                if req.state != "running":
                    continue  # the read just finished THIS request
                extra = self._alloc(need)
            while extra is None:
                if self._preempt_youngest(exclude=req) is None:
                    break
                extra = self._alloc(need)
            if extra is None:
                self._preempt(req)
                continue
            req.pages.extend(extra)

    def _host_bound(self, rows) -> bool:
        """Whether the next step of ``rows`` needs the host to have seen
        their last tokens, decided by what the rows are: a grammar row's
        mask or device state and a penalty row's counts are built on the
        host from the tokens it has seen (``_sample_unified``,
        ``_build_decode_state``), and a prefill-role engine's product is
        the export of the step that just ran. A unified step of such rows
        keeps the synchronous order: it reads the step before it ahead of
        its own dispatch, and its own tokens right after."""
        return self.cfg.mode == "prefill" or any(
            r.gstate is not None or r.sampling.needs_penalties()
            for r in rows)

    # hot_path
    def _unified_step(self) -> List[StepEvent]:
        """ONE ragged device dispatch for the whole batch: every
        mid-prefill row contributes its next chunk, every decoding row
        contributes one step, packed on a flat token axis with per-token
        row ids (ops/ragged_paged_attention). Sampling mirrors the legacy
        paths exactly — per-row keys are fold_in(row_key, token position),
        grammar masks apply before penalties — so outputs are
        bit-identical to the split prefill/decode programs.

        The step ends at the sampler's dispatch and leaves its tokens as
        the engine's pending read; what it returns are the events of the
        step BEFORE it, fetched after this one was dispatched, so the
        device computes this step while the host emits, delivers, admits
        and packs the next. A decode row whose last token is still unread
        takes it from that step's device array (``_unread_tokens``), be it
        a unified step's or a fused window's. What the next pack needs of
        the host is booked at dispatch: a decode row's ``seq_len`` (a
        pending step's tokens are already counted in it, the invariant
        the runtime-LoRA drain protects — see _rebuild_lora_stack), a
        finishing prefill row's ``state``. Rows that need the host
        between steps keep the synchronous order (``_host_bound``): the
        same two reads, each made one call earlier."""
        events: List[StepEvent] = []
        # The fused window's device state goes stale with the rows this
        # step advances; its unread tokens stay the pending read.
        self._dec = None
        host_bound = self._host_bound(self.running)
        if host_bound:
            events.extend(self._read_pending())
        with _Phase(self, _PACK, "unified"):
            packed = self._pack_unified(events)
        if packed is None:
            events.extend(self._read_pending())
            return events
        entries, sample_rows, Ttot, Rb, Tb, dev = packed

        with _Phase(self, _DISPATCH, "unified"):
            self._note_dispatch("unified", len(entries), Ttot, Rb, Tb)
            self.metrics["unified_rows"] += len(entries)
            self.metrics["unified_chunk_rows"] += sum(
                end - start > 1 for _, start, end in entries)
            fn = self._get_ragged_fn(Rb, Tb)
            logits, *pools = fn(
                self.params, *dev[:6], self.cache.k_pages,
                self.cache.v_pages, self.cache.k_scales, self.cache.v_scales,
                *dev[6:], **self._state_kw([r for r, _, _ in entries], Rb))
            self._put_pools(*pools)

            # Host bookkeeping at dispatch: the next pack reads it before
            # this step's tokens are read.
            self._mark(_BOOK)
            for req, start, end in entries:
                if end > start:
                    req.prefill_pos = end
                    req.seq_len = end
                    self.metrics["prefill_tokens"] += end - start
                    if end == len(req.prompt):
                        req.state = "running"
                else:
                    req.seq_len += 1
            self._mark(_SAMPLE)
            prev, self._pending = self._pending, None
            if sample_rows:
                toks, lps = self._sample_unified(logits, sample_rows)
                self._pending = _Unread(
                    rows=[r for r, _, _, _ in sample_rows], toks=toks,
                    lps=lps, visited=None, valid=[1] * len(sample_rows),
                    first=[not dec for _, _, _, dec in sample_rows],
                    last=toks)
        if prev is not None:
            events.extend(self._emit_pending(prev))
        if host_bound:
            events.extend(self._read_pending())
        return events

    def _unread_columns(self) -> Dict[int, int]:
        """Where rows about to decode find their input tokens while the
        step before them is unread: ``id(request)`` to its entry of that
        step's ``last`` (``_unread_tokens``). A row that is not in it takes
        the host's ``last_token`` (``_place_tokens``: ``take`` -1)."""
        unread = self._pending
        if unread is None:
            return {}
        return {id(r): j for j, r in enumerate(unread.rows)}

    def _unread_tokens(self):
        """The unread step's ``last`` on the device, which
        ``_unread_columns`` indexes: as wide as the widest line of rows,
        so that one program a shape serves every step before it
        (``warm_place`` compiles the widening). No device read."""
        unread = self._pending
        if unread is None:
            return self._no_tokens
        prev = unread.last
        n = prev.shape[0]
        if n != self._rows_max:
            wide = np.full(self._rows_max, -1, np.int32)
            wide[:n] = np.arange(n, dtype=np.int32)
            prev = _place(self._no_tokens, self._put(wide), prev)
        return prev

    # hot_path
    def _pack_unified(self, events: List[StepEvent]):
        """The unified step's host side: this step's rows, their packed
        arrays in numpy, and the uploads. Whatever a read ahead of a
        preemption emits is appended to ``events``. Returns None when no
        row has work, else (entries, sample_rows, packed tokens, row
        bucket, token bucket, the device arrays in the ragged program's
        argument order: the six packed lines, then where its decode rows'
        tokens are taken from, then the packed offsets its head runs
        on)."""
        # A row whose tokens in flight already fill its length budget can
        # only finish: it is known to end without its token being read.
        inflight = self._pending_counts()
        decode = [r for r in self.running if r.state == "running"
                  and len(r.output) + inflight.get(id(r), 0)
                  < r.sampling.max_new_tokens]
        self._grow_decode_pages(decode, events)

        entries = []                 # (req, start, end) — end==start: decode
        stepping = {id(r) for r in decode}
        for r in self.running:
            if r.state == "prefill":
                start = r.prefill_pos
                end = min(start + self.cfg.prefill_chunk, len(r.prompt))
                entries.append((r, start, end))
            elif r.state == "running" and id(r) in stepping:
                entries.append((r, r.seq_len, r.seq_len))
        if not entries:
            return None
        if self.window_allocator is not None:
            with trace.annotation(obs_names.SPAN_KV_WINDOW_RELEASE):
                for r, start, end in entries:
                    self._window_pages_for(r, start, max(end, start + 1))

        self._mark(_FILL)
        P = self.cfg.max_pages_per_seq
        Rb = self._bucket(len(entries))
        Ttot = sum((e - s) if e > s else 1 for _, s, e in entries)
        Tb = self._token_bucket(Ttot)
        tok = np.zeros((1, Tb), np.int32)
        # Where a decode row's token is still on the device: its entry of
        # ``prev``; -1 elsewhere (``_place_tokens``, inside the program).
        take = np.full((1, Tb), -1, np.int32)
        column = self._unread_columns()
        # Pad tokens carry position -1 — the ragged-pack pad contract
        # (ops/ragged_paged_attention): the XLA fallback's unpack routes
        # them out of its scatter and the kernel skips them outright.
        pos = np.full((1, Tb), -1, np.int32)
        tmask = np.zeros((1, Tb), bool)
        row_ids = np.zeros(Tb, np.int32)
        kvl = np.zeros(Rb, np.int32)
        table = np.zeros((Rb, P), np.int32)
        off = 0
        sample_rows = []             # (req, packed_idx, key_pos, is_decode)
        for i, (req, start, end) in enumerate(entries):
            if end > start:          # prefill chunk
                n = end - start
                tok[0, off:off + n] = req.prompt[start:end]
                pos[0, off:off + n] = np.arange(start, end, dtype=np.int32)
                kvl[i] = end
                if end == len(req.prompt):
                    # Finishing row: its first output token samples at the
                    # position right after the prompt (key rule: a token at
                    # absolute position p is keyed by p).
                    sample_rows.append((req, off + n - 1, end, False))
            else:                    # decode step: write last token, sample
                n = 1
                j = column.get(id(req))
                if j is None:
                    tok[0, off] = req.last_token
                else:
                    take[0, off] = j
                pos[0, off] = req.seq_len
                kvl[i] = req.seq_len + 1
                sample_rows.append((req, off, req.seq_len + 1, True))
            tmask[0, off:off + n] = True
            row_ids[off:off + n] = i
            table[i, :len(req.pages)] = req.pages
            off += n
        # The packed offsets the head runs on: the sampling rows', in
        # ``sample_rows``' order, as wide as the widest line of rows.
        rows = np.zeros(self._rows_max, np.int32)
        rows[:len(sample_rows)] = [i for _, i, _, _ in sample_rows]
        self._mark(_UPLOAD)
        prev = self._unread_tokens()
        dev = [self._put(a) for a in (tok, pos, tmask, row_ids, kvl, table,
                                      take)]
        return (entries, sample_rows, Ttot, Rb, Tb,
                dev + [prev, self._put(rows)])

    # hot_path
    def _sample_unified(self, logits, sample_rows):
        """One batched sampler dispatch for every sampling row — decode
        steps and finishing prefills together (the _prefill_step /
        fused-scan sampler, so outputs stay bit-identical). ``logits`` are
        the sampling rows' alone, in ``sample_rows``' order, as the ragged
        program's head left them: ``[rows, V]`` at the widest line of rows,
        which is the width the sampler runs at and its tokens have, so the
        step after this one takes them by index (``_unread_tokens``) in one
        program a shape. Returns the device arrays (tokens, logprobs or
        None)."""
        reqs = [r for r, _, _, _ in sample_rows]
        Bs = self._rows_max
        sel = logits                 # [Bs, V]: the program picked the rows
        temps, ks, tps, mps, seeds, rids, pen, lp, sorts = \
            self._sampling_rows(reqs, Bs)
        self._note_sampler(sorts)
        key_pos = np.zeros(Bs, np.int32)
        for n, (_, _, kpos, _) in enumerate(sample_rows):
            key_pos[n] = kpos
        put = self._put
        keys = step_keys(row_keys(seeds, self._sample_base, rids, put),
                         put(key_pos))
        if any(r.gstate is not None for r in reqs):
            # Host-side grammar masks (the unified step host-syncs every
            # token anyway, so tabled and table-less grammars both apply
            # the mask-then-penalties order of the host path).
            gm = np.ones((Bs, self.mcfg.vocab_size), bool)
            for n, req in enumerate(reqs):
                if req.gstate is not None:
                    gm[n] = self._gmask(req.grammar, req.gstate)
            sel = jnp.where(put(gm), sel, NEG_INF)
        args = [sel, keys, put(temps), put(ks), put(tps), put(mps)]
        if pen:
            pen_rows = self._penalty_rows(reqs, Bs)
            oc = pen_rows[1]
            for n, req in enumerate(reqs):
                np.add.at(oc[n], np.asarray(req.output, np.int64), 1)
            args += [put(a) for a in pen_rows]
        return self._get_sampler(pen, lp)(*args)

    # ---- prefill ----

    def _prefill_step(self) -> List[StepEvent]:
        """Advance every prefilling request by one chunk — BATCHED: all
        in-flight prefills share one (B, chunk) forward (rows carry their own
        positions/lengths/page tables), so admission bursts fill the MXU
        instead of running B=1 chunks serially."""
        batch = [r for r in self.running if r.state == "prefill"]
        if not batch:
            return []
        chunk = self.cfg.prefill_chunk
        rows = []
        for req in batch:
            start = req.prefill_pos
            end = min(start + chunk, len(req.prompt))
            rows.append((req, start, end))
            if self.window_allocator is not None:
                self._window_pages_for(req, start, end)

        B = self._bucket(len(batch))
        self._note_dispatch("prefill", len(rows),
                            sum(e - s for _, s, e in rows), B, B * chunk)
        logits = self._run(
            tokens=[req.prompt[s:e] for req, s, e in rows],
            positions=[list(range(s, e)) for _, s, e in rows],
            lens=[e for _, _, e in rows],
            pages=[req.pages for req, _, _ in rows],
            T_bucket=chunk, B_bucket=B,
            reqs=[req for req, _, _ in rows],
        )

        finishing = []
        for i, (req, start, end) in enumerate(rows):
            req.prefill_pos = end
            req.seq_len = end
            self.metrics["prefill_tokens"] += end - start
            if end == len(req.prompt):
                finishing.append((i, end - start - 1, req))
        if not finishing:
            return []

        # One batched sample for every finishing row — a single gather +
        # sampler dispatch + host transfer (mirrors the decode path).
        with _Phase(self, _DISPATCH):
            toks, lps, reqs = self._sample_finishing(logits, finishing)
        with _Phase(self, _SYNC):
            # One batched fetch — same single-transfer emission as the
            # unified step.
            toks, lps = jax.device_get((toks, lps))
        events = []
        with _Phase(self, _EMIT):
            for n, req in enumerate(reqs):
                req.state = "running"
                req.t_first = time.perf_counter()
                events.append(self._emit(
                    req, int(toks[n]),
                    float(lps[n]) if lps is not None and req.sampling.logprobs
                    else None))
        return events

    def _sample_finishing(self, logits, finishing):
        """The split prefill path's sampler dispatch for the rows whose
        prompt ended in this chunk: (tokens, logprobs or None) on the
        device, and the requests in row order."""
        Bs = self._bucket(len(finishing))
        pad = Bs - len(finishing)
        row_idx = np.asarray([i for i, _, _ in finishing] + [0] * pad, np.int32)
        tok_idx = np.asarray([j for _, j, _ in finishing] + [0] * pad, np.int32)
        sel = logits[jnp.asarray(row_idx), jnp.asarray(tok_idx)]  # [Bs, V]
        reqs = [req for _, _, req in finishing]
        temps, ks, tps, mps, seeds, rids, pen, lp, sorts = \
            self._sampling_rows(reqs, Bs)
        self._note_sampler(sorts)
        poss = np.zeros(Bs, np.int32)
        for n, req in enumerate(reqs):
            poss[n] = req.seq_len  # position of the token being sampled
        keys = step_keys(row_keys(seeds, self._sample_base, rids),
                         jnp.asarray(poss))
        gr = any(r.gstate is not None for r in reqs)
        if gr:
            # First output token must already obey the grammar.
            gm = np.ones((Bs, self.mcfg.vocab_size), bool)
            for n, req in enumerate(reqs):
                if req.gstate is not None:
                    gm[n] = self._gmask(req.grammar, req.gstate)
            sel = jnp.where(jnp.asarray(gm), sel, NEG_INF)
        args = [sel, keys, jnp.asarray(temps), jnp.asarray(ks),
                jnp.asarray(tps), jnp.asarray(mps)]
        if pen:
            # First sampled token: output is empty except for pre-preemption
            # tokens folded into the prompt (counted as output by
            # _penalty_rows's oc_base).
            args += [jnp.asarray(a) for a in self._penalty_rows(reqs, Bs)]
        toks, lps = self._get_sampler(pen, lp)(*args)
        return toks, lps, reqs

    def _sampling_rows(self, reqs, B: int):
        """Per-row sampling arrays + static variant flags for a batch —
        the ONE gather shared by prefill finish, fused decode build, and
        the speculative verify (a new sampling knob lands here once).
        The last value is no variant: whether ``sample`` will sort the
        vocabulary for this batch (``_note_sampler`` counts it)."""
        temps = np.zeros(B, np.float32)
        ks = np.zeros(B, np.int32)
        tps = np.ones(B, np.float32)
        mps = np.zeros(B, np.float32)
        seeds: List[Optional[int]] = [None] * B
        rids = [0] * B
        for i, r in enumerate(reqs):
            sp = r.sampling
            temps[i], ks[i], tps[i], mps[i] = (sp.temperature, sp.top_k,
                                               sp.top_p, sp.min_p)
            seeds[i], rids[i] = sp.seed, r.id
        pen = any(r.sampling.needs_penalties() for r in reqs)
        lp = any(r.sampling.logprobs for r in reqs)
        sorts = any(r.sampling.needs_sort() for r in reqs)
        return temps, ks, tps, mps, seeds, rids, pen, lp, sorts

    def _note_sampler(self, sorts: bool, steps: int = 1) -> None:
        """Count ``steps`` runs of ``sample`` as dispatched, and those of
        them in which it sorts (``_sampling_rows``' last value)."""
        self.metrics["sampler_steps"] += steps
        if sorts:
            self.metrics["sampler_sort_steps"] += steps

    def _lora_rows(self, reqs, B: int):
        """(lora_ids [B] on the host, or None): None when no row uses an
        adapter — callers compile the adapter-free variant in that case."""
        if self.lora_stack is None or not any(r.lora_idx for r in reqs):
            return None
        ids = np.zeros(B, np.int32)
        for i, r in enumerate(reqs):
            ids[i] = r.lora_idx
        return ids

    def _penalty_rows(self, reqs, B: int):
        """Host-built penalty state, on the host: prompt-seen mask,
        output-count base, and per-row factors (repetition, presence,
        frequency). [B, V] is only materialized when some request
        in the batch actually uses penalties (callers compile separate
        variants otherwise). A preempted-and-resumed request carries its
        pre-preemption output inside ``prompt`` — those tokens count as
        OUTPUT (oc_base), not prompt, so presence/frequency penalties and
        seeded reproducibility survive preemption."""
        V = self.mcfg.vocab_size
        pmask = np.zeros((B, V), bool)
        oc_base = np.zeros((B, V), np.int32)
        rep = np.ones(B, np.float32)
        pres = np.zeros(B, np.float32)
        freq = np.zeros(B, np.float32)
        for n, req in enumerate(reqs):
            sp = req.sampling
            pmask[n, np.asarray(req.prompt[:req.orig_prompt_len],
                                np.int64)] = True
            np.add.at(oc_base[n],
                      np.asarray(req.prompt[req.orig_prompt_len:], np.int64),
                      1)
            rep[n], pres[n], freq[n] = (sp.repetition_penalty,
                                        sp.presence_penalty,
                                        sp.frequency_penalty)
        return pmask, oc_base, rep, pres, freq

    # hot_path
    def _get_sampler(self, pen: bool, lp: bool):
        fn = self._samplers.get((pen, lp))
        if fn is None:
            if pen:
                def f(sel, keys, temps, ks, tps, mps, pmask, ocounts,
                      rep, pres, freq):
                    return sample(sel, keys, temps, ks, tps, mps,
                                  prompt_mask=pmask, out_counts=ocounts,
                                  rep=rep, pres=pres, freq=freq,
                                  want_logprobs=lp)
            else:
                def f(sel, keys, temps, ks, tps, mps):
                    return sample(sel, keys, temps, ks, tps, mps,
                                  want_logprobs=lp)
            f.__name__ = PROGRAM_SAMPLER   # jitwatch catalog name
            fn = jax.jit(f)
            self._samplers[(pen, lp)] = fn
        return fn

    # ---- decode ----

    def _pending_counts(self) -> Dict[int, int]:
        """id(req) → number of un-emitted tokens awaiting fetch."""
        unread = self._pending
        if unread is None:
            return {}
        return {id(r): v for r, v in zip(unread.rows, unread.valid)}

    def _decode_batch(self) -> List[Request]:
        """Running requests worth dispatching. Rows whose length budget is
        already consumed by pending (un-emitted) tokens are excluded: they
        can only finish, and dispatching them would write KV tokens past
        prompt+max_new_tokens — potentially past max_seq_len."""
        pend = self._pending_counts()
        out = []
        for r in self.running:
            if r.state != "running":
                continue
            if (r.gstate is not None and self.cfg.speculative != "ngram"
                    and not self._row_fusable(r)):
                # Table-less grammar rows (pushdown JSON / budget-exceeded
                # / tables off) decode via the host-synced step; tabled
                # grammars join the fused window.
                continue
            if len(r.output) + pend.get(id(r), 0) >= r.sampling.max_new_tokens:
                continue
            out.append(r)
        return out

    def _emit_pending(self, unread: _Unread) -> List[StepEvent]:
        """Fetch a step's tokens and emit them: the one path by which a
        fused window's and a unified step's tokens reach the host. A row
        that a stop token ended meanwhile (it computed one step more, or
        the rest of its window) drops what is left: its pages, window
        pages and state slot went back when it finished, after the
        program that last wrote them was dispatched."""
        with _Phase(self, _SYNC):
            # One batched fetch (device_get resolves the leaves in a
            # single transfer; a None leaf passes through untouched).
            # lint: allow[jit-hygiene] the one intrinsic emission fetch — sampled tokens must reach the host to stream
            vals, lpv = jax.device_get((unread.toks, unread.lps))
        vals = np.atleast_2d(vals)           # [K, B]; a unified step's K is 1
        if lpv is not None:
            lpv = np.atleast_2d(lpv)
        events = []
        with _Phase(self, _EMIT):
            if unread.visited is not None:   # copied since dispatch
                self.metrics["moe_experts_visited"] += int(unread.visited)
                moe_layers = self.mcfg.num_moe_layers
                self.metrics["moe_expert_slots"] += (
                    len(vals) * moe_layers * self.mcfg.experts_here)
                # The (row, expert) pairs those visits served: a row is live
                # in ``valid`` steps of the window, as the device masks it.
                # Of a held range of the experts, the pairs that fall on it
                # where routing is uniform (what the host can know).
                self.metrics["moe_routed_rows"] += (
                    sum(unread.valid) * moe_layers
                    * self.mcfg.experts_per_token
                    * self.mcfg.experts_here // self.mcfg.num_experts)
            first = unread.first
            for i, req in enumerate(unread.rows):
                for k in range(unread.valid[i]):
                    if req.state != "running":
                        break                # stop token cut the window short
                    if first is not None and first[i]:
                        req.t_first = time.perf_counter()
                    else:
                        self.metrics["decode_tokens"] += 1
                    lp = (float(lpv[k, i]) if lpv is not None
                          and req.sampling.logprobs else None)
                    events.append(self._emit(req, int(vals[k, i]), lp))
        return events

    def _read_pending(self) -> List[StepEvent]:
        """Fetch and emit the unread step now, if there is one: the host
        has then seen every token computed so far."""
        unread, self._pending = self._pending, None
        return self._emit_pending(unread) if unread is not None else []

    def _drain_decode(self) -> List[StepEvent]:
        """Fetch + emit the pending tokens and discard the decode window's
        device state (forcing a rebuild). Called whenever the decode batch
        composition changes, or before preemption releases pages that host
        bookkeeping must observe consistently."""
        self._dec = None
        return self._read_pending()

    # hot_path
    def _decode_window(self) -> int:
        """Fused-scan window length for THIS step. Continuous batching:
        when a join is possible and work is waiting (a service submission
        beyond this step's admissions, or an engine-queued request while a
        batch slot is free — i.e. page-blocked), the window shortens to 1
        so the scan 'exits early' and absorbs the join next step instead
        of making it wait out a full multi_step window."""
        K = self.cfg.multi_step
        if K == 1 or self.cfg.ragged == "off":
            return K   # 'off' IS the window-boundary baseline behavior
        if (len(self.running) < self.cfg.max_batch
                and (self.join_hint or self.waiting)):
            # A join is actually possible (free slot) and work is waiting
            # (page-blocked in the engine queue, or still queued at the
            # service): short windows surface finishes — and the pages
            # they release — at step granularity so the join lands next
            # step. When the batch is FULL, shortening buys nothing and
            # costs the window's dispatch amortization — keep K.
            return 1
        return K

    def _get_decode_fn(self, B: int, pen: bool, lp: bool,
                       la: bool = False, gr: bool = False,
                       K: Optional[int] = None):
        """One fused jitted program per (decode bucket, penalties-active,
        logprobs-active, grammar-active): a lax.scan window of
        ``multi_step`` iterations, each = forward + on-device sampling +
        position/length increment, with the sampled token fed straight
        back as the next iteration's input. Per-row sampling keys are
        fold_in(row_key, position) — no key-split carry, and a state
        rebuild replays the identical stream. Steady state does ZERO
        host→device transfers per window and one device→host fetch (the
        [K, B] token ids, one window late). Penalty state ([B, V] prompt
        mask + output counts), grammar state (per-row table-state id +
        the shared [S, V] transition/legality arrays), and per-step
        logprobs only exist in the variants that need them.

        The grammar variant masks logits with ``glegal[gstate]`` BEFORE
        sampling (the exact order of the host-synced path: mask, then
        penalties inside ``sample``) and transitions ``gstate =
        gnext[gstate, tok]`` on device — a constrained row costs the same
        dispatches as an unconstrained one. A −1 transition (EOS from a
        non-identity state can't happen; defensive) keeps the old state,
        mirroring ``_emit``'s keep-state-on-EOS bookkeeping."""
        if K is None:
            K = self.cfg.multi_step
        fn = self._dec_fn_cache.get((B, pen, lp, la, gr, K))
        if fn is not None:
            return fn
        import functools
        base = functools.partial(forward_paged, cfg=self.mcfg,
                                 use_pallas=self.cfg.use_pallas,
                                 experts_whole=self._experts_whole,
                                 sharded=self.mesh is not None)

        def fused(params, tok, pos, kvl, table, mask, limit, k_pages,
                  v_pages, k_scales, v_scales, keys, temps, ks, tps, mps,
                  pmask=None, ocounts=None, rep=None, pres=None, freq=None,
                  lora=None, lids=None, gnext=None, glegal=None,
                  gstate=None, gactive=None, state=None, slots=None,
                  window=None, wtable=None):
            def body(carry, _):
                tok, pos, kvl, kp, vp, ksc, vsc, oc, gs, st, wp = carry
                # Rows at their length limit (mid-window finishers) stop
                # writing KV and stop advancing — their sampled values are
                # discarded host-side via the per-row valid count.
                write_ok = mask & (pos < limit)[:, None]    # [B, 1]
                logits, kp, vp, ksc, vsc, *visited = base(
                    params, tokens=tok[:, None], positions=pos[:, None],
                    token_mask=write_ok, kv_lens=kvl, page_table=table,
                    k_pages=kp, v_pages=vp, k_scales=ksc, v_scales=vsc,
                    lora=lora, lora_ids=lids, state=st, state_slots=slots,
                    window_pages=wp, window_table=wtable)
                # The new state follows the pools (its place, where the
                # model has window layers alone), then the window class.
                if st is not None or wp is not None:
                    st = visited.pop(0)
                if wp is not None:
                    wp = visited.pop(0)
                # The experts a hit-only step visited; none to count where
                # the experts are sharded or the step is dense.
                visited = visited[0] if visited else None
                pkw = (dict(prompt_mask=pmask, out_counts=oc, rep=rep,
                            pres=pres, freq=freq) if pen else {})
                lg = logits[:, 0, :]
                if gr:
                    # Grammar mask first, penalties inside sample() after —
                    # the identical order the host-synced path applies.
                    lg = jnp.where(glegal[gs] | ~gactive[:, None],
                                   lg, NEG_INF)
                # Key by the OUTPUT token's position (pos + 1): the input
                # token at ``pos`` was itself sampled with key fold_in(row,
                # pos) — prefill keys its first token by seq_len, so reusing
                # ``pos`` here would replay that exact Gumbel noise.
                toks, lps = sample(lg, step_keys(keys, pos + 1),
                                   temps, ks, tps, mps, want_logprobs=lp,
                                   **pkw)
                active = write_ok[:, 0]
                if pen:
                    oc = oc.at[jnp.arange(oc.shape[0]), toks].add(
                        active.astype(jnp.int32))
                if gr:
                    ns = gnext[gs, toks]
                    gs = jnp.where(gactive & active & (ns >= 0), ns, gs)
                pos = jnp.where(active, pos + 1, pos)
                kvl = jnp.where(active, kvl + 1, kvl)
                tok = jnp.where(active, toks, tok)
                return (tok, pos, kvl, kp, vp, ksc, vsc, oc, gs, st, wp), (
                    toks, lps if lp else None, visited)

            oc0 = ocounts if pen else jnp.zeros((), jnp.int32)
            gs0 = gstate if gr else jnp.zeros((), jnp.int32)
            carry, ys = jax.lax.scan(
                body, (tok, pos, kvl, k_pages, v_pages, k_scales, v_scales,
                       oc0, gs0, state, window), None, length=K)
            tok, pos, kvl, kp, vp, ksc, vsc, oc, gs, st, wp = carry
            toks_seq, lp_seq, visited = ys
            if visited is not None:          # hit experts only: the window's
                visited = visited.sum()      # count rides out beside the tokens
            out = (toks_seq, lp_seq, visited, tok, pos, kvl, kp, vp, ksc,
                   vsc, oc, gs)
            # A model with recurrent layers: its state pool comes last;
            # one with window layers: the state's place, then that class.
            if wp is not None:
                return out + (st, wp)
            return out if st is None else out + (st,)

        # tok is NOT donated: the pending fetch reads last window's output
        # after it has been fed back as this window's input. keys is reused
        # across windows (constant); ocounts is carried and donated.
        donate = [2, 3]  # pos, kvl
        donate += [7, 8, 9, 10] if self.cache.quantized else [7, 8]
        if pen:
            donate.append(17)  # ocounts
        fused.__name__ = PROGRAM_FUSED_DECODE   # jitwatch catalog name
        fn = jax.jit(fused, donate_argnums=tuple(donate),
                     donate_argnames=self._donate_state)
        self._dec_fn_cache[(B, pen, lp, la, gr, K)] = fn
        return fn

    def _build_decode_state(self, batch: List[Request]) -> dict:
        """The fused window's device state for ``batch``: the host arrays
        first (``engine.pack.fill``), then their puts
        (``engine.pack.upload``)."""
        self._mark(_FILL)
        B = self._bucket(len(batch))
        P = self.cfg.max_pages_per_seq
        tok = np.zeros(B, np.int32)
        pos = np.zeros(B, np.int32)
        kvl = np.zeros(B, np.int32)
        mask = np.zeros((B, 1), bool)
        limit = np.zeros(B, np.int32)
        table = np.zeros((B, P), np.int32)
        temps, ks, tps, mps, seeds, rids, pen, lp, sorts = \
            self._sampling_rows(batch, B)
        # A row that a unified step just advanced has its last token in
        # that step's unread line: the window takes it from there, and
        # unified -> decode chains with no read between.
        take = np.full(B, -1, np.int32)
        column = self._unread_columns()
        for i, r in enumerate(batch):
            j = column.get(id(r))
            if j is None:
                tok[i] = r.last_token
            else:
                take[i] = j
            pos[i] = r.seq_len
            kvl[i] = r.seq_len + 1
            mask[i, 0] = True
            limit[i] = r.max_len()
            table[i, :len(r.pages)] = r.pages
        lids = self._lora_rows(batch, B)
        slots = wtable = pen_rows = None
        if self.state is not None:
            slots = self._slot_rows(batch, B)
        if self.window_allocator is not None:
            wtable = self._window_table(batch, B)
        if pen:
            pen_rows = self._penalty_rows(batch, B)
            for i, r in enumerate(batch):
                np.add.at(pen_rows[1][i], np.asarray(r.output, np.int64), 1)
        gr_rows = [r for r in batch if r.gstate is not None]
        if gr_rows:
            # Device-resident grammar decode: per-row table-state ids into
            # the stacked [S, V] tables. A rebuild recovers the device
            # state exactly from req.gstate — host bookkeeping (_emit)
            # advances it token-by-token, and every engine gstate is
            # whole-token-reachable, so the lookup cannot miss. (A table
            # met for the first time goes up here, once, and stays.)
            gnext, glegal, offsets = self._device_grammar_tables(
                [r.grammar for r in gr_rows])
            gstate = np.zeros(B, np.int32)
            gactive = np.zeros(B, bool)
            for i, r in enumerate(batch):
                if r.gstate is not None:
                    t = self._grammar_table(r.grammar)
                    gstate[i] = offsets[id(r.grammar)] + t.state_ids[r.gstate]
                    gactive[i] = True

        self._mark(_UPLOAD)
        put = self._put
        prev = self._unread_tokens()
        st = {
            "rows": list(batch), "B": B, "pen": pen, "lp": lp,
            "sorts": sorts, "gr": bool(gr_rows),
            "lids": put(lids) if lids is not None else None,
            "tok": _place(put(tok), put(take), prev) if column else put(tok),
            "pos": put(pos), "kvl": put(kvl), "mask": put(mask),
            "limit": put(limit),
            "temps": put(temps), "ks": put(ks),
            "tps": put(tps), "mps": put(mps),
            "keys": row_keys(seeds, self._sample_base, rids, put),
            "table_np": table, "table": put(table),
        }
        if slots is not None:
            st["slots"] = put(slots)
        if wtable is not None:
            st["wtable"] = put(wtable)
        if pen:
            pmask, oc, rep, pres, freq = pen_rows
            st.update(pmask=put(pmask), ocounts=put(oc), rep=put(rep),
                      pres=put(pres), freq=put(freq))
        if gr_rows:
            st.update(gnext=gnext, glegal=glegal, gstate=put(gstate),
                      gactive=put(gactive))
        return st

    def _decode_step(self) -> List[StepEvent]:
        if self.cfg.speculative == "ngram":
            # Speculative mode: the host-synced verify step owns the whole
            # batch (drafts, grammar masks, and penalties together —
            # penalized/grammar rows simply never draft).
            events = self._drain_decode()
            return events + self._spec_decode_step()
        if any(r.gstate is not None and not self._row_fusable(r)
               for r in self.running if r.state == "running"):
            # Mixed traffic: ONLY table-less grammar rows pay the
            # per-token host-synced step; everyone else — tabled grammar
            # rows included — keeps the fused multi-step path (its
            # _decode_batch excludes exactly the host-synced rows).
            events = self._spec_decode_step(grammar_only=True)
            return events + self._fused_decode_step()
        return self._fused_decode_step()

    # hot_path
    def _fused_decode_step(self) -> List[StepEvent]:
        events: List[StepEvent] = []
        with _Phase(self, _PACK, "decode"):
            packed = self._pack_decode(events)
        if packed is None:
            return events
        st, batch, K = packed

        with _Phase(self, _DISPATCH, "decode"):
            self._note_dispatch("decode", len(batch), len(batch) * K,
                                st["B"], st["B"] * K)
            self._note_sampler(st["sorts"], K)
            fn = self._get_decode_fn(st["B"], st["pen"], st["lp"],
                                     st["lids"] is not None, st["gr"], K=K)
            kw = {}
            if st["pen"]:
                kw.update(pmask=st["pmask"], ocounts=st["ocounts"],
                          rep=st["rep"], pres=st["pres"], freq=st["freq"])
            if st["lids"] is not None:
                kw.update(lora=self.lora_stack, lids=st["lids"])
            if st["gr"]:
                kw.update(gnext=st["gnext"], glegal=st["glegal"],
                          gstate=st["gstate"], gactive=st["gactive"])
            if self.state is not None:
                kw.update(state=self.state.arrays, slots=st["slots"])
            if self.window_allocator is not None:
                kw.update(window=self.cache.window_pages,
                          wtable=st["wtable"])
            (toks_seq, lp_seq, visited, tok, pos, kvl, kp, vp, ksc, vsc, oc,
             gs, *state) = fn(
                self.params, st["tok"], st["pos"], st["kvl"], st["table"],
                st["mask"], st["limit"], self.cache.k_pages,
                self.cache.v_pages, self.cache.k_scales, self.cache.v_scales,
                st["keys"], st["temps"], st["ks"], st["tps"], st["mps"], **kw)
            self._put_pools(kp, vp, ksc, vsc, *state)
            if visited is not None:
                # On the host by the time the lagged fetch reads it: a
                # second blocking read a step would cost 0.3 ms of emit.
                visited.copy_to_host_async()
            self._mark(_BOOK)
            st["tok"], st["pos"], st["kvl"] = tok, pos, kvl
            if st["pen"]:
                st["ocounts"] = oc
            if st["gr"]:
                st["gstate"] = gs
            valid = []
            for req in batch:
                valid.append(min(K, req.max_len() - req.seq_len))
                req.seq_len = min(req.seq_len + K, req.max_len())

            prev, self._pending = self._pending, _Unread(
                rows=list(batch), toks=toks_seq, lps=lp_seq, visited=visited,
                valid=valid, first=None, last=tok)
        if prev is not None:
            events.extend(self._emit_pending(prev))
        return events

    # hot_path
    def _pack_decode(self, events: List[StepEvent]):
        """The decode step's host side: the batch, pages for its window
        (preempting on exhaustion), and the device state, built anew when
        the batch changed and patched when only its pages did. Whatever a
        drain emits on the way is appended to ``events``. Returns None
        when no row is left to dispatch, else (state, batch, window)."""
        batch = self._decode_batch()
        st = self._dec
        if st is not None and st["rows"] != batch:
            # A row left the batch (it is at its length, or a stop token or
            # a cancel ended it) or joined it: the state is built anew, and
            # takes the rows' tokens from the unread step like any other.
            st = self._dec = None
        if not batch:
            events.extend(self._drain_decode())
            return None
        if st is None and self._pending is not None \
                and self._host_bound(batch):
            # The state about to be built holds grammar states and output
            # counts, which the host builds from the tokens it has seen.
            events.extend(self._read_pending())
            batch = self._decode_batch()
            if not batch:
                return None

        # Ensure pages exist for the whole decode window; preempt the
        # youngest requests on exhaustion. Oldest-first so old requests
        # finish and release memory (deadlock-free under oversubscription).
        K = self._decode_window()
        pages_changed = False
        for req in sorted(batch, key=lambda r: r.t_submit):
            if req.state != "running":
                continue  # preempted earlier in this very loop
            horizon = min(req.seq_len + K, req.max_len())
            need = pages_for_tokens(horizon, self.cfg.page_size) - len(req.pages)
            if need > 0:
                extra = self._alloc(need)
                while extra is None:
                    # Emit in-flight tokens before any pages are released:
                    # a preempted request must not receive a stale token
                    # (and an emitted finish may free enough on its own).
                    events.extend(self._drain_decode())
                    st = None
                    if req.state != "running":
                        break  # the drain just finished THIS request
                    extra = self._alloc(need)
                    if extra is not None:
                        break
                    victim = self._preempt_youngest(exclude=req)
                    if victim is None:
                        break
                    extra = self._alloc(need)
                if req.state != "running":
                    # Finished by a pending stop token emitted in the drain:
                    # its pages are already released — growing or preempting
                    # it now would leak pages / resurrect a finished stream.
                    if extra:
                        self.allocator.release(extra)
                    continue
                if extra is None:
                    events.extend(self._drain_decode())
                    st = None
                    if req.state != "running":
                        continue
                    self._preempt(req)
                    continue
                req.pages.extend(extra)
                pages_changed = True
        batch2 = self._decode_batch()
        if batch2 != batch:
            if st is not None:
                events.extend(self._drain_decode())
                st = None
            batch = batch2
        if not batch:
            return None

        moved = False
        if self.window_allocator is not None:
            # A row's line moves once in ``page_size`` steps; the table is
            # laid out anew in the steps in which some row's did.
            with trace.annotation(obs_names.SPAN_KV_WINDOW_RELEASE):
                moved = any([self._window_pages_for(
                    r, r.seq_len, min(r.seq_len + K, r.max_len()))
                    for r in batch])
        if st is None:
            st = self._dec = self._build_decode_state(batch)
        elif moved or pages_changed:
            # The patch of a batch that did not change: the lines first,
            # then their puts.
            self._mark(_FILL)
            wtable = self._window_table(batch, st["B"]) if moved else None
            if pages_changed:
                for i, r in enumerate(batch):
                    row = st["table_np"][i]
                    row[:len(r.pages)] = r.pages
                    row[len(r.pages):] = 0
            self._mark(_UPLOAD)
            if moved:
                st["wtable"] = self._put(wtable)
            if pages_changed:
                st["table"] = self._put(st["table_np"])
        return st, batch, K

    # ---- speculative decode (prompt-lookup drafting) ----

    def _ensure_ngram(self, req: Request):
        """Lazily build/extend the request's n-gram index over its logical
        sequence (prompt + output — stable across preemption, which only
        moves tokens between the two)."""
        from rbg_tpu.engine.spec import NGramIndex
        if req.ngram is None:
            req.ngram = NGramIndex(self.cfg.spec_ngram)
        idx = req.ngram
        have = len(idx.tokens)
        total = req.total_len
        if have < total:
            seq = req.prompt + req.output
            idx.extend(seq[have:total])

    def _get_spec_fn(self, B: int, lp: bool, pen: bool = False,
                     gr: bool = False, la: bool = False):
        """One jitted verify program per (bucket, logprobs, pen,
        grammar): a (B, K+1) paged forward + per-position sampling, keys
        fold_in(row, pos+1) — the same keys the sequential path would use,
        so accepted tokens are exactly what non-speculative decoding would
        have produced. Penalized rows use host-built counts (constant
        across the window — those rows never draft, so only their slot-0
        sample is consumed). Grammar rows get per-slot allowed-token masks
        computed host-side along the draft path."""
        key = (B, lp, pen, gr, la)
        fn = self._spec_fn_cache.get(key)
        if fn is not None:
            return fn
        import functools
        base = functools.partial(forward_paged, cfg=self.mcfg,
                                 use_pallas=self.cfg.use_pallas)

        def specfn(params, tok, pos, mask, kvl, table, k_pages, v_pages,
                   k_scales, v_scales, keys, temps, ks, tps, mps,
                   pmask=None, ocounts=None, rep=None, pres=None, freq=None,
                   gmasks=None, lora=None, lids=None):
            logits, kp, vp, ksc, vsc = base(
                params, tokens=tok, positions=pos, token_mask=mask,
                kv_lens=kvl, page_table=table, k_pages=k_pages,
                v_pages=v_pages, k_scales=k_scales, v_scales=v_scales,
                lora=lora, lora_ids=lids)
            pkw = (dict(prompt_mask=pmask, out_counts=ocounts, rep=rep,
                        pres=pres, freq=freq) if pen else {})

            def samp(lg_t, pos_t, gm_t):    # [B, V], [B], [B, V]
                if gr:
                    lg_t = jnp.where(gm_t, lg_t, NEG_INF)
                return sample(lg_t, step_keys(keys, pos_t + 1),
                              temps, ks, tps, mps, want_logprobs=lp,
                              **pkw)

            gm = gmasks if gr else jnp.zeros(
                (logits.shape[0], logits.shape[1], 1), bool)
            toks, lps = jax.vmap(samp, in_axes=(1, 1, 1))(logits, pos, gm)
            return toks, lps, kp, vp, ksc, vsc  # toks/lps: [T, B]

        specfn.__name__ = PROGRAM_SPEC_VERIFY   # jitwatch catalog name
        donate = (6, 7, 8, 9) if self.cache.quantized else (6, 7)
        fn = jax.jit(specfn, donate_argnums=donate)
        self._spec_fn_cache[key] = fn
        return fn

    def _spec_decode_step(self, grammar_only: bool = False) -> List[StepEvent]:
        events: List[StepEvent] = []
        batch = [r for r in self.running if r.state == "running"
                 and (not grammar_only
                      or (r.gstate is not None and not self._row_fusable(r)))
                 and len(r.output) < r.sampling.max_new_tokens]
        if not batch:
            return events
        with _Phase(self, _PACK):
            K = self.cfg.spec_k if self.cfg.speculative == "ngram" else 0
            ps = self.cfg.page_size
            drafts: Dict[int, List[int]] = {}
            gmask_rows: Dict[int, list] = {}
            # Draft + grow pages, oldest-first (preempt youngest on exhaustion;
            # a row sheds its drafts before anyone gets preempted for them).
            # Penalized rows never draft (their counts are sequential); grammar
            # rows draft along the automaton — masks are computed assuming the
            # draft prefix is accepted, which holds for every accepted prefix.
            for req in sorted(batch, key=lambda r: r.t_submit):
                if req.state != "running":
                    continue
                cap = min(K, req.sampling.max_new_tokens - len(req.output) - 1,
                          self.cfg.max_seq_len - req.seq_len - 1)
                if cap > 0 and not req.sampling.needs_penalties():
                    self._ensure_ngram(req)
                    d = req.ngram.draft(cap)
                else:
                    d = []
                if req.gstate is not None:
                    g = req.grammar
                    s = req.gstate
                    masks = [self._gmask(g, s)]
                    kept = []
                    for dt in d:
                        ns = g.advance_token(s, dt)
                        if ns is None:
                            break           # draft leaves the grammar — cut here
                        kept.append(dt)
                        masks.append(self._gmask(g, ns))
                        s = ns
                    d = kept
                    gmask_rows[id(req)] = masks
                while True:
                    need = (pages_for_tokens(req.seq_len + 1 + len(d), ps)
                            - len(req.pages))
                    if need <= 0:
                        break
                    extra = self._alloc(need)
                    if extra is not None:
                        req.pages.extend(extra)
                        break
                    if d:
                        d = []          # shed drafts before preempting others
                        continue
                    if self._preempt_youngest(exclude=req) is None:
                        self._preempt(req)
                        break
                if req.state == "running":
                    drafts[id(req)] = d
            batch = [r for r in batch if r.state == "running"]
            if not batch:
                return events

            B = self._bucket(len(batch))
            T = K + 1
            P = self.cfg.max_pages_per_seq
            tok = np.zeros((B, T), np.int32)
            pos = np.zeros((B, T), np.int32)
            mask = np.zeros((B, T), bool)
            kvl = np.zeros(B, np.int32)
            table = np.zeros((B, P), np.int32)
            temps, ks, tps, mps, seeds, rids, pen, lp, sorts = \
                self._sampling_rows(batch, B)
            gr = any(r.gstate is not None for r in batch)
            gmasks = (np.ones((B, T, self.mcfg.vocab_size), bool)
                      if gr else None)
            for i, r in enumerate(batch):
                d = drafts[id(r)]
                tok[i, 0] = r.last_token
                tok[i, 1:1 + len(d)] = d
                pos[i, :] = r.seq_len + np.arange(T)
                mask[i, :1 + len(d)] = True
                kvl[i] = r.seq_len + 1 + len(d)
                table[i, :len(r.pages)] = r.pages
                if gr and id(r) in gmask_rows:
                    for t, m in enumerate(gmask_rows[id(r)]):
                        gmasks[i, t] = m
            kw = {}
            if pen:
                pmask, oc, rep, pres, freq = self._penalty_rows(batch, B)
                for i, r in enumerate(batch):
                    np.add.at(oc[i], np.asarray(r.output, np.int64), 1)
                kw.update(pmask=jnp.asarray(pmask), ocounts=jnp.asarray(oc),
                          rep=jnp.asarray(rep), pres=jnp.asarray(pres),
                          freq=jnp.asarray(freq))
            if gr:
                kw["gmasks"] = jnp.asarray(gmasks)
            lids = self._lora_rows(batch, B)
            if lids is not None:
                kw.update(lora=self.lora_stack, lids=jnp.asarray(lids))
        with _Phase(self, _DISPATCH):
            self._note_dispatch("spec", len(batch),
                                int(mask.sum()), B, B * T)
            self._note_sampler(sorts)
            fn = self._get_spec_fn(B, lp, pen, gr, lids is not None)
            toks_out, lps_out, *pools = fn(
                self.params, jnp.asarray(tok), jnp.asarray(pos),
                jnp.asarray(mask), jnp.asarray(kvl), jnp.asarray(table),
                self.cache.k_pages, self.cache.v_pages,
                self.cache.k_scales, self.cache.v_scales,
                row_keys(seeds, self._sample_base, rids),
                jnp.asarray(temps), jnp.asarray(ks), jnp.asarray(tps),
                jnp.asarray(mps), **kw)
            self._put_pools(*pools)
        with _Phase(self, _SYNC):
            vals = np.asarray(toks_out)                       # [T, B]
            lpv = np.asarray(lps_out) if lps_out is not None else None
        with _Phase(self, _EMIT):
            self.metrics["spec_steps"] += 1
            for i, req in enumerate(batch):
                d = drafts[id(req)]
                m = 0
                while m < len(d) and int(vals[m, i]) == d[m]:
                    m += 1
                # d_0..d_{m-1} verified; vals[m] is the true next token at the
                # first mismatch (or the bonus token when every draft held).
                self.metrics["spec_drafted"] += len(d)
                self.metrics["spec_accepted"] += m
                emit_n = m + 1
                req.seq_len += emit_n   # KV valid through the last GOOD input
                for t in range(emit_n):
                    if req.state != "running":
                        break           # stop token cut the window short
                    self.metrics["decode_tokens"] += 1
                    lpt = (float(lpv[t, i])
                           if lpv is not None and req.sampling.logprobs else None)
                    events.append(self._emit(req, int(vals[t, i]), lpt))
        return events

    def _emit(self, req: Request, tok: int,
              logprob: Optional[float] = None) -> StepEvent:
        req.output.append(tok)
        if req.ngram is not None:
            req.ngram.append(tok)
        if req.gstate is not None and req.grammar is not None:
            nxt = req.grammar.advance_token(req.gstate, tok)
            if nxt is not None:     # defensively keep old state on EOS etc.
                req.gstate = nxt
        req.last_token = tok
        finished = (
            len(req.output) >= req.sampling.max_new_tokens
            or (req.sampling.stop_token is not None and tok == req.sampling.stop_token)
        )
        if finished:
            self._finish(req)
        return StepEvent(req.id, tok, finished, logprob=logprob)

    # ---- lifecycle ----

    def _finish(self, req: Request):
        req.state = "finished"
        self.running = [r for r in self.running if r is not req]
        if self.cfg.mode == "prefill":
            # Disaggregated prefill: the pages ARE the product — the PD layer
            # exports them to a decode peer, then calls release_request().
            req.state = "exported"
            return
        self._release_slot(req)
        self._release_window(req)
        if (self.radix is not None and req.lora_idx == 0
                and not self.mcfg.unbuilt_for):
            # Cache the full sequence (prompt + output) for future prefixes
            # (base-model requests only — adapter KV must not cross-match;
            # never a model with recurrent or window layers: see ``_admit``).
            self.radix.insert(req.prompt + req.output[:-1], req.pages)
            if self.host_tier is not None:
                self._publish_tier_gauges()
        self.allocator.release(req.pages)
        req.pages = []
        # Don't retain finished requests forever (long-running servers).
        self.requests.pop(req.id, None)

    def release_request(self, req_id: int):
        """Release an exported request's pages (prefill mode)."""
        req = self.requests.pop(req_id)
        if req.pages:
            self.allocator.release(req.pages)
            req.pages = []

    def cancel_request(self, req_id: int) -> bool:
        """Abort a request: drop it from the queues and recycle its pages.
        (Must be called from the thread driving step() — the EngineService
        routes cancellations through its loop.)"""
        req = self.requests.get(req_id)
        if req is None or req.state == "finished":
            return False
        req.state = "finished"
        self.waiting = [r for r in self.waiting if r is not req]
        self.running = [r for r in self.running if r is not req]
        if req.pages:
            self.allocator.release(req.pages)
            req.pages = []
        self._release_slot(req)
        self._release_window(req)
        self.requests.pop(req_id, None)
        return True

    def _preempt(self, req: Request):
        self.metrics["preemptions"] += 1
        self.allocator.release(req.pages)
        req.pages = []
        # Its next admission prefills from position 0 into a fresh slot
        # (and a window line that holds nothing).
        self._release_slot(req)
        self._release_window(req)
        req.state = "waiting"
        req.prefill_pos = 0
        req.seq_len = 0
        req.shared_tokens = 0
        # Re-queued: join accounting restarts from the preemption step
        # (time spent RUNNING must not read as queue wait).
        req.enqueue_step = self.metrics["steps"]
        req.blocked_steps = 0
        req.t_enqueue = time.perf_counter()
        # Restart cleanly: generated tokens so far are kept as prompt
        # extension so decoding resumes where it left off.
        if req.output:
            req.prompt = req.prompt + req.output
            req.sampling = dataclasses.replace(
                req.sampling,
                max_new_tokens=req.sampling.max_new_tokens - len(req.output))
            req.output = []
        self.running = [r for r in self.running if r is not req]
        self.waiting.insert(0, req)

    def _preempt_youngest(self, exclude: Request) -> Optional[Request]:
        candidates = [r for r in self.running if r.state == "running" and r is not exclude]
        if not candidates:
            return None
        victim = max(candidates, key=lambda r: r.t_submit)
        self._preempt(victim)
        return victim

    # ---- device dispatch ----

    def _decode_walk_kernel_copies(self) -> int:
        """How many of a step's decode walks copy their own pages inside
        the kernel (``ops/pallas/page_walk.kernel_copies``, read off the
        pools' shapes and dtypes as the step programs hand them over: a
        latent model's pair without its singleton axis,
        ``page_walk.latent_pools``): an entry of the leading axis of each
        class's pools, where this process runs the kernels at all. 0:
        every walk takes its pages from the pipeline (int8 pools, float32
        pools, heads under a lane tile, the XLA forms)."""
        from rbg_tpu.ops import pallas
        from rbg_tpu.ops.pallas import page_walk
        if not pallas.takes_kernel(self.cfg.use_pallas):
            return 0
        cache = self.cache
        classes = [(cache.k_pages, cache.v_pages, cache.k_scales,
                    cache.v_scales)]
        if cache.window_k is not None:
            classes.append((cache.window_k, cache.window_v))

        def as_handed_over(pools):
            shapes = [jax.ShapeDtypeStruct(p.shape[1:], p.dtype)
                      for p in pools if p is not None]
            if self.mcfg.mla:
                shapes[:2] = jax.eval_shape(page_walk.latent_pools,
                                            *shapes[:2])
            return shapes

        return sum(pools[0].shape[0] for pools in classes
                   if page_walk.kernel_copies(as_handed_over(pools)))

    # bucket_fn
    def _bucket(self, n: int) -> int:
        for b in self.cfg.decode_buckets:
            if b >= n:
                return min(b, max(self.cfg.decode_buckets))
        return max(self.cfg.decode_buckets)

    def _get_fwd(self, B: int, T: int, la: bool = False):
        key = (B, T, la)
        fn = self._fwd_cache.get(key)
        if fn is None:
            import functools
            base = functools.partial(forward_paged, cfg=self.mcfg,
                                     use_pallas=self.cfg.use_pallas)

            def wrapped(params, tokens, positions, token_mask, kv_lens,
                        page_table, k_pages, v_pages, k_scales, v_scales,
                        lora=None, lids=None, state=None, slots=None,
                        window=None, wtable=None):
                return base(params, tokens=tokens, positions=positions,
                            token_mask=token_mask, kv_lens=kv_lens,
                            page_table=page_table, k_pages=k_pages,
                            v_pages=v_pages, k_scales=k_scales,
                            v_scales=v_scales, lora=lora, lora_ids=lids,
                            state=state, state_slots=slots,
                            window_pages=window, window_table=wtable)

            wrapped.__name__ = PROGRAM_PAGED_FWD   # jitwatch catalog name
            donate = (6, 7, 8, 9) if self.cache.quantized else (6, 7)
            fn = jax.jit(wrapped, donate_argnums=donate,
                         donate_argnames=self._donate_state)
            self._fwd_cache[key] = fn
        return fn

    def _run(self, tokens, positions, lens, pages, T_bucket, B_bucket=None,
             reqs=None):
        """Pad host-side lists to (B_bucket, T_bucket) and dispatch.
        ``reqs`` (row-aligned) selects per-row LoRA adapters when given."""
        B = B_bucket or 1
        T = T_bucket
        P = self.cfg.max_pages_per_seq
        with _Phase(self, _PACK):
            tok = np.zeros((B, T), np.int32)
            pos = np.zeros((B, T), np.int32)
            mask = np.zeros((B, T), bool)
            kvl = np.zeros((B,), np.int32)
            table = np.zeros((B, P), np.int32)
            for i, (ts, ps_, ln, pg) in enumerate(zip(tokens, positions,
                                                      lens, pages)):
                tok[i, :len(ts)] = ts
                pos[i, :len(ps_)] = ps_
                mask[i, :len(ts)] = True
                kvl[i] = ln
                table[i, :len(pg)] = pg
            lids = self._lora_rows(reqs, B) if reqs is not None else None
            kw = ({"lora": self.lora_stack, "lids": jnp.asarray(lids)}
                  if lids is not None else {})
            kw.update(self._state_kw(reqs or [], B))
            dev = [jnp.asarray(a) for a in (tok, pos, mask, kvl, table)]
        with _Phase(self, _DISPATCH):
            fn = self._get_fwd(B, T, lids is not None)
            logits, *pools = fn(
                self.params, *dev, self.cache.k_pages, self.cache.v_pages,
                self.cache.k_scales, self.cache.v_scales, **kw)
            self._put_pools(*pools)
        return logits  # device array; callers slice what they need
