"""Engine configuration."""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from rbg_tpu.models.config import ModelConfig, get_config


@dataclasses.dataclass
class EngineConfig:
    model: str = "tiny"
    page_size: int = 16
    num_pages: int = 256                    # KV pool size (pages)
    max_batch: int = 8                      # decode batch ceiling
    max_seq_len: int = 512                  # per-sequence ceiling
    prefill_chunk: int = 64                 # chunked-prefill bucket
    decode_buckets: Tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64)
    enable_radix_cache: bool = True
    # Host-DRAM KV spill tier (engine/kvtier.py): when > 0, radix-cache
    # evictions spill their pages into a host trie bounded to this many
    # bytes instead of discarding them, and admission promotes host-held
    # prefixes back onto device. Mooncake's "more storage for less
    # computation" level — needs the radix cache; int8 KV pools keep it
    # off (spilled pages would need their scales carried too).
    host_tier_bytes: int = 0
    # Decode steps fused into ONE device dispatch (lax.scan window) — the
    # JetStream-style device-side decode loop. Each window samples K tokens
    # per sequence before control returns to the host, amortizing dispatch
    # overhead K-fold; tokens stream out in bursts of K (ITL burstiness is
    # the price, throughput the prize). Stop-token checks still happen
    # host-side, so up to K-1 speculative KV writes are discarded on stop.
    multi_step: int = 1
    # Speculative decoding: "ngram" = prompt-lookup drafting (no draft
    # model) + one (B, spec_k+1) verify forward per step. Because sampling
    # randomness is position-keyed (sampler.py), output is bit-identical
    # to non-speculative decoding — greedy AND sampled. Best on
    # repetitive/structured text; host-syncs every step, so it replaces
    # (and excludes) the fused multi_step window.
    speculative: str = "off"                # off | ngram
    spec_k: int = 4                         # max drafted tokens per step
    spec_ngram: int = 3                     # trailing n-gram for lookup
    # Device-resident grammar decode: finite-state grammars (regex /
    # json_schema) compile to dense token-level transition tables
    # (next_state[S, V] int32 + legal[S, V] bool) uploaded once per
    # (grammar, vocab), so constrained rows run INSIDE the fused
    # multi-step scan with zero per-token host syncs. "auto" tables every
    # eligible grammar and falls back to the host-synced mask path when
    # the reachable state count exceeds grammar_state_budget (or for the
    # pushdown JSON grammar, which has no finite table); "off" keeps
    # every constrained row on the host-synced path.
    grammar_table: str = "auto"             # auto | off
    # Max token-level states materialized per grammar. A grammar's table
    # costs pow2(S) × V × 5 bytes (int32 + bool) host- AND device-side
    # (device blocks are pow-2-padded and live while the grammar sits in
    # the 64-entry pattern/schema LRU — budget the AGGREGATE against
    # your vocab and HBM, worst case 64 × budget × V × 5).
    grammar_state_budget: int = 512
    use_pallas: str = "auto"                # auto | always | never
    # Ragged unified prefill/decode dispatch (continuous batching): while
    # any row is mid-prefill, the WHOLE batch — prefill chunks and decode
    # steps together — rides one ragged forward (ops/ragged_paged_attention)
    # instead of phase-split prefill-then-decode programs, and the fused
    # decode scan shortens its window to absorb waiting joins. "off" keeps
    # the split paths (the bit-identity baseline). Pure-decode batches use
    # the fused multi-step scan either way; MLA models, speculative mode,
    # and LoRA-mixed batches fall back to the split paths automatically.
    ragged: str = "auto"                    # auto | off
    # Per-request SLO targets the serving loop judges every FINISHED
    # request against (obs/slo.py): seconds to first token, and seconds
    # per output token after the first. Judgment is cheap host-side
    # bookkeeping at finish — it publishes rbg_slo_* attainment/goodput
    # series but never gates admission. 0 disables that dimension (it
    # always counts as met).
    slo_ttft_s: float = 2.0
    slo_tpot_s: float = 0.5
    # Predictive early rejection (Mooncake's overload story): admission
    # predicts TTFT — measured queue wait plus prefill time net of the
    # prefix hit this request would get — and sheds at INGRESS with
    # retry_after_s when the prediction exceeds early_reject_factor ×
    # slo_ttft_s, before any prefill compute is spent. "auto" arms it
    # whenever slo_ttft_s > 0; "off" keeps the PR-2 deadline-only gate.
    early_reject: str = "off"               # off | auto
    early_reject_factor: float = 1.5
    mode: str = "unified"                   # unified | prefill | decode
    mesh_spec: Optional[dict] = None        # {"dp": 1, "tp": 4} — from discovery
    checkpoint_path: str = ""               # orbax dir or local HF dir
    kv_dtype: str = "model"                 # model | int8 (quantized KV pool)
    vocab_size: int = 0                     # override preset vocab (0 = keep)
    seed: int = 0

    @property
    def model_config(self) -> ModelConfig:
        if self.vocab_size:
            return get_config(self.model, vocab_size=self.vocab_size)
        return get_config(self.model)

    @property
    def max_pages_per_seq(self) -> int:
        return (self.max_seq_len + self.page_size - 1) // self.page_size

    def validate(self) -> None:
        if self.max_batch > max(self.decode_buckets):
            raise ValueError("max_batch exceeds largest decode bucket")
        if self.num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is reserved)")
        if self.multi_step < 1:
            raise ValueError("multi_step must be >= 1")
        if self.speculative not in ("off", "ngram"):
            raise ValueError(f"speculative {self.speculative!r} not in "
                             "(off, ngram)")
        if self.speculative != "off":
            if self.multi_step != 1:
                raise ValueError("speculative decoding and multi_step are "
                                 "mutually exclusive (both own the decode "
                                 "dispatch)")
            if self.spec_k < 1 or self.spec_ngram < 1:
                raise ValueError("spec_k and spec_ngram must be >= 1")
        if self.ragged not in ("auto", "off"):
            raise ValueError(f"ragged {self.ragged!r} not in (auto, off)")
        if self.grammar_table not in ("auto", "off"):
            raise ValueError(f"grammar_table {self.grammar_table!r} not in "
                             "(auto, off)")
        if self.grammar_state_budget < 2:
            raise ValueError("grammar_state_budget must be >= 2 (initial "
                             "state + at least one successor)")
        if self.slo_ttft_s < 0 or self.slo_tpot_s < 0:
            raise ValueError("slo_ttft_s / slo_tpot_s must be >= 0 "
                             "(0 disables that SLO dimension)")
        if self.host_tier_bytes < 0:
            raise ValueError("host_tier_bytes must be >= 0 (0 disables "
                             "the host spill tier)")
        if self.host_tier_bytes and self.kv_dtype == "int8":
            raise ValueError("host_tier_bytes with kv_dtype='int8': the "
                             "spill tier does not carry page scales yet")
        if self.host_tier_bytes and not self.enable_radix_cache:
            raise ValueError(
                "host_tier_bytes needs the radix cache (spills come from "
                "its evictions) — a silently absent tier would discard "
                "every evicted prefix the operator budgeted RAM to keep")
        if self.early_reject not in ("off", "auto"):
            raise ValueError(f"early_reject {self.early_reject!r} not in "
                             "(off, auto)")
        if self.early_reject_factor <= 0:
            raise ValueError("early_reject_factor must be > 0")
        if self.kv_dtype not in ("model", "int8"):
            raise ValueError(f"kv_dtype {self.kv_dtype!r} not in (model, int8)")
        if self.kv_dtype == "int8" and self.mode != "unified":
            raise ValueError(
                "int8 KV is unified-mode only for now (PD bundles carry "
                "unquantized pages)")
        self.model_config  # fail fast on an unknown preset


@dataclasses.dataclass
class SamplingParams:
    max_new_tokens: int = 16
    temperature: float = 0.0        # 0 = greedy
    top_k: int = 0                  # 0 = full vocab
    top_p: float = 1.0              # nucleus mass; 1.0 = disabled
    min_p: float = 0.0              # min prob ratio vs argmax; 0.0 = disabled
    repetition_penalty: float = 1.0  # >1 discourages prompt+output tokens
    presence_penalty: float = 0.0   # subtract once per distinct output token
    frequency_penalty: float = 0.0  # subtract per output occurrence
    seed: Optional[int] = None      # per-request PRNG stream (reproducible)
    logprobs: bool = False          # emit chosen-token logprob per step
    json_mode: bool = False         # grammar-constrained: output is valid JSON
    regex: Optional[str] = None     # grammar-constrained: output matches
                                    # this anchored byte-level regex
    json_schema: Optional[dict] = None  # grammar-constrained: output is
                                        # compact JSON valid under this
                                        # schema subset (guided_json)
    lora: Optional[str] = None      # adapter name (engine-registered)
    stop_token: Optional[int] = None

    def needs_penalties(self) -> bool:
        return (self.repetition_penalty != 1.0
                or self.presence_penalty != 0.0
                or self.frequency_penalty != 0.0)

    def needs_sort(self) -> bool:
        """Whether this row makes ``sampler.sample`` sort the vocabulary:
        it samples, and through top-k, top-p or min-p (the predicates of
        the sampler's ``lax.cond``s, known here without a device read)."""
        return self.temperature > 0 and (
            self.top_k > 0 or self.top_p < 1.0 or self.min_p > 0.0)

    def validate(self) -> None:
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if self.temperature < 0:
            raise ValueError("temperature must be >= 0")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError("top_p must be in (0, 1]")
        if not 0.0 <= self.min_p < 1.0:
            raise ValueError("min_p must be in [0, 1)")
        if self.repetition_penalty <= 0:
            raise ValueError("repetition_penalty must be > 0")
        constraints = ((1 if self.json_mode else 0)
                       + (1 if self.regex is not None else 0)
                       + (1 if self.json_schema is not None else 0))
        if constraints > 1:
            raise ValueError("json_mode, regex, and json_schema are "
                             "mutually exclusive constraints")

    @classmethod
    def from_wire(cls, obj: dict, *, default_max_tokens: int = 16,
                  stop_token: Optional[int] = None) -> "SamplingParams":
        """Parse sampling fields off a protocol message (engine server /
        decode_bundle / HTTP front end all speak the same field names)."""
        sp = cls(
            max_new_tokens=int(obj.get("max_new_tokens", default_max_tokens)),
            temperature=float(obj.get("temperature", 0.0)),
            top_k=int(obj.get("top_k", 0)),
            top_p=float(obj.get("top_p", 1.0)),
            min_p=float(obj.get("min_p", 0.0)),
            repetition_penalty=float(obj.get("repetition_penalty", 1.0)),
            presence_penalty=float(obj.get("presence_penalty", 0.0)),
            frequency_penalty=float(obj.get("frequency_penalty", 0.0)),
            seed=(int(obj["seed"]) if obj.get("seed") is not None else None),
            logprobs=bool(obj.get("logprobs", False)),
            json_mode=bool(obj.get("json_mode", False)),
            # `is not None` checks: regex="" means "empty output only" and
            # json_schema={} means "any JSON" — truthiness would silently
            # drop both and return UNCONSTRAINED output.
            regex=(str(obj["regex"]) if obj.get("regex") is not None
                   else None),
            json_schema=(dict(obj["json_schema"])
                         if obj.get("json_schema") is not None else None),
            lora=(str(obj["lora"]) if obj.get("lora") else None),
            stop_token=(obj.get("stop_token") if obj.get("stop_token") is None
                        else int(obj["stop_token"])),
        )
        if stop_token is not None and sp.stop_token is None:
            sp.stop_token = stop_token
        sp.validate()
        return sp


def warm_prompt(input_len: int, wave: int = 0, row: int = 0) -> list:
    """Deterministic warmup prompt, distinct per (wave, row) — identical
    prompts would radix-hit and skip the very prefill shapes warmup exists
    to compile. Token ids stay in [1, 200): inside every preset's vocab
    and clear of special ids. The ONE generator for all warmup paths
    (EngineService / DecodeService / PrefillWorker)."""
    base = (wave * 131 + row * 17) % 199
    return [1 + (base + j) % 199 for j in range(input_len)]
