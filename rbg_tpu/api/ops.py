"""Canonical catalog of wire ops: the cross-plane request/reply contract.

Every op that rides a socket between rbg-tpu processes — the admin plane
(``runtime/admin.py``), the engine data plane (``engine/server.py``), the
kv-pool / directory plane (``engine/kvpool.py``), and the router plane
(``engine/router.py``) — is declared HERE, once: its name, owning
plane(s), auth gate, request fields (required/optional + coarse type),
reply fields per outcome, and the error codes it may return
(⊆ ``api/errors.ALL_CODES``).

Why a registry: the plane speaks ~30 ops across four server surfaces and
eight-plus client call sites. A reply field a client reads but no server
sets — or an op/error-code that exists on one side only — is silent
drift an e2e test catches only by luck. The catalog makes the contract a
build artifact: the ``op-registry`` / ``field-discipline`` /
``error-code-flow`` lint rules (``analysis/rules/wire.py``) audit both
sides statically, and the ``RBG_WIRECHECK`` sentry
(``utils/wirecheck.py``) validates live frames against the same specs.
Same playbook as ``api/errors.py`` (PR 4) and the ``BUCKET_FNS`` catalog
(PR 19): declare once, lint both directions, arm a runtime sentry.

This module is dependency-free on purpose (stdlib ``typing`` only): the
lint rules and the wirecheck sentry import it without jax, and the
engine server imports its constants before jax loads.

Conventions (see docs/static-analysis.md for the adding-an-op checklist):

* request field types are coarse (``int``/``float``/``str``/``bool``/
  ``tokens``/``list``/``dict``/``any``); a ``?`` suffix marks the field
  optional, everything else is required on the wire;
* ``response`` maps outcome name → reply field tuple; validators use the
  union across outcomes (streamed ops emit several frame shapes);
* error frames are universal: any reply may instead be
  ``{"error", "code"?, "retry_after_s"?, "done"?}`` (``REPLY_ERROR_FIELDS``)
  — only the ``code`` value is per-op, gated by ``errors``;
* ``REQUEST_UNIVERSAL`` fields (``op``/``token``/``trace``/``timeout_s``/
  ``page_size``) are stamped by transport helpers onto any request and
  are never declared per op;
* keys starting with ``_`` are process-local annotations (e.g. the
  router's ``_router_t_dispatch`` TTFT stamp) — they never cross the
  wire and validators ignore them.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

from rbg_tpu.api.errors import (ALL_CODES, CODE_DEADLINE, CODE_DRAINING,
                                CODE_KV_INTEGRITY, CODE_KV_STREAM,
                                CODE_OVERLOADED, CODE_REJECTED)

# ---- op name constants (import these; never inline the literal) ----

OP_HEALTH = "health"
OP_METRICS = "metrics"
OP_SLO = "slo"
OP_TRACES = "traces"

# admin plane
OP_LIST = "list"
OP_GET = "get"
OP_APPLY = "apply"
OP_DELETE = "delete"
OP_STATUS = "status"
OP_HISTORY = "history"
OP_DIFF = "diff"
OP_UNDO = "undo"
OP_AUTOSCALE = "autoscale"
OP_TOPOLOGY = "topology"
OP_PROFILE = "profile"
OP_EVENTS = "events"
OP_CONTROLPLANE = "controlplane"
OP_HA = "ha"

# engine data plane
OP_WARMUP = "warmup"
OP_GENERATE = "generate"
OP_GENERATE_TEXT = "generate_text"
OP_EMBED = "embed"
OP_PREFILL = "prefill"
OP_DECODE_BUNDLE = "decode_bundle"
OP_KV_STREAM = "kv_stream"
OP_DECODE_STREAM = "decode_stream"

# KV chunk-stream sub-frames (ride the decode server's kv_stream socket
# and the standalone transport listener; kvtransfer/transport.py)
OP_KV_META = "kv_meta"
OP_KV_CHUNK = "kv_chunk"
OP_KV_FIRST = "kv_first"
OP_KV_FIN = "kv_fin"

# kv-pool / directory plane
OP_POOL_MATCH = "pool_match"
OP_POOL_PUT = "pool_put"
OP_POOL_STATS = "pool_stats"
OP_DIR_REGISTER = "dir_register"
OP_DIR_LOOKUP = "dir_lookup"
OP_DIR_INVALIDATE = "dir_invalidate"
OP_DIR_STATS = "dir_stats"

PLANE_ADMIN = "admin"
PLANE_ENGINE = "engine"
PLANE_KVPOOL = "kvpool"
PLANE_ROUTER = "router"

# ---- universal fields ----

#: Stamped onto any request by transport/client helpers (token gates,
#: per-hop deadline rebudgeting, trace propagation, the kv-pool
#: page-size handshake). Never declared per op.
REQUEST_UNIVERSAL = frozenset({"op", "token", "trace", "timeout_s",
                               "page_size"})

#: Any reply may be a structured error frame instead of its declared
#: outcome. ``code`` values are gated per op by ``OpSpec.errors``.
REPLY_ERROR_FIELDS = frozenset({"error", "code", "retry_after_s", "done"})

#: Added/consumed by the codec itself (``send_msg``/``recv_msg`` binary
#: payload lengths) — framing, not contract.
FRAMING_FIELDS = frozenset({"bin_k", "bin_v"})


class OpSpec(NamedTuple):
    """One op's wire contract. ``request`` maps field → coarse type
    (``?`` suffix = optional); ``response`` maps outcome → reply fields;
    ``errors`` are the ``code`` values this op may return."""

    op: str
    plane: str
    auth: bool
    request: Dict[str, str]
    response: Dict[str, Tuple[str, ...]]
    errors: Tuple[str, ...] = ()


def request_fields(spec: OpSpec) -> frozenset:
    return frozenset(spec.request)


def required_fields(spec: OpSpec) -> frozenset:
    return frozenset(f for f, t in spec.request.items()
                     if not t.endswith("?"))


def reply_fields(spec: OpSpec) -> frozenset:
    out = set()
    for fields in spec.response.values():
        out.update(fields)
    return frozenset(out)


# Sampling knobs ride generate/prefill/decode requests verbatim
# (SamplingParams.from_wire, engine/config.py; forwarded by the router's
# _FWD_DECODE_KEYS). All optional.
_SAMPLING_REQ = {
    "max_new_tokens": "int?",
    "temperature": "float?",
    "top_k": "int?",
    "top_p": "float?",
    "min_p": "float?",
    "repetition_penalty": "float?",
    "presence_penalty": "float?",
    "frequency_penalty": "float?",
    "seed": "int?",
    "logprobs": "bool?",
    "json_mode": "bool?",
    "regex": "str?",
    "json_schema": "dict?",
    "lora": "str?",
    "stop_token": "int?",
}

# Shared operator-payload reply shapes (obs/slo.py::slo_response,
# obs/trace.py::traces_response, obs/profiler.py::sample_profile) — the
# admin plane and the engine server serve the same helpers.
SLO_RESPONSE_FIELDS = ("window_s", "sampler", "signals",
                       "signals_by_window", "cache", "trackers")
TRACES_RESPONSE_FIELDS = ("recent", "slowest", "active", "waterfall",
                          "exemplars")
PROFILE_RESPONSE_FIELDS = ("seconds", "samples", "top", "folded")

# Reject codes a generation-style op can return: admission shed, spent
# budget, SIGTERM drain, or the structured base rejection.
_GEN_ERRORS = (CODE_OVERLOADED, CODE_DEADLINE, CODE_DRAINING,
               CODE_REJECTED)

# Streamed generation reply outcomes: blocking reply, incremental stream
# frames, the terminal done frame.
_GEN_RESPONSE = {
    "ok": ("tokens", "ttft_s", "logprobs"),
    "stream": ("tokens", "logprobs", "done"),
    "final": ("tokens", "done", "ttft_s"),
}


def _spec(op: str, plane: str, auth: bool, request: Dict[str, str],
          response: Dict[str, Tuple[str, ...]],
          errors: Tuple[str, ...] = ()) -> OpSpec:
    return OpSpec(op, plane, auth, request, response, errors)


# ---- admin plane (runtime/admin.py; bearer token on all but health) ----

ADMIN_OPS: Dict[str, OpSpec] = {
    OP_HEALTH: _spec(OP_HEALTH, PLANE_ADMIN, False, {},
                     {"ok": ("ok", "disruption", "spare_pool")}),
    OP_LIST: _spec(OP_LIST, PLANE_ADMIN, True,
                   {"kind": "str", "namespace": "str?", "all": "bool?"},
                   {"ok": ("items",)}),
    OP_GET: _spec(OP_GET, PLANE_ADMIN, True,
                  {"kind": "str", "name": "str", "namespace": "str?"},
                  {"ok": ("object",)}),
    OP_APPLY: _spec(OP_APPLY, PLANE_ADMIN, True, {"manifest": "str"},
                    {"ok": ("ok", "kind", "name")}),
    OP_DELETE: _spec(OP_DELETE, PLANE_ADMIN, True,
                     {"kind": "str", "name": "str", "namespace": "str?"},
                     {"ok": ("ok",)}),
    OP_STATUS: _spec(OP_STATUS, PLANE_ADMIN, True,
                     {"name": "str", "namespace": "str?"},
                     {"ok": ("name", "ready", "reason", "revision",
                             "roles", "specReplicas", "pods")}),
    OP_HISTORY: _spec(OP_HISTORY, PLANE_ADMIN, True,
                      {"name": "str", "namespace": "str?"},
                      {"ok": ("revisions",)}),
    OP_DIFF: _spec(OP_DIFF, PLANE_ADMIN, True,
                   {"name": "str", "revision": "int?",
                    "namespace": "str?"},
                   {"ok": ("revision", "diff")}),
    OP_UNDO: _spec(OP_UNDO, PLANE_ADMIN, True,
                   {"name": "str", "revision": "int?",
                    "namespace": "str?"},
                   {"ok": ("ok", "restoredRevision")}),
    OP_METRICS: _spec(OP_METRICS, PLANE_ADMIN, True, {},
                      {"ok": ("text",)}),
    OP_SLO: _spec(OP_SLO, PLANE_ADMIN, True, {"window": "float?"},
                  {"ok": SLO_RESPONSE_FIELDS}),
    OP_AUTOSCALE: _spec(OP_AUTOSCALE, PLANE_ADMIN, True,
                        {"enable": "str?", "disable": "str?"},
                        {"ok": ("autoscale",)}),
    OP_TOPOLOGY: _spec(OP_TOPOLOGY, PLANE_ADMIN, True,
                       {"enable": "str?", "disable": "str?",
                        "namespace": "str?"},
                       {"ok": ("topology",)}),
    OP_TRACES: _spec(OP_TRACES, PLANE_ADMIN, True, {"n": "int?"},
                     {"ok": TRACES_RESPONSE_FIELDS}),
    OP_PROFILE: _spec(OP_PROFILE, PLANE_ADMIN, True,
                      {"seconds": "float?"},
                      {"ok": PROFILE_RESPONSE_FIELDS}),
    OP_EVENTS: _spec(OP_EVENTS, PLANE_ADMIN, True,
                     {"namespace": "str?", "kind": "str?", "name": "str?",
                      "limit": "int?", "since": "float?", "reason": "str?",
                      "type": "str?"},
                     {"ok": ("events", "stats")}),
    OP_CONTROLPLANE: _spec(OP_CONTROLPLANE, PLANE_ADMIN, True, {},
                           {"ok": ("controlplane",)}),
    OP_HA: _spec(OP_HA, PLANE_ADMIN, True, {},
                 {"ok": ("ha",)}),
}

# ---- engine data plane (engine/server.py; token on data ops) ----

ENGINE_OPS: Dict[str, OpSpec] = {
    OP_HEALTH: _spec(OP_HEALTH, PLANE_ENGINE, False, {},
                     {"ok": ("ok", "mode", "draining", "draining_for_s",
                             "device")}),
    OP_WARMUP: _spec(OP_WARMUP, PLANE_ENGINE, True,
                     {"input_len": "int?"},
                     {"ok": ("ok", "elapsed_s")}),
    # ``metrics`` carries the step timeline's clocks and counts as
    # ``Engine.metrics`` holds them (docs/observability.md section 2.5):
    # among them ``steps_run``, ``device_waited_steps`` and, beside it,
    # ``lagged_steps`` (steps dispatched while the step before them was
    # unread: the one pending read of both step kinds); the clocks of
    # the six sub-phases that tile ``engine.pack`` and ``engine.dispatch``
    # (``t_unified_<sub>_s``, ``t_decode_<sub>_s``), ``uploads``, and the
    # starved-time bounds ``t_starved_s`` (split into ``_between_s``,
    # ``_pack_s``, ``_dispatch_s``) and ``t_starved_max_s``.
    OP_METRICS: _spec(OP_METRICS, PLANE_ENGINE, False, {},
                      {"ok": ("metrics", "mode")}),
    OP_SLO: _spec(OP_SLO, PLANE_ENGINE, False, {"window": "float?"},
                  {"ok": SLO_RESPONSE_FIELDS}),
    # ``steps_since`` (time.monotonic() seconds; 0 = all) asks for the
    # engine's step records too: ``steps`` later than the cursor,
    # ``steps_dropped`` and the ``late_steps`` records by the same
    # cursor (Engine.steps_since).
    OP_TRACES: _spec(OP_TRACES, PLANE_ENGINE, True,
                     {"n": "int?", "steps_since": "float?"},
                     {"ok": TRACES_RESPONSE_FIELDS
                      + ("steps", "steps_dropped", "late_steps")}),
    OP_GENERATE: _spec(OP_GENERATE, PLANE_ENGINE, True,
                       {"prompt": "tokens", "stream": "bool?",
                        **_SAMPLING_REQ},
                       _GEN_RESPONSE, _GEN_ERRORS),
    OP_GENERATE_TEXT: _spec(OP_GENERATE_TEXT, PLANE_ENGINE, True,
                            {"text": "str", **_SAMPLING_REQ},
                            {"ok": ("text", "tokens", "ttft_s")},
                            _GEN_ERRORS),
    OP_EMBED: _spec(OP_EMBED, PLANE_ENGINE, True,
                    {"prompts": "list?", "text": "str?",
                     "prompt": "tokens?"},
                    {"ok": ("embeddings", "dim", "prompt_tokens",
                            "embedding")},
                    (CODE_DRAINING,)),
    OP_PREFILL: _spec(OP_PREFILL, PLANE_ENGINE, True,
                      {"prompt": "tokens", "push_to": "str?",
                       "stream_id": "str?", **_SAMPLING_REQ},
                      {"pushed": ("pushed", "stream_id", "first_token",
                                  "prompt", "kv_bytes", "push_error",
                                  "link_rates"),
                       "bundle": ("prompt", "first_token", "shape",
                                  "dtype")},
                      _GEN_ERRORS),
    OP_DECODE_BUNDLE: _spec(OP_DECODE_BUNDLE, PLANE_ENGINE, True,
                            {"prompt": "tokens", "first_token": "int",
                             "shape": "list", "dtype": "str",
                             "stream": "bool?", **_SAMPLING_REQ},
                            _GEN_RESPONSE, _GEN_ERRORS),
    OP_KV_STREAM: _spec(OP_KV_STREAM, PLANE_ENGINE, True,
                        {"stream_id": "str"},
                        {"ok": ("ok", "bytes")}),
    OP_DECODE_STREAM: _spec(OP_DECODE_STREAM, PLANE_ENGINE, True,
                            {"stream_id": "str", "stream": "bool?",
                             **_SAMPLING_REQ},
                            _GEN_RESPONSE,
                            _GEN_ERRORS + (CODE_KV_STREAM,
                                           CODE_KV_INTEGRITY)),
    # KV chunk-stream sub-frames: requests with no per-frame reply (the
    # FIN ack is the kv_stream op's reply). kv_fin's "error" is a
    # REQUEST field here — the sender reports its abort reason.
    OP_KV_META: _spec(OP_KV_META, PLANE_ENGINE, False,
                      {"stream_id": "str", "prompt": "tokens",
                       "n_pages": "int", "k_page_shape": "list",
                       "v_page_shape": "list", "dtype": "str",
                       "layers": "int", "page_size": "int"},
                      {}),
    OP_KV_CHUNK: _spec(OP_KV_CHUNK, PLANE_ENGINE, False,
                       {"stream_id": "str", "seq": "int",
                        "layer_lo": "int", "layer_hi": "int",
                        "page_lo": "int", "page_hi": "int",
                        "checksum": "int?"},
                       {}),
    OP_KV_FIRST: _spec(OP_KV_FIRST, PLANE_ENGINE, False,
                       {"stream_id": "str", "first_token": "int"},
                       {}),
    OP_KV_FIN: _spec(OP_KV_FIN, PLANE_ENGINE, False,
                     {"stream_id": "str", "n_chunks": "int",
                      "aborted": "bool?", "error": "str?"},
                     {"ok": ("ok", "bytes")}),
}

# ---- kv-pool / directory plane (engine/kvpool.py; token on all but
# health; page_size handshake on pool_match/pool_put) ----

KVPOOL_OPS: Dict[str, OpSpec] = {
    OP_HEALTH: _spec(OP_HEALTH, PLANE_KVPOOL, False, {},
                     {"ok": ("ok", "mode")}),
    OP_POOL_MATCH: _spec(OP_POOL_MATCH, PLANE_KVPOOL, True,
                         {"prompt": "tokens"},
                         {"miss": ("matched",),
                          "hit": ("matched", "k_shape", "v_shape",
                                  "dtype", "checksum")}),
    OP_POOL_PUT: _spec(OP_POOL_PUT, PLANE_KVPOOL, True,
                       {"prompt": "tokens", "k_shape": "list",
                        "v_shape": "list", "dtype": "str"},
                       {"ok": ("stored_pages",)}),
    OP_POOL_STATS: _spec(OP_POOL_STATS, PLANE_KVPOOL, True, {},
                         {"ok": ("metrics", "mode", "directory")}),
    # `metrics` aliases pool_stats on this plane (same reply shape).
    OP_METRICS: _spec(OP_METRICS, PLANE_KVPOOL, True, {},
                      {"ok": ("metrics", "mode", "directory")}),
    OP_DIR_REGISTER: _spec(OP_DIR_REGISTER, PLANE_KVPOOL, True,
                           {"keys": "list?", "backend": "str?",
                            "slice_id": "str?", "tier": "str?"},
                           {"ok": ("registered",)}),
    OP_DIR_LOOKUP: _spec(OP_DIR_LOOKUP, PLANE_KVPOOL, True,
                         {"keys": "list?", "prompt": "tokens?",
                          "detail": "bool?"},
                         {"ok": ("matched", "matched_tokens", "holders",
                                 "detail")}),
    OP_DIR_INVALIDATE: _spec(OP_DIR_INVALIDATE, PLANE_KVPOOL, True,
                             {"keys": "list?", "backend": "str?",
                              "slice_id": "str?", "reason": "str?"},
                             {"ok": ("invalidated",)}),
    OP_DIR_STATS: _spec(OP_DIR_STATS, PLANE_KVPOOL, True, {},
                        {"ok": ("directory", "mode")}),
}

# ---- router plane (engine/router.py; token on embed/generate and the
# privileged half of health) ----

ROUTER_OPS: Dict[str, OpSpec] = {
    OP_HEALTH: _spec(OP_HEALTH, PLANE_ROUTER, False, {},
                     {"ok": ("ok", "pd", "draining", "router_id"),
                      "authorized": ("inactive_roles", "metrics",
                                     "backends", "draining_backends",
                                     "retry_budget", "kv", "slo")}),
    OP_GENERATE: _spec(OP_GENERATE, PLANE_ROUTER, True,
                       {"prompt": "tokens", "stream": "bool?",
                        **_SAMPLING_REQ},
                       _GEN_RESPONSE,
                       _GEN_ERRORS + (CODE_KV_STREAM,
                                      CODE_KV_INTEGRITY)),
    OP_EMBED: _spec(OP_EMBED, PLANE_ROUTER, True,
                    {"prompts": "list?", "text": "str?",
                     "prompt": "tokens?"},
                    {"ok": ("embeddings", "dim", "prompt_tokens",
                            "embedding")},
                    (CODE_OVERLOADED, CODE_DEADLINE, CODE_DRAINING,
                     CODE_REJECTED)),
}

#: plane name → catalog. The lint rules map server modules onto planes
#: through this (analysis/rules/wire.py::PLANE_MODULES).
PLANES: Dict[str, Dict[str, OpSpec]] = {
    PLANE_ADMIN: ADMIN_OPS,
    PLANE_ENGINE: ENGINE_OPS,
    PLANE_KVPOOL: KVPOOL_OPS,
    PLANE_ROUTER: ROUTER_OPS,
}

#: Every cataloged op name, across planes.
ALL_OP_NAMES = frozenset(op for cat in PLANES.values() for op in cat)


def _merge() -> Dict[str, dict]:
    """Per-op view merged across planes (a client can't know statically
    which plane an address serves): required = intersection (a field
    every plane demands), request/reply/errors = union."""
    merged: Dict[str, dict] = {}
    for plane, cat in PLANES.items():
        for op, spec in cat.items():
            m = merged.setdefault(op, {
                "required": None, "request": set(), "reply": set(),
                "errors": set(), "planes": [],
            })
            req = required_fields(spec)
            m["required"] = (req if m["required"] is None
                             else m["required"] & req)
            m["request"] |= request_fields(spec)
            m["reply"] |= reply_fields(spec)
            m["errors"] |= set(spec.errors)
            m["planes"].append(plane)
    for m in merged.values():
        m["required"] = frozenset(m["required"] or ())
        m["request"] = frozenset(m["request"])
        m["reply"] = frozenset(m["reply"])
        m["errors"] = frozenset(m["errors"])
        m["planes"] = tuple(m["planes"])
    return merged


#: op → {"required", "request", "reply", "errors", "planes"} — the view
#: the runtime wirecheck sentry and the client-side lint checks consume.
MERGED: Dict[str, dict] = _merge()

# Catalog self-check: declared codes must exist in the error registry —
# a typo'd code here would teach both validators to accept it.
for _cat in PLANES.values():
    for _s in _cat.values():
        _bad = set(_s.errors) - ALL_CODES
        if _bad:
            raise ValueError(
                f"op {_s.op!r} ({_s.plane}) declares unknown error "
                f"code(s) {sorted(_bad)} — not in api/errors.ALL_CODES")
del _cat, _s, _bad
