"""Canonical catalog of ``rbg_*`` metric names.

One module owns every metric name the project emits. Call sites import the
constant instead of retyping the string — the ``metric-name-registry``
lint rule (``rbg_tpu/analysis/rules/metricnames.py``) flags any ``rbg_*``
literal passed to a ``REGISTRY`` method that is not cataloged here, any
counter whose name is missing the ``_total`` suffix, and any name
registered under two different kinds (e.g. the same name used as both a
counter and a gauge).

Naming contract (Prometheus conventions):

* counters end in ``_total``;
* histograms of durations end in ``_seconds``;
* gauges are bare nouns (``..._depth``, ``..._draining``).

Keep this module to plain ``NAME = "literal"`` assignments grouped by
kind — the lint rule parses it statically.
"""

from __future__ import annotations

# ---- counters (monotonic, name must end in _total) ----

RECONCILE_TOTAL = "rbg_reconcile_total"
SERVING_SHED_TOTAL = "rbg_serving_shed_total"
SERVING_DEADLINE_EXCEEDED_TOTAL = "rbg_serving_deadline_exceeded_total"
SERVING_DRAINS_TOTAL = "rbg_serving_drains_total"
SERVING_DRAIN_REFUSALS_TOTAL = "rbg_serving_drain_refusals_total"
DISRUPTION_NOTICES_TOTAL = "rbg_disruption_notices_total"
DISRUPTION_PREEMPTIONS_TOTAL = "rbg_disruption_preemptions_total"
DISRUPTION_GANG_KILLS_TOTAL = "rbg_disruption_gang_kills_total"
DISRUPTION_MIGRATIONS_COMPLETED_TOTAL = (
    "rbg_disruption_migrations_completed_total")
DISRUPTION_MIGRATIONS_MISSED_DEADLINE_TOTAL = (
    "rbg_disruption_migrations_missed_deadline_total")
DISRUPTION_SLICES_RELEASED_TOTAL = "rbg_disruption_slices_released_total"
DISRUPTION_SPARES_CONSUMED_TOTAL = "rbg_disruption_spares_consumed_total"
LOCKTRACE_INVERSIONS_TOTAL = "rbg_locktrace_inversions_total"
RACE_VIOLATIONS_TOTAL = "rbg_race_violations_total"
JIT_UNWARMED_COMPILES_TOTAL = "rbg_jit_unwarmed_compiles_total"
JIT_HOST_SYNCS_TOTAL = "rbg_jit_host_syncs_total"
WIRE_CONTRACT_VIOLATIONS_TOTAL = "rbg_wire_contract_violations_total"
TRACE_TRACES_TOTAL = "rbg_trace_traces_total"
TRACE_SPANS_DROPPED_TOTAL = "rbg_trace_spans_dropped_total"
SERVING_REQUESTS_FINISHED_TOTAL = "rbg_serving_requests_finished_total"
SERVING_TOKENS_TOTAL = "rbg_serving_tokens_total"
SLO_JUDGED_TOTAL = "rbg_slo_judged_total"
SLO_TTFT_MET_TOTAL = "rbg_slo_ttft_met_total"
SLO_TPOT_MET_TOTAL = "rbg_slo_tpot_met_total"
SLO_GOODPUT_TOTAL = "rbg_slo_goodput_total"
AUTOSCALE_DECISIONS_TOTAL = "rbg_autoscale_decisions_total"
AUTOSCALE_CLAMPED_TOTAL = "rbg_autoscale_clamped_total"
AUTOSCALE_COOLDOWN_SUPPRESSED_TOTAL = (
    "rbg_autoscale_cooldown_suppressed_total")
AUTOSCALE_STALE_HOLDS_TOTAL = "rbg_autoscale_stale_holds_total"
AUTOSCALE_CONFLICTS_TOTAL = "rbg_autoscale_conflicts_total"
AUTOSCALE_SPARE_GRANTS_TOTAL = "rbg_autoscale_spare_grants_total"
KVT_CHUNKS_TOTAL = "rbg_kvtransfer_chunks_total"
KVT_BYTES_TOTAL = "rbg_kvtransfer_bytes_total"
KVT_STREAMS_TOTAL = "rbg_kvtransfer_streams_total"
KVT_LAYER_ADMIT_TOTAL = "rbg_kvtransfer_layer_admit_total"
KVT_DIR_LOOKUPS_TOTAL = "rbg_kvtransfer_dir_lookups_total"
KVT_DIR_INVALIDATIONS_TOTAL = "rbg_kvtransfer_dir_invalidations_total"
WORKQUEUE_ADDS_TOTAL = "rbg_workqueue_adds_total"
RECONCILE_REQUEUES_TOTAL = "rbg_reconcile_requeues_total"
RECONCILE_DEDUPED_TOTAL = "rbg_reconcile_deduped_total"
RESYNC_BACKSTOP_ENQUEUED_TOTAL = "rbg_resync_backstop_enqueued_total"
RESYNC_BACKSTOP_SKIPPED_TOTAL = "rbg_resync_backstop_skipped_total"
SCHED_SHARD_SCANS_TOTAL = "rbg_sched_shard_scans_total"
SCHED_SHARD_SKIPS_TOTAL = "rbg_sched_shard_skips_total"
WATCH_REPLAYS_TOTAL = "rbg_watch_replays_total"
WATCH_EVENTS_TOTAL = "rbg_watch_events_total"
WATCH_DELIVERIES_TOTAL = "rbg_watch_deliveries_total"
SCHED_BINDS_TOTAL = "rbg_sched_binds_total"
EVENTS_RECORDED_TOTAL = "rbg_events_recorded_total"
EVENTS_DEDUPED_TOTAL = "rbg_events_deduped_total"
EVENTS_EVICTED_TOTAL = "rbg_events_evicted_total"
TOPOLOGY_FLIPS_TOTAL = "rbg_topology_flips_total"
TOPOLOGY_HOLDS_TOTAL = "rbg_topology_holds_total"
TOPOLOGY_COST_GATED_TOTAL = "rbg_topology_cost_gated_total"
TOPOLOGY_CONFLICTS_TOTAL = "rbg_topology_conflicts_total"
KVC_TIER_HITS_TOTAL = "rbg_kvcache_tier_hits_total"
KVC_TIER_MISSES_TOTAL = "rbg_kvcache_tier_misses_total"
KVC_TIER_SPILLED_PAGES_TOTAL = "rbg_kvcache_tier_spilled_pages_total"
KVC_TIER_PROMOTED_PAGES_TOTAL = "rbg_kvcache_tier_promoted_pages_total"
KVC_TIER_EVICTED_PAGES_TOTAL = "rbg_kvcache_tier_evicted_pages_total"
KVT_DIR_REPLICATIONS_TOTAL = "rbg_kvtransfer_dir_replications_total"
ROUTER_INGRESS_TOKENS_TOTAL = "rbg_router_ingress_tokens_total"
SERVING_EARLY_REJECTS_TOTAL = "rbg_serving_early_rejects_total"
ROUTER_RING_ROUTES_TOTAL = "rbg_router_ring_routes_total"
ROUTER_RING_RESHARDS_TOTAL = "rbg_router_ring_reshards_total"
ROUTER_PEER_EVENTS_TOTAL = "rbg_router_peer_events_total"
PLANE_LEADER_TRANSITIONS_TOTAL = "rbg_plane_leader_transitions_total"
PLANE_FENCED_WRITES_TOTAL = "rbg_plane_fenced_writes_total"
PLANE_STANDBY_TAIL_EVENTS_TOTAL = "rbg_plane_standby_tail_events_total"
KVT_DIR_BREAKER_OPEN_TOTAL = "rbg_kvtransfer_dir_breaker_open_total"
KVT_CHUNKS_DUPLICATE_TOTAL = "rbg_kvtransfer_chunks_duplicate_total"
KVT_CHUNKS_REORDERED_TOTAL = "rbg_kvtransfer_chunks_reordered_total"
KVT_INTEGRITY_FAILURES_TOTAL = "rbg_kvtransfer_integrity_failures_total"
CHAOS_FAULTS_INJECTED_TOTAL = "rbg_chaos_faults_injected_total"
PLANE_SELF_DEMOTIONS_TOTAL = "rbg_plane_self_demotions_total"

# ---- gauges (last-write-wins) ----

SERVING_DRAINING = "rbg_serving_draining"
DISRUPTION_SPARE_POOL_DEPTH = "rbg_disruption_spare_pool_depth"
RACE_GUARDED_CLASSES = "rbg_race_guarded_classes"
SLO_TTFT_ATTAINMENT = "rbg_slo_ttft_attainment"
SLO_TPOT_ATTAINMENT = "rbg_slo_tpot_attainment"
SLO_GOODPUT_RPS = "rbg_slo_goodput_rps"
ROUTER_BACKEND_OUTSTANDING = "rbg_router_backend_outstanding"
ROUTER_BACKEND_DRAINING = "rbg_router_backend_draining"
AUTOSCALE_TARGET_REPLICAS = "rbg_autoscale_target_replicas"
AUTOSCALE_ACTUAL_REPLICAS = "rbg_autoscale_actual_replicas"
KVT_LINK_RATE = "rbg_kvtransfer_link_bytes_per_s"
KVT_DIR_ENTRIES = "rbg_kvtransfer_dir_entries"
WORKQUEUE_DEPTH = "rbg_workqueue_depth"
WORKQUEUE_RETRIES_PENDING = "rbg_workqueue_retries_pending"
EVENTS_OBJECTS = "rbg_events_objects"
TOPOLOGY_POSTURE = "rbg_topology_posture"
KVC_TIER_PAGES = "rbg_kvcache_tier_pages"
KVC_TIER_BYTES = "rbg_kvcache_tier_bytes"
ROUTER_RING_MEMBERS = "rbg_router_ring_members"
PLANE_LEADER_STATE = "rbg_plane_leader_state"
PLANE_LEADER_EPOCH = "rbg_plane_leader_epoch"
SERVING_RETRY_BUDGET_TOKENS = "rbg_serving_retry_budget_tokens"
DEGRADED_MODE = "rbg_degraded_mode"

# ---- histograms ----

RECONCILE_DURATION_SECONDS = "rbg_reconcile_duration_seconds"
SERVING_QUEUE_DEPTH = "rbg_serving_queue_depth"
SERVING_REQUEST_DURATION_SECONDS = "rbg_serving_request_duration_seconds"
SERVING_BATCH_OCCUPANCY = "rbg_serving_batch_occupancy"
SERVING_JOIN_LATENCY_SECONDS = "rbg_serving_join_latency_seconds"
SLO_TTFT_SECONDS = "rbg_slo_ttft_seconds"
SLO_TPOT_SECONDS = "rbg_slo_tpot_seconds"
PD_LOCK_HOLD_SECONDS = "rbg_pd_lock_hold_seconds"
KVT_ADMIT_LEAD_SECONDS = "rbg_kvtransfer_admit_lead_seconds"
KVT_LAYER_ADMIT_LEAD_SECONDS = "rbg_kvtransfer_layer_admit_lead_seconds"
KVT_LAYER_ADMIT_COVERAGE_LAYERS = (
    "rbg_kvtransfer_layer_admit_coverage_layers")
WORKQUEUE_QUEUE_AGE_SECONDS = "rbg_workqueue_queue_age_seconds"
WATCH_DISPATCH_SECONDS = "rbg_watch_dispatch_seconds"
SCHED_FEASIBILITY_SCAN_SECONDS = "rbg_sched_feasibility_scan_seconds"
TOPOLOGY_SWITCH_DURATION_SECONDS = "rbg_topology_switch_duration_seconds"
KVC_TIER_SPILL_SECONDS = "rbg_kvcache_tier_spill_seconds"
KVC_TIER_PROMOTE_SECONDS = "rbg_kvcache_tier_promote_seconds"
SERVING_PREDICTED_TTFT_SECONDS = "rbg_serving_predicted_ttft_seconds"

# ---- catalog sets (consumed by the lint rule and strict-mode registry) ----

COUNTERS = frozenset({
    RECONCILE_TOTAL,
    SERVING_SHED_TOTAL,
    SERVING_DEADLINE_EXCEEDED_TOTAL,
    SERVING_DRAINS_TOTAL,
    SERVING_DRAIN_REFUSALS_TOTAL,
    DISRUPTION_NOTICES_TOTAL,
    DISRUPTION_PREEMPTIONS_TOTAL,
    DISRUPTION_GANG_KILLS_TOTAL,
    DISRUPTION_MIGRATIONS_COMPLETED_TOTAL,
    DISRUPTION_MIGRATIONS_MISSED_DEADLINE_TOTAL,
    DISRUPTION_SLICES_RELEASED_TOTAL,
    DISRUPTION_SPARES_CONSUMED_TOTAL,
    LOCKTRACE_INVERSIONS_TOTAL,
    RACE_VIOLATIONS_TOTAL,
    JIT_UNWARMED_COMPILES_TOTAL,
    JIT_HOST_SYNCS_TOTAL,
    WIRE_CONTRACT_VIOLATIONS_TOTAL,
    TRACE_TRACES_TOTAL,
    TRACE_SPANS_DROPPED_TOTAL,
    SERVING_REQUESTS_FINISHED_TOTAL,
    SERVING_TOKENS_TOTAL,
    SLO_JUDGED_TOTAL,
    SLO_TTFT_MET_TOTAL,
    SLO_TPOT_MET_TOTAL,
    SLO_GOODPUT_TOTAL,
    AUTOSCALE_DECISIONS_TOTAL,
    AUTOSCALE_CLAMPED_TOTAL,
    AUTOSCALE_COOLDOWN_SUPPRESSED_TOTAL,
    AUTOSCALE_STALE_HOLDS_TOTAL,
    AUTOSCALE_CONFLICTS_TOTAL,
    AUTOSCALE_SPARE_GRANTS_TOTAL,
    KVT_CHUNKS_TOTAL,
    KVT_BYTES_TOTAL,
    KVT_STREAMS_TOTAL,
    KVT_LAYER_ADMIT_TOTAL,
    KVT_DIR_LOOKUPS_TOTAL,
    KVT_DIR_INVALIDATIONS_TOTAL,
    WORKQUEUE_ADDS_TOTAL,
    RECONCILE_REQUEUES_TOTAL,
    RECONCILE_DEDUPED_TOTAL,
    RESYNC_BACKSTOP_ENQUEUED_TOTAL,
    RESYNC_BACKSTOP_SKIPPED_TOTAL,
    SCHED_SHARD_SCANS_TOTAL,
    SCHED_SHARD_SKIPS_TOTAL,
    WATCH_REPLAYS_TOTAL,
    WATCH_EVENTS_TOTAL,
    WATCH_DELIVERIES_TOTAL,
    SCHED_BINDS_TOTAL,
    EVENTS_RECORDED_TOTAL,
    EVENTS_DEDUPED_TOTAL,
    EVENTS_EVICTED_TOTAL,
    TOPOLOGY_FLIPS_TOTAL,
    TOPOLOGY_HOLDS_TOTAL,
    TOPOLOGY_COST_GATED_TOTAL,
    TOPOLOGY_CONFLICTS_TOTAL,
    KVC_TIER_HITS_TOTAL,
    KVC_TIER_MISSES_TOTAL,
    KVC_TIER_SPILLED_PAGES_TOTAL,
    KVC_TIER_PROMOTED_PAGES_TOTAL,
    KVC_TIER_EVICTED_PAGES_TOTAL,
    KVT_DIR_REPLICATIONS_TOTAL,
    ROUTER_INGRESS_TOKENS_TOTAL,
    SERVING_EARLY_REJECTS_TOTAL,
    ROUTER_RING_ROUTES_TOTAL,
    ROUTER_RING_RESHARDS_TOTAL,
    ROUTER_PEER_EVENTS_TOTAL,
    PLANE_LEADER_TRANSITIONS_TOTAL,
    PLANE_FENCED_WRITES_TOTAL,
    PLANE_STANDBY_TAIL_EVENTS_TOTAL,
    KVT_DIR_BREAKER_OPEN_TOTAL,
    KVT_CHUNKS_DUPLICATE_TOTAL,
    KVT_CHUNKS_REORDERED_TOTAL,
    KVT_INTEGRITY_FAILURES_TOTAL,
    CHAOS_FAULTS_INJECTED_TOTAL,
    PLANE_SELF_DEMOTIONS_TOTAL,
})

GAUGES = frozenset({
    SERVING_DRAINING,
    DISRUPTION_SPARE_POOL_DEPTH,
    RACE_GUARDED_CLASSES,
    SLO_TTFT_ATTAINMENT,
    SLO_TPOT_ATTAINMENT,
    SLO_GOODPUT_RPS,
    ROUTER_BACKEND_OUTSTANDING,
    ROUTER_BACKEND_DRAINING,
    AUTOSCALE_TARGET_REPLICAS,
    AUTOSCALE_ACTUAL_REPLICAS,
    KVT_LINK_RATE,
    KVT_DIR_ENTRIES,
    WORKQUEUE_DEPTH,
    WORKQUEUE_RETRIES_PENDING,
    EVENTS_OBJECTS,
    TOPOLOGY_POSTURE,
    KVC_TIER_PAGES,
    KVC_TIER_BYTES,
    ROUTER_RING_MEMBERS,
    PLANE_LEADER_STATE,
    PLANE_LEADER_EPOCH,
    SERVING_RETRY_BUDGET_TOKENS,
    DEGRADED_MODE,
})

HISTOGRAMS = frozenset({
    RECONCILE_DURATION_SECONDS,
    SERVING_QUEUE_DEPTH,
    SERVING_REQUEST_DURATION_SECONDS,
    SERVING_BATCH_OCCUPANCY,
    SERVING_JOIN_LATENCY_SECONDS,
    SLO_TTFT_SECONDS,
    SLO_TPOT_SECONDS,
    PD_LOCK_HOLD_SECONDS,
    KVT_ADMIT_LEAD_SECONDS,
    KVT_LAYER_ADMIT_LEAD_SECONDS,
    KVT_LAYER_ADMIT_COVERAGE_LAYERS,
    WORKQUEUE_QUEUE_AGE_SECONDS,
    WATCH_DISPATCH_SECONDS,
    SCHED_FEASIBILITY_SCAN_SECONDS,
    TOPOLOGY_SWITCH_DURATION_SECONDS,
    KVC_TIER_SPILL_SECONDS,
    KVC_TIER_PROMOTE_SECONDS,
    SERVING_PREDICTED_TTFT_SECONDS,
})

ALL_NAMES = COUNTERS | GAUGES | HISTOGRAMS

# ---- exposition help text (render() emits it as # HELP) ----

HELP = {
    RECONCILE_TOTAL: "Reconcile passes per controller and result",
    SERVING_SHED_TOTAL: "Requests shed by admission control",
    SERVING_DEADLINE_EXCEEDED_TOTAL:
        "Requests dropped or aborted past their deadline, per stage",
    SERVING_DRAINS_TOTAL: "SIGTERM drains started",
    SERVING_DRAIN_REFUSALS_TOTAL: "Data ops refused while draining",
    DISRUPTION_NOTICES_TOTAL: "Advance maintenance notices observed",
    DISRUPTION_PREEMPTIONS_TOTAL: "No-notice slice preemptions observed",
    DISRUPTION_GANG_KILLS_TOTAL: "Whole-gang kills after partial slice loss",
    DISRUPTION_MIGRATIONS_COMPLETED_TOTAL:
        "Maintenance migrations completed before their deadline",
    DISRUPTION_MIGRATIONS_MISSED_DEADLINE_TOTAL:
        "Maintenance migrations that missed their deadline",
    DISRUPTION_SLICES_RELEASED_TOTAL: "Slices released to maintenance",
    DISRUPTION_SPARES_CONSUMED_TOTAL: "Warm spare slices granted",
    LOCKTRACE_INVERSIONS_TOTAL: "Lock acquisition-order inversions observed",
    RACE_VIOLATIONS_TOTAL: "Guarded-field accesses without the owning lock",
    JIT_UNWARMED_COMPILES_TOTAL:
        "Cataloged programs compiled after warmup_complete(), per program",
    JIT_HOST_SYNCS_TOTAL:
        "Device-to-host syncs observed by the jitwatch probe",
    WIRE_CONTRACT_VIOLATIONS_TOTAL:
        "Wire frames violating the api/ops.py contract, per op and kind",
    TRACE_TRACES_TOTAL: "Traces finalized into the trace sink, per result",
    TRACE_SPANS_DROPPED_TOTAL:
        "Spans dropped by the per-trace span bound",
    SERVING_DRAINING: "1 while this process is draining",
    DISRUPTION_SPARE_POOL_DEPTH: "Reserved warm spare slices per topology",
    RACE_GUARDED_CLASSES: "Classes instrumented by the race detector",
    RECONCILE_DURATION_SECONDS: "Reconcile latency per controller",
    SERVING_QUEUE_DEPTH: "Service queue depth observed at submission",
    SERVING_REQUEST_DURATION_SECONDS:
        "End-to-end request latency inside the serving loop",
    SERVING_BATCH_OCCUPANCY:
        "Running-batch fill fraction (running / max_batch) observed per "
        "engine step",
    SERVING_JOIN_LATENCY_SECONDS:
        "Wait between entering the engine queue and joining the running "
        "batch",
    SERVING_REQUESTS_FINISHED_TOTAL:
        "Requests that finished generation (the SLO-judged population)",
    SERVING_TOKENS_TOTAL: "Output tokens produced by finished requests",
    SLO_JUDGED_TOTAL: "Finished requests judged against the SLO targets",
    SLO_TTFT_MET_TOTAL: "Judged requests whose TTFT met its target",
    SLO_TPOT_MET_TOTAL: "Judged requests whose TPOT met its target",
    SLO_GOODPUT_TOTAL:
        "Judged requests meeting BOTH the TTFT and TPOT targets",
    SLO_TTFT_ATTAINMENT:
        "Sliding-window fraction of judged requests meeting the TTFT "
        "target",
    SLO_TPOT_ATTAINMENT:
        "Sliding-window fraction of judged requests meeting the TPOT "
        "target",
    SLO_GOODPUT_RPS:
        "Sliding-window requests/s meeting both SLO targets",
    ROUTER_BACKEND_OUTSTANDING:
        "In-flight requests the router holds against one backend",
    ROUTER_BACKEND_DRAINING: "1 while the router sees this backend draining",
    AUTOSCALE_DECISIONS_TOTAL:
        "Autoscaler actuations per role and direction (up/down)",
    AUTOSCALE_CLAMPED_TOTAL:
        "Autoscaler targets clamped by min/max or the coordination skew "
        "bound",
    AUTOSCALE_COOLDOWN_SUPPRESSED_TOTAL:
        "Autoscaler decisions suppressed by the post-actuation cooldown",
    AUTOSCALE_STALE_HOLDS_TOTAL:
        "Autoscaler evaluations held because the signal plane was stale",
    AUTOSCALE_CONFLICTS_TOTAL:
        "Autoscaler back-offs after a foreign writer touched the adapter",
    AUTOSCALE_SPARE_GRANTS_TOTAL:
        "Warm spare slices granted to autoscaler-created instances",
    AUTOSCALE_TARGET_REPLICAS:
        "Replica target the autoscaler last wrote, per role",
    AUTOSCALE_ACTUAL_REPLICAS: "Ready replicas observed per role",
    SLO_TTFT_SECONDS: "Time to first token of judged requests",
    SLO_TPOT_SECONDS:
        "Per-output-token latency after the first token, per judged "
        "request",
    KVT_CHUNKS_TOTAL: "KV transfer chunks moved, per direction",
    KVT_BYTES_TOTAL: "KV transfer payload bytes moved, per direction "
                     "and transport",
    KVT_STREAMS_TOTAL: "KV chunk streams completed, per outcome",
    KVT_DIR_LOOKUPS_TOTAL:
        "Cluster prefix-directory lookups, per result (hit/miss)",
    KVT_DIR_INVALIDATIONS_TOTAL:
        "Prefix-directory entries invalidated, per reason",
    KVT_LINK_RATE:
        "Measured KV link throughput from real transfers, per transport",
    KVT_DIR_ENTRIES: "Live prefix-directory entries",
    PD_LOCK_HOLD_SECONDS:
        "Time a PD critical-section lock was held, per lock",
    KVT_ADMIT_LEAD_SECONDS:
        "How long before its stream finished a streamed decode row was "
        "admitted (coverage-complete vs stream-close lead)",
    KVT_LAYER_ADMIT_TOTAL:
        "Layer-sliced decode admissions dispatched (first decode step "
        "started before full KV coverage)",
    KVT_LAYER_ADMIT_LEAD_SECONDS:
        "How long before FULL coverage a layer-sliced admission could "
        "start (layer-watermark-ready vs coverage-complete lead)",
    KVT_LAYER_ADMIT_COVERAGE_LAYERS:
        "Leading fully-covered layers at the moment of a layer-sliced "
        "admission",
    WORKQUEUE_ADDS_TOTAL:
        "Keys enqueued into a controller workqueue, per controller",
    RECONCILE_REQUEUES_TOTAL:
        "Reconcile keys re-queued, per controller and reason "
        "(error backoff vs requeue_after revisit)",
    RECONCILE_DEDUPED_TOTAL:
        "Dequeued keys skipped because every pending trigger version was "
        "already covered by a completed reconcile, per controller "
        "(coalesced stale events, status-only self-writes, backstop "
        "sweeps of unchanged objects)",
    RESYNC_BACKSTOP_ENQUEUED_TOTAL:
        "Keys the periodic drift-backstop resync enqueued, per controller "
        "(a healthy event path keeps this near zero useful work — the "
        "dedup counter absorbs unchanged keys)",
    RESYNC_BACKSTOP_SKIPPED_TOTAL:
        "Keys the drift-backstop resync skipped because the event path "
        "already reconciled them since the last backstop tick, per "
        "controller",
    SCHED_SHARD_SCANS_TOTAL:
        "Topology shards (slices) whose hosts the feasibility scan "
        "actually visited",
    SCHED_SHARD_SKIPS_TOTAL:
        "Topology shards pruned by the free-capacity index before any "
        "host was visited (shard cannot fit the gang)",
    WATCH_REPLAYS_TOTAL:
        "Store watch events replayed to a subscriber resuming from a "
        "resourceVersion watermark, per kind",
    WATCH_EVENTS_TOTAL: "Store watch events published, per kind and type",
    WATCH_DELIVERIES_TOTAL:
        "Watch handler invocations (event fan-out), per kind",
    SCHED_BINDS_TOTAL: "Pods bound to nodes by the scheduler",
    EVENTS_RECORDED_TOTAL:
        "Control-plane events recorded, per type (dedup bumps included)",
    EVENTS_DEDUPED_TOTAL:
        "Event records collapsed into an existing record's count",
    EVENTS_EVICTED_TOTAL:
        "Event occurrences evicted by the per-object/per-plane bounds",
    WORKQUEUE_DEPTH: "Ready keys in a controller workqueue, per controller",
    WORKQUEUE_RETRIES_PENDING:
        "Keys currently carrying failure backoff, per controller",
    EVENTS_OBJECTS: "Objects with live event history in the recorder",
    WORKQUEUE_QUEUE_AGE_SECONDS:
        "Enqueue-to-dequeue wait of workqueue keys (intentional "
        "add_after delay excluded), per controller",
    WATCH_DISPATCH_SECONDS:
        "Time to deliver one store event to every subscriber, per kind",
    SCHED_FEASIBILITY_SCAN_SECONDS:
        "Scheduler feasibility scan (placement plan computation) duration",
    TOPOLOGY_POSTURE:
        "PD shape of a role group: 0 unified, 1 disaggregated, 0.5 while "
        "a flip is in progress",
    TOPOLOGY_FLIPS_TOTAL:
        "Completed topology flips, per group and target shape",
    TOPOLOGY_HOLDS_TOTAL:
        "Topology evaluations that held the current shape, per reason "
        "(stale / deadband / stabilizing / cooldown / no_ratio / "
        "low_sample)",
    TOPOLOGY_COST_GATED_TOTAL:
        "Topology flips vetoed because the estimated KV move cost over "
        "measured link rates exceeded the gate",
    TOPOLOGY_CONFLICTS_TOTAL:
        "Topology flips backed off because another actuator's adapter "
        "write was in flight",
    TOPOLOGY_SWITCH_DURATION_SECONDS:
        "Wall time of a completed topology flip (warm start to old-shape "
        "drained), per target shape",
    KVC_TIER_HITS_TOTAL:
        "Prefix-cache hits per tier (device = radix, host = spill tier)",
    KVC_TIER_MISSES_TOTAL:
        "Prefix lookups that missed every cache tier",
    KVC_TIER_SPILLED_PAGES_TOTAL:
        "KV pages spilled device-tier → host-tier on device eviction",
    KVC_TIER_PROMOTED_PAGES_TOTAL:
        "KV pages promoted host-tier → device-tier on a host hit",
    KVC_TIER_EVICTED_PAGES_TOTAL:
        "Cached KV pages evicted from a tier's bounded store, per tier "
        "(host = byte-budget LRU-by-hotness eviction)",
    KVC_TIER_PAGES: "Cached KV pages resident, per tier",
    KVC_TIER_BYTES: "Cached KV bytes resident, per tier",
    KVC_TIER_SPILL_SECONDS:
        "Device→host page spill latency (device readback + trie insert)",
    KVC_TIER_PROMOTE_SECONDS:
        "Host→device page promotion latency (trie take + device scatter)",
    KVT_DIR_REPLICATIONS_TOTAL:
        "Hot single-holder prefixes the router deliberately routed to a "
        "non-holder so a second replica computes and registers them",
    ROUTER_INGRESS_TOKENS_TOTAL:
        "Tokens observed at router ingress, per kind (prefill = prompt "
        "tokens dispatched, decode = output tokens delivered) — the "
        "production prefill:decode ratio signal for the topology policy",
    SERVING_EARLY_REJECTS_TOTAL:
        "Requests shed at ingress because predicted TTFT (queue wait + "
        "prefill net of the prefix hit this request would get) exceeded "
        "the SLO gate — before any prefill compute was spent",
    SERVING_PREDICTED_TTFT_SECONDS:
        "Predicted TTFT computed by the admission gate for each "
        "submission it evaluated",
    ROUTER_RING_MEMBERS:
        "Live (non-draining) router replicas on the consistent-hash ring",
    ROUTER_RING_ROUTES_TOTAL:
        "Tier routing decisions, per result (affinity = hash owner taken, "
        "fallback = bounded-load spill to the next replica, rescue = "
        "owner dead/draining, range absorbed by a peer)",
    ROUTER_RING_RESHARDS_TOTAL:
        "Ring membership changes (a router joined, drained, or died — "
        "its hash range moved to peers)",
    ROUTER_PEER_EVENTS_TOTAL:
        "Router-to-router feed events delivered, per type (backend "
        "health/draining transitions, measured link rates, ingress "
        "token counters)",
    PLANE_LEADER_STATE:
        "1 while this control-plane candidate holds the leader lease, "
        "0 on standby, per plane",
    PLANE_LEADER_EPOCH:
        "Fencing epoch of the current leader lease (monotone; bumps on "
        "every takeover)",
    PLANE_LEADER_TRANSITIONS_TOTAL:
        "Leadership acquisitions, per plane (a takeover after leader "
        "death or graceful handover)",
    PLANE_FENCED_WRITES_TOTAL:
        "Store writes refused because they carried a stale lease epoch "
        "(a deposed leader's in-flight actuation), per lease",
    PLANE_STANDBY_TAIL_EVENTS_TOTAL:
        "Store watch events tailed by a standby plane keeping its resume "
        "watermark warm, per plane",
    KVT_DIR_BREAKER_OPEN_TOTAL:
        "Prefix-directory client circuit-breaker opens (decorrelated-"
        "jitter exponential window, not a fixed wall-clock hold)",
    SERVING_RETRY_BUDGET_TOKENS:
        "Retry-budget tokens currently available in THIS router process "
        "(fleet-wide effective budget is N x per-replica after router "
        "scale-out)",
    KVT_CHUNKS_DUPLICATE_TOTAL:
        "KV chunk frames delivered more than once (already fully "
        "written when they arrived) — a degrading link retransmits "
        "before it truncates",
    KVT_CHUNKS_REORDERED_TOTAL:
        "KV chunk frames that arrived out of send order (a lower seq "
        "after a higher one, duplicates excluded) — reorder depth is a "
        "link-health leading indicator",
    KVT_INTEGRITY_FAILURES_TOTAL:
        "KV payloads whose bytes failed their end-to-end checksum, per "
        "surface (chunk = wire frame at decode commit, pool = cached "
        "page at match/extend, peer_fetch = directory-advertised "
        "remote page) — every one was refused, never served",
    CHAOS_FAULTS_INJECTED_TOTAL:
        "Faults the deterministic chaos plane injected, per kind "
        "(partition / corrupt / skew / brownout) — drill-only; nonzero "
        "in production means a chaos schedule leaked into prod config",
    PLANE_SELF_DEMOTIONS_TOTAL:
        "Leaders that stepped down proactively because lease renewal "
        "stopped landing (partition from the lease store) before their "
        "TTL could expire under a contending standby, per plane",
    DEGRADED_MODE:
        "1 while a graceful-degradation ladder rung is engaged, per "
        "ladder (directory = local-affinity-only routing, peer_feed = "
        "stale tier members excluded from the ring, lease = leader "
        "self-demoted on renewal failure) — 0 after heal",
}

# ---- span names (obs/trace.py) ----
#
# Same contract as the metric catalog: every span name the tracer emits is
# declared here once, the ``span-name-registry`` lint rule flags literals
# that are not, and ``RBG_TRACE_STRICT=1`` adds the same check at span
# creation time. Naming contract: lowercase dotted ``component.phase``.

SPAN_HTTP_REQUEST = "http.request"
SPAN_ROUTER_REQUEST = "router.request"
SPAN_ROUTER_ATTEMPT = "router.attempt"
SPAN_ENGINE_OP = "engine.op"
SPAN_SERVICE_QUEUE_WAIT = "service.queue_wait"
SPAN_SERVICE_SCAN = "service.scan"
SPAN_PD_PREFILL = "pd.prefill"
SPAN_PD_KV_HANDOFF = "pd.kv_handoff"
SPAN_KVT_PUSH = "kvtransfer.push"
SPAN_KVT_COMMIT = "kvtransfer.commit"
SPAN_PD_LAYER_SLICED_STEP = "pd.layer_sliced_step"
SPAN_STRESS_REQUEST = "stress.request"
SPAN_CTRL_EVENT = "controller.event"
SPAN_CTRL_RECONCILE = "controller.reconcile"
SPAN_TOPOLOGY_FLIP = "topology.flip"
SPAN_TOPOLOGY_WARM = "topology.warm"
SPAN_TOPOLOGY_CUTOVER = "topology.cutover"
SPAN_TOPOLOGY_DRAIN = "topology.drain"
SPAN_PLANE_TAKEOVER = "plane.takeover"
SPAN_ROUTER_RESHARD = "router.reshard"
# The step timeline (docs/observability.md "Step timeline"): phases of one
# turn of the serving loop, emitted as ``jax.profiler`` annotations by
# ``trace.annotation`` on the loop thread, never as per-request spans.
# The timeline's clocks and counts are no metrics of this catalog: they ride
# the ``metrics`` op as ``Engine.metrics`` holds them (docs/observability.md
# section 2.5 lists each: ``steps_run``, ``unified_steps_run`` and beside it
# ``unified_rows`` / ``unified_chunk_rows``, the rows of the unified steps
# that dispatched and those of them that held a chunk; ``lagged_steps``
# beside ``device_waited_steps``, the steps dispatched while the step before
# them was unread).
SPAN_SERVICE_INTAKE = "service.intake"
SPAN_SERVICE_DELIVER = "service.deliver"
SPAN_SERVICE_IDLE = "service.idle"
SPAN_ENGINE_STEP = "engine.step"
SPAN_ENGINE_ADMIT = "engine.admit"
SPAN_ENGINE_PACK = "engine.pack"
SPAN_ENGINE_DISPATCH = "engine.dispatch"
SPAN_ENGINE_SYNC = "engine.sync"
SPAN_ENGINE_EMIT = "engine.emit"
# The six sub-phases that tile ``engine.pack`` and ``engine.dispatch`` of a
# unified and of a fused decode step (``engine._Phase.mark``): flat, one
# open at a time, each with a cumulative clock by the kind of step,
# ``t_unified_<sub>_s`` / ``t_decode_<sub>_s`` with ``<sub>`` the name's last
# word (a decode step has no ``sample``: the window samples inside its
# program). Every mark also asks whether the step in flight has finished
# (``Engine.probe``), which is what ``t_starved_s`` (with its split
# ``t_starved_between_s`` / ``t_starved_pack_s`` / ``t_starved_dispatch_s``)
# and ``t_starved_max_s`` are reckoned from; ``uploads`` counts the
# host-to-device puts of the two step paths (``Engine._put``).
SPAN_ENGINE_PACK_ROWS = "engine.pack.rows"
SPAN_ENGINE_PACK_FILL = "engine.pack.fill"
SPAN_ENGINE_PACK_UPLOAD = "engine.pack.upload"
SPAN_ENGINE_DISPATCH_CALL = "engine.dispatch.call"
SPAN_ENGINE_DISPATCH_BOOK = "engine.dispatch.book"
SPAN_ENGINE_DISPATCH_SAMPLE = "engine.dispatch.sample"
# A late turn of the loop (zero length, entered when its record is made)
# and the relay's socket write of one token frame (a connection thread).
SPAN_ENGINE_LATE_STEP = "engine.late_step"
SPAN_SERVER_RELAY_SEND = "server.relay_send"
# Inside ``engine.pack``, a model with window layers: the rows' pages of
# the window class brought to what the step reads (those below every later
# window given back, those the step writes taken;
# ``Engine._window_pages_for``). The wire counters beside it:
# ``kv_window_pages_released``, ``kv_window_live_token_steps``,
# ``kv_window_held_slot_steps``.
SPAN_KV_WINDOW_RELEASE = "kv.window_release"

# ---- model scopes (device time by block) ----
#
# The ``jax.named_scope`` components ``models/llama.py`` and
# ``engine/sampler.py`` open around a layer's blocks: a device trace
# carries them in each operation's path, and the benchmark's
# ``device.*_share`` metrics sum device time by them
# (docs/observability.md "Outlet 2"). Every dot of a step program sits in
# exactly one of the first five (``tests/test_layer_walk.py``); ``router``
# and ``shared`` open inside ``moe`` (paths ``moe/router``, ``moe/shared``).
MODEL_SCOPES = ("attention", "mlp", "moe", "lm_head", "sampler")
MOE_INNER_SCOPES = ("router", "shared")
# Inside ``attention``: a recurrent layer's mixer and its ``wo``, by kind
# (``models/llama.py::_kda_attention``, ``_conv_attention``);
# ``device.kda_share`` and ``device.conv_share`` read the paths.
# ``window``: a window layer's mixer and its ``wo`` (a full layer's stay
# directly under ``attention``); ``device.window_attn_share`` reads it.
ATTENTION_INNER_SCOPES = ("kda", "conv", "window")
# Parts of a mixer with a path of their own under ``attention``: the
# output gate of a gated grouped-query attention layer
# (``models/llama.py::_attn_gate``; ``device.attn_gate_share``), and a KDA
# layer's projections with its ``wo`` (``device.kda_proj_share``).
ATTENTION_PART_SCOPES = ("gate", "kda/proj")
# BESIDE the five above and inside none (they hold no dot): the two norms
# a model with sandwich norms puts on a sub-layer's output before its
# residual add (``models/llama.py::_post_norm``), and the final norm that
# closes every pass of a looped model (``_pass_norm``; a model of one pass
# norms inside ``lm_head``). ``device.post_norm_share`` reads both.
NORM_SCOPES = ("post_norm", "pass_norm")

# ---- kernel calls (device time by kernel) ----
#
# The Pallas kernels' jitted wrappers: a device trace prints each custom
# call under its wrapper's name (``_moe_visit_call.<n>``), and the
# benchmark's ``kernel.*`` metrics find it by that name
# (docs/observability.md "Outlet 2"), so a rename is a change of a metric's
# source. The ``_q`` forms (int8 pools) share their prefix.
KERNEL_CALLS = (
    "_decode_call",             # paged_attention_kernel: a decode walk
    "_mla_decode_call",         # ... over latent pages
    "_block_ragged_call",       # ragged_attention_kernel: a packed step
    "_block_ragged_mla_call",   # ... over latent pages
    "_kda_decode_call",         # kda_kernel: a decode step's delta rule
    "_moe_visit_call",          # moe_visit_kernel: a decode step's visits
)                               # to its hit experts, a call a layer

# ---- jitted program catalog (jitwatch sentry + warmers) ----
#
# Same contract as the metric catalog: every named hot-path XLA program
# the engine builds is declared here once — the builders stamp the inner
# callable's __name__ with the constant (XLA's sym_name is "jit_" + that
# name), the warmers pre-compile them, and the jitwatch sentry gates on
# exactly this set after warmup_complete(). A program missing here is
# invisible to the recompile gate; a warmer that silently stops covering
# a cataloged variant is a drill failure. Naming contract: ``rbg_<area>``.

PROGRAM_RAGGED_FWD = "rbg_ragged_fwd"          # Engine._get_ragged_fn
PROGRAM_PAGED_FWD = "rbg_paged_fwd"            # Engine._get_fwd
PROGRAM_FUSED_DECODE = "rbg_fused_decode"      # Engine._get_decode_fn
PROGRAM_SPEC_VERIFY = "rbg_spec_verify"        # Engine._get_spec_fn
PROGRAM_SAMPLER = "rbg_sampler"                # Engine._get_sampler
PROGRAM_PLACE_TOKENS = "rbg_place_tokens"      # engine._place (Engine.warm_place)
PROGRAM_PD_WINDOW = "rbg_pd_window"            # DecodeWorker._get_window_fn
PROGRAM_PD_HEAD = "rbg_pd_head"                # DecodeWorker._get_head_fn
PROGRAM_EMBED_POOLED = "rbg_embed_pooled"      # service._embed_batch
PROGRAM_KVTIER_PROMOTE = "rbg_kvtier_promote"  # kvtier._promote_scatter

PROGRAMS = frozenset({
    PROGRAM_RAGGED_FWD,
    PROGRAM_PAGED_FWD,
    PROGRAM_FUSED_DECODE,
    PROGRAM_SPEC_VERIFY,
    PROGRAM_SAMPLER,
    PROGRAM_PLACE_TOKENS,
    PROGRAM_PD_WINDOW,
    PROGRAM_PD_HEAD,
    PROGRAM_EMBED_POOLED,
    PROGRAM_KVTIER_PROMOTE,
})

# ---- bucketing-helper catalog (bucket-discipline lint rule) ----
#
# The registered shape launderers: a raw shape (len(...), .shape) may
# reach a jitted program's cache key or a program-getter argument only
# through one of these (each carries a ``# bucket_fn`` annotation at its
# definition). The static rule audits the annotation set against this
# catalog so a helper added in code but not cataloged (or vice versa) is
# itself a finding.

BUCKET_FNS = frozenset({
    "_pow2_bucket",      # engine/kvtier.py — pow2 page counts
    "_bucket",           # engine/engine.py — decode_buckets table
    "_token_bucket",     # engine/engine.py — packed-token pow2 (>= 8)
    "_chunk_bucket",     # engine/service.py — chunk-multiple pow2
})

SPANS = frozenset({
    SPAN_HTTP_REQUEST,
    SPAN_ROUTER_REQUEST,
    SPAN_ROUTER_ATTEMPT,
    SPAN_ENGINE_OP,
    SPAN_SERVICE_QUEUE_WAIT,
    SPAN_SERVICE_SCAN,
    SPAN_PD_PREFILL,
    SPAN_PD_KV_HANDOFF,
    SPAN_KVT_PUSH,
    SPAN_KVT_COMMIT,
    SPAN_PD_LAYER_SLICED_STEP,
    SPAN_STRESS_REQUEST,
    SPAN_CTRL_EVENT,
    SPAN_CTRL_RECONCILE,
    SPAN_TOPOLOGY_FLIP,
    SPAN_TOPOLOGY_WARM,
    SPAN_TOPOLOGY_CUTOVER,
    SPAN_TOPOLOGY_DRAIN,
    SPAN_PLANE_TAKEOVER,
    SPAN_ROUTER_RESHARD,
    SPAN_SERVICE_INTAKE,
    SPAN_SERVICE_DELIVER,
    SPAN_SERVICE_IDLE,
    SPAN_ENGINE_STEP,
    SPAN_ENGINE_ADMIT,
    SPAN_ENGINE_PACK,
    SPAN_ENGINE_DISPATCH,
    SPAN_ENGINE_SYNC,
    SPAN_ENGINE_EMIT,
    SPAN_ENGINE_PACK_ROWS,
    SPAN_ENGINE_PACK_FILL,
    SPAN_ENGINE_PACK_UPLOAD,
    SPAN_ENGINE_DISPATCH_CALL,
    SPAN_ENGINE_DISPATCH_BOOK,
    SPAN_ENGINE_DISPATCH_SAMPLE,
    SPAN_ENGINE_LATE_STEP,
    SPAN_SERVER_RELAY_SEND,
    SPAN_KV_WINDOW_RELEASE,
})
