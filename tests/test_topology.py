"""Adaptive agg↔disagg topology subsystem (rbg_tpu/topology): pure
policy transitions under an injected clock, and the controller's
persistent flip state machine against a live mini-plane — every
transition scripted, no engine: HOLD on stale/no-ratio/deadband,
cost-gate veto, cooldown suppression, mid-flip plane restart resuming
from annotations, and the autoscaler-conflict backoff.
"""

from __future__ import annotations

import json
import time

import pytest

from rbg_tpu.api import constants as C
from rbg_tpu.api.group import IdentityMode, ScalingAdapterHook
from rbg_tpu.obs import names
from rbg_tpu.obs.metrics import REGISTRY
from rbg_tpu.runtime.controllers.scalingadapter import adapter_name
from rbg_tpu.runtime.plane import ControlPlane
from rbg_tpu.testutil import make_group, make_tpu_nodes, simple_role
from rbg_tpu.topology import (
    GroupTopology, POSTURE_DISAGG, POSTURE_UNIFIED, REC_HOLD,
    TopologyConfig, TopologyPolicy, TopologyPolicyConfig, TopologySignals,
)


def _sig(ratio=None, fresh=True, judged=10, kv=None, link=None, **kw):
    return TopologySignals(fresh=fresh, prefill_decode_ratio=ratio,
                           judged=judged, kv_bytes_to_move=kv,
                           link_bytes_per_s=link, **kw)


def _cfg(**kw) -> TopologyPolicyConfig:
    base = dict(disagg_ratio=6.0, unified_ratio=2.0, min_judged=3,
                disagg_stabilization_s=1.0, unified_stabilization_s=2.0,
                cooldown_s=5.0, max_switch_cost_s=10.0)
    base.update(kw)
    return TopologyPolicyConfig(**base)


# ---- policy (pure, injected clock) -----------------------------------------


def test_policy_flips_off_live_router_ingress_counters():
    """The production ratio seam: real rbg_router_ingress_tokens_total
    counter increments, sampled by the windowed plane, must drive the
    policy to a flip — no drill-only scripted signals involved."""
    from rbg_tpu.obs.metrics import Registry
    from rbg_tpu.obs.timeseries import TimeSeriesSampler
    from rbg_tpu.topology import router_ingress_signals_fn

    reg = Registry()
    sampler = TimeSeriesSampler(registry=reg, interval_s=1.0,
                                retention_s=300.0)
    fn = router_ingress_signals_fn(sampler, window_s=60.0)
    # No samples yet → absence of signal, never ratio 0/∞.
    assert fn(None) == {}
    reg.inc(names.ROUTER_INGRESS_TOKENS_TOTAL, 100.0, kind="prefill")
    reg.inc(names.ROUTER_INGRESS_TOKENS_TOTAL, 100.0, kind="decode")
    sampler.sample_now(now=0.0)
    # Sustained long-prompt mix: 10:1 prompt:output tokens at ingress —
    # what a router serving system-prompt-heavy traffic would publish.
    reg.inc(names.ROUTER_INGRESS_TOKENS_TOTAL, 10000.0, kind="prefill")
    reg.inc(names.ROUTER_INGRESS_TOKENS_TOTAL, 1000.0, kind="decode")
    sampler.sample_now(now=10.0)
    extras = fn(None)
    assert 9.0 <= extras["prefill_decode_ratio"] <= 11.0
    # One side idle → no ratio (the controller falls back / HOLDs).
    reg2 = Registry()
    s2 = TimeSeriesSampler(registry=reg2, interval_s=1.0, retention_s=300.0)
    reg2.inc(names.ROUTER_INGRESS_TOKENS_TOTAL, 100.0, kind="prefill")
    s2.sample_now(now=0.0)
    reg2.inc(names.ROUTER_INGRESS_TOKENS_TOTAL, 900.0, kind="prefill")
    s2.sample_now(now=10.0)
    assert router_ingress_signals_fn(s2, window_s=60.0)(None) == {}
    # The measured ratio drives a real flip through the policy's own
    # stabilization machinery.
    p = TopologyPolicy(_cfg())
    sig = _sig(ratio=extras["prefill_decode_ratio"])
    assert p.decide(0.0, sig, POSTURE_UNIFIED).recommendation == REC_HOLD
    d = p.decide(1.1, sig, POSTURE_UNIFIED)
    assert d.recommendation == POSTURE_DISAGG


def test_policy_stale_holds_and_forgets_onset():
    p = TopologyPolicy(_cfg())
    d = p.decide(0.0, _sig(ratio=10.0), POSTURE_UNIFIED)
    assert d.recommendation == REC_HOLD and d.suppressed == "stabilizing"
    # Stale window in the middle forgets the pressure onset...
    d = p.decide(0.5, _sig(ratio=10.0, fresh=False), POSTURE_UNIFIED)
    assert d.suppressed == "stale"
    # ...so pressure at t=1.2 has NOT been sustained since t=0.
    d = p.decide(1.2, _sig(ratio=10.0), POSTURE_UNIFIED)
    assert d.recommendation == REC_HOLD and d.suppressed == "stabilizing"
    d = p.decide(2.3, _sig(ratio=10.0), POSTURE_UNIFIED)
    assert d.recommendation == POSTURE_DISAGG


def test_policy_missing_ratio_and_low_sample_hold():
    p = TopologyPolicy(_cfg())
    d = p.decide(0.0, _sig(ratio=None), POSTURE_UNIFIED)
    assert d.recommendation == REC_HOLD and d.suppressed == "no_ratio"
    d = p.decide(1.0, _sig(ratio=10.0, judged=1), POSTURE_UNIFIED)
    assert d.recommendation == REC_HOLD and d.suppressed == "low_sample"


def test_policy_deadband_and_already_there_hold():
    p = TopologyPolicy(_cfg())
    d = p.decide(0.0, _sig(ratio=4.0), POSTURE_UNIFIED)
    assert d.recommendation == REC_HOLD and d.suppressed == "deadband"
    d = p.decide(1.0, _sig(ratio=1.0), POSTURE_UNIFIED)
    assert d.recommendation == REC_HOLD and d.suppressed is None


def test_policy_direction_split_stabilization_and_both_directions():
    p = TopologyPolicy(_cfg())
    assert p.decide(0.0, _sig(ratio=10.0),
                    POSTURE_UNIFIED).suppressed == "stabilizing"
    d = p.decide(1.1, _sig(ratio=10.0), POSTURE_UNIFIED)
    assert d.recommendation == POSTURE_DISAGG
    # The unified direction uses ITS OWN (longer) window, and the onset
    # restarts when the pressure direction changes.
    p2 = TopologyPolicy(_cfg())
    assert p2.decide(0.0, _sig(ratio=1.0),
                     POSTURE_DISAGG).suppressed == "stabilizing"
    assert p2.decide(1.1, _sig(ratio=1.0),
                     POSTURE_DISAGG).suppressed == "stabilizing"
    d = p2.decide(2.2, _sig(ratio=1.0), POSTURE_DISAGG)
    assert d.recommendation == POSTURE_UNIFIED


def test_policy_cooldown_suppresses_and_revoke_returns_it():
    p = TopologyPolicy(_cfg())
    p.decide(0.0, _sig(ratio=10.0), POSTURE_UNIFIED)
    d = p.decide(1.1, _sig(ratio=10.0), POSTURE_UNIFIED)
    assert d.recommendation == POSTURE_DISAGG
    assert p.cooldown_remaining(1.2) > 0
    # Flip back immediately: suppressed by cooldown even after the
    # unified stabilization window.
    p.decide(1.2, _sig(ratio=1.0), POSTURE_DISAGG)
    d = p.decide(3.4, _sig(ratio=1.0), POSTURE_DISAGG)
    assert d.recommendation == REC_HOLD and d.suppressed == "cooldown"
    # revoke(): the controller could not START the flip — the retry is
    # not charged cooldown + a fresh stabilization window.
    p3 = TopologyPolicy(_cfg())
    p3.decide(0.0, _sig(ratio=10.0), POSTURE_UNIFIED)
    d = p3.decide(1.1, _sig(ratio=10.0), POSTURE_UNIFIED)
    assert d.recommendation == POSTURE_DISAGG
    p3.revoke(d)
    assert p3.cooldown_remaining(1.2) == 0.0
    d = p3.decide(1.2, _sig(ratio=10.0), POSTURE_UNIFIED)
    assert d.recommendation == POSTURE_DISAGG


def test_policy_cost_gate_vetoes_until_affordable():
    p = TopologyPolicy(_cfg(max_switch_cost_s=2.0))
    p.decide(0.0, _sig(ratio=10.0), POSTURE_UNIFIED)
    # 1 GiB over 10 MB/s ~ 107 s: vetoed, with the estimate reported.
    d = p.decide(1.1, _sig(ratio=10.0, kv=float(1 << 30), link=10e6),
                 POSTURE_UNIFIED)
    assert d.recommendation == REC_HOLD and d.suppressed == "cost_gated"
    assert d.est_switch_cost_s == pytest.approx((1 << 30) / 10e6)
    # The veto does NOT burn cooldown; once the link speeds up (or the
    # resident KV shrinks) the same pressure flips.
    d = p.decide(1.2, _sig(ratio=10.0, kv=float(1 << 30), link=2e9),
                 POSTURE_UNIFIED)
    assert d.recommendation == POSTURE_DISAGG
    # Unknown cost (no measured link yet) never blocks the first flip.
    p2 = TopologyPolicy(_cfg(max_switch_cost_s=2.0))
    p2.decide(0.0, _sig(ratio=10.0, kv=float(1 << 30)), POSTURE_UNIFIED)
    d = p2.decide(1.1, _sig(ratio=10.0, kv=float(1 << 30)),
                  POSTURE_UNIFIED)
    assert d.recommendation == POSTURE_DISAGG


def test_policy_disabled_holds():
    p = TopologyPolicy(_cfg(enabled=False))
    d = p.decide(0.0, _sig(ratio=10.0), POSTURE_UNIFIED)
    assert d.recommendation == REC_HOLD and d.suppressed == "disabled"


# ---- controller state machine (live mini-plane, scripted signals) ----------


GROUP = "tp"


def _mk_plane(script: dict, candidacy_log=None, groups=None,
              policy_kw=None):
    """Mini-plane with one 3-role group and a TopologyController whose
    signals come from the mutable ``script`` dict."""
    gt = GroupTopology(group=GROUP, unified_replicas=2,
                       prefill_replicas=1, decode_replicas=1)

    def signals_fn(_gt):
        return dict(script)

    def candidacy_fn(group, role, active):
        if candidacy_log is not None:
            candidacy_log.append((role, active))

    pol = dict(disagg_ratio=6.0, unified_ratio=2.0, min_judged=3,
               disagg_stabilization_s=0.1, unified_stabilization_s=0.1,
               cooldown_s=0.3, max_switch_cost_s=0.0)
    pol.update(policy_kw or {})
    cfg = TopologyConfig(
        groups=[gt], policy=TopologyPolicyConfig(**pol),
        eval_period_s=0.05, window_s=2.0, stale_after_s=10.0,
        signals_fn=signals_fn, candidacy_fn=candidacy_fn)
    plane = ControlPlane(backend="fake", topology=cfg)
    make_tpu_nodes(plane.store, slices=2, hosts_per_slice=2)
    return plane, gt


def _mk_group(gt):
    roles = []
    for name, n in ((gt.unified_role, gt.unified_replicas),
                    (gt.prefill_role, 0), (gt.decode_role, 0)):
        r = simple_role(name, replicas=n)
        r.identity = IdentityMode.RANDOM
        r.drain_seconds = 0.2
        r.scaling_adapter = ScalingAdapterHook(enabled=True,
                                               min_replicas=0,
                                               max_replicas=4)
        roles.append(r)
    return make_group(GROUP, *roles)


def _ann(plane, key):
    g = plane.store.get("RoleBasedGroup", "default", GROUP, copy_=False)
    return g.metadata.annotations.get(key)


def test_controller_full_flip_lifecycle():
    script = {"fresh": True, "prefill_decode_ratio": 1.0, "judged": 20}
    cand = []
    plane, gt = _mk_plane(script, candidacy_log=cand)
    flips0 = REGISTRY.counter(names.TOPOLOGY_FLIPS_TOTAL, group=GROUP,
                              target=POSTURE_DISAGG)
    with plane:
        plane.apply(_mk_group(gt))
        plane.wait_group_ready(GROUP, timeout=30)
        # Chat mix: no flip, posture unified.
        time.sleep(0.3)
        assert _ann(plane, C.ANN_TOPOLOGY_STATE) is None
        assert REGISTRY.gauge(names.TOPOLOGY_POSTURE, group=GROUP) == 0.0
        # Sustained long-prompt mix: flip to disagg must run the whole
        # machine — warm, cutover, drain — and land with the old shape
        # gone.
        script["prefill_decode_ratio"] = 12.0
        plane.wait_for(
            lambda: _ann(plane, C.ANN_TOPOLOGY_POSTURE) == POSTURE_DISAGG
            and not _ann(plane, C.ANN_TOPOLOGY_STATE),
            timeout=30, desc="flip completed")
        # Old shape drained: no unified instances survive.
        assert not plane.store.list(
            "RoleInstance", namespace="default",
            selector={C.LABEL_GROUP_NAME: GROUP,
                      C.LABEL_ROLE_NAME: gt.unified_role})
        # Target shape serving.
        g = plane.store.get("RoleBasedGroup", "default", GROUP)
        assert g.status.role(gt.prefill_role).ready_replicas >= 1
        assert g.status.role(gt.decode_role).ready_replicas >= 1
        # Adapters: old shape written to 0, both stamped (two-writer
        # protocol — whoever writes, stamps).
        sa = plane.store.get("ScalingAdapter", "default",
                             adapter_name(GROUP, gt.unified_role))
        assert sa.spec.replicas == 0
        assert sa.metadata.annotations[C.ANN_AUTOSCALE_LAST_WRITE] == "0"
        sa = plane.store.get("ScalingAdapter", "default",
                             adapter_name(GROUP, gt.prefill_role))
        assert sa.spec.replicas == 1
        assert sa.metadata.annotations[C.ANN_AUTOSCALE_LAST_WRITE] == "1"
        # Candidacy flipped role-by-role: targets active BEFORE the old
        # role was withdrawn.
        on = [i for i, (r, a) in enumerate(cand) if a]
        off = [i for i, (r, a) in enumerate(cand) if not a]
        assert on and off and max(on[:2]) < min(off)
        assert (gt.unified_role, False) in cand
        # Serving-roles annotation reflects the new shape only.
        serving = json.loads(_ann(plane, C.ANN_TOPOLOGY_SERVING))
        assert serving == sorted([gt.prefill_role, gt.decode_role])
        # The annotation clear and the gauge write are two systems (store
        # + registry) — the gauge lands an instant after the wait_for
        # condition above, so poll it rather than race it.
        plane.wait_for(
            lambda: REGISTRY.gauge(names.TOPOLOGY_POSTURE,
                                   group=GROUP) == 1.0,
            timeout=10, desc="posture gauge settled")
        assert REGISTRY.counter(names.TOPOLOGY_FLIPS_TOTAL, group=GROUP,
                                target=POSTURE_DISAGG) == flips0 + 1


def test_controller_mid_flip_restart_resumes_from_annotations():
    script = {"fresh": True, "prefill_decode_ratio": 12.0, "judged": 20}
    plane, gt = _mk_plane(script)
    store = plane.store
    with plane:
        plane.apply(_mk_group(gt))
        plane.wait_group_ready(GROUP, timeout=30)
        plane.wait_for(lambda: _ann(plane, C.ANN_TOPOLOGY_STATE),
                       timeout=30, desc="flip started")
    # Plane died mid-flip. A FRESH plane over the same store (new
    # controller instance, no in-memory state) must resume the flip from
    # the annotations and complete it.
    assert _ann(plane, C.ANN_TOPOLOGY_STATE) in ("Warming", "CutOver",
                                                 "Draining")
    cfg2 = TopologyConfig(
              groups=[gt],
              policy=TopologyPolicyConfig(
                  disagg_ratio=6.0, unified_ratio=2.0, min_judged=3,
                  disagg_stabilization_s=0.1,
                  unified_stabilization_s=0.1, cooldown_s=0.3,
                  max_switch_cost_s=0.0),
              eval_period_s=0.05, window_s=2.0, stale_after_s=10.0,
              signals_fn=lambda _gt: dict(script))
    resumed = ControlPlane(store=store, backend="fake", topology=cfg2)
    with resumed:
        resumed.wait_for(
            lambda: _ann(resumed, C.ANN_TOPOLOGY_POSTURE)
            == POSTURE_DISAGG
            and not _ann(resumed, C.ANN_TOPOLOGY_STATE),
            timeout=30, desc="resumed flip completed")
        assert not resumed.store.list(
            "RoleInstance", namespace="default",
            selector={C.LABEL_GROUP_NAME: GROUP,
                      C.LABEL_ROLE_NAME: gt.unified_role})


def test_controller_autoscaler_conflict_backs_off():
    # In the deadband until the foreign write has landed: a controller
    # that already wants the flip can start it before the write (0.1 s of
    # stabilization against this thread's scheduling), and no conflict is
    # ever counted.
    script = {"fresh": True, "prefill_decode_ratio": 4.0, "judged": 20}
    plane, gt = _mk_plane(script)
    conflicts0 = REGISTRY.counter(names.TOPOLOGY_CONFLICTS_TOTAL,
                                  group=GROUP)
    with plane:
        plane.apply(_mk_group(gt))
        plane.wait_group_ready(GROUP, timeout=30)
        sa_name = adapter_name(GROUP, gt.unified_role)
        plane.wait_for(
            lambda: plane.store.get("ScalingAdapter", "default", sa_name),
            timeout=30, desc="auto adapter")
        # Simulate an in-flight foreign/autoscaler write: stamp and
        # spec.replicas disagree — the flip must NOT start.
        def foreign(a):
            a.spec.replicas = 2
            a.metadata.annotations[C.ANN_AUTOSCALE_LAST_WRITE] = "1"
            return True
        plane.store.mutate("ScalingAdapter", "default", sa_name, foreign)
        script["prefill_decode_ratio"] = 12.0
        plane.wait_for(
            lambda: REGISTRY.counter(names.TOPOLOGY_CONFLICTS_TOTAL,
                                     group=GROUP) > conflicts0,
            timeout=30, desc="conflict counted")
        assert _ann(plane, C.ANN_TOPOLOGY_STATE) is None
        # The stamping writer adopts (stamp catches up): the flip
        # proceeds on a later cycle — and the backoff did not burn the
        # policy cooldown.
        def adopt(a):
            a.metadata.annotations[C.ANN_AUTOSCALE_LAST_WRITE] = \
                str(a.spec.replicas)
            return True
        plane.store.mutate("ScalingAdapter", "default", sa_name, adopt)
        plane.wait_for(
            lambda: _ann(plane, C.ANN_TOPOLOGY_POSTURE) == POSTURE_DISAGG
            and not _ann(plane, C.ANN_TOPOLOGY_STATE),
            timeout=30, desc="flip after adoption")


def test_controller_holds_are_counted_and_status_reported():
    script = {"fresh": True, "prefill_decode_ratio": 4.0, "judged": 20}
    plane, gt = _mk_plane(script)
    holds0 = REGISTRY.counter(names.TOPOLOGY_HOLDS_TOTAL, group=GROUP,
                              reason="deadband")
    with plane:
        plane.apply(_mk_group(gt))
        plane.wait_group_ready(GROUP, timeout=30)
        plane.wait_for(
            lambda: REGISTRY.counter(names.TOPOLOGY_HOLDS_TOTAL,
                                     group=GROUP,
                                     reason="deadband") > holds0,
            timeout=30, desc="deadband hold counted")
        st = plane.topology_controller.status()
        row = next(r for r in st["groups"] if r["group"] == GROUP)
        assert row["posture"] == POSTURE_UNIFIED
        assert row["last_decision"]["suppressed"] == "deadband"
        # Kill switch: disabled groups hold with the reason reported.
        assert plane.topology_controller.set_enabled(GROUP, False)
        plane.wait_for(
            lambda: (plane.topology_controller.status()["groups"][0]
                     ["last_decision"] or {}).get("suppressed")
            == "disabled",
            timeout=30, desc="disabled hold")
        assert not plane.topology_controller.set_enabled("nope", False)


def test_controller_refuses_infeasible_flip_bounds():
    """Adapter bounds that make a flip un-completable (old shape with
    min_replicas > 0 can never drain; target capped under its plan) must
    refuse the flip UP FRONT — a visible retriable HOLD, never a
    permanent mid-flip wedge."""
    # In the deadband until the bounds are pinned (the same race as in
    # test_controller_autoscaler_conflict_backs_off).
    script = {"fresh": True, "prefill_decode_ratio": 4.0, "judged": 20}
    plane, gt = _mk_plane(script)
    holds0 = REGISTRY.counter(names.TOPOLOGY_HOLDS_TOTAL, group=GROUP,
                              reason="infeasible")
    with plane:
        plane.apply(_mk_group(gt))
        plane.wait_group_ready(GROUP, timeout=30)
        sa_name = adapter_name(GROUP, gt.unified_role)
        plane.wait_for(
            lambda: plane.store.get("ScalingAdapter", "default", sa_name),
            timeout=30, desc="auto adapter")
        def pin_min(a):
            a.spec.min_replicas = 1
            return True
        plane.store.mutate("ScalingAdapter", "default", sa_name, pin_min)
        script["prefill_decode_ratio"] = 12.0
        plane.wait_for(
            lambda: REGISTRY.counter(names.TOPOLOGY_HOLDS_TOTAL,
                                     group=GROUP,
                                     reason="infeasible") > holds0,
            timeout=30, desc="infeasible hold counted")
        assert _ann(plane, C.ANN_TOPOLOGY_STATE) is None
        # Lifting the bound lets the same sustained pressure flip (the
        # refusal burned no cooldown).
        def unpin(a):
            a.spec.min_replicas = 0
            return True
        plane.store.mutate("ScalingAdapter", "default", sa_name, unpin)
        plane.wait_for(
            lambda: _ann(plane, C.ANN_TOPOLOGY_POSTURE) == POSTURE_DISAGG
            and not _ann(plane, C.ANN_TOPOLOGY_STATE),
            timeout=30, desc="flip after bound lift")


# ---- admin op --------------------------------------------------------------


def test_admin_topology_op_and_kill_switch():
    from rbg_tpu.engine.protocol import request_once
    from rbg_tpu.runtime.admin import AdminServer

    script = {"fresh": True, "prefill_decode_ratio": 4.0, "judged": 20}
    plane, gt = _mk_plane(script)
    admin = AdminServer(plane, port=0).start()
    addr = f"127.0.0.1:{admin.port}"
    try:
        with plane:
            plane.apply(_mk_group(gt))
            plane.wait_group_ready(GROUP, timeout=30)
            resp, _, _ = request_once(addr, {"op": "topology"})
            rows = resp["topology"]["groups"]
            assert rows and rows[0]["group"] == GROUP
            assert rows[0]["posture"] == POSTURE_UNIFIED
            resp, _, _ = request_once(addr, {"op": "topology",
                                             "disable": GROUP})
            assert not resp["topology"]["groups"][0]["enabled"]
            resp, _, _ = request_once(addr, {"op": "topology",
                                             "enable": "unknown"})
            assert "error" in resp
    finally:
        admin.stop()


# ---- router candidacy seam -------------------------------------------------


def test_router_candidacy_withdraws_roles():
    from rbg_tpu.engine.router import Registry, RouterState
    state = RouterState(Registry(None), None,
                        {"prefill": ["10.0.0.1:1"],
                         "decode": ["10.0.0.2:1"],
                         "unified": ["10.0.0.3:1"]})
    assert state.pd_mode()
    assert state.candidates("prefill")
    state.set_role_candidacy("prefill", False)
    state.set_role_candidacy("decode", False)
    # Withdrawn roles take no NEW requests; the unified role now fronts
    # generate traffic.
    assert not state.pd_mode()
    assert state.candidates("prefill") == []
    assert state.worker_role() == "unified"
    state.set_role_candidacy("prefill", True)
    state.set_role_candidacy("decode", True)
    assert state.pd_mode()
