"""Subprocess e2e scenario matrix (reference: the kind-cluster ginkgo suite,
``test/e2e/e2e_test.go:30-96`` — update_strategy, convergence,
shared_service_selection, port_allocator, warmup, coordinated_policy,
webhook_validation, inplace, restart stability, roletemplate...).

Every scenario here drives the SHIPPED binary path: a ``rbg-tpu serve``
subprocess (plane + scheduler + fake kubelet + admin API) spoken to over the
admin wire protocol — nothing reaches into plane internals. The plane-kill
convergence scenario additionally exercises the state-file resume path.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time

import pytest

from rbg_tpu.api import constants as C
from rbg_tpu.api import serde
from rbg_tpu.api.group import RoleBasedGroupSet
from rbg_tpu.engine.protocol import request_once
from rbg_tpu.testutil import make_group, simple_role, tpu_leaderworker_role

pytestmark = pytest.mark.e2e


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class ServedPlane:
    """A real ``rbg-tpu serve`` subprocess + admin-wire client."""

    def __init__(self, state_file=None, token="e2e-token", slices=4, hosts=2):
        self.port = _free_port()
        self.token = token
        self.state_file = state_file
        self.slices, self.hosts = slices, hosts
        self.proc = None

    def start(self, timeout=90):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("RBG_ADMIN_TOKEN", None)
        cmd = [sys.executable, "-m", "rbg_tpu.cli.main", "serve",
               "--backend", "fake", "--admin-port", str(self.port),
               "--slices", str(self.slices), "--hosts", str(self.hosts),
               "--admin-token", self.token]
        if self.state_file:
            cmd += ["--state-file", self.state_file]
        self.proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                                     stderr=subprocess.STDOUT, text=True)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                resp, _, _ = request_once(f"127.0.0.1:{self.port}",
                                          {"op": "health"}, timeout=2.0)
                if resp and resp.get("ok"):
                    return self
            except OSError:
                pass
            if self.proc.poll() is not None:
                out = self.proc.stdout.read()
                raise RuntimeError(f"serve died rc={self.proc.returncode}:\n{out}")
            time.sleep(0.2)
        raise TimeoutError("serve did not come up")

    def stop(self):
        if self.proc and self.proc.poll() is None:
            self.proc.send_signal(signal.SIGINT)
            try:
                self.proc.wait(15)
            except subprocess.TimeoutExpired:
                self.proc.kill()

    def kill9(self):
        self.proc.kill()
        self.proc.wait(10)

    # ---- wire client ----

    def call(self, **obj):
        obj.setdefault("token", self.token)
        resp, _, _ = request_once(f"127.0.0.1:{self.port}", obj, timeout=30.0)
        assert resp is not None, "admin closed connection"
        return resp

    def ok(self, **obj):
        resp = self.call(**obj)
        assert "error" not in resp, resp
        return resp

    def apply(self, manifest):
        if not isinstance(manifest, dict):
            manifest = dict(serde.to_dict(manifest), kind=manifest.kind)
        return self.ok(op="apply", manifest=manifest)

    def wait(self, fn, timeout=60, desc="condition"):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                v = fn()
            except AssertionError:
                v = None
            if v:
                return v
            time.sleep(0.2)
        raise TimeoutError(f"e2e timed out waiting for {desc}")

    def wait_ready(self, name, timeout=90):
        return self.wait(
            lambda: (lambda st: st if st.get("ready") else None)(
                self.call(op="status", name=name)),
            timeout=timeout, desc=f"group {name} ready")

    def pods(self, group):
        return self.call(op="status", name=group).get("pods", [])

    def get(self, kind, name):
        r = self.call(op="get", kind=kind, name=name)
        return r.get("object")


@pytest.fixture(scope="module")
def plane():
    p = ServedPlane().start()
    yield p
    p.stop()


# ---- scenario 1: update_strategy (surge x partition over the wire) ----

def test_update_strategy_partition_and_surge(plane):
    g = make_group("us", simple_role("srv", replicas=3, image="engine:v1"))
    g.spec.roles[0].rolling_update.max_surge = 1
    g.spec.roles[0].rolling_update.partition = 2
    g.spec.roles[0].rolling_update.in_place_if_possible = False
    plane.apply(g)
    plane.wait_ready("us")

    g = serde.from_dict(type(g), plane.get("RoleBasedGroup", "us"))
    g.spec.roles[0].template.containers[0].image = "engine:v2"
    plane.apply(g)

    def partitioned():
        ris = plane.get("RoleInstanceSet", "us-srv")
        st = ris.get("status", {})
        return (st.get("updatedReadyReplicas") == 1
                and st.get("readyReplicas", 0) >= 3)
    plane.wait(partitioned, desc="only ordinal >= partition updated")
    # The partition must HOLD: rather than a fixed sleep (flaky on slow
    # CI), require N consecutive observations at exactly 1 updated-ready.
    # More than 1 means the partition broke — fail immediately; fewer
    # (a readiness flap) resets the stability counter.
    stable = 0
    deadline = time.monotonic() + 10.0
    while stable < 5:
        assert time.monotonic() < deadline, "partition stability poll timeout"
        ris = plane.get("RoleInstanceSet", "us-srv")
        # serde drops default-valued fields: absent == 0 (a flap).
        updated = ris["status"].get("updatedReadyReplicas", 0)
        assert updated <= 1, "partition must hold the rollout"
        stable = stable + 1 if updated == 1 else 0
        time.sleep(0.1)

    g = serde.from_dict(type(g), plane.get("RoleBasedGroup", "us"))
    g.spec.roles[0].rolling_update.partition = 0
    plane.apply(g)
    plane.wait(
        lambda: plane.get("RoleInstanceSet", "us-srv")["status"]
        .get("updatedReadyReplicas") == 3,
        desc="open partition rolls everyone")
    plane.wait_ready("us")


# ---- scenario 2: admission rejects (webhook_validation analog) ----

def test_admission_rejects_bad_manifests(plane):
    dup = serde.to_dict(make_group("bad", simple_role("a"), simple_role("a")))
    r = plane.call(op="apply", manifest=dict(dup, kind="RoleBasedGroup"))
    assert "error" in r and "duplicated" in r["error"]

    bad_id = serde.to_dict(make_group("bad2", simple_role("a")))
    bad_id["spec"]["roles"][0]["identity"] = "Random"  # misspelled
    r = plane.call(op="apply", manifest=dict(bad_id, kind="RoleBasedGroup"))
    assert "error" in r and "IdentityMode" in r["error"]

    typo = serde.to_dict(make_group("bad3", simple_role("a")))
    typo["spec"]["rolez"] = []  # unknown key = strict-parse error
    r = plane.call(op="apply", manifest=dict(typo, kind="RoleBasedGroup"))
    assert "error" in r

    assert plane.get("RoleBasedGroup", "bad") is None


# ---- scenario 3: v1alpha1 manifest converts live ----

def test_v1alpha1_manifest_served(plane):
    doc = serde.to_dict(make_group("legacy", simple_role("srv", replicas=2)))
    doc = dict(doc, kind="RoleBasedGroup",
               apiVersion="rbg.tpu.x-k8s.io/v1alpha1")
    doc["spec"]["roles"][0].pop("identity", None)
    doc["spec"]["roles"][0]["stateful"] = False
    plane.apply(doc)
    plane.wait_ready("legacy")
    g = plane.get("RoleBasedGroup", "legacy")
    assert g["spec"]["roles"][0]["identity"] == "random"
    # stateless instances got random ids, not ordinals
    names = [p["name"] for p in plane.pods("legacy")]
    assert names and all(not n.rsplit("-", 1)[-1].isdigit() for n in names)


# ---- scenario 4: shared_service_selection LeaderOnly (KEP-260) ----

def test_shared_service_selection_leader_only(plane):
    role = tpu_leaderworker_role("tp", replicas=1, topology="2x4")
    role.service_selection = "LeaderOnly"
    plane.apply(make_group("svc-sel", role))
    plane.wait_ready("svc-sel")
    svc = plane.get("Service", "s-svc-sel-tp")
    assert svc is not None and svc.get("leaderOnly") is True
    pods = plane.pods("svc-sel")
    assert len(pods) == 2  # leader + worker on a 2-host slice


# ---- scenario 5: port allocator (KEP-171) ----

def test_port_allocator_roundtrip(plane):
    g = make_group("ports", simple_role("srv", replicas=1))
    g.spec.roles[0].template.annotations = {
        C.ANN_PORT_ALLOCATOR: json.dumps([{"name": "dist", "scope": "role"}]),
    }
    plane.apply(g)
    plane.wait_ready("ports")
    ris = plane.get("RoleInstanceSet", "ports-srv")
    alloc = ris["metadata"].get("annotations", {}).get(C.ANN_ALLOCATED_PORTS)
    assert alloc, "role-scoped port not persisted on the RIS"
    assert json.loads(alloc)


# ---- scenario 6: warmup jobs (KEP-129) ----

def test_warmup_completes_on_group_nodes(plane):
    plane.apply(make_group("wsvc", simple_role("srv", replicas=2)))
    plane.wait_ready("wsvc")
    from rbg_tpu.api.policy import Warmup
    w = Warmup()
    w.metadata.name = "prime"
    w.spec.target.group_name = "wsvc"
    plane.apply(dict(serde.to_dict(w), kind="Warmup"))
    plane.wait(
        lambda: (plane.get("Warmup", "prime").get("status", {})
                 .get("succeededNodes", 0)) >= 1,
        desc="warmup succeeded on the group's nodes")


# ---- scenario 7: coordinated_policy maxSkew scaling ----

def test_coordinated_policy_staged_scaling(plane):
    from rbg_tpu.api.policy import (
        CoordinatedPolicy, CoordinatedPolicySpec, CoordinatedScaling,
    )
    plane.apply(make_group("cp", simple_role("prefill", replicas=4),
                           simple_role("decode", replicas=4)))
    pol = CoordinatedPolicy()
    pol.metadata.name = "cp-pol"
    pol.spec = CoordinatedPolicySpec(
        group_name="cp",
        scaling=CoordinatedScaling(roles=["prefill", "decode"],
                                   max_skew_percent=25))
    plane.apply(dict(serde.to_dict(pol), kind="CoordinatedPolicy"))
    plane.wait_ready("cp", timeout=120)
    assert len(plane.pods("cp")) == 8


# ---- scenario 8: self-healing after pod delete (restart stability) ----

def test_pod_delete_self_heals(plane):
    plane.apply(make_group("heal", simple_role("srv", replicas=2)))
    plane.wait_ready("heal")
    victim = plane.pods("heal")[0]["name"]
    plane.ok(op="delete", kind="Pod", name=victim)
    plane.wait(
        lambda: (lambda ps: len(ps) == 2 and all(p["ready"] for p in ps))(
            plane.pods("heal")),
        desc="deleted pod recreated and ready")
    plane.wait_ready("heal")


# ---- scenario 9: rollout history + undo over the wire ----

def test_rollout_undo_restores_image(plane):
    g = make_group("undo", simple_role("srv", replicas=1, image="engine:v1"))
    plane.apply(g)
    plane.wait_ready("undo")
    g = serde.from_dict(type(g), plane.get("RoleBasedGroup", "undo"))
    g.spec.roles[0].template.containers[0].image = "engine:v2"
    plane.apply(g)
    plane.wait(
        lambda: len(plane.ok(op="history", name="undo")["revisions"]) == 2,
        desc="two revisions")
    plane.wait_ready("undo")
    plane.ok(op="undo", name="undo")
    plane.wait(
        lambda: plane.get("RoleBasedGroup", "undo")["spec"]["roles"][0]
        ["template"]["containers"][0]["image"] == "engine:v1",
        desc="undo restored v1")
    plane.wait_ready("undo")


# ---- scenario 10: in-place update keeps the pod ----

def test_inplace_update_preserves_pod(plane):
    g = make_group("inp", simple_role("srv", replicas=1, image="engine:v1"))
    g.spec.roles[0].rolling_update.in_place_if_possible = True
    plane.apply(g)
    plane.wait_ready("inp")
    uid0 = {p["name"] for p in plane.pods("inp")}

    g = serde.from_dict(type(g), plane.get("RoleBasedGroup", "inp"))
    g.spec.roles[0].template.containers[0].image = "engine:v2"
    plane.apply(g)
    plane.wait(
        lambda: plane.get("RoleInstanceSet", "inp-srv")["status"]
        .get("updatedReadyReplicas") == 1,
        desc="in-place update done")
    assert {p["name"] for p in plane.pods("inp")} == uid0, \
        "image-only change must not recreate the pod"


# ---- scenario 11: groupset fleet over the wire ----

def test_groupset_fleet_rollout(plane):
    gs = RoleBasedGroupSet()
    gs.metadata.name = "fleet"
    gs.spec.replicas = 2
    gs.spec.max_unavailable = 0  # both cells at once: keep e2e fast
    gs.spec.template.spec.roles = [simple_role("srv", replicas=1,
                                               image="engine:v1")]
    plane.apply(dict(serde.to_dict(gs), kind="RoleBasedGroupSet"))
    plane.wait(
        lambda: (plane.get("RoleBasedGroupSet", "fleet") or {}).get(
            "status", {}).get("readyReplicas") == 2,
        desc="fleet of 2 ready")

    gs2 = plane.get("RoleBasedGroupSet", "fleet")
    gs2["spec"]["template"]["spec"]["roles"][0]["template"]["containers"][0][
        "image"] = "engine:v2"
    plane.apply(dict(gs2, kind="RoleBasedGroupSet"))
    plane.wait(
        lambda: all(
            (plane.get("RoleBasedGroup", f"fleet-{i}") or {})["spec"]["roles"]
            [0]["template"]["containers"][0]["image"] == "engine:v2"
            for i in (0, 1)),
        desc="template bump reaches every cell")
    plane.wait(
        lambda: (plane.get("RoleBasedGroupSet", "fleet") or {}).get(
            "status", {}).get("updatedReplicas") == 2,
        desc="fleet updated counter")


# ---- scenario 12: convergence after plane SIGKILL mid-rollout ----

@pytest.mark.slow
def test_convergence_after_plane_kill(tmp_path):
    state = str(tmp_path / "state.json")
    p = ServedPlane(state_file=state, slices=2, hosts=2)
    p.start()
    try:
        g = make_group("conv", simple_role("srv", replicas=3,
                                           image="engine:v1"))
        g.spec.roles[0].rolling_update.in_place_if_possible = False
        p.apply(g)
        p.wait_ready("conv")
        # Ensure the pre-rollout state hit disk (5s autosave cadence).
        p.wait(lambda: os.path.exists(state), desc="state file exists")
        time.sleep(6.0)

        g = serde.from_dict(type(g), p.get("RoleBasedGroup", "conv"))
        g.spec.roles[0].template.containers[0].image = "engine:v2"
        p.apply(g)
        time.sleep(6.0)  # let the rollout start + autosave mid-flight
        p.kill9()
    finally:
        if p.proc.poll() is None:
            p.stop()

    # Restart from the state file: the rollout must finish, not restart.
    p2 = ServedPlane(state_file=state, slices=2, hosts=2)
    p2.port = _free_port()
    p2.start()
    try:
        p2.wait_ready("conv", timeout=120)
        ris = p2.get("RoleInstanceSet", "conv-srv")
        assert ris["status"].get("updatedReadyReplicas") == 3
        pods = p2.pods("conv")
        assert len(pods) == 3 and all(pp["ready"] for pp in pods)
    finally:
        p2.stop()
