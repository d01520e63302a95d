"""LocalExecutor in-place update: an image-only change restarts the real
process while the pod object (uid, name, registry identity) survives."""

import os

import pytest

from rbg_tpu.api.pod import Container, Node
from rbg_tpu.runtime.plane import ControlPlane
from rbg_tpu.testutil import make_group, simple_role

WORKER = (
    "import os,time,socketserver,threading\n"
    "from rbg_tpu.engine.protocol import recv_msg, send_msg\n"
    "open(os.environ['MARKER'] + '.' + os.environ['RBG_CONTAINER_IMAGE'], 'a').write('x')\n"
    "class H(socketserver.BaseRequestHandler):\n"
    "    def handle(self):\n"
    "        while True:\n"
    "            o, _, _ = recv_msg(self.request)\n"
    "            if o is None: return\n"
    "            send_msg(self.request, {'ok': True})\n"
    "s = socketserver.ThreadingTCPServer(('127.0.0.1', int(os.environ['RBG_SERVE_PORT'])), H)\n"
    "s.daemon_threads = True\n"
    "threading.Thread(target=s.serve_forever, daemon=True).start()\n"
    "time.sleep(3600)\n"
)


@pytest.mark.e2e
def test_inplace_image_update_restarts_process(tmp_path):
    marker = str(tmp_path / "marker")
    role = simple_role("svc", replicas=1)
    role.template.containers = [Container(
        name="svc", image="v1", command=["python", "-c", WORKER],
    )]

    plane = ControlPlane(
        backend="local",
        executor_env={"JAX_PLATFORMS": "cpu", "MARKER": marker},
    )
    node = Node()
    node.metadata.name = "localhost"
    plane.store.create(node)

    with plane:
        plane.apply(make_group("ip", role))
        plane.wait_group_ready("ip", timeout=120)
        pod0 = plane.store.list("Pod", namespace="default")[0]
        uid0 = pod0.metadata.uid
        assert os.path.exists(marker + ".v1")

        cur = plane.store.get("RoleBasedGroup", "default", "ip")
        cur.spec.roles[0].template.containers[0].image = "v2"  # image-ONLY
        plane.store.update(cur)

        def restarted_in_place():
            pods = [p for p in plane.store.list("Pod", namespace="default") if p.active]
            return (pods and os.path.exists(marker + ".v2") and pods[0].running_ready)

        plane.wait_for(restarted_in_place, timeout=120,
                       desc="process restarted with new image")
        pods = [p for p in plane.store.list("Pod", namespace="default") if p.active]
        assert len(pods) == 1
        # In-place: same pod object — the slice/identity survived the rollout.
        assert pods[0].metadata.uid == uid0
        assert pods[0].template.containers[0].image == "v2"

        # Restart-policy-ONLY change: no container differs, so there is
        # nothing to drain and no backend ack to wait for — the group must
        # return to Ready without a process restart (review finding: the
        # gate used to wait forever for an observed_revision the executor
        # never reports on label-only patches).
        cur = plane.store.get("RoleBasedGroup", "default", "ip")
        cur.spec.roles[0].restart_policy.base_delay_seconds = 9.0
        plane.store.update(cur)

        def policy_landed():
            insts = plane.store.list("RoleInstance", namespace="default")
            return (insts
                    and insts[0].spec.restart_policy.base_delay_seconds == 9.0
                    or None)

        plane.wait_for(policy_landed, timeout=60, desc="policy landed")
        plane.wait_group_ready("ip", timeout=60)
        pods = [p for p in plane.store.list("Pod", namespace="default") if p.active]
        assert pods[0].metadata.uid == uid0 and pods[0].running_ready
