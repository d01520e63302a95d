"""Full-stack e2e: the control plane orchestrates REAL engine processes.

The closest analog to the reference's kind-cluster e2e tier (SURVEY.md §4
tier 3): apply a PD-disagg RoleBasedGroup → the scheduler places pods → the
LocalExecutor spawns actual engine/router subprocesses with the injected
env → dependency ordering gates the router until prefill+decode serve →
a generate request flows router → prefill (KV bundle over TCP) → decode.
"""

import numpy as np
import pytest

from rbg_tpu.api import constants as C
from rbg_tpu.api.group import RoleSpec
from rbg_tpu.api.pod import Container, Node, PodTemplate
from rbg_tpu.engine import Engine, EngineConfig, SamplingParams
from rbg_tpu.engine.protocol import request_once
from rbg_tpu.runtime.plane import ControlPlane
from rbg_tpu.testutil import make_group

ENGINE_ARGS = ["--model", "tiny", "--page-size", "8", "--num-pages", "128",
               "--max-seq-len", "128", "--prefill-chunk", "16",
               "--use-pallas", "never"]


def engine_role(name: str, mode: str) -> RoleSpec:
    return RoleSpec(
        name=name, replicas=1,
        template=PodTemplate(containers=[Container(
            name="engine",
            command=["python", "-m", "rbg_tpu.engine.server"],
            args=["--mode", mode] + ENGINE_ARGS,
        )]),
    )


def router_role() -> RoleSpec:
    return RoleSpec(
        name="router", replicas=1, dependencies=["prefill", "decode"],
        template=PodTemplate(containers=[Container(
            name="router",
            command=["python", "-m", "rbg_tpu.engine.router"],
        )]),
    )


@pytest.mark.e2e
@pytest.mark.slow
def test_pd_disagg_serves_through_real_processes(tmp_path):
    plane = ControlPlane(
        backend="local",
        executor_env={
            "JAX_PLATFORMS": "cpu", "RBG_TPU_NATIVE": "1",
        },
    )
    node = Node()
    node.metadata.name = "localhost"
    plane.store.create(node)

    with plane:
        plane.apply(make_group(
            "pd", engine_role("prefill", "prefill"),
            engine_role("decode", "decode"), router_role(),
        ))
        plane.wait_group_ready("pd", timeout=180)

        # Dependency contract: router started only after prefill+decode ready.
        pods = plane.store.list("Pod", namespace="default")
        by_role = {p.metadata.labels[C.LABEL_ROLE_NAME]: p for p in pods}
        assert set(by_role) == {"prefill", "decode", "router"}

        router_port = plane.kubelet.port_of("default", by_role["router"].metadata.name)
        assert router_port is not None

        # Health: router must report PD mode (both roles discovered).
        health, _, _ = request_once(f"127.0.0.1:{router_port}", {"op": "health"})
        assert health["ok"] and health["pd"] is True

        prompt = list(range(1, 13))
        resp, _, _ = request_once(
            f"127.0.0.1:{router_port}",
            {"op": "generate", "prompt": prompt, "max_new_tokens": 6},
            timeout=300.0,
        )
        assert "error" not in resp, resp
        tokens = resp["tokens"]
        assert len(tokens) == 6

        # Numerics: identical to an in-process engine with the same seed.
        ref = Engine(EngineConfig(model="tiny", page_size=8, num_pages=128,
                                  max_seq_len=128, prefill_chunk=16,
                                  use_pallas="never"))
        expect = ref.generate([prompt], SamplingParams(max_new_tokens=6))[0]
        assert tokens == expect

        # KV actually crossed the wire.
        health, _, _ = request_once(f"127.0.0.1:{router_port}", {"op": "health"})
        assert health["metrics"]["kv_bytes_routed"] > 0
        assert health["metrics"]["pd_requests"] == 1
