"""LocalExecutor: run pods as real local processes.

The second implementation of the kubelet contract (FakeKubelet being the
envtest one): every scheduled Pod becomes a subprocess on this host, with the
control plane's injected env materialized for real — so a PD-disagg group
applied via ``rbg-tpu apply --backend local`` actually serves traffic.

Mechanics:
* picks a free localhost port per pod, exports ``RBG_SERVE_PORT``
* maintains the address registry (JSON, atomic rename) mapping pod FQDN →
  127.0.0.1:port + role/group — the router's service-discovery file
* writes the group topology ConfigMap content to a temp dir and points
  ``RBG_CONFIG_PATH`` at it (the /etc/rbg mount equivalent)
* readiness = TCP health probe; process exit → pod Failed (which feeds the
  restart-policy engine — real crash recovery end to end)
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, Optional

from rbg_tpu.api import constants as C
from rbg_tpu.runtime.store import EVENT_WARNING, Event, Store
from rbg_tpu.utils.locktrace import named_lock
from rbg_tpu.utils.racetrace import guard as _race_guard


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@_race_guard
class LocalExecutor:
    def __init__(self, store: Store, workdir: Optional[str] = None,
                 extra_env: Optional[Dict[str, str]] = None,
                 health_timeout: float = 120.0):
        self.store = store
        self.workdir = workdir or tempfile.mkdtemp(prefix="rbg-tpu-")
        self.registry_path = os.path.join(self.workdir, "registry.json")
        self.extra_env = dict(extra_env or {})
        self.health_timeout = health_timeout
        self._procs: Dict[tuple, subprocess.Popen] = {}  # guarded_by[runtime.executor]
        self._ports: Dict[tuple, int] = {}  # guarded_by[runtime.executor]
        # Host chip index held by each one-chip pod (see _claim_chip).
        self._chips: Dict[tuple, int] = {}  # guarded_by[runtime.executor]
        self._generations: Dict[tuple, int] = {}  # guarded_by[runtime.executor]
        self._lock = named_lock("runtime.executor")
        self._stopped = False
        self._registry: Dict[str, dict] = {}  # guarded_by[runtime.executor]

    # ---- kubelet contract ----

    def start(self):
        self.store.watch("Pod", self._on_event)
        for pod in self.store.list("Pod"):
            # Restored-from-snapshot pods claim to be Running but have no
            # backing process on this (fresh) executor — fail them so the
            # restart-policy engine relaunches real processes (the node-
            # reboot analog). Without this a resumed plane is a zombie:
            # Ready status, dead ports.
            with self._lock:
                known = (pod.metadata.namespace,
                         pod.metadata.name) in self._procs
            if pod.status.phase == "Running" and not known:
                self._set_status((pod.metadata.namespace, pod.metadata.name),
                                 "Failed", ready=False)
                continue
            self._on_event(Event(Event.ADDED, pod))

    def stop(self):
        self._stopped = True
        with self._lock:
            procs = [p for p in self._procs.values()
                     if isinstance(p, subprocess.Popen)]
            self._procs.clear()
            self._ports.clear()
            self._chips.clear()
            self._generations.clear()
        for p in procs:
            try:
                p.terminate()
            except OSError:
                pass
        for p in procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()

    def _on_event(self, ev: Event):
        if self._stopped:
            return
        pod = ev.object
        key = (pod.metadata.namespace, pod.metadata.name)
        if ev.type == Event.DELETED or pod.metadata.deletion_timestamp is not None:
            threading.Thread(target=self._teardown, args=(key,), daemon=True).start()
            return
        if pod.node_name and pod.status.phase == "Pending":
            with self._lock:
                if key in self._procs:
                    return
                self._procs[key] = None  # claim
                self._generations[key] = pod.metadata.generation
            threading.Thread(target=self._launch, args=(key, pod), daemon=True).start()
            return
        # In-place update: the pod object mutated (new container images) while
        # its process runs the old ones — restart the process in place (pod
        # identity, port, and registry entry survive).
        if ev.type == Event.MODIFIED and pod.status.phase == "Running":
            with self._lock:
                proc = self._procs.get(key)
                launched_gen = self._generations.get(key)
            if (proc is not None and launched_gen is not None
                    and pod.metadata.generation > launched_gen):
                threading.Thread(target=self._restart_in_place,
                                 args=(key, pod), daemon=True).start()

    # ---- launch ----

    def _launch(self, key, pod):
        try:
            port = _free_port()
            with self._lock:
                self._ports[key] = port
            container = pod.template.containers[0]
            env = self._claim_chip(key, container.resources.tpu_chips)
            for k, val in self.extra_env.items():
                if val is None:
                    env.pop(k, None)  # None = unset (e.g. host-image hooks)
                else:
                    env[k] = val
            for e in container.env:
                env[e.name] = e.value
            env["RBG_SERVE_PORT"] = str(port)
            env["RBG_REGISTRY_PATH"] = self.registry_path
            env["RBG_CONTAINER_IMAGE"] = container.image
            env.setdefault("RBG_TPU_NATIVE", "1")
            self._write_topology(env, pod)

            cmd = list(container.command) + list(container.args)
            if cmd and cmd[0] in ("python", "python3"):
                cmd[0] = sys.executable
            log_path = os.path.join(self.workdir, f"{pod.metadata.name}.log")
            log = open(log_path, "ab")
            proc = subprocess.Popen(cmd, env=env, stdout=log, stderr=log,
                                    cwd=os.path.dirname(os.path.dirname(
                                        os.path.abspath(__file__))) + "/..")
            with self._lock:
                if self._stopped:
                    proc.terminate()
                    return
                self._procs[key] = proc

            self._register(pod, port)
            if self._wait_healthy(port, proc):
                self._set_status(key, "Running", ready=True, port=port)
                threading.Thread(target=self._babysit, args=(key, proc),
                                 daemon=True).start()
            else:
                # Health timeout: reap the process and its registry entry —
                # a half-alive engine must never stay routable (and it
                # would go on holding its chip).
                self._unregister(pod.metadata.name)
                if proc.poll() is None:
                    proc.terminate()
                    try:
                        proc.wait(timeout=5)
                    except subprocess.TimeoutExpired:
                        proc.kill()
                self._set_status(key, "Failed", ready=False)
        except Exception as e:
            self.store.record_event(pod, "LaunchFailed", str(e),
                                    type_=EVENT_WARNING)
            self._set_status(key, "Failed", ready=False)

    def _claim_chip(self, key, tpu_chips: int) -> dict:
        """The process environment for a pod that asks for ``tpu_chips``.
        A chip belongs to one process, and every pod of this executor
        runs on this one host: a one-chip pod gets the lowest chip index
        no other live pod here holds, pinned through libtpu's
        environment, and keeps it until teardown. Pods that ask for no
        chip, or for several, inherit the environment as it is (several
        one-host pods asking for more than one chip each still collide:
        ROADMAP R3)."""
        if tpu_chips != 1:
            return dict(os.environ)
        from rbg_tpu.utils.chipenv import chip_env
        with self._lock:
            self._chips.pop(key, None)        # in-place restart: re-claim
            held = set(self._chips.values())
            chip = next(i for i in range(len(held) + 1) if i not in held)
            self._chips[key] = chip
            return chip_env(chip, len(held) + 1)

    def _write_topology(self, env, pod):
        group = pod.metadata.labels.get(C.LABEL_GROUP_NAME, "")
        if not group:
            return
        from rbg_tpu.discovery.config_builder import topology_configmap_name
        cm = self.store.get("ConfigMap", pod.metadata.namespace,
                            topology_configmap_name(group))
        if cm is None:
            return
        d = os.path.join(self.workdir, f"etc-rbg-{pod.metadata.name}")
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, C.DISCOVERY_CONFIG_FILE)
        with open(path, "w") as f:
            f.write(cm.data.get(C.DISCOVERY_CONFIG_FILE, ""))
        env[C.ENV_CONFIG_PATH] = path

    def _flush_registry_locked_data(self) -> str:
        return json.dumps(self._registry, indent=1, sort_keys=True)

    def _flush_registry(self, data: str):
        tmp = self.registry_path + ".tmp"
        with open(tmp, "w") as f:
            f.write(data)
        os.replace(tmp, self.registry_path)  # atomic swap for readers

    def _register(self, pod, port):
        group = pod.metadata.labels.get(C.LABEL_GROUP_NAME, "")
        role = pod.metadata.labels.get(C.LABEL_ROLE_NAME, "")
        svc = C.service_name(group, role) if group else ""
        fqdn = f"{pod.metadata.name}.{svc}" if svc else pod.metadata.name
        leader = pod.metadata.labels.get(C.LABEL_COMPONENT_INDEX, "0") == "0"
        # Role-level routing policy comes from the Service (KEP-260
        # sharedServiceSelection) — the registry carries it to the router.
        leader_only = False
        if svc:
            service = self.store.get("Service", pod.metadata.namespace, svc)
            leader_only = bool(service and service.leader_only)
        with self._lock:
            self._registry[fqdn] = {
                "addr": f"127.0.0.1:{port}",
                "role": role, "group": group, "pod": pod.metadata.name,
                "leader": leader, "leaderOnly": leader_only,
            }
            data = self._flush_registry_locked_data()
        self._flush_registry(data)

    def _unregister(self, pod_name: str):
        with self._lock:
            self._registry = {k: v for k, v in self._registry.items()
                              if v.get("pod") != pod_name}
            data = self._flush_registry_locked_data()
        self._flush_registry(data)

    def _wait_healthy(self, port: int, proc) -> bool:
        from rbg_tpu.engine.protocol import request_once
        deadline = time.monotonic() + self.health_timeout
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                return False
            try:
                resp, _, _ = request_once(f"127.0.0.1:{port}", {"op": "health"},
                                          timeout=2.0)
                if resp and resp.get("ok"):
                    return True
            except OSError:
                pass
            time.sleep(0.2)
        return False

    def _babysit(self, key, proc):
        rc = proc.wait()
        if self._stopped:
            return
        with self._lock:
            known = self._procs.get(key) is proc
        if not known:
            return
        pod = self.store.get("Pod", key[0], key[1])
        job_like = (pod is not None and pod.metadata.annotations.get(
            f"{C.DOMAIN}/run-to-completion") == "true")
        phase = "Succeeded" if (rc == 0 and job_like) else "Failed"
        self._set_status(key, phase, ready=False)

    def _restart_in_place(self, key, pod):
        with self._lock:
            proc = self._procs.get(key)
            if not isinstance(proc, subprocess.Popen):
                return  # another restart/launch holds the claim — leave it
            self._generations[key] = pod.metadata.generation
            self._procs[key] = None  # re-claim for the relaunch
            self._ports.pop(key, None)
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                proc.kill()
        self._unregister(key[1])
        # Claim (procs[key] = None) stays held: the Pending status event
        # must not trigger a second concurrent launch.
        self._set_status(key, "Pending", ready=False)
        self._launch(key, pod)

    def _teardown(self, key):
        with self._lock:
            proc = self._procs.pop(key, None)
            self._ports.pop(key, None)
            self._chips.pop(key, None)
            self._generations.pop(key, None)
        self._unregister(key[1])
        if proc is not None and proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                proc.kill()
        try:
            self.store.finalize_delete("Pod", key[0], key[1])
        except Exception:
            pass

    def _set_status(self, key, phase: str, ready: bool, port: int = 0):
        try:
            def fn(p):
                p.status.phase = phase
                p.status.ready = ready
                p.status.node_name = p.node_name
                p.status.pod_ip = "127.0.0.1"
                if port:
                    p.status.start_time = time.time()
                if phase == "Running":
                    # The relaunched process runs whatever the pod spec says
                    # now — report that revision (in-place update ack).
                    from rbg_tpu.api import constants as _C
                    p.status.observed_revision = p.metadata.labels.get(
                        _C.LABEL_REVISION_NAME, p.status.observed_revision)
                return True
            self.store.mutate("Pod", key[0], key[1], fn, status=True)
        except Exception:
            pass

    # ---- introspection ----

    def port_of(self, namespace: str, name: str) -> Optional[int]:
        with self._lock:
            return self._ports.get((namespace, name))

    def registry(self) -> Dict[str, dict]:
        with self._lock:
            return dict(self._registry)
