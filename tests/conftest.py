"""Test bootstrap: force an 8-device virtual CPU platform BEFORE jax imports.

Mirrors the reference's envtest approach (SURVEY.md §4: real apiserver, no
kubelet, synthetic status) — here: real XLA, no TPU, virtual 8-device mesh.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

# not collected, so not rewritten unless asked: a contract test that fails
# then shows the RMS and the limit it held it to
pytest.register_assert_rewrite("model_contract", "kda_packed_case")


@pytest.fixture(scope="session")
def mesh8():
    from rbg_tpu.parallel import make_mesh
    return make_mesh(dp=2, sp=2, tp=2)


@pytest.fixture()
def interpreted(monkeypatch):
    """``use_pallas="always"`` off the chip: every kernel
    ``dispatch_pallas`` can take (``rbg_tpu.ops.pallas.KERNELS``), in
    interpret mode. The one seam: a test names no kernel."""
    import functools

    from rbg_tpu.ops import pallas
    for name in pallas.KERNELS:
        monkeypatch.setattr(pallas.home(name), name, functools.partial(
            pallas.kernel(name), interpret=True))


class SpawnedEngineServer:
    """Shared spawn-server + health-poll boilerplate for subprocess e2e
    tests (the pattern previously copy-pasted per test file). Scrubs the
    CPU env AND ambient data-plane/port vars so a developer's exported
    RBG_DATA_TOKEN / RBG_SERVE_PORT never silently arms a gate or
    rebinds the port under the test.

        with SpawnedEngineServer("--model", "tiny", ...) as srv:
            request_once(srv.addr, {...})
    """

    def __init__(self, *args, env_extra=None, timeout=240.0):
        import socket
        import subprocess
        import sys as _sys

        from rbg_tpu.utils import scrubbed_cpu_env

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        env = scrubbed_cpu_env(extra={
            "RBG_DATA_TOKEN": None, "RBG_SERVE_PORT": str(self.port),
            "RBG_PORT_SERVE": None, **(env_extra or {})})
        self.addr = f"127.0.0.1:{self.port}"
        self.timeout = timeout
        self.proc = subprocess.Popen(
            [_sys.executable, "-m", "rbg_tpu.engine.server", *args],
            env=env, stdout=__import__("subprocess").DEVNULL,
            stderr=__import__("subprocess").DEVNULL)

    def wait_ready(self):
        import time

        from rbg_tpu.engine.protocol import request_once
        deadline = time.monotonic() + self.timeout
        while True:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"engine server died at startup rc={self.proc.returncode}")
            try:
                h, _, _ = request_once(self.addr, {"op": "health"}, timeout=2)
                if h and h.get("ok"):
                    return self
            except OSError:
                pass
            assert time.monotonic() < deadline, "server never healthy"
            time.sleep(0.3)

    def __enter__(self):
        return self.wait_ready()

    def __exit__(self, *exc):
        self.proc.terminate()
        self.proc.wait()
