"""Process start-up on an accelerator host.

A chip belongs to one process at a time, and a sealed machine keeps no
compiled program between runs unless the cache has a fixed place. The
helpers every entry point shares live here: the environment that gives a
child process exactly one chip of its host, the one place JAX's
persistent compile cache is configured, what device this process holds,
and a counter of what it compiled.

Importing this module imports no JAX: a parent that launches
chip-holding children must stay off the backend itself.
"""

from __future__ import annotations

import collections
import os
import re
import threading
import time
from typing import Optional

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

# jax.monitoring's public compile signal: one scalar event when a program
# enters ``compile_or_get_cached`` and one duration event when it leaves,
# both tagged ``fun_name="jit(<name>)"``. A persistent-cache hit fires
# them too, plus the hit event below.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

# libtpu's per-process view of a host's chips (Cloud TPU docs, "run one
# JAX process per chip"): which chips the process may open, and the
# bounds of the one-chip topology it then forms on its own.
_ONE_CHIP_BOUNDS = {"TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                    "TPU_PROCESS_BOUNDS": "1,1,1"}
_MESH_CONTROLLER_PORT = 8476


def chip_env(index: int, count: int, base: Optional[dict] = None) -> dict:
    """Environment for child ``index`` of ``count`` chip-holding processes
    on this host: a copy of ``base`` (default ``os.environ``) in which
    libtpu sees chip ``index`` and nothing else. The ``count`` children
    get disjoint chips and disjoint controller ports, so they start side
    by side; the parent must not have initialised a JAX backend."""
    if not 0 <= index < count:
        raise ValueError(f"chip index {index} outside [0, {count})")
    env = dict(os.environ if base is None else base)
    env.update(_ONE_CHIP_BOUNDS)
    env["TPU_VISIBLE_CHIPS"] = str(index)
    port = _MESH_CONTROLLER_PORT + index
    env["TPU_MESH_CONTROLLER_ADDRESS"] = f"localhost:{port}"
    env["TPU_MESH_CONTROLLER_PORT"] = str(port)
    return env


def repo_cache_dir() -> str:
    """The compile cache's fixed place inside this checkout. Fixed
    because the directory is part of every cache key's lookup: a path
    made from a pid, a time or ``mkdtemp`` never hits twice."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, ".jax_cache")


def configure_compile_cache() -> str:
    """Place JAX's persistent compile cache; call once at process start,
    before the first compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set
    JAX has already read it and nothing is touched; otherwise the cache
    goes to ``<checkout>/.jax_cache``. Returns the directory in use. No
    other code sets a cache directory."""
    import jax
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", repo_cache_dir())
    return jax.config.jax_compilation_cache_dir


def cache_files(path: Optional[str]) -> int:
    """Entries in a compile cache directory (0 when it does not exist)."""
    if not path or not os.path.isdir(path):
        return 0
    return sum(len(files) for _, _, files in os.walk(path))


def _open_chip_files() -> list:
    """Chip device files this process holds open (``/dev/vfio/N`` or
    ``/dev/accelN``). Inside a pinned process JAX numbers its one device
    0 whichever chip it is, so this is the identity two replicas can be
    told apart by."""
    held = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue
        if re.fullmatch(r"/dev/(vfio/\d+|accel\d+)", target):
            held.add(target)
    return sorted(held)


def device_summary() -> dict:
    """The device this process computes on, as JAX reports it, and which
    chip of the host that is. Touches the backend: on an accelerator host
    the process holds the chip from here on."""
    import jax
    devs = jax.devices()
    d = devs[0]
    out = {"platform": d.platform, "kind": d.device_kind, "id": d.id,
           "count": len(devs), "chip_files": _open_chip_files()}
    stats = d.memory_stats() or {}
    if "bytes_limit" in stats:
        out["hbm_bytes"] = stats["bytes_limit"]
    return out


def memory_summary() -> dict:
    """Device memory in use now and at its peak, where the backend
    reports it (the CPU backend reports nothing)."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use",
                                  "bytes_limit") if k in stats}


class CompileCounter:
    """Programs this process compiled (or loaded from the persistent
    cache), the seconds that took, and how many were cache hits — set-up
    cost, read from ``jax.monitoring``'s public compile events. The last
    ``RECENT`` of them are kept by name, ``(time.monotonic(), fun_name,
    seconds)``, so that a compile in the middle of serving can be named
    from the wire."""

    RECENT = 64

    def __init__(self):
        self._lock = threading.Lock()
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.recent = collections.deque(maxlen=self.RECENT)

    def install(self) -> "CompileCounter":
        import jax.monitoring as monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.programs += 1
                self.seconds += seconds
                self.recent.append((time.monotonic(),
                                    str(kw.get("fun_name")), seconds))

    def _on_event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"programs": self.programs,
                    "seconds": round(self.seconds, 3),
                    "cache_hits": self.cache_hits,
                    "recent": [list(r) for r in self.recent]}


_PROCESS_COUNTER: Optional[CompileCounter] = None
_PROCESS_COUNTER_LOCK = threading.Lock()


def compile_counter() -> CompileCounter:
    """This process's one installed counter, for the server's ``metrics``
    op and the serving loop's late-step records: ``jax.monitoring`` keeps
    a listener for the life of the process, so those that only read share
    one."""
    global _PROCESS_COUNTER
    with _PROCESS_COUNTER_LOCK:
        if _PROCESS_COUNTER is None:
            _PROCESS_COUNTER = CompileCounter().install()
        return _PROCESS_COUNTER
