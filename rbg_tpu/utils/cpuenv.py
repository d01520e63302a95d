"""CPU-only environment for subprocesses.

Tests, dry runs and CPU benchmarks start children that must never reach
for an accelerator: :func:`scrubbed_cpu_env` is the one place that forces
``JAX_PLATFORMS=cpu`` and, where asked, the number of virtual CPU devices
(both must be in the environment before the child's JAX starts). Its
counterpart for children that DO hold a chip is ``chipenv.chip_env``.
"""

from __future__ import annotations

import os


def scrubbed_cpu_env(base: dict | None = None, *,
                     host_devices: int | None = None,
                     extra: dict | None = None) -> dict:
    """Return a copy of ``base`` (default ``os.environ``) forced to CPU.

    ``host_devices`` adds ``--xla_force_host_platform_device_count=N`` to
    ``XLA_FLAGS`` (replacing any existing such flag). ``extra`` entries are
    merged last; a value of ``None`` deletes the key.
    """
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    if host_devices is not None:
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if not f.startswith("--xla_force_host_platform_device_count")]
        flags.append(f"--xla_force_host_platform_device_count={host_devices}")
        env["XLA_FLAGS"] = " ".join(flags)
    for key, val in (extra or {}).items():
        if val is None:
            env.pop(key, None)
        else:
            env[key] = val
    return env
