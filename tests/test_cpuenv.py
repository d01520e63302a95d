"""CPU-only subprocess environment (rbg_tpu.utils.cpuenv): children of
tests, dry runs and CPU benchmarks must come up on the CPU whatever the
parent's environment says."""

from rbg_tpu.utils import scrubbed_cpu_env


def test_forces_cpu_and_leaves_the_rest():
    base = {"JAX_PLATFORMS": "tpu", "TPU_VISIBLE_CHIPS": "2",
            "PATH": "/usr/bin"}
    env = scrubbed_cpu_env(base)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["PATH"] == "/usr/bin" and env["TPU_VISIBLE_CHIPS"] == "2"
    assert base["JAX_PLATFORMS"] == "tpu"  # input not mutated


def test_host_devices_replaces_existing_flag():
    base = {"XLA_FLAGS": "--xla_force_host_platform_device_count=2 --foo=1"}
    env = scrubbed_cpu_env(base, host_devices=8)
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    assert "device_count=2" not in env["XLA_FLAGS"]
    assert "--foo=1" in env["XLA_FLAGS"]


def test_extra_merges_and_none_deletes():
    base = {"KEEP": "1", "DROP": "1"}
    env = scrubbed_cpu_env(base, extra={"DROP": None, "NEW": "v"})
    assert "DROP" not in env
    assert env["NEW"] == "v"
    assert env["KEEP"] == "1"
