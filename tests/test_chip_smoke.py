"""What ``chip_smoke.py`` and the start-up helpers promise where there is
no chip: the smoke fails instead of running on the CPU, the compile cache
has one configurable place, chip-holding children get disjoint chips, and
a parent that only launches them never starts a JAX backend."""

import json
import os
import subprocess
import sys

import pytest

from rbg_tpu.utils import chipenv, scrubbed_cpu_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(code_or_argv, env):
    argv = ([sys.executable, "-c", code_or_argv]
            if isinstance(code_or_argv, str) else
            [sys.executable, *code_or_argv])
    return subprocess.run(argv, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=600)


def test_smoke_fails_without_a_chip():
    """On the CPU the script must exit non-zero and end ``"ok": false``:
    no phase runs on the CPU, in interpret mode or on a reference."""
    proc = _python(["chip_smoke.py"], scrubbed_cpu_env())
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert "kernel " not in proc.stdout and "serve:" not in proc.stdout


@pytest.mark.slow
def test_rehearsal_walks_every_phase_and_never_passes():
    proc = _python(["chip_smoke.py", "--rehearse"], scrubbed_cpu_env())
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1])["ok"] is False
    for phase in ("device", "kernels", "serve", "reference"):
        assert any(ln.startswith(f"phase {phase}: ok") for ln in lines), phase


_CACHE_DIR = ("import jax; from rbg_tpu.utils import chipenv; "
              "chipenv.configure_compile_cache(); "
              "print(jax.config.jax_compilation_cache_dir)")


def test_cache_goes_where_the_environment_says(tmp_path):
    env = scrubbed_cpu_env(extra={chipenv.CACHE_ENV: str(tmp_path)})
    proc = _python(_CACHE_DIR, env)
    assert proc.stdout.strip() == str(tmp_path), proc.stderr


def test_cache_defaults_to_a_fixed_place_in_the_checkout():
    proc = _python(_CACHE_DIR, scrubbed_cpu_env(extra={chipenv.CACHE_ENV: None}))
    assert proc.stdout.strip() == os.path.join(REPO, ".jax_cache"), proc.stderr
    assert chipenv.repo_cache_dir() == os.path.join(REPO, ".jax_cache")


def test_no_other_code_sets_a_cache_directory():
    hits = subprocess.run(
        ["grep", "-rlE", "jax_compilation_cache_dir|compilation_cache.set",
         "rbg_tpu", "chip_smoke.py", "__graft_entry__.py"],
        cwd=REPO, capture_output=True, text=True).stdout.split()
    assert hits == ["rbg_tpu/utils/chipenv.py"]


def test_chip_env_gives_disjoint_chips():
    base = {"PATH": "/usr/bin", "TPU_VISIBLE_CHIPS": "0,1,2,3"}
    envs = [chipenv.chip_env(i, 4, base) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert len({e["TPU_MESH_CONTROLLER_PORT"] for e in envs}) == 4
    for e in envs:
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert e["PATH"] == "/usr/bin"
    assert base["TPU_VISIBLE_CHIPS"] == "0,1,2,3"     # input not mutated
    with pytest.raises(ValueError):
        chipenv.chip_env(4, 4, base)


def test_executor_pins_one_chip_pods_apart():
    from rbg_tpu.runtime.executor import LocalExecutor
    from rbg_tpu.runtime.store import Store
    ex = LocalExecutor(Store())
    a, b = ("default", "a"), ("default", "b")
    assert ex._claim_chip(a, 1)["TPU_VISIBLE_CHIPS"] == "0"
    assert ex._claim_chip(b, 1)["TPU_VISIBLE_CHIPS"] == "1"
    assert ex._claim_chip(a, 1)["TPU_VISIBLE_CHIPS"] == "0"   # restart
    # A pod that asks for no chip inherits the environment untouched.
    assert ex._claim_chip(("default", "router"), 0) == dict(os.environ)
    ex._teardown(a)
    assert ex._claim_chip(("default", "c"), 1)["TPU_VISIBLE_CHIPS"] == "0"


def test_importing_the_engine_starts_no_backend():
    """A parent that launches chip-holding children (chip_smoke.py)
    imports the engine package for its wire helpers; that must not take
    the chip away from them."""
    proc = _python(
        "import rbg_tpu.engine, chip_smoke; "
        "from jax._src import xla_bridge; "
        "print(xla_bridge.backends_are_initialized())",
        scrubbed_cpu_env())
    assert proc.stdout.strip() == "False", proc.stderr
