"""jitwatch: the runtime compile & host-sync sentry. The arming matrix,
warmup_complete gating (including a seeded violation proving the sentry
actually fires), warn-mode counters, the hot_section sync probe, and the
off-by-default zero-overhead contract."""

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from rbg_tpu.obs import names
from rbg_tpu.utils import jitwatch


@pytest.fixture()
def watch(monkeypatch):
    monkeypatch.setenv("RBG_JITWATCH", "1")
    jitwatch.disarm()
    yield jitwatch
    jitwatch.disarm()


def _compile_cataloged(program, shape=(4,)):
    """Force a fresh XLA compile whose sym_name matches a cataloged
    program — the same __name__-stamping the engine getters use."""
    def f(x):
        return x * 2 + 1
    f.__name__ = program
    return jax.jit(f)(jnp.ones(shape))


# ---- arming matrix ----


@pytest.mark.parametrize("value,expect", [
    ("1", "raise"), ("true", "raise"), ("warn", "warn"),
    ("0", ""), ("false", ""), ("off", ""), ("", ""),
])
def test_arming_matrix(monkeypatch, value, expect):
    monkeypatch.setenv("RBG_JITWATCH", value)
    assert jitwatch.mode() == expect
    assert jitwatch.enabled() == bool(expect)


def _listening() -> bool:
    """Does a compile reach jitwatch's records right now?"""
    before = len(jitwatch.compiles())
    _compile_cataloged("listening_probe")
    return len(jitwatch.compiles()) > before


def test_off_by_default_nothing_patched(monkeypatch):
    monkeypatch.delenv("RBG_JITWATCH", raising=False)
    jitwatch.disarm()
    from jax._src.array import ArrayImpl
    assert not _listening()
    item = getattr(ArrayImpl, "item", None)
    assert item is None or not item.__name__.startswith("jitwatch_")
    assert jax.device_get.__name__ != "traced_device_get"
    # hot_section without hooks is a no-op, not an error.
    with jitwatch.hot_section("cold", strict=True):
        pass


def test_disarm_restores_all_seams(watch):
    orig_get = jax.device_get
    watch.arm()
    assert _listening()
    assert jax.device_get is not orig_get
    watch.disarm()
    assert not _listening()
    assert jax.device_get is orig_get


def test_blind_hook_makes_arm_raise(watch, monkeypatch):
    """A JAX that renames or drops the compile event must fail the gate
    loudly: arm() compiles a throwaway program and insists on seeing it."""
    monkeypatch.setattr(jitwatch, "COMPILE_EVENT", "/jax/no/such/event")
    with pytest.raises(watch.JitWatchBlindError):
        watch.arm()
    # A failed arm leaves nothing installed behind.
    assert jax.device_get.__name__ != "traced_device_get"


def test_self_test_leaves_no_record(watch):
    watch.arm()
    assert watch.compiles() == [] and watch.warmed_programs() == set()


# ---- warmup_complete gating ----


def test_sentry_fires_on_post_warmup_cataloged_compile(watch):
    """The seeded fixture: a cataloged program compiling AFTER the gate
    must raise — this is the proof the sentry is live, not decorative."""
    watch.arm()
    _compile_cataloged(names.PROGRAM_FUSED_DECODE)       # warmup set
    n = watch.warmup_complete()
    assert n >= 1 and watch.gate_armed()
    assert names.PROGRAM_FUSED_DECODE in watch.warmed_programs()
    with pytest.raises(watch.JitCompileError):
        _compile_cataloged(names.PROGRAM_FUSED_DECODE, shape=(8,))
    assert watch.violations()
    assert watch.unwarmed_by_program() == {names.PROGRAM_FUSED_DECODE: 1}


def test_pre_gate_compiles_are_the_blessed_warmup_set(watch):
    watch.arm()
    _compile_cataloged(names.PROGRAM_RAGGED_FWD)
    _compile_cataloged(names.PROGRAM_SAMPLER)
    watch.warmup_complete()
    assert {names.PROGRAM_RAGGED_FWD,
            names.PROGRAM_SAMPLER} <= watch.warmed_programs()
    assert watch.violations() == []
    assert watch.counters()["rbg_jit_unwarmed_compiles_total"] == 0.0


def test_uncataloged_compiles_never_gate(watch):
    """Eager-op scaffolding and test helpers compile freely post-gate:
    only the PROGRAMS catalog is the contract."""
    watch.arm()
    watch.warmup_complete()

    def f(x):
        return x + 3
    f.__name__ = "totally_uncataloged_program"
    jax.jit(f)(jnp.ones(3))
    assert watch.violations() == []
    recs = [r for r in watch.compiles()
            if r["program"] == "totally_uncataloged_program"]
    assert recs and recs[0]["post_warmup"] and not recs[0]["violation"]


def test_violation_names_program_and_origin(watch):
    watch.arm()
    watch.warmup_complete()
    with pytest.raises(watch.JitCompileError) as ei:
        _compile_cataloged(names.PROGRAM_PD_HEAD)
    assert names.PROGRAM_PD_HEAD in str(ei.value)
    assert "after warmup_complete()" in str(ei.value)


def test_warn_mode_counts_instead_of_raising(monkeypatch):
    monkeypatch.setenv("RBG_JITWATCH", "warn")
    jitwatch.disarm()
    try:
        jitwatch.arm()
        _compile_cataloged(names.PROGRAM_SAMPLER)
        jitwatch.warmup_complete()
        _compile_cataloged(names.PROGRAM_SAMPLER, shape=(8,))   # no raise
        c = jitwatch.counters()
        assert c["rbg_jit_unwarmed_compiles_total"] == 1.0
        assert c["rbg_jit_compiles_total"] >= 2.0
        assert jitwatch.unwarmed_by_program() == {names.PROGRAM_SAMPLER: 1}
        assert len(jitwatch.violations()) == 1
        assert len(jitwatch.unwarmed()) == 1
    finally:
        jitwatch.disarm()


def test_reset_clears_records_but_keeps_hooks(watch):
    watch.arm()
    _compile_cataloged(names.PROGRAM_RAGGED_FWD)
    watch.warmup_complete()
    watch.reset()
    assert not watch.gate_armed()
    assert watch.compiles() == [] and watch.warmed_programs() == set()
    assert _listening()


def test_warmup_complete_without_arm_is_harmless(monkeypatch):
    monkeypatch.delenv("RBG_JITWATCH", raising=False)
    jitwatch.disarm()
    try:
        assert jitwatch.warmup_complete() == 0
        jnp.ones(2).block_until_ready()      # no wrappers: nothing counted
        assert jitwatch.counters()["rbg_jit_host_syncs_total"] == 0.0
    finally:
        jitwatch.disarm()


# ---- host-sync probe ----


def test_hot_section_strict_raises_on_forcer(watch):
    watch.arm()
    x = jnp.ones(2)
    with watch.hot_section("decode", strict=True):
        with pytest.raises(watch.HostSyncError):
            x.item()


def test_hot_section_counts_without_strict(watch):
    watch.arm()
    x = jnp.arange(4)
    before = watch.counters()["rbg_jit_host_syncs_total"]
    with watch.hot_section("decode"):
        float(x[0])
    assert watch.counters()["rbg_jit_host_syncs_total"] > before


def test_gate_armed_counts_syncs_outside_hot_sections(watch):
    watch.arm()
    x = jnp.ones(3)
    watch.warmup_complete()
    base = watch.counters()["rbg_jit_host_syncs_total"]
    x.block_until_ready()
    assert watch.counters()["rbg_jit_host_syncs_total"] >= base + 1


def test_syncs_before_gate_and_outside_sections_are_free(watch):
    watch.arm()
    x = jnp.ones(3)
    x.block_until_ready()                     # pre-gate, not hot: untracked
    assert watch.counters()["rbg_jit_host_syncs_total"] == 0.0


def test_hot_section_nesting_unwinds_cleanly(watch):
    watch.arm()
    with watch.hot_section("outer"):
        with watch.hot_section("inner", strict=False):
            pass
    # Depth unwound: a sync after the sections (gate unarmed) is free.
    jnp.ones(2).block_until_ready()
    assert watch.counters()["rbg_jit_host_syncs_total"] == 0.0


# ---- catalog agreement ----


def test_programs_catalog_names_are_stamped_constants():
    """The PROGRAMS frozenset and the PROGRAM_* constants must agree —
    the warmers stamp __name__ from the constants and the sentry gates on
    the frozenset, so drift here silently disables the gate."""
    constants = {v for k, v in vars(names).items()
                 if k.startswith("PROGRAM_") and isinstance(v, str)}
    assert constants == set(names.PROGRAMS)
    assert all(p.startswith("rbg_") for p in names.PROGRAMS)


# ---- the engine's warmers against the sentry --------------------------------


@pytest.mark.parametrize("model", ["tiny", "tiny-mla", "tiny-joyai"])
def test_warm_engine_serves_a_mixed_trace_without_a_compile(watch, model):
    """After ``warm_ragged`` / ``warm_decode`` / ``warm_join_windows`` /
    ``warm_samplers`` / ``warm_place`` (what ``EngineService.warmup`` runs)
    a trace of mixed prompt lengths, with arrivals joining a running batch,
    compiles no cataloged program: not the placement of a window's tokens
    for the unified step after it either, nor a decode state's built off an
    unread unified step, at any bucket the joins pass through. On the chip
    the benchmark reads the same count as ``setup.compiles_in_window``."""
    from rbg_tpu.engine import Engine, EngineConfig, SamplingParams
    watch.arm()
    eng = Engine(EngineConfig(
        model=model, page_size=8, num_pages=128, max_batch=4,
        max_seq_len=128, prefill_chunk=16, enable_radix_cache=False,
        multi_step=4, use_pallas="never"))
    warmed = (eng.warm_ragged() + eng.warm_decode()
              + eng.warm_join_windows() + eng.warm_samplers())
    # The placement is one jitted function of the module, not of the
    # engine: a second engine of the process finds it compiled.
    assert eng.warm_place() == 5            # buckets 1, 2, 4: 3 + 2
    assert warmed > 0 and watch.warmup_complete() >= warmed

    sp = SamplingParams(max_new_tokens=9)
    prompts = [list(range(1, 1 + n)) for n in (5, 40, 17, 3, 33, 12)]
    ids, done = [], {}
    for step in range(400):
        if prompts and step % 3 == 0:       # joins land mid-decode
            ids.append(eng.add_request(prompts.pop(), sp))
        for ev in eng.step():
            done.setdefault(ev.request_id, []).append(ev.token)
        if not prompts and not eng.has_work():
            break
    assert sorted(done) == sorted(ids)
    assert all(len(toks) == 9 for toks in done.values())
    assert watch.violations() == []
    assert watch.counters()["rbg_jit_unwarmed_compiles_total"] == 0.0
    # The trace did chain its steps: all but those that found the engine
    # with nothing in flight (every row at its length: nothing to dispatch
    # before the read).
    assert eng.metrics["lagged_steps"] > eng.metrics["steps_run"] // 2


SAMPLING_MIXES = {
    "greedy": [{}] * 4,
    "top_k": [{"temperature": 0.8, "top_k": 5}] * 4,
    "top_p": [{"temperature": 1.0, "top_p": 0.9, "min_p": 0.05}] * 4,
    "mixed": [{}, {"temperature": 0.7}, {"temperature": 0.9, "top_k": 3},
              {"temperature": 1.1, "top_p": 0.8}],
}


@pytest.fixture(scope="module")
def warmed_engine():
    from rbg_tpu.engine import Engine, EngineConfig
    eng = Engine(EngineConfig(
        model="tiny", page_size=8, num_pages=128, max_batch=4,
        max_seq_len=128, prefill_chunk=16, enable_radix_cache=False,
        multi_step=4, use_pallas="never"))
    eng.warm_ragged(), eng.warm_decode()
    eng.warm_join_windows(), eng.warm_samplers(), eng.warm_place()
    return eng


@pytest.mark.parametrize("mix", sorted(SAMPLING_MIXES))
def test_warm_engine_serves_every_sampling_mix_without_a_compile(
        watch, warmed_engine, mix):
    """``sample`` gates its stages on the device from the rows' own
    parameters, so a bucket has ONE plain decode program and ONE host-path
    sampler, and the warmers compile both: no mix of greedy, top-k and
    top-p rows compiles anything in serving, and the counters say how many
    of the sampler's runs sorted."""
    from rbg_tpu.engine import SamplingParams
    eng = warmed_engine
    watch.arm()
    watch.warmup_complete()     # every cataloged compile from here counts
    programs = (set(eng._dec_fn_cache), set(eng._samplers))
    runs, sorting = (eng.metrics[k] for k in ("sampler_steps",
                                              "sampler_sort_steps"))

    ids, done = [], {}
    for n, kw in enumerate(SAMPLING_MIXES[mix]):
        ids.append(eng.add_request(list(range(1, 6 + 7 * n)),
                                   SamplingParams(max_new_tokens=9, **kw)))
    while eng.has_work():
        for ev in eng.step():
            done.setdefault(ev.request_id, []).append(ev.token)
    assert sorted(done) == sorted(ids)
    assert watch.violations() == []
    assert watch.counters()["rbg_jit_unwarmed_compiles_total"] == 0.0
    assert (set(eng._dec_fn_cache), set(eng._samplers)) == programs
    runs = eng.metrics["sampler_steps"] - runs
    sorting = eng.metrics["sampler_sort_steps"] - sorting
    assert runs > 0
    if mix == "greedy":
        assert sorting == 0
    elif mix == "mixed":
        assert 0 < sorting <= runs
    else:
        assert sorting == runs
