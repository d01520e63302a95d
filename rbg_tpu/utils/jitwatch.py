"""Opt-in runtime compile & host-sync sentry — the dynamic half of the
``jit-hygiene`` discipline (the static rules prove the LEXICAL contract;
this module catches what they cannot see: a program variant the warmers
silently stopped covering, a shape that slipped past its bucket, a host
sync introduced behind a dynamic dispatch).

Two probes share one arming matrix (mirroring locktrace/racetrace):

* **Compile sentry** — ``arm()`` listens to JAX's public compile signal
  (``jax.monitoring``'s ``backend_compile_duration`` scalar event, fired
  as a program enters the backend compiler; unregistered by
  ``disarm()``) and records every XLA compile as (program name, origin
  stack). ``arm()`` then compiles a throwaway ``jit`` and raises
  :class:`JitWatchBlindError` unless the listener saw it: a JAX that
  moved the signal must fail the gate, not pass it vacuously. Compiles
  recorded before
  :func:`warmup_complete` are the warmup set; after it the gate is armed
  and any compile of a *cataloged* program (``obs.names.PROGRAMS`` — the
  catalog warmers and sentry agree on) raises :class:`JitCompileError`
  (``RBG_JITWATCH=1``) or warns + counts
  ``rbg_jit_unwarmed_compiles_total{program=}`` (``RBG_JITWATCH=warn``).
  Non-cataloged compiles (XLA's tiny eager-op programs, test scaffolding)
  are recorded for the report but never gate: the catalog IS the contract.

* **Host-sync probe** — armed alongside the sentry: the device→host
  forcers (``ArrayImpl.item/__array__/__float__/__int__/__bool__/
  __index__/block_until_ready`` and ``jax.device_get``) are wrapped to
  count ``rbg_jit_host_syncs_total`` once the gate is armed, and
  :func:`hot_section` scopes a strict probe (count always; raise
  :class:`HostSyncError` with ``strict=True``) over a critical region.
  ``jax.transfer_guard`` is layered on in strict sections as belt and
  braces for real accelerators — on the CPU backend it does not fire
  (verified on this image), which is why the forcers are wrapped directly.

Off by default: nothing is patched, zero overhead. Armed by
``RBG_JITWATCH=1`` (raise) or ``RBG_JITWATCH=warn`` (log + count, the
stress-drill mode). Like RBG_RACETRACE, set the env var / call ``arm()``
BEFORE warmup so the warmup set is recorded; ``rbg-tpu stress --jitwatch``
does exactly this and folds the verdict into a ``zero_unwarmed_compiles``
invariant.
"""

from __future__ import annotations

import logging
import os
import threading
import traceback
from typing import Dict, List, Optional

from rbg_tpu.utils.chipenv import COMPILE_EVENT

log = logging.getLogger("rbg_tpu.jitwatch")

ENV_VAR = "RBG_JITWATCH"

MAX_RECORDS = 500          # bound the report payload
STACK_FRAMES = 4           # innermost rbg_tpu frames kept per record


class JitCompileError(RuntimeError):
    """A cataloged program compiled after warmup_complete()."""


class HostSyncError(RuntimeError):
    """A device→host sync fired inside a strict hot_section()."""


class JitWatchBlindError(RuntimeError):
    """arm() compiled a program and the compile listener saw nothing."""


def mode() -> str:
    """"" (disabled) | "raise" | "warn" — from the RBG_JITWATCH env var."""
    v = (os.environ.get(ENV_VAR) or "").strip().lower()
    if not v or v in ("0", "false", "off"):
        return ""
    return "warn" if v == "warn" else "raise"


def enabled() -> bool:
    return bool(mode())


# ---- global state ----

_state = threading.Lock()
_tls = threading.local()        # .hot: int depth, .strict: bool
_installed = [False]
_saved: Dict[str, tuple] = {}   # "<seam>" -> restore info
_gate = [False]                 # True after warmup_complete()
_mode = ["raise"]
_records: List[dict] = []       # every compile seen while armed
_warmed: set = set()            # program names compiled before the gate
_unwarmed_counts: Dict[str, int] = {}
_violations: List[str] = []
_syncs = [0]


# ---- arming ----

def arm(strict: Optional[bool] = None) -> bool:
    """Install the compile listener + sync wrappers (idempotent), then
    prove the listener sees a compile. ``strict`` overrides the env mode
    (True = raise, False = warn). Call BEFORE warmup so the warmup compile
    set is recorded. Returns True once installed; raises
    :class:`JitWatchBlindError` when a compile goes unseen."""
    m = mode() or "raise"
    if strict is not None:
        m = "raise" if strict else "warn"
    _mode[0] = m
    if _installed[0]:
        return True
    _install_compile_hook()
    _install_sync_wrappers()
    _installed[0] = True
    try:
        _self_test()
    except JitWatchBlindError:
        disarm()
        raise
    return True


_SELF_TEST_PROGRAM = "rbg_jitwatch_self_test"


def _self_test() -> None:
    """Compile a throwaway program and require its record. The program is
    new each time (a fresh function object), so jit's own cache never
    answers for it; the record is dropped again so reports count only the
    caller's compiles."""
    import jax
    import jax.numpy as jnp

    def probe(x):
        return x + 1
    probe.__name__ = _SELF_TEST_PROGRAM
    jax.jit(probe)(jnp.zeros((), jnp.int32))
    with _state:
        seen = any(r["program"] == _SELF_TEST_PROGRAM for r in _records)
        _records[:] = [r for r in _records
                       if r["program"] != _SELF_TEST_PROGRAM]
        _warmed.discard(_SELF_TEST_PROGRAM)
    if not seen:
        raise JitWatchBlindError(
            f"jitwatch armed but blind: a jit compile fired no "
            f"{COMPILE_EVENT!r} event on jax {jax.__version__} — every "
            f"zero-unwarmed-compiles verdict would be vacuous")


def disarm() -> None:
    """Remove every patch and reset all state (test isolation)."""
    import jax
    for key, (obj_kind, attr, had, value) in list(_saved.items()):
        if obj_kind == "monitoring":
            jax.monitoring.unregister_scalar_listener(value)
        elif obj_kind == "arrayimpl":
            from jax._src.array import ArrayImpl
            if had:
                setattr(ArrayImpl, attr, value)
            else:
                try:
                    delattr(ArrayImpl, attr)
                except AttributeError:
                    pass
        elif obj_kind == "jax":
            setattr(jax, attr, value)
        del _saved[key]
    _installed[0] = False
    reset()


def reset() -> None:
    """Clear records and disarm the gate (the hooks stay installed)."""
    with _state:
        _gate[0] = False
        _records.clear()
        _warmed.clear()
        _unwarmed_counts.clear()
        _violations.clear()
        _syncs[0] = 0


def warmup_complete() -> int:
    """Arm the gate: compiles recorded so far are the blessed warmup set;
    any cataloged program compiling after this call is a violation.
    Idempotent. Returns the number of warmup compiles recorded."""
    with _state:
        n = len(_records)
        _gate[0] = True
    if _installed[0]:
        log.info("jitwatch gate armed after %d warmup compiles", n)
    return n


def gate_armed() -> bool:
    return _gate[0]


# ---- report surface ----

def compiles() -> List[dict]:
    with _state:
        return [dict(r) for r in _records]


def unwarmed() -> List[dict]:
    with _state:
        return [dict(r) for r in _records if r["violation"]]


def warmed_programs() -> set:
    with _state:
        return set(_warmed)


def unwarmed_by_program() -> Dict[str, int]:
    with _state:
        return dict(_unwarmed_counts)


def violations() -> List[str]:
    with _state:
        return list(_violations)


def counters() -> Dict[str, float]:
    """The ``rbg_jit_*`` counter snapshot for reports."""
    with _state:
        return {
            "rbg_jit_compiles_total": float(len(_records)),
            "rbg_jit_unwarmed_compiles_total":
                float(sum(_unwarmed_counts.values())),
            "rbg_jit_host_syncs_total": float(_syncs[0]),
        }


# ---- compile hook ----

def _program_name(fun_name: str) -> str:
    """The jitted callable's name out of the event's ``fun_name`` tag
    (``jit(<name>)``, ``pmap(<name>)``), so it matches the
    ``obs.names.PROGRAMS`` catalog."""
    if fun_name.endswith(")") and "(" in fun_name:
        return fun_name[fun_name.index("(") + 1:-1]
    return fun_name or "unknown"


def _origin() -> List[str]:
    frames = [f"{os.path.basename(f.filename)}:{f.lineno}:{f.name}"
              for f in traceback.extract_stack()
              if f"rbg_tpu{os.sep}" in f.filename
              and "jitwatch" not in f.filename]
    return frames[-STACK_FRAMES:]


def _record_compile(prog: str) -> None:
    from rbg_tpu.obs import names
    cataloged = prog in names.PROGRAMS
    desc = None
    with _state:
        rec = {
            "program": prog,
            "origin": _origin(),
            "post_warmup": _gate[0],
            "violation": bool(_gate[0] and cataloged),
        }
        if len(_records) < MAX_RECORDS:
            _records.append(rec)
        if not _gate[0]:
            _warmed.add(prog)
            return
        if not cataloged:
            return
        _unwarmed_counts[prog] = _unwarmed_counts.get(prog, 0) + 1
        desc = (f"unwarmed compile of {prog} after warmup_complete() at "
                f"{' <- '.join(reversed(rec['origin'])) or '<no rbg frame>'}")
        if len(_violations) < MAX_RECORDS:
            _violations.append(desc)
    try:
        from rbg_tpu.obs import metrics
        metrics.REGISTRY.inc(names.JIT_UNWARMED_COMPILES_TOTAL,
                             program=prog)
    except Exception:   # metrics must never mask the finding
        pass
    if _mode[0] != "warn":
        raise JitCompileError(desc)
    log.warning("%s", desc)


def _on_compile_event(event: str, _value, fun_name: str = "",
                      **_kw) -> None:
    # Fired as the program ENTERS the compiler: a strict-mode raise here
    # surfaces from the jit call itself, before the compile is paid.
    if event == COMPILE_EVENT:
        _record_compile(_program_name(fun_name))


def _install_compile_hook() -> None:
    import jax
    jax.monitoring.register_scalar_listener(_on_compile_event)
    _saved["monitoring.compile"] = (
        "monitoring", "scalar_listener", True, _on_compile_event)


# ---- host-sync probe ----

_FORCERS = ("item", "block_until_ready", "__array__", "__float__",
            "__int__", "__bool__", "__index__")


def _on_sync(kind: str) -> None:
    hot = getattr(_tls, "hot", 0) > 0
    if not (hot or _gate[0]):
        return
    with _state:
        _syncs[0] += 1
    try:
        from rbg_tpu.obs import metrics, names
        metrics.REGISTRY.inc(names.JIT_HOST_SYNCS_TOTAL)
    except Exception:
        pass
    if hot and getattr(_tls, "strict", False):
        raise HostSyncError(
            f"device->host sync ({kind}) inside a strict hot_section")


def _install_sync_wrappers() -> None:
    import jax
    from jax._src.array import ArrayImpl

    def make(attr, orig):
        def traced(self, *a, **kw):
            _on_sync(attr)
            return orig(self, *a, **kw)
        traced.__name__ = f"jitwatch_{attr}"
        return traced

    for attr in _FORCERS:
        had = attr in ArrayImpl.__dict__
        orig = getattr(ArrayImpl, attr, None)
        if orig is None:
            continue
        _saved[f"arrayimpl.{attr}"] = ("arrayimpl", attr, had, orig)
        setattr(ArrayImpl, attr, make(attr, orig))

    orig_get = jax.device_get

    def traced_device_get(x):
        _on_sync("device_get")
        return orig_get(x)

    _saved["jax.device_get"] = ("jax", "device_get", True, orig_get)
    jax.device_get = traced_device_get


class hot_section:
    """Context manager: count every device→host sync in the section (and
    raise :class:`HostSyncError` at the first one when ``strict=True``).
    Layers ``jax.transfer_guard_device_to_host`` over strict sections as
    belt-and-braces for real accelerators (inert on CPU — the wrapped
    forcers installed by :func:`arm` do the counting there). Requires
    :func:`arm` to have installed the wrappers; a disarmed hot_section is
    a no-op."""

    def __init__(self, label: str = "hot", strict: bool = False):
        self.label = label
        self.strict = strict
        self._guard = None

    def __enter__(self):
        _tls.hot = getattr(_tls, "hot", 0) + 1
        _tls.strict = self.strict
        if self.strict and _installed[0]:
            try:
                import jax
                self._guard = jax.transfer_guard_device_to_host("disallow")
                self._guard.__enter__()
            except Exception:
                self._guard = None
        return self

    def __exit__(self, *exc):
        _tls.hot = max(0, getattr(_tls, "hot", 1) - 1)
        if _tls.hot == 0:
            _tls.strict = False
        if self._guard is not None:
            try:
                self._guard.__exit__(*exc)
            except Exception:
                pass
            self._guard = None
        return False
