"""span-name-registry: every span name the tracer emits is cataloged once
in ``rbg_tpu/obs/names.py`` ``SPANS`` (the tracing sibling of
metric-name-registry; ``RBG_TRACE_STRICT=1`` is the runtime complement).

Flags, at tracer call sites:

* names not in the catalog — at calls on the trace module itself
  (``trace.start_trace`` / ``trace.ingress_span`` / ``trace.child`` /
  ``trace.from_wire`` / ``trace.annotation``, resolved through this
  file's imports) and at
  ``<span>.child(...)`` method calls whose first argument is a
  dotted-lowercase span literal or a catalog constant;
* names that break the ``component.phase`` naming contract (lowercase
  dotted) at trusted trace-module calls.

And, cross-file at finalize time, the catalog module itself: duplicate
``SPAN_*`` values, constants declared but missing from the ``SPANS``
frozenset (an unregistered constant would pass call-site checks while
strict mode rejects it at runtime), and contract-breaking values.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional

from rbg_tpu.analysis.core import (FileContext, Finding, Rule, parse_module,
                                   str_const)

CATALOG_MODULE = "rbg_tpu.obs.names"
TRACE_MODULE = "rbg_tpu.obs.trace"

# Functions on the trace module that take a span name, and where it sits.
TRACE_FUNCS = {"child": 0, "start_trace": 0, "ingress_span": 0,
               "from_wire": 1, "annotation": 0}

# Naming contract: lowercase dotted component.phase (underscores allowed).
SPAN_NAME_RE = re.compile(r"^[a-z0-9_]+(\.[a-z0-9_]+)+$")


class SpanNameRegistry(Rule):
    name = "span-name-registry"
    description = ("span names must be cataloged in obs/names.py SPANS "
                   "and follow the lowercase component.phase contract")

    def __init__(self):
        from rbg_tpu.obs import names
        self.spans = names.SPANS
        self._names_module = names.__file__

    def _resolve_name_arg(self, arg: Optional[ast.expr],
                          imports: Dict[str, str]) -> Optional[str]:
        """A string literal, or a catalog-constant reference resolved
        through THIS file's import of the catalog module (same discipline
        as metric-name-registry: a foreign same-named constant must not
        borrow the catalog's value)."""
        lit = str_const(arg)
        if lit is not None:
            return lit
        from rbg_tpu.obs import names as names_mod
        const = None
        if (isinstance(arg, ast.Attribute)
                and isinstance(arg.value, ast.Name)
                and imports.get(arg.value.id) == CATALOG_MODULE):
            const = arg.attr
        elif (isinstance(arg, ast.Name)
              and imports.get(arg.id) == f"{CATALOG_MODULE}.{arg.id}"):
            const = arg.id
        if const is not None:
            value = getattr(names_mod, const, None)
            if isinstance(value, str):
                return value
        return None

    def check(self, ctx: FileContext) -> List[Finding]:
        findings: List[Finding] = []
        imports = ctx.imports()
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name_idx, trusted = self._span_call(node, imports)
            if name_idx is None or len(node.args) <= name_idx:
                continue
            span_name = self._resolve_name_arg(node.args[name_idx], imports)
            if span_name is None:
                continue
            if not trusted and not (span_name in self.spans
                                    or SPAN_NAME_RE.match(span_name)):
                # A bare `.child("text")` on an unknown object whose
                # argument looks nothing like a span name: out of scope.
                continue
            if span_name not in self.spans:
                findings.append(Finding(
                    self.name, ctx.path, node.lineno, node.col_offset,
                    f"span name {span_name!r} is not in the obs/names.py "
                    f"SPANS catalog — add a SPAN_* constant (and the SPANS "
                    f"entry) or fix the typo; RBG_TRACE_STRICT=1 would "
                    f"reject it at runtime"))
            elif trusted and not SPAN_NAME_RE.match(span_name):
                findings.append(Finding(
                    self.name, ctx.path, node.lineno, node.col_offset,
                    f"span name {span_name!r} breaks the lowercase dotted "
                    f"component.phase naming contract"))
        return findings

    def _span_call(self, node: ast.Call, imports: Dict[str, str]):
        """(name_arg_index, trusted) for a tracer call, (None, False)
        otherwise. ``trusted`` = provably a call into the trace module;
        untrusted = a ``.child(...)`` method call on some object, which is
        checked only when its argument already reads as a span name."""
        func = node.func
        if isinstance(func, ast.Attribute):
            if (isinstance(func.value, ast.Name)
                    and imports.get(func.value.id) == TRACE_MODULE
                    and func.attr in TRACE_FUNCS):
                return TRACE_FUNCS[func.attr], True
            if func.attr == "child":
                return 0, False
        elif isinstance(func, ast.Name):
            target = imports.get(func.id, "")
            if (target.startswith(f"{TRACE_MODULE}.")
                    and target.rsplit(".", 1)[1] in TRACE_FUNCS):
                return TRACE_FUNCS[target.rsplit(".", 1)[1]], True
        return None, False

    def finalize(self) -> List[Finding]:
        """Audit the catalog: duplicates, unregistered SPAN_* constants,
        contract-breaking values."""
        findings: List[Finding] = []
        try:
            _, tree = parse_module(self._names_module)
        except (OSError, SyntaxError):
            return findings
        seen: Dict[str, str] = {}
        for node in tree.body:
            if not (isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and node.targets[0].id.startswith("SPAN_")):
                continue
            const = node.targets[0].id
            value = str_const(node.value)
            if value is None:
                continue
            if value in seen:
                findings.append(Finding(
                    self.name, self._names_module, node.lineno, 0,
                    f"duplicate span registration: {const} and "
                    f"{seen[value]} both name {value!r}"))
            seen[value] = const
            if value not in self.spans:
                findings.append(Finding(
                    self.name, self._names_module, node.lineno, 0,
                    f"span constant {const} = {value!r} is not in the "
                    f"SPANS frozenset — call sites using the constant "
                    f"would pass the lint while RBG_TRACE_STRICT rejects "
                    f"them at runtime"))
            if not SPAN_NAME_RE.match(value):
                findings.append(Finding(
                    self.name, self._names_module, node.lineno, 0,
                    f"cataloged span name {value!r} breaks the lowercase "
                    f"dotted component.phase naming contract"))
        return findings
