"""Checker ``metrics``: emissions match the declared trace inventory.

``utils/trace.py`` is the single source of truth for metric families —
every ``declare("pas_…", kind, help)`` call there populates
``trace.METRICS`` and drives both exposition and trace-lint's runtime
scrape.  This checker covers the two halves the runtime scrape cannot:

  * **undeclared-metric** — a ``COUNTERS.inc("name", …)`` /
    ``set_gauge("name", …)`` whose statically-resolved family name is
    not declared.  At runtime this emits a family exposition never
    advertises, which trace-lint only notices if the code path actually
    fires during the lint run.
  * **dead-metric** — a declared family with no emission site anywhere
    in the package.  Dead declarations rot the dashboards and hide
    real regressions (a panel stuck at zero looks healthy).

Family-name resolution: string literals, module-level string constants
(``HISTOGRAM_METRIC``), and ``module.CONST`` attribute references.
Wrapper methods whose family name arrives as a *function parameter*
(workqueue's ``self._inc(name)``) are skipped silently — their callers
are resolved instead.  The dead-metric scan additionally accepts any
equal string literal elsewhere in the package (in the inventory module,
anywhere but a ``declare()`` call's own arguments) as evidence of use,
so indirection — a table of families moved in by one ``inc_many`` —
doesn't false-positive.

``LatencyRecorder.observe`` is not an emission in this model: its
family is fixed (``utils.tracing.HISTOGRAM_METRIC``) and its argument
is a verb *label*, not a family name.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from platform_aware_scheduling_tpu.analysis.core import (
    Finding,
    ModuleInfo,
    dotted_name,
    enclosing_functions,
)

#: methods whose first argument is a metric family name
EMIT_METHODS = frozenset({"inc", "set_gauge"})

#: the module whose ``declare(...)`` calls define the inventory
DEFAULT_INVENTORY = "utils.trace"


def _inventory(mod: ModuleInfo) -> Dict[str, int]:
    """family name -> declare() line, from literal declare calls."""
    families: Dict[str, int] = {}
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        callee = node.func
        name = callee.id if isinstance(callee, ast.Name) else (
            callee.attr if isinstance(callee, ast.Attribute) else None
        )
        if name != "declare" or not node.args:
            continue
        first = node.args[0]
        if isinstance(first, ast.Constant) and isinstance(first.value, str):
            families.setdefault(first.value, node.lineno)
    return families


def _resolve_family(
    node: ast.AST,
    mod: ModuleInfo,
    modules: Dict[str, ModuleInfo],
    params: Set[str],
) -> Tuple[Optional[str], bool]:
    """(family, is_param): the statically-resolved family name, or
    (None, True) for the sanctioned wrapper pattern (name is a function
    parameter), or (None, False) for anything else unresolvable."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value, False
    if isinstance(node, ast.Name):
        if node.id in params:
            return None, True
        if node.id in mod.constants:
            return mod.constants[node.id], False
        return None, False
    if isinstance(node, ast.Attribute):
        dotted = dotted_name(node, mod.imports)
        if dotted and "." in dotted:
            owner, const = dotted.rsplit(".", 1)
            target = modules.get(owner)
            if target is not None and const in target.constants:
                return target.constants[const], False
        return None, False
    return None, False


def _function_params(mod: ModuleInfo, qual: str) -> Set[str]:
    node = mod.functions.get(qual)
    if node is None or not isinstance(
        node, (ast.FunctionDef, ast.AsyncFunctionDef)
    ):
        return set()
    args = node.args
    return {
        arg.arg
        for arg in (
            list(args.posonlyargs)
            + list(args.args)
            + list(args.kwonlyargs)
            + ([args.vararg] if args.vararg else [])
            + ([args.kwarg] if args.kwarg else [])
        )
    }


def check(
    modules: Dict[str, ModuleInfo], inventory: Optional[str] = None
) -> List[Finding]:
    inv_modname = inventory or DEFAULT_INVENTORY
    inv_mod = modules.get(inv_modname)
    if inv_mod is None:
        return []  # fixture trees without an inventory: nothing to check
    families = _inventory(inv_mod)
    findings: List[Finding] = []
    emitted: Set[str] = set()
    literal_refs: Set[str] = set()
    for mod in modules.values():
        spans: Optional[Dict[int, str]] = None
        declared_here: Set[int] = set()
        if mod.modname == inv_modname:
            declared_here = {
                id(arg)
                for node in ast.walk(mod.tree)
                if isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "declare"
                for arg in node.args
            }
        for node in ast.walk(mod.tree):
            if (
                isinstance(node, ast.Constant)
                and isinstance(node.value, str)
                and id(node) not in declared_here
            ):
                literal_refs.add(node.value)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            callee = node.func
            if not (
                isinstance(callee, ast.Attribute)
                and callee.attr in EMIT_METHODS
            ):
                continue
            first: Optional[ast.AST] = node.args[0] if node.args else None
            if first is None:
                for kw in node.keywords:
                    if kw.arg == "name":
                        first = kw.value
                        break
            if first is None:
                continue
            if spans is None:
                spans = enclosing_functions(mod.tree)
            func = spans.get(node.lineno, "<module>")
            family, is_param = _resolve_family(
                first, mod, modules, _function_params(mod, func)
            )
            if family is None:
                continue  # wrapper pattern or dynamic name; dead-scan
                # still sees literal indirection, and wrappers' callers
                # resolve on their own
            emitted.add(family)
            if family not in families:
                findings.append(Finding(
                    "metrics",
                    "undeclared-metric",
                    mod.relpath,
                    node.lineno,
                    f"{func}:{family}",
                    f"emission of {family!r} in {func} but the family is "
                    "not declared in trace.METRICS — add a declare() to "
                    "utils/trace.py (exposition and trace-lint only see "
                    "declared families)",
                ))
    for family, line in sorted(families.items()):
        if family in emitted or family in literal_refs:
            continue
        findings.append(Finding(
            "metrics",
            "dead-metric",
            inv_mod.relpath,
            line,
            f"declare:{family}",
            f"family {family!r} is declared but has no emission site or "
            "reference anywhere in the package — delete the declare() or "
            "wire up the emission (a permanently-absent family hides "
            "regressions behind healthy-looking dashboards)",
        ))
    return findings
