"""jit-purity: no host side effects lexically inside jitted kernels.

A ``jax.jit`` / ``shard_map`` body runs twice in spirit: once as a
Python trace (where a ``print``, metric bump, ledger stamp or clock
read executes at TRACE time — then never again, silently) and forever
after as compiled XLA (where it doesn't exist at all).  Worse, a value-
dependent host call forces a retrace per shape.  The contract for
``ops/``: kernel bodies are pure array programs; telemetry lives in the
host-side wrappers (the solver's ``kernel`` span et al.).

Detection is lexical: functions decorated with ``jit``/``jax.jit``
(including ``partial(jax.jit, ...)``) or passed by name to
``jax.jit(...)`` / ``shard_map(...)`` are kernels; their bodies —
nested defs included — must not call ``print``, any alias of the
metrics or ledger modules, or read ``time.*`` / ``datetime.*`` (ALL of
``time``, including ``perf_counter``: inside a kernel even duration
telemetry is trace-time-only noise).
"""

from __future__ import annotations

import ast
from typing import List, Set

from ..framework import (Finding, LintContext, ParsedModule, Rule,
                         dotted_name, import_aliases, importfrom_aliases)

_DEFAULT_SCOPE = ("ops/",)


def _is_jit_expr(node: ast.AST) -> bool:
    """True for `jit`, `jax.jit`, `shard_map`, `partial(jax.jit, ...)`,
    `functools.partial(jit, ...)` decorator/callee expressions."""
    dn = dotted_name(node)
    if dn in ("jit", "jax.jit", "shard_map",
              "jax.shard_map"):
        return True
    if isinstance(node, ast.Call):
        fdn = dotted_name(node.func)
        if fdn in ("partial", "functools.partial") and node.args:
            return _is_jit_expr(node.args[0])
        # shard_map(body, mesh=...)(...) style wrappers
        return _is_jit_expr(node.func)
    return False


class JitPurityRule(Rule):
    name = "jit-purity"
    description = ("no prints, metric bumps, ledger stamps or clock "
                   "reads inside jitted/shard_map kernel bodies in ops/")

    def __init__(self, scope=_DEFAULT_SCOPE):
        self.scope = tuple(scope)

    def check(self, ctx: LintContext) -> List[Finding]:
        out: List[Finding] = []
        for mod in ctx.modules:
            if not ctx.in_scope(mod, self.scope):
                continue
            out.extend(self._check_module(mod))
        return out

    def _check_module(self, mod: ParsedModule) -> List[Finding]:
        kernels = self._find_kernels(mod)
        if not kernels:
            return []
        time_names = import_aliases(mod.tree, "time") | {"time"}
        dt_names = import_aliases(mod.tree, "datetime") | {"datetime"}
        metric_names = (importfrom_aliases(mod.tree, "metrics")
                        | import_aliases(mod.tree, "metrics"))
        ledger_names = (importfrom_aliases(mod.tree, "trace",
                                           {"ledger"})
                        | importfrom_aliases(mod.tree, "trace.ledger"))
        out: List[Finding] = []
        for fn in kernels:
            for node in ast.walk(fn):
                self._check_node(mod, fn, node, time_names, dt_names,
                                 metric_names, ledger_names, out)
        return out

    def _check_node(self, mod, fn, node, time_names, dt_names,
                    metric_names, ledger_names, out) -> None:
        if isinstance(node, ast.Call):
            if isinstance(node.func, ast.Name) \
                    and node.func.id == "print":
                out.append(mod.finding(
                    self.name, node,
                    f"print() inside jitted kernel `{fn.name}` — "
                    f"executes at trace time only"))
                return
            root = node.func
            while isinstance(root, ast.Attribute):
                root = root.value
            if isinstance(root, ast.Name):
                if root.id in metric_names:
                    out.append(mod.finding(
                        self.name, node,
                        f"metric call inside jitted kernel `{fn.name}` "
                        f"— no-ops under tracing; bump in the host "
                        f"wrapper"))
                elif root.id in ledger_names:
                    out.append(mod.finding(
                        self.name, node,
                        f"ledger stamp inside jitted kernel "
                        f"`{fn.name}` — no-ops under tracing"))
            return
        if isinstance(node, ast.Attribute):
            dn = dotted_name(node)
            if dn is None:
                return
            parts = dn.split(".")
            if len(parts) >= 2 and (parts[0] in time_names
                                    or parts[0] in dt_names) \
                    and parts[0] not in ("self",):
                out.append(mod.finding(
                    self.name, node,
                    f"clock read `{dn}` inside jitted kernel "
                    f"`{fn.name}` — trace-time constant, not a "
                    f"runtime value"))

    # -- kernel discovery -------------------------------------------------

    def _find_kernels(self, mod: ParsedModule) -> List[ast.FunctionDef]:
        defs_by_name = {}
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.FunctionDef):
                defs_by_name.setdefault(node.name, []).append(node)
        kernels: Set[ast.FunctionDef] = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.FunctionDef):
                if any(_is_jit_expr(d) for d in node.decorator_list):
                    kernels.add(node)
            elif isinstance(node, ast.Call) \
                    and not isinstance(node.func, ast.Call) \
                    and _is_jit_expr(node.func):
                for arg in node.args[:1]:
                    if isinstance(arg, ast.Name):
                        for d in defs_by_name.get(arg.id, ()):
                            kernels.add(d)
        # drop kernels nested inside other kernels: the outer walk
        # visits them anyway and double-reporting is noise
        nested = {child for k in kernels for child in ast.walk(k)
                  if isinstance(child, ast.FunctionDef)
                  and child is not k and child in kernels}
        return sorted(kernels - nested, key=lambda f: f.lineno)
