"""HS01 — host-sync leak inside traced (jitted / loop-body) code.

The fused BSP drivers' headline invariant is ONE host sync per run
(pinned at runtime by the `engine.dispatch.*` counters of `repro.obs`). A `np.asarray`,
`.item()`, `float()`, `bool()` or `jax.device_get` on a traced value
inside a `@jax.jit` function or a `lax.while_loop`/`lax.scan` body either
breaks tracing outright (ConcretizationTypeError at the first run with a
new shape) or — worse — silently forces a device round-trip on every
call when the value happens to be concrete. This checker protects the
single-dispatch invariant statically.

Traced scopes are collected per module:
  - functions decorated `@jax.jit` / `@functools.partial(jax.jit, ...)`,
  - functions wrapped by a `jax.jit(fn)` / `shard_map(fn, ...)` call,
  - functions (or lambdas) passed to `lax.while_loop` / `lax.scan` /
    `lax.fori_loop` / `lax.cond` / `lax.switch` / `lax.map` or used as a
    `pl.pallas_call` kernel,
  - anything lexically nested inside one of the above.

`float()`/`bool()`/`int()` are flagged only when the argument is clearly
dynamic (not a literal, `len(...)`, `.shape`/`.ndim` access, or a module
constant spelled UPPER_CASE) — converting static shape arithmetic is fine.
"""
from __future__ import annotations

import ast

from repro.analysis.astutil import (
    build_import_map,
    call_qualname,
    decorator_is_jit,
    dotted_name,
    qualify,
    unparse,
)
from repro.analysis.core import Checker, register_checker

# Canonical (import-map-qualified) names that force a device->host sync.
SYNC_CALLS = {
    "numpy.asarray",
    "numpy.array",
    "numpy.asscalar",
    "jax.device_get",
    "jax.block_until_ready",
}
SYNC_METHODS = {"item", "tolist", "block_until_ready"}
CAST_BUILTINS = {"float", "bool", "int"}

# lax control-flow primitives whose callable args become traced bodies.
LOOP_PRIMS = {
    "jax.lax.while_loop",
    "jax.lax.scan",
    "jax.lax.fori_loop",
    "jax.lax.cond",
    "jax.lax.switch",
    "jax.lax.map",
    "jax.lax.associative_scan",
}
WRAPPERS = {"jax.jit", "jax.pmap", "jax.vmap"}
KERNEL_WRAPPERS = {"pallas_call", "shard_map"}


def _is_static_expr(node: ast.AST) -> bool:
    """Expressions whose host conversion is trace-safe (static metadata)."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Name):
        # Module-level UPPER_CASE constants (INF, BLOCK_E, ...) are static.
        return node.id.isupper()
    if isinstance(node, ast.Attribute):
        return node.attr in ("ndim", "size", "dtype") or node.attr.isupper()
    if isinstance(node, ast.Subscript):
        base = node.value
        return isinstance(base, ast.Attribute) and base.attr == "shape"
    if isinstance(node, ast.Call):
        fn = dotted_name(node.func)
        return fn in ("len", "min", "max") and all(_is_static_expr(a) for a in node.args)
    if isinstance(node, ast.BinOp):
        return _is_static_expr(node.left) and _is_static_expr(node.right)
    if isinstance(node, ast.UnaryOp):
        return _is_static_expr(node.operand)
    return False


def _callable_args(call: ast.Call, qn: str) -> list:
    """The argument positions of `call` that are traced callables."""
    if qn in LOOP_PRIMS:
        return list(call.args)
    if qn in WRAPPERS or qn.rsplit(".", 1)[-1] in KERNEL_WRAPPERS:
        return list(call.args[:1]) + [
            kw.value for kw in call.keywords if kw.arg in ("f", "fun", "kernel")
        ]
    return []


def _jit_static_names(dec: ast.AST) -> set:
    """Literal static_argnames on a jit decorator call — those parameters
    are concrete Python values inside the trace, not tracers."""
    names: set = set()
    if isinstance(dec, ast.Call):
        for kw in dec.keywords:
            if kw.arg in ("static_argnames", "static_argnums"):
                for node in ast.walk(kw.value):
                    if isinstance(node, ast.Constant) and isinstance(node.value, str):
                        names.add(node.value)
    return names


def _collect_traced(tree: ast.Module, imports: dict) -> list:
    """(scope node, static param names) pairs whose bodies trace under jit."""
    local_funcs = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # Innermost definition wins for nested same-name defs; good
            # enough for scope marking (names are module-unique in practice).
            local_funcs.setdefault(node.name, node)

    traced = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if decorator_is_jit(dec, imports):
                    traced.append((node, _jit_static_names(dec)))
                    break
        elif isinstance(node, ast.Call):
            qn = call_qualname(node, imports) or ""
            args = _callable_args(node, qn)
            statics = _jit_static_names(node)
            # functools.partial(kernel, ...) as a pallas_call kernel arg.
            expanded = []
            for a in args:
                if (
                    isinstance(a, ast.Call)
                    and qualify(dotted_name(a.func), imports) == "functools.partial"
                    and a.args
                ):
                    expanded.append(a.args[0])
                else:
                    expanded.append(a)
            for a in expanded:
                if isinstance(a, ast.Lambda):
                    traced.append((a, statics))
                elif isinstance(a, ast.Name) and a.id in local_funcs:
                    traced.append((local_funcs[a.id], statics))
    return traced


@register_checker
class HostSyncChecker(Checker):
    code = "HS01"
    name = "host-sync-leak"
    description = (
        "np.asarray/.item()/float()/bool()/jax.device_get on traced values inside "
        "@jax.jit functions or lax.while_loop/lax.scan bodies (breaks the "
        "single-dispatch invariant)"
    )
    severity = "error"
    scope = "module"

    def check_module(self, module, report) -> None:
        imports = build_import_map(module.tree)
        traced = _collect_traced(module.tree, imports)
        # Nested functions inside traced scopes are traced too; ast.walk from
        # each traced root covers them, and run_checkers dedupes overlaps.
        seen = set()
        for scope, statics in traced:
            scope_name = getattr(scope, "name", "<lambda>")
            for node in ast.walk(scope):
                if not isinstance(node, ast.Call) or id(node) in seen:
                    continue
                seen.add(id(node))
                self._check_call(node, imports, module, scope_name, statics, report)

    def _check_call(self, node: ast.Call, imports, module, scope_name, statics, report) -> None:
        qn = call_qualname(node, imports)
        if qn in SYNC_CALLS:
            report(
                module.path,
                node.lineno,
                node.col_offset,
                f"`{unparse(node)}` inside traced scope `{scope_name}` forces a "
                "device->host sync (or fails to trace); hoist it out of the jitted code",
                anchor=scope_name,
            )
            return
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in SYNC_METHODS
            and not node.args
            and dotted_name(node.func.value) not in imports  # e.g. config.item(...) modules
        ):
            report(
                module.path,
                node.lineno,
                node.col_offset,
                f"`.{node.func.attr}()` inside traced scope `{scope_name}` forces a "
                "device->host sync; return the array and convert outside the trace",
                anchor=scope_name,
            )
            return
        if (
            isinstance(node.func, ast.Name)
            and node.func.id in CAST_BUILTINS
            and len(node.args) == 1
            and not _is_static_expr(node.args[0])
            and not (
                isinstance(node.args[0], ast.Name) and node.args[0].id in statics
            )
        ):
            report(
                module.path,
                node.lineno,
                node.col_offset,
                f"`{unparse(node)}` inside traced scope `{scope_name}` concretizes a "
                f"traced value; use jnp.{node.func.id}32-style casts or move the "
                "conversion to the host side",
                anchor=scope_name,
            )
