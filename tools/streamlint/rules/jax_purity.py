"""SL3xx — jit/x64 purity in the jax engine.

The jax backend owes its parity story to two disciplines: x64 is
enabled *scoped* (the ``repro.compat.scoped_x64`` context inside the
``x64`` kernel wrapper), never via the process-global config flip that would silently
retrace every other jax user in the process; and the jitted kernel
seams stay pure — no host syncs, no Python control flow on traced
values (shape/ndim dispatch is fine: it is resolved at trace time).

* SL301 — global x64 flip: ``jax.config.update("jax_enable_x64", …)``
  (any spelling) or assignment to ``jax.config.jax_enable_x64``.
  Checked in **every** scanned file.
* SL302 — host sync inside a jitted kernel: ``.item()``/``.tolist()``/
  ``.block_until_ready()``, any ``np.*``/``numpy.*`` call, or
  ``float()``/``int()``/``bool()`` on a non-constant value.
* SL303 — data-dependent Python branch inside a jitted kernel: ``if``/
  ``while``/``assert`` whose test involves anything beyond shapes,
  dtypes, ``len()``/``isinstance()`` and constants.

"Jitted kernel" means, within the configured jax-engine module: any
def decorated with a jit wrapper (``x64``, ``jit``, ``jax.jit``), any
def whose name is passed into such a wrapper (including through
``jax.vmap(...)``), and every def nested inside one.
"""

from __future__ import annotations

import ast
from typing import Iterable, Iterator

from tools.streamlint.engine import (Diagnostic, Project, SourceFile,
                                     rule)
from tools.streamlint.rules._helpers import dotted

#: attribute accesses that are resolved at trace time, not run time
_STATIC_ATTRS = {"ndim", "shape", "dtype", "size"}
_STATIC_CALLS = {"len", "isinstance", "issubclass"}
_HOST_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_CASTS = {"float", "int", "bool"}


def _is_x64_flip(node: ast.AST) -> bool:
    if isinstance(node, ast.Call):
        d = dotted(node.func) or ""
        if d.split(".")[-1] == "update" and node.args:
            arg = node.args[0]
            return (isinstance(arg, ast.Constant)
                    and arg.value == "jax_enable_x64")
    if isinstance(node, ast.Assign):
        return any((dotted(t) or "").endswith("config.jax_enable_x64")
                   for t in node.targets)
    return False


@rule("SL301", "never flip jax_enable_x64 globally; use the scoped "
               "repro.compat.scoped_x64 context")
def sl301(project: Project,
          scanned: list[SourceFile]) -> Iterable[Diagnostic]:
    for sf in scanned:
        for node in ast.walk(sf.tree):
            if _is_x64_flip(node):
                yield Diagnostic(
                    rule="SL301", file=sf.path, line=node.lineno,
                    message=("global jax_enable_x64 flip; use "
                             "repro.compat.scoped_x64() around kernel "
                             "calls instead"))


def _wrapper_hit(node: ast.AST, wrappers: tuple[str, ...]) -> bool:
    d = dotted(node)
    if d is None:
        return False
    return d.split(".")[-1] in wrappers or d == "jax.jit"


def _names_fed_to_wrappers(tree: ast.Module,
                           wrappers: tuple[str, ...]) -> set[str]:
    """Function names passed into jit wrappers, unwrapping nested
    transforms (``x64(jax.vmap(fifo1))`` feeds ``fifo1``)."""
    roots: set[str] = set()

    def harvest(arg: ast.AST) -> None:
        if isinstance(arg, ast.Name):
            roots.add(arg.id)
        elif isinstance(arg, ast.Call):
            for sub in arg.args:
                harvest(sub)

    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _wrapper_hit(node.func, wrappers):
            for arg in node.args:
                harvest(arg)
    return roots


def _jitted_defs(tree: ast.Module,
                 wrappers: tuple[str, ...]) -> Iterator[ast.FunctionDef]:
    roots = _names_fed_to_wrappers(tree, wrappers)
    for node in ast.walk(tree):
        if not isinstance(node, ast.FunctionDef):
            continue
        if node.name in roots or any(
                _wrapper_hit(d if not isinstance(d, ast.Call) else d.func,
                             wrappers)
                for d in node.decorator_list):
            yield node


def _static_test(node: ast.AST) -> bool:
    """True when the expression is resolvable at trace time."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Attribute):
        return node.attr in _STATIC_ATTRS
    if isinstance(node, ast.Subscript):
        return _static_test(node.value) and isinstance(
            node.slice, ast.Constant)
    if isinstance(node, ast.Call):
        d = dotted(node.func) or ""
        return d.split(".")[-1] in _STATIC_CALLS
    if isinstance(node, ast.BoolOp):
        return all(_static_test(v) for v in node.values)
    if isinstance(node, ast.UnaryOp):
        return _static_test(node.operand)
    if isinstance(node, ast.BinOp):
        return _static_test(node.left) and _static_test(node.right)
    if isinstance(node, ast.Compare):
        return _static_test(node.left) and all(
            _static_test(c) for c in node.comparators)
    if isinstance(node, (ast.Tuple, ast.List)):
        return all(_static_test(e) for e in node.elts)
    return False


def _check_kernel_body(sf: SourceFile,
                       fn: ast.FunctionDef) -> Iterator[Diagnostic]:
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            d = dotted(node.func) or ""
            parts = d.split(".")
            if parts[-1] in _HOST_SYNC_METHODS and len(parts) > 1:
                yield Diagnostic(
                    rule="SL302", file=sf.path, line=node.lineno,
                    message=(f".{parts[-1]}() inside jitted kernel "
                             f"{fn.name!r} forces a host sync"))
            elif parts[0] in ("np", "numpy") and len(parts) > 1:
                yield Diagnostic(
                    rule="SL302", file=sf.path, line=node.lineno,
                    message=(f"{d}() inside jitted kernel {fn.name!r}; "
                             f"numpy calls sync traced values to host"))
            elif d in _CASTS and node.args and not isinstance(
                    node.args[0], ast.Constant):
                yield Diagnostic(
                    rule="SL302", file=sf.path, line=node.lineno,
                    message=(f"{d}() on a traced value inside jitted "
                             f"kernel {fn.name!r} forces a host sync"))
        elif isinstance(node, ast.While):
            yield Diagnostic(
                rule="SL303", file=sf.path, line=node.lineno,
                message=(f"Python while-loop inside jitted kernel "
                         f"{fn.name!r}; use lax.while_loop"))
        elif isinstance(node, ast.If) and not _static_test(node.test):
            yield Diagnostic(
                rule="SL303", file=sf.path, line=node.lineno,
                message=(f"data-dependent Python branch inside jitted "
                         f"kernel {fn.name!r}; use jnp.where/lax.cond"))
        elif isinstance(node, ast.Assert) and not _static_test(node.test):
            yield Diagnostic(
                rule="SL303", file=sf.path, line=node.lineno,
                message=(f"assert on a traced value inside jitted "
                         f"kernel {fn.name!r}"))


@rule("SL302", "no host syncs inside jitted kernel seams")
def sl302(project: Project,
          scanned: list[SourceFile]) -> Iterable[Diagnostic]:
    yield from _kernel_findings(project, scanned, "SL302")


@rule("SL303", "no data-dependent Python control flow inside jitted "
               "kernel seams")
def sl303(project: Project,
          scanned: list[SourceFile]) -> Iterable[Diagnostic]:
    yield from _kernel_findings(project, scanned, "SL303")


def _kernel_findings(project: Project, scanned: list[SourceFile],
                     rule_id: str) -> Iterator[Diagnostic]:
    cfg = project.config
    sf = next((s for s in scanned if s.path == cfg.jax_engine), None) \
        or project.file(cfg.jax_engine)
    if sf is None:
        return
    seen: set[tuple[str, int, str]] = set()
    for fn in _jitted_defs(sf.tree, cfg.jit_wrappers):
        for diag in _check_kernel_body(sf, fn):
            # Nested jitted defs are walked by their enclosing def too;
            # report each site once, for the rule being evaluated.
            key = (diag.rule, diag.line, diag.message)
            if diag.rule == rule_id and key not in seen:
                seen.add(key)
                yield diag
