"""Layer-2 source lint: AST rules enforcing the repo's jit idioms.

These are the conventions PR reviews kept re-litigating, promoted to
machine checks (ids in `report.RULES`):

AST001  `np.<fn>(...)` inside a *traced function* of a jit-reachable
        module.  numpy silently concretizes tracers (or runs per-call on
        the host).  "Traced" is the repo's signature convention: any
        function with a `jax.Array`-annotated parameter.  Host-side table
        builders (annotated `np.ndarray`/config-only params), module-level
        operator tables, and `@property` config math are exempt — those
        run at trace/config time by design.
AST002  Python `random` in a jit-reachable module: untraced RNG breaks
        the bit-replayable checkpoint contract.
AST003  subscripting a module-level numpy array constant directly in
        arithmetic (`_RK_A[stage] * du`).  The element is a numpy f64
        scalar — it re-promotes a bf16/f32 carry; the convention is
        `float(_RK_A[stage])` (a weak Python float cannot promote).
AST004  `jnp.float64` literal anywhere.
AST005  a kernel-module function signature defaulting `interpret` to a
        concrete bool — kernels must default `interpret=None` so
        `policy.resolve_interpret` keeps backend selection centralized.
AST006  `envs.make("<name>")` with a literal name missing from the
        registry (examples/benchmarks rot when scenarios are renamed).
AST007  a `# repro-lint: disable=...` comment without a ` -- reason`.

Suppression: append `# repro-lint: disable=AST001 -- <reason>` to the
offending line.  Multiple ids comma-separate; the reason is mandatory.
"""
from __future__ import annotations

import ast
import os
import re
from typing import Iterable

from .report import Finding, Report

# jit-reachable module set for AST001/AST002/AST003: everything that can
# end up inside a traced program.  Paths relative to the repo root.
HOT_PREFIXES = (
    "src/repro/envs/",
    "src/repro/cfd/",
    "src/repro/kernels/",
    "src/repro/fleet/",
    "src/repro/optim/",
    "src/repro/core/",
    "src/repro/serve/",
)
# host-side orchestration inside those packages (never traced)
HOT_EXCLUDES = (
    "src/repro/core/runner.py",      # checkpoint/metrics host loop
    "src/repro/core/elastic.py",     # host-side pool management
    "src/repro/fleet/pipeline.py",   # host loop around the jitted programs
    "src/repro/fleet/scheduler.py",  # schedule built once on the host
    "src/repro/kernels/policy.py",   # env-var policy, host only
    "src/repro/serve/batcher.py",    # host-side request queues / padding
    "src/repro/serve/loader.py",     # checkpoint restore on the host
)

_SUPPRESS_RE = re.compile(
    r"#\s*repro-lint:\s*disable=([A-Z0-9,\s]+?)(?:\s*--\s*(.*\S))?\s*$")


def _suppressions(src: str) -> tuple[dict[int, tuple[set, str]], list]:
    """line -> (rule ids, reason); plus AST007 findings for missing reasons."""
    out: dict[int, tuple[set, str]] = {}
    bad: list[tuple[int, str]] = []
    for i, line in enumerate(src.splitlines(), start=1):
        m = _SUPPRESS_RE.search(line)
        if not m:
            continue
        rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
        reason = (m.group(2) or "").strip()
        if not reason:
            bad.append((i, ", ".join(sorted(rules))))
        out[i] = (rules, reason)
    return out, bad


def _numpy_aliases(tree: ast.Module) -> set[str]:
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "numpy":
                    names.add(a.asname or "numpy")
    return names


def _module_np_arrays(tree: ast.Module, np_names: set[str]) -> set[str]:
    """Module-level `NAME = np.array(...)`-style constant tables."""
    out = set()
    for node in tree.body:
        if not isinstance(node, ast.Assign):
            continue
        val = node.value
        if (isinstance(val, ast.Call)
                and isinstance(val.func, ast.Attribute)
                and isinstance(val.func.value, ast.Name)
                and val.func.value.id in np_names):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    out.add(tgt.id)
    return out


def _takes_tracer(node) -> bool:
    """The repo's traced-function convention: >= 1 param annotated with
    jax.Array (jnp aliases included).  Lambdas and un-annotated helpers
    count as traced when nested inside a traced function (see caller)."""
    args = node.args
    all_args = args.posonlyargs + args.args + args.kwonlyargs
    for a in all_args:
        if a.annotation is None:
            continue
        try:
            txt = ast.unparse(a.annotation)
        except Exception:
            continue
        if "jax.Array" in txt or "jnp.ndarray" in txt:
            return True
    return False


class _FileLint(ast.NodeVisitor):
    def __init__(self, path: str, tree: ast.Module, *, hot: bool,
                 kernel_module: bool, registry_names: frozenset[str]):
        self.path = path
        self.hot = hot
        self.kernel_module = kernel_module
        self.registry = registry_names
        self.np_names = _numpy_aliases(tree)
        self.np_arrays = _module_np_arrays(tree, self.np_names)
        self.findings: list[Finding] = []
        self._fn_depth = 0
        self._prop_depth = 0
        self._traced_stack: list[bool] = []

    def add(self, rule: str, node: ast.AST, message: str) -> None:
        self.findings.append(Finding(
            rule=rule, message=message, file=self.path,
            line=getattr(node, "lineno", 0)))

    # --- function context ----------------------------------------------------
    def _visit_fn(self, node) -> None:
        is_prop = any(
            (isinstance(d, ast.Name) and d.id in ("property",
                                                  "cached_property"))
            or (isinstance(d, ast.Attribute) and d.attr == "cached_property")
            for d in node.decorator_list)
        if self.kernel_module:
            for arg, default in zip(
                    reversed(node.args.args + node.args.kwonlyargs),
                    reversed(node.args.defaults + node.args.kw_defaults)):
                if (arg.arg == "interpret" and default is not None
                        and isinstance(default, ast.Constant)
                        and default.value is not None):
                    self.add("AST005", node,
                             f"`{node.name}` defaults interpret="
                             f"{default.value!r}; kernels must default "
                             "interpret=None (policy.resolve_interpret)")
        self._fn_depth += 1
        self._prop_depth += is_prop
        self._traced_stack.append(_takes_tracer(node))
        self.generic_visit(node)
        self._traced_stack.pop()
        self._prop_depth -= is_prop
        self._fn_depth -= 1

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    # --- calls ---------------------------------------------------------------
    @property
    def _in_traced_body(self) -> bool:
        """Inside a function that takes a jax.Array (or a closure nested in
        one) and is not config-time `@property` math."""
        return (self.hot and any(self._traced_stack)
                and self._prop_depth == 0)

    def visit_Call(self, node: ast.Call) -> None:
        f = node.func
        in_traced_body = self._in_traced_body
        if (in_traced_body and isinstance(f, ast.Attribute)
                and isinstance(f.value, ast.Name)
                and f.value.id in self.np_names):
            self.add("AST001", node,
                     f"`{f.value.id}.{f.attr}(...)` in a jit-reachable "
                     "function body — use jnp, or hoist to module level")
        if (in_traced_body and isinstance(f, ast.Attribute)
                and isinstance(f.value, ast.Name)
                and f.value.id == "random"):
            self.add("AST002", node,
                     f"`random.{f.attr}(...)` in a jit-reachable module — "
                     "use jax.random with a threaded key")
        if (isinstance(f, ast.Attribute) and f.attr == "make"
                and isinstance(f.value, ast.Name)
                and f.value.id in ("envs", "registry")
                and node.args and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
                and self.registry
                and node.args[0].value not in self.registry):
            self.add("AST006", node,
                     f"envs.make({node.args[0].value!r}): not a registered "
                     "scenario name")
        self.generic_visit(node)

    def visit_Import(self, node: ast.Import) -> None:
        if self.hot and any(a.name == "random" for a in node.names):
            self.add("AST002", node, "`import random` in a jit-reachable "
                                     "module — use jax.random")
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if self.hot and node.module == "random":
            self.add("AST002", node, "`from random import ...` in a "
                                     "jit-reachable module — use jax.random")
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if (node.attr == "float64" and isinstance(node.value, ast.Name)
                and node.value.id in ("jnp", "jax")):
            self.add("AST004", node, "jnp.float64 — x64 is never enabled "
                                     "in production")
        self.generic_visit(node)

    def visit_BinOp(self, node: ast.BinOp) -> None:
        # a float()-wrapped subscript never appears here: the wrap makes
        # the operand a Call node, so a bare Subscript operand is exactly
        # the un-wrapped pattern
        if self._in_traced_body:
            for side in (node.left, node.right):
                if (isinstance(side, ast.Subscript)
                        and isinstance(side.value, ast.Name)
                        and side.value.id in self.np_arrays):
                    self.add("AST003", node,
                             f"`{side.value.id}[...]` used in arithmetic "
                             "without float() wrap — the numpy f64 scalar "
                             "re-promotes the carry dtype")
        self.generic_visit(node)


def _registry_names() -> frozenset[str]:
    try:
        from .. import envs
        return frozenset(envs.registered())
    except Exception:
        return frozenset()


def lint_source(path: str, src: str, *, hot: bool | None = None,
                kernel_module: bool | None = None,
                registry_names: frozenset[str] | None = None
                ) -> list[Finding]:
    """All AST findings for one file (suppressions applied)."""
    rel = path.replace(os.sep, "/")
    if hot is None:
        hot = (any(p in rel for p in HOT_PREFIXES)
               and not any(rel.endswith(e.split("/")[-1]) and e in rel
                           for e in HOT_EXCLUDES))
    if kernel_module is None:
        kernel_module = ("src/repro/kernels/" in rel
                         and not rel.endswith("policy.py"))
    tree = ast.parse(src, filename=path)
    lint = _FileLint(path, tree, hot=hot, kernel_module=kernel_module,
                     registry_names=(_registry_names()
                                     if registry_names is None
                                     else registry_names))
    lint.visit(tree)

    supp, missing_reason = _suppressions(src)
    for line, rules in missing_reason:
        lint.findings.append(Finding(
            rule="AST007", file=path, line=line,
            message=f"suppression of {rules} has no ` -- reason`"))
    for f in lint.findings:
        rules, reason = supp.get(f.line, (set(), ""))
        if f.rule in rules and reason:
            f.suppressed, f.suppress_reason = True, reason
    return lint.findings


def iter_python_files(root: str) -> Iterable[str]:
    for base in ("src", "examples", "benchmarks", "tests"):
        top = os.path.join(root, base)
        for dirpath, dirnames, filenames in os.walk(top):
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__", "fixtures")]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def run(report: Report | None = None, root: str = ".") -> Report:
    report = report or Report()
    names = _registry_names()
    n_files = 0
    for path in iter_python_files(root):
        with open(path) as fh:
            src = fh.read()
        rel = os.path.relpath(path, root)
        report.extend(lint_source(rel, src, registry_names=names))
        n_files += 1
    report.meta.setdefault("ast_rules", {})["files_scanned"] = n_files
    return report
