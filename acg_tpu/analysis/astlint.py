"""Repo-specific AST linter: the hard-won lowering rules as checks.

Each rule encodes a hazard this repo has already paid for once:

- **E1 gather** (hot modules ``ops/``, ``solvers/``, ``parallel/``):
  ellipsis subscripts — ``x[..., a:b]`` slicing (the PR 2 regression:
  on traced operands the ellipsis form can lower to ``stablehlo.gather``
  instead of a slice; use ``lax.slice_in_dim``) and ellipsis advanced
  indexing ``x[..., idx]`` (a real gather — deliberate only at the
  declared operator-tier sites).  Static literal indices, ``[..., None]``
  broadcasts, ``.at[...]`` updates and NumPy-call bases are exempt.
- **E2 axis-name**: ``psum``/``ppermute``/``all_gather``/… without an
  explicit axis — a collective that silently binds whatever axis is in
  scope is a wrong-mesh bug waiting for the first nested shard_map.
- **E3 traced-branch** (hot modules): Python ``if`` on, or
  ``float()``/``int()``/``bool()`` of, a loop-carry parameter inside a
  ``body``/``cond`` while-loop function — a host round-trip (or
  ConcretizationTypeError) inside the hot loop.
- **E4 debug-callback**: ``jax.debug`` use outside the throttled
  monitor path (``acg_tpu/obs/monitor.py``) — an unthrottled callback
  is a per-iteration host transfer, exactly what contract rule C6
  fails compiled programs for.
- **E5 kernel-name**: a ``pallas_call`` without ``name=`` — an unnamed
  kernel is traced under a name that moves with every compile, so no
  profile reduction can find it again (acg_tpu/obs/trace.py names
  kernels ``acg_<kernel>``).

Deliberate exceptions carry a ``# acg: allow-<rule>`` pragma on the
offending line (or the line above).  ``scripts/lint_source.py`` runs
the linter over ``acg_tpu/`` and exits nonzero on any unsuppressed
finding.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re

RULES = {
    "gather": "E1: ellipsis subscript lowers to gather on traced "
              "operands; use lax.slice_in_dim (or pragma a deliberate "
              "operator-tier gather)",
    "axis-name": "E2: collective without an explicit axis name",
    "traced-branch": "E3: Python branch/cast on a traced loop-carry "
                     "value inside a while-loop body",
    "debug-callback": "E4: jax.debug outside the throttled monitor path",
    "kernel-name": "E5: pallas_call without a stable name= (acg_<kernel>)",
}

# rule E1/E3 apply to the hot subpackages only (host-side preprocessing
# is free to slice NumPy arrays however it likes)
_HOT_PARTS = ("ops", "solvers", "parallel")

# E2's vocabulary: the mesh collectives the solvers issue
_COLLECTIVES = {"psum", "ppermute", "all_gather", "pmean", "pmax",
                "pmin", "psum_scatter", "all_to_all"}

# E3's scope: the lax.while_loop body/cond naming convention of
# acg_tpu/solvers/loops.py
_LOOP_FN_NAMES = {"body", "cond", "_body", "_cond", "body_fn", "cond_fn"}

_PRAGMA_RE = re.compile(r"#\s*acg:\s*allow-([\w-]+)")


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str
    line: int
    rule: str
    msg: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.msg}"


def _pragmas(src: str) -> dict:
    """line number -> set of allowed rule slugs (a pragma suppresses its
    own line and the line below, so it can sit above a long expression)."""
    out: dict = {}
    for i, line in enumerate(src.splitlines(), start=1):
        for rule in _PRAGMA_RE.findall(line):
            out.setdefault(i, set()).add(rule)
    return out


def _dotted(node) -> str:
    """Best-effort dotted name of an expression ("jax.lax.psum"), empty
    when it is not a plain attribute chain."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_static_literal(node) -> bool:
    """Indices that lower to static slices: literals, negated literals,
    None, and arithmetic over them + bare short names (loop counters of
    unrolled Python loops — static at trace time by convention)."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.UnaryOp):
        return _is_static_literal(node.operand)
    if isinstance(node, ast.BinOp):
        return (_is_static_literal(node.left)
                and _is_static_literal(node.right))
    if isinstance(node, ast.Name):
        return len(node.id) <= 1
    return False


def _is_numpy_call(node) -> bool:
    return (isinstance(node, ast.Call)
            and _dotted(node.func).split(".")[0] in ("np", "numpy"))


class _Visitor(ast.NodeVisitor):
    def __init__(self, path: str, hot: bool, monitor_module: bool):
        self.path = path
        self.hot = hot
        self.monitor_module = monitor_module
        self.findings: list[Finding] = []
        self._fn_stack: list[ast.FunctionDef] = []

    def _emit(self, node, rule: str, msg: str) -> None:
        self.findings.append(Finding(self.path, node.lineno, rule, msg))

    # -- E1 -----------------------------------------------------------------

    def visit_Subscript(self, node: ast.Subscript) -> None:
        if self.hot and isinstance(node.ctx, ast.Load):
            self._check_ellipsis_subscript(node)
        self.generic_visit(node)

    def _check_ellipsis_subscript(self, node: ast.Subscript) -> None:
        sl = node.slice
        if not (isinstance(sl, ast.Tuple)
                and any(isinstance(e, ast.Constant) and e.value is Ellipsis
                        for e in sl.elts)):
            return
        # .at[...] updates are the scatter idiom, not this rule; NumPy
        # call bases are host arrays
        if (isinstance(node.value, ast.Attribute)
                and node.value.attr == "at") or _is_numpy_call(node.value):
            return
        for e in sl.elts:
            if isinstance(e, ast.Constant):     # Ellipsis, None, ints
                continue
            if isinstance(e, ast.Slice):
                if e.lower is None and e.upper is None and e.step is None:
                    continue
                self._emit(node, "gather",
                           "ellipsis slice x[..., a:b] — lowers via "
                           "gather on traced operands; use "
                           "lax.slice_in_dim")
                return
            if _is_static_literal(e):
                continue
            self._emit(node, "gather",
                       "ellipsis advanced index x[..., idx] lowers to a "
                       "gather; confine gathers to declared operator-"
                       "tier sites")
            return

    # -- E2 -----------------------------------------------------------------

    def visit_Call(self, node: ast.Call) -> None:
        name = _dotted(node.func)
        leaf = name.split(".")[-1]
        if leaf in _COLLECTIVES and (name.startswith("jax.lax.")
                                     or name.startswith("lax.")
                                     or name == leaf):
            explicit = (len(node.args) >= 2
                        or any(kw.arg in ("axis_name", "axis")
                               for kw in node.keywords))
            if not explicit:
                self._emit(node, "axis-name",
                           f"{leaf}() without an explicit axis name")
        if leaf == "pallas_call" \
                and not any(kw.arg == "name" for kw in node.keywords):
            self._emit(node, "kernel-name",
                       "pallas_call() without name=: give the kernel a "
                       "stable acg_<kernel> name")
        if self._in_loop_fn() and leaf in ("float", "int", "bool") \
                and name == leaf and node.args \
                and self._touches_params(node.args[0]):
            self._emit(node, "traced-branch",
                       f"{leaf}() on a loop-carry value inside a "
                       "while-loop body forces a host transfer")
        self.generic_visit(node)

    # -- E3 -----------------------------------------------------------------

    def _in_loop_fn(self):
        return (self.hot and self._fn_stack
                and self._fn_stack[-1].name in _LOOP_FN_NAMES)

    def _touches_params(self, expr) -> bool:
        fn = self._fn_stack[-1]
        params = {a.arg for a in fn.args.args + fn.args.kwonlyargs
                  + fn.args.posonlyargs}
        return any(isinstance(n, ast.Name) and n.id in params
                   for n in ast.walk(expr))

    def visit_If(self, node: ast.If) -> None:
        if self._in_loop_fn() and self._touches_params(node.test):
            self._emit(node, "traced-branch",
                       "Python `if` on a loop-carry value inside a "
                       "while-loop body; use lax.cond/jnp.where")
        self.generic_visit(node)

    # -- E4 -----------------------------------------------------------------

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if not self.monitor_module and _dotted(node) == "jax.debug":
            self._emit(node, "debug-callback",
                       "jax.debug outside acg_tpu/obs/monitor.py — "
                       "host callbacks belong behind the throttled "
                       "monitor tier")
        self.generic_visit(node)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._fn_stack.append(node)
        self.generic_visit(node)
        self._fn_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef


def _rel_parts(path: str) -> tuple:
    rel = path.replace(os.sep, "/")
    if "acg_tpu/" in rel:
        rel = rel.split("acg_tpu/", 1)[1]
    return tuple(rel.split("/"))


def lint_source(src: str, path: str = "<string>") -> list[Finding]:
    """Lint one module's source; returns the unsuppressed findings."""
    parts = _rel_parts(path)
    hot = bool(parts) and parts[0] in _HOT_PARTS
    monitor = parts[-2:] == ("obs", "monitor.py")
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [Finding(path, e.lineno or 0, "syntax", str(e))]
    v = _Visitor(path, hot=hot, monitor_module=monitor)
    v.visit(tree)
    allowed = _pragmas(src)
    out = []
    for f in v.findings:
        if f.rule in allowed.get(f.line, ()) \
                or f.rule in allowed.get(f.line - 1, ()):
            continue
        out.append(f)
    return out


def lint_file(path: str) -> list[Finding]:
    with open(path) as fh:
        return lint_source(fh.read(), path)


def lint_tree(root: str) -> list[Finding]:
    """Lint every ``.py`` under ``root`` (sorted, so findings are
    stable); skips ``__pycache__``."""
    findings: list[Finding] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                findings.extend(lint_file(os.path.join(dirpath, fn)))
    return findings
