"""Layer 2: source-AST lint for repo-specific jit-discipline bug classes.

Pure path-based analysis — no imports of the linted code — so CI can run
it on stripped *copies* of kernel modules to prove the rules actually
guard the annotations (remove one ``# repro: host-boundary`` or one
``TRACE_COUNTS[...] += 1`` and the lint run must flip to failing).

Rules (each pins a bug class this repo has actually fixed):

``ast-host-sync-in-jit`` (error)
    A host materializer — ``float(x)``, ``x.item()``, ``np.asarray(x)``,
    ``np.array(x)``, ``jax.device_get(x)`` — lexically inside a
    jit-wrapped function.  Inside a traced body these either fail at
    trace time or, worse, silently bake a traced value into a constant;
    there is no legitimate use, so the annotation comment is only an
    escape hatch for exotic cases.

``ast-host-sync-unannotated`` (error)
    The same materializers in a *device-adjacent* function of a kernel
    module (a file carrying the ``# repro: kernel-module`` marker),
    without a ``# repro: host-boundary`` annotation on the call line or
    the line above.  Device-adjacent = the function's source mentions
    jax/jnp/lax, the lazy-grid internals (``_raw``, ``_LAZY_FIELDS``,
    ``_cell_scalar``), ``jax_env.x64``, or ``device_get`` — i.e. places
    where an innocuous-looking ``np.asarray`` can be an accidental
    device->host transfer of a whole sweep tensor.  Annotating makes the
    intentional boundary crossings (lazy-grid ``cell()`` gathers, winner
    payload marshaling) explicit and budgeted; everything else is a bug.

``ast-truthy-table`` (error)
    ``x or default`` / ``if x`` / ``not x`` / ``x if ... else`` tests on
    a value whose annotation or construction names a ``__len__``-bearing
    table type (ModelTable, TopologyTable, WorkloadTable, SuiteTable,
    the grid classes).  An *empty* table is falsy, so ``model or
    DEFAULT`` silently swaps in the default — the PR-4 ModelTable bug
    class.  Use ``is None``.

``ast-jit-no-counter`` (error)
    A function wrapped by ``jax.jit`` (decorator, ``functools.partial``
    decorator, or a ``jax.jit(fn)`` call naming a function defined in an
    enclosing scope) whose body never increments the registry trace
    counter (``TRACE_COUNTS[...] += 1`` or ``count_trace(...)``).
    Uncounted kernels are invisible to the one-compile-per-shape
    contract the benches assert; opt out explicitly with
    ``# repro: no-trace-count`` for wrappers that jit *caller-supplied*
    functions.
"""

from __future__ import annotations

import ast
import dataclasses
import os

from .findings import Finding, relpath

#: Marker opting a module into the kernel-module rule set (host-sync
#: annotation discipline).  A comment so stripped copies keep it.
KERNEL_MODULE_MARK = "# repro: kernel-module"
#: Annotation acknowledging an intentional device->host materialization.
HOST_BOUNDARY_MARK = "# repro: host-boundary"
#: Annotation opting a jit wrapper out of the trace-counter rule.
NO_COUNT_MARK = "# repro: no-trace-count"

#: Substrings that make a function "device-adjacent": its body plausibly
#: holds device arrays, so bare materializers need the annotation.
DEVICE_TOKENS = (
    "jnp.",
    "jax.",
    "lax.",
    "._raw(",
    "_LAZY_FIELDS",
    "_cell_scalar",
    "jax_env.x64",
    "device_get",
)

#: ``__len__``-bearing table/grid classes truthiness is banned on.
TABLE_TYPES = (
    "ModelTable",
    "TopologyTable",
    "WorkloadTable",
    "SuiteTable",
    "ExplorationGrid",
    "VariationGrid",
    "SuiteGrid",
    "SuiteVariationGrid",
)

_NUMPY_NAMES = ("np", "numpy", "jnp")


def _is_jit_expr(node: ast.AST) -> bool:
    """``jax.jit`` / ``jit`` / ``partial(jax.jit, ...)`` /
    ``functools.partial(jax.jit, ...)``."""
    if isinstance(node, ast.Attribute) and node.attr == "jit":
        return True
    if isinstance(node, ast.Name) and node.id == "jit":
        return True
    if isinstance(node, ast.Call):
        f = node.func
        is_partial = (
            isinstance(f, ast.Attribute) and f.attr == "partial"
        ) or (isinstance(f, ast.Name) and f.id == "partial")
        if is_partial and node.args:
            return _is_jit_expr(node.args[0])
    return False


def _materializer(call: ast.Call) -> "str | None":
    """The host-materializer kind of a call, or None."""
    f = call.func
    if isinstance(f, ast.Name) and f.id == "float" and call.args:
        return "float()"
    if isinstance(f, ast.Attribute):
        if f.attr == "item" and not call.args:
            return ".item()"
        if f.attr in ("asarray", "array"):
            base = f.value
            if isinstance(base, ast.Name) and base.id in ("np", "numpy"):
                return f"np.{f.attr}()"
            # `B.np.asarray` style module aliasing
            if (
                isinstance(base, ast.Attribute)
                and base.attr in ("np", "numpy")
            ):
                return f"np.{f.attr}()"
        if f.attr == "device_get":
            return "jax.device_get()"
    return None


@dataclasses.dataclass
class _Scope:
    """A lexical scope (module or function) and its immediate child
    function definitions, for resolving ``jax.jit(fn)`` by name."""

    node: ast.AST
    parent: "_Scope | None"
    defs: dict
    #: every child def, including same-named methods of sibling classes
    #: (``defs`` keeps first-wins name resolution; the walk must still
    #: visit ALL of them or later classes' methods escape the lint)
    all_defs: list

    def resolve(self, name: str) -> "ast.FunctionDef | None":
        s: "_Scope | None" = self
        while s is not None:
            if name in s.defs:
                return s.defs[name]
            s = s.parent
        return None


def _child_defs(node: ast.AST) -> "tuple[dict, list]":
    """Function defs belonging to ``node``'s scope — looking *through*
    class bodies and control-flow blocks (a method or a conditionally
    defined function is still this scope's child, not a separate one),
    but not into nested functions."""
    by_name = {}
    all_defs = []
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            by_name.setdefault(n.name, n)
            all_defs.append(n)
        elif not isinstance(n, ast.Lambda):
            stack.extend(ast.iter_child_nodes(n))
    all_defs.sort(key=lambda f: f.lineno)
    return by_name, all_defs


def _walk_scopes(node: ast.AST, parent: "_Scope | None" = None):
    by_name, all_defs = _child_defs(node)
    scope = _Scope(node=node, parent=parent, defs=by_name, all_defs=all_defs)
    yield scope
    for fn in scope.all_defs:
        yield from _walk_scopes(fn, scope)


def _scope_calls(scope: _Scope):
    """Call nodes belonging to ``scope`` itself (not nested functions)."""
    skip = set()
    for fn in scope.all_defs:
        for sub in ast.walk(fn):
            skip.add(id(sub))
    for sub in ast.walk(scope.node):
        if id(sub) in skip or sub is scope.node:
            continue
        yield sub


def _ann_names(annotation: "ast.AST | None") -> str:
    if annotation is None:
        return ""
    try:
        return ast.unparse(annotation)
    except Exception:  # pragma: no cover - unparse is total on ast nodes
        return ""


def _tableish_type(text: str) -> bool:
    """Whether an annotation names a table type *as the value's own
    type* — ``ModelTable``, ``Optional[ModelTable]``, ``ModelTable |
    None`` — and not merely as a generic parameter of a container
    (``Mapping[str, WorkloadTable]`` is a dict; its truthiness is
    fine)."""
    t = text.strip().strip("\"'").strip()
    if t.startswith("Optional[") and t.endswith("]"):
        t = t[len("Optional["):-1]
    parts = [p.strip().strip("\"'") for p in t.split("|")]
    parts = [p for p in parts if p and p != "None"]
    return len(parts) == 1 and parts[0] in TABLE_TYPES


class _FileLint:
    def __init__(self, path: str, source: str, root: "str | None"):
        self.path = relpath(path, root)
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self.is_kernel_module = KERNEL_MODULE_MARK in source
        self.findings: list[Finding] = []
        # ast.walk order is stable but not line-ordered; sort at the end.

    # -- comment-annotation helpers -------------------------------------

    def _line(self, n: int) -> str:
        return self.lines[n - 1] if 1 <= n <= len(self.lines) else ""

    def _annotated(self, lineno: int, mark: str) -> bool:
        return mark in self._line(lineno) or mark in self._line(lineno - 1)

    def _add(self, rule: str, node: ast.AST, message: str) -> None:
        line = getattr(node, "lineno", 0)
        self.findings.append(
            Finding(
                rule=rule,
                severity="error",
                path=self.path,
                line=line,
                message=message,
                context=self._line(line).strip(),
            )
        )

    # -- jit-wrapper discovery ------------------------------------------

    def _jit_wrapped(self) -> "dict[int, ast.FunctionDef]":
        """id(FunctionDef) -> node for every function this file jit-wraps:
        decorated defs, plus defs named as the first argument of a
        ``jax.jit(...)`` call in an enclosing scope."""
        wrapped: dict[int, ast.FunctionDef] = {}
        self._jit_sites: dict[int, int] = {}  # id(def) -> jit call line
        for scope in _walk_scopes(self.tree):
            for fn in scope.all_defs:
                for dec in fn.decorator_list:
                    if _is_jit_expr(dec):
                        wrapped[id(fn)] = fn
                        self._jit_sites[id(fn)] = dec.lineno
        for scope in _walk_scopes(self.tree):
            for sub in _scope_calls(scope):
                if not isinstance(sub, ast.Call):
                    continue
                if not _is_jit_expr(sub.func) or isinstance(
                    sub.func, ast.Call
                ):
                    # `partial(jax.jit, ...)` as a *call* is a decorator
                    # factory, handled above; here we want jax.jit(fn).
                    continue
                if sub.args and isinstance(sub.args[0], ast.Name):
                    target = scope.resolve(sub.args[0].id)
                    if target is not None:
                        wrapped[id(target)] = target
                        self._jit_sites.setdefault(id(target), sub.lineno)
        return wrapped

    # -- rules -----------------------------------------------------------

    def run(self) -> list[Finding]:
        wrapped = self._jit_wrapped()
        self._rule_jit_no_counter(wrapped)
        self._rule_host_sync(wrapped)
        self._rule_truthy_table()
        self.findings.sort(key=lambda f: (f.line, f.rule))
        return self.findings

    def _rule_jit_no_counter(self, wrapped) -> None:
        for fn in wrapped.values():
            has_counter = False
            for sub in ast.walk(fn):
                if (
                    isinstance(sub, ast.AugAssign)
                    and isinstance(sub.op, ast.Add)
                    and isinstance(sub.target, ast.Subscript)
                ):
                    base = sub.target.value
                    if (
                        isinstance(base, ast.Name)
                        and base.id == "TRACE_COUNTS"
                    ) or (
                        isinstance(base, ast.Attribute)
                        and base.attr == "TRACE_COUNTS"
                    ):
                        has_counter = True
                        break
                if isinstance(sub, ast.Call):
                    f = sub.func
                    if (
                        isinstance(f, ast.Name) and f.id == "count_trace"
                    ) or (
                        isinstance(f, ast.Attribute)
                        and f.attr == "count_trace"
                    ):
                        has_counter = True
                        break
            if has_counter:
                continue
            site = self._jit_sites.get(id(fn), fn.lineno)
            if self._annotated(fn.lineno, NO_COUNT_MARK) or self._annotated(
                site, NO_COUNT_MARK
            ):
                continue
            self._add(
                "ast-jit-no-counter",
                fn,
                f"jit-wrapped function {fn.name!r} never increments the "
                f"registry trace counter (TRACE_COUNTS[...] += 1 / "
                f"count_trace(...)); uncounted kernels escape the "
                f"one-compile-per-shape contract "
                f"(opt out with {NO_COUNT_MARK!r})",
            )

    def _device_adjacent(self, fn: ast.FunctionDef) -> bool:
        try:
            seg = ast.get_source_segment(self.source, fn) or ""
        except Exception:  # pragma: no cover
            seg = ""
        return any(tok in seg for tok in DEVICE_TOKENS)

    def _rule_host_sync(self, wrapped) -> None:
        # inside-jit: always an error, anywhere
        for fn in wrapped.values():
            for sub in ast.walk(fn):
                if not isinstance(sub, ast.Call):
                    continue
                kind = _materializer(sub)
                if kind is None:
                    continue
                if self._annotated(sub.lineno, HOST_BOUNDARY_MARK):
                    continue
                self._add(
                    "ast-host-sync-in-jit",
                    sub,
                    f"{kind} inside the jit-wrapped function "
                    f"{fn.name!r}: a host sync in a traced body either "
                    f"fails at trace time or bakes a traced value into "
                    f"a constant",
                )
        if not self.is_kernel_module:
            return
        wrapped_ids = set(wrapped)
        seen: set[int] = set()
        for scope in _walk_scopes(self.tree):
            fn = scope.node
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if id(fn) in wrapped_ids or not self._device_adjacent(fn):
                continue
            for sub in _scope_calls(scope):
                if not isinstance(sub, ast.Call) or id(sub) in seen:
                    continue
                kind = _materializer(sub)
                if kind is None:
                    continue
                seen.add(id(sub))
                # nested-in-jit calls already reported above
                if self._annotated(sub.lineno, HOST_BOUNDARY_MARK):
                    continue
                self._add(
                    "ast-host-sync-unannotated",
                    sub,
                    f"{kind} in device-adjacent function {fn.name!r} "
                    f"of a kernel module: if the operand is a device "
                    f"array this is a hidden device->host transfer — "
                    f"annotate the intentional boundary with "
                    f"{HOST_BOUNDARY_MARK!r} or keep the value on "
                    f"device",
                )

    def _rule_truthy_table(self) -> None:
        for scope in _walk_scopes(self.tree):
            tableish = self._tableish_names(scope)
            if not tableish:
                continue
            for sub in _scope_calls(scope):
                name = self._truthiness_target(sub)
                if name is not None and name in tableish:
                    self._add(
                        "ast-truthy-table",
                        sub,
                        f"truthiness test on {name!r}, a __len__-bearing "
                        f"table ({tableish[name]}): an empty table is "
                        f"falsy, so `or`-defaults/`if` silently replace "
                        f"it — use `is None`",
                    )

    def _tableish_names(self, scope: _Scope) -> dict[str, str]:
        """Names in ``scope`` whose annotation or construction names a
        table type."""
        node = scope.node
        out: dict[str, str] = {}
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            args = list(node.args.args) + list(node.args.kwonlyargs)
            if node.args.vararg:
                args.append(node.args.vararg)
            for a in args:
                ann = _ann_names(a.annotation)
                if _tableish_type(ann):
                    out[a.arg] = ann
        for sub in _scope_calls(scope):
            targets: list[ast.AST] = []
            value = None
            if isinstance(sub, ast.Assign):
                targets, value = sub.targets, sub.value
            elif isinstance(sub, ast.AnnAssign) and sub.target is not None:
                ann = _ann_names(sub.annotation)
                if _tableish_type(ann) and isinstance(
                    sub.target, ast.Name
                ):
                    out[sub.target.id] = ann
                targets, value = [sub.target], sub.value
            if value is None or not isinstance(value, ast.Call):
                continue
            ctor = value.func
            ctor_name = ""
            if isinstance(ctor, ast.Name):
                ctor_name = ctor.id
            elif isinstance(ctor, ast.Attribute):
                # ModelTable.from_models(...), TopologyTable.from_...
                base = ctor.value
                if isinstance(base, ast.Name):
                    ctor_name = base.id
            if ctor_name in TABLE_TYPES:
                for t in targets:
                    if isinstance(t, ast.Name):
                        out[t.id] = ctor_name
        return out

    @staticmethod
    def _truthiness_target(node: ast.AST) -> "str | None":
        """The bare name whose truthiness ``node`` tests, if any."""
        if isinstance(node, ast.BoolOp) and isinstance(node.op, ast.Or):
            first = node.values[0]
            if isinstance(first, ast.Name):
                return first.id
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.Not):
            if isinstance(node.operand, ast.Name):
                return node.operand.id
        if isinstance(node, (ast.If, ast.IfExp)):
            if isinstance(node.test, ast.Name):
                return node.test.id
        if isinstance(node, ast.While) and isinstance(node.test, ast.Name):
            return node.test.id
        return None


def lint_file(path: str, root: "str | None" = None) -> list[Finding]:
    with open(path) as f:
        source = f.read()
    try:
        return _FileLint(path, source, root).run()
    except SyntaxError as e:
        return [
            Finding(
                rule="ast-syntax-error",
                severity="error",
                path=relpath(path, root),
                line=e.lineno or 0,
                message=f"cannot parse: {e.msg}",
                context="",
            )
        ]


def lint_paths(
    paths: "list[str]", root: "str | None" = None
) -> list[Finding]:
    """Lint ``paths`` (files or directory trees of ``.py`` files)."""
    files: list[str] = []
    for p in paths:
        if os.path.isdir(p):
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = [
                    d for d in dirnames if d != "__pycache__"
                ]
                files.extend(
                    os.path.join(dirpath, f)
                    for f in sorted(filenames)
                    if f.endswith(".py")
                )
        else:
            files.append(p)
    out: list[Finding] = []
    for f in sorted(set(files)):
        out.extend(lint_file(f, root))
    return out
