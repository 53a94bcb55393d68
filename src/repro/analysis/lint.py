"""AST lint pass: repo-specific rules over ``src/repro``.

Each rule encodes a bug class a previous PR actually shipped and fixed —
the lint exists so the class cannot regress silently (docs/ANALYSIS.md has
the full catalog). Pure-AST, no jax import: this module must be runnable
in environments where compiling programs is off the table (CI's lint leg,
editors).

Suppressions are explicit and must carry a reason::

    x = jax.jit(fn)  # repro: allow-raw-jit — one-shot CLI compile

A suppression comment on the violation line, or on a contiguous comment
block immediately above it, silences the rule; a marker without a reason is
itself a violation (``bare-suppression``).
"""
from __future__ import annotations

import ast
import dataclasses
import os
import re


@dataclasses.dataclass(frozen=True)
class Rule:
    """One lint rule: what it flags and the shipped bug it guards against."""

    rule_id: str
    summary: str
    history: str


RULES = {r.rule_id: r for r in [
    Rule("raw-jit",
         "jax.jit called (or applied as a decorator) inside a function or "
         "method body instead of at module level",
         "PR 2: every Engine instance built its own jax.jit wrapper, so "
         "each instance recompiled the identical preprocess program; the "
         "fix moved dispatch to one module-level cache in "
         "engine/service.py (preprocess_jit/sample_jit/convert_jit)."),
    Rule("scatter-write",
         ".at[...].set/.add/... indexed write in a convert-spine module "
         "(Ordering/Reshaping/Reindexing/shard)",
         "PR 3: a .at[dest].set relocation in the sort spine lowered to "
         "HLO scatter, which serializes under GSPMD and has no Mosaic "
         "fast path; the fix routed every relocation through the gather "
         "router (set_partition.gather_sources_from_counts)."),
    Rule("traced-if",
         "Python if/while branching on a jnp/lax expression",
         "Python control flow on a traced value either raises "
         "TracerBoolConversionError under jit or silently constant-folds "
         "at trace time — the epilogue dispatch in pipeline.convert must "
         "stay host-side (pointer_reindex_strategy on static metadata)."),
    Rule("host-numpy-in-jit",
         "host numpy call inside a jax.jit-decorated function body",
         "np.* executes at trace time on host values: it constant-folds "
         "per compilation, silently pinning what should be traced inputs "
         "(dtype/iinfo-style metadata lookups are allowed)."),
    Rule("mutable-default",
         "mutable literal ([]/{}/set) as a parameter default",
         "One list shared across every call — in serve/'s threaded "
         "request path that is cross-request state leakage (the serve "
         "engine keeps per-request state in Request/Slot objects "
         "instead)."),
    Rule("bare-suppression",
         "a '# repro: allow-<rule>' marker with no reason text",
         "Suppressions document why the rule does not apply at that site; "
         "a bare marker is indistinguishable from silencing noise."),
]}

# Modules where the relocation spine lives: an .at[...] indexed write here
# is (absent a reasoned suppression) the PR-3 scatter regression class.
SPINE_MODULES = (
    "core/ordering.py", "core/set_partition.py", "core/set_count.py",
    "core/reshaping.py", "core/reindexing.py", "core/pipeline.py",
    "engine/shard.py",
)

# numpy attributes that are metadata, not host compute
_NP_META = {
    "int8", "int16", "int32", "int64", "uint8", "uint16", "uint32",
    "uint64", "float16", "float32", "float64", "bool_", "dtype",
    "iinfo", "finfo", "ndarray", "generic",
}

_AT_WRITE_METHODS = {"set", "add", "subtract", "multiply", "divide",
                     "max", "min", "power"}

_ALLOW_RE = re.compile(r"#\s*repro:\s*allow-([\w-]+)[ \t]*[—:–-]?[ \t]*(.*)")


@dataclasses.dataclass(frozen=True)
class LintViolation:
    path: str  # repo-relative
    line: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


def _suppressions(src: str) -> dict[int, tuple[str, bool]]:
    """line number → (rule id, has_reason) for every allow marker."""
    out: dict[int, tuple[str, bool]] = {}
    for i, line in enumerate(src.splitlines(), start=1):
        m = _ALLOW_RE.search(line)
        if m:
            out[i] = (m.group(1), len(m.group(2).strip()) >= 3)
    return out


class _Aliases:
    """Import-derived name resolution for jax / jax.numpy / numpy."""

    def __init__(self) -> None:
        self.jax: set[str] = set()        # names bound to the jax module
        self.jit: set[str] = set()        # names bound to jax.jit itself
        self.np: set[str] = set()         # names bound to HOST numpy
        self.traced: set[str] = set()     # jax.numpy / jax.lax modules

    def collect(self, tree: ast.AST) -> None:
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    name = a.asname or a.name.split(".")[0]
                    if a.name == "jax":
                        self.jax.add(name)
                    elif a.name == "numpy":
                        self.np.add(name)
                    elif a.name in ("jax.numpy", "jax.lax"):
                        self.traced.add(a.asname or a.name)
            elif isinstance(node, ast.ImportFrom):
                if node.module == "jax":
                    for a in node.names:
                        if a.name == "jit":
                            self.jit.add(a.asname or "jit")
                        elif a.name in ("numpy", "lax"):
                            self.traced.add(a.asname or a.name)
                elif node.module == "numpy":
                    pass  # from numpy import X — host compute, but rare
                          # enough that attribute resolution isn't worth it

    def is_jit(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.jit
        return (isinstance(node, ast.Attribute) and node.attr == "jit"
                and isinstance(node.value, ast.Name)
                and node.value.id in self.jax)

    def is_traced_module(self, node: ast.AST) -> bool:
        """node is a reference to jax.numpy / jax.lax (or an alias)."""
        if isinstance(node, ast.Name):
            return node.id in self.traced
        return (isinstance(node, ast.Attribute)
                and node.attr in ("numpy", "lax")
                and isinstance(node.value, ast.Name)
                and node.value.id in self.jax)


def _is_at_write(node: ast.Call) -> bool:
    """x.at[...].set(...) / .add(...) / ... — the indexed-write pattern."""
    f = node.func
    return (isinstance(f, ast.Attribute) and f.attr in _AT_WRITE_METHODS
            and isinstance(f.value, ast.Subscript)
            and isinstance(f.value.value, ast.Attribute)
            and f.value.value.attr == "at")


def _has_jit_decorator(node: ast.FunctionDef | ast.AsyncFunctionDef,
                       aliases: _Aliases) -> bool:
    return any(aliases.is_jit(n) for dec in node.decorator_list
               for n in ast.walk(dec))


def _traced_call_in(expr: ast.AST, aliases: _Aliases) -> ast.Call | None:
    """First call to a jnp/lax function anywhere inside ``expr``."""
    for n in ast.walk(expr):
        if (isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
                and aliases.is_traced_module(n.func.value)):
            return n
    return None


_FUNC_NODES = (ast.FunctionDef, ast.AsyncFunctionDef)


def lint_source(src: str, rel_path: str) -> list[LintViolation]:
    """Lint one file's source. ``rel_path`` is src/repro-relative (used for
    spine-module scoping and reported verbatim)."""
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [LintViolation(rel_path, e.lineno or 0, "parse-error",
                              f"file does not parse: {e.msg}")]
    aliases = _Aliases()
    aliases.collect(tree)
    in_spine = rel_path.replace(os.sep, "/") in SPINE_MODULES
    raw: list[LintViolation] = []

    def flag(node: ast.AST, rule: str, message: str) -> None:
        raw.append(LintViolation(rel_path, getattr(node, "lineno", 0),
                                 rule, message))

    def visit(node: ast.AST, func_depth: int, jitted: bool) -> None:
        if isinstance(node, _FUNC_NODES):
            if func_depth > 0:
                for dec in node.decorator_list:
                    for n in ast.walk(dec):
                        if aliases.is_jit(n):
                            flag(dec, "raw-jit",
                                 f"@jax.jit on nested function "
                                 f"'{node.name}' builds a fresh compile "
                                 f"cache per enclosing call")
                            break
            defaults = list(node.args.defaults) + [
                d for d in node.args.kw_defaults if d is not None]
            for d in defaults:
                if isinstance(d, (ast.List, ast.Dict, ast.Set,
                                  ast.ListComp, ast.DictComp, ast.SetComp)):
                    flag(d, "mutable-default",
                         f"mutable default in '{node.name}' is shared "
                         f"across every call")
            inner_jitted = jitted or _has_jit_decorator(node, aliases)
            for child in ast.iter_child_nodes(node):
                visit(child, func_depth + 1, inner_jitted)
            return

        if isinstance(node, ast.Call):
            if func_depth > 0 and aliases.is_jit(node.func):
                flag(node, "raw-jit",
                     "jax.jit called inside a function body — dispatch "
                     "through the module-level cache (engine/service.py) "
                     "or hoist to module scope")
            if in_spine and _is_at_write(node):
                flag(node, "scatter-write",
                     f".at[...].{node.func.attr} in a convert-spine "
                     f"module lowers to HLO scatter — use the gather "
                     f"router")
            if (jitted and isinstance(node.func, ast.Attribute)
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in aliases.np
                    and node.func.attr not in _NP_META):
                flag(node, "host-numpy-in-jit",
                     f"np.{node.func.attr} inside a jitted body runs at "
                     f"trace time and constant-folds per compilation")

        if isinstance(node, (ast.If, ast.While)):
            hit = _traced_call_in(node.test, aliases)
            if hit is not None:
                flag(node, "traced-if",
                     "Python control flow on a jnp/lax expression — "
                     "under jit this raises or constant-folds; use "
                     "lax.cond/jnp.where or branch on static metadata")

        for child in ast.iter_child_nodes(node):
            visit(child, func_depth, jitted)

    visit(tree, 0, False)

    # apply suppressions: marker on the violation line, or in the
    # contiguous comment block immediately above it
    marks = _suppressions(src)
    lines = src.splitlines()

    def suppressed(v: LintViolation) -> bool:
        # a matching marker suppresses even without a reason — the
        # bare-suppression violation below replaces the original finding
        # rather than doubling it
        ln = v.line
        while ln >= 1:
            if ln in marks and marks[ln][0] == v.rule:
                return True
            if ln == v.line:  # same-line marker checked; now walk the
                ln -= 1       # comment block above
                continue
            if ln <= len(lines) and lines[ln - 1].lstrip().startswith("#"):
                ln -= 1
                continue
            return False
        return False

    out = [v for v in raw if not suppressed(v)]
    for ln, (rule, has_reason) in sorted(marks.items()):
        if not has_reason:
            out.append(LintViolation(
                rel_path, ln, "bare-suppression",
                f"allow-{rule} marker has no reason"))
        elif rule not in RULES and rule != "parse-error":
            out.append(LintViolation(
                rel_path, ln, "bare-suppression",
                f"allow-{rule} names no known rule "
                f"({', '.join(sorted(RULES))})"))
    return sorted(out, key=lambda v: (v.line, v.rule))


def lint_file(path: str, root: str) -> list[LintViolation]:
    with open(path) as f:
        src = f.read()
    return lint_source(src, os.path.relpath(path, root))


def lint_tree(root: str | None = None) -> list[LintViolation]:
    """Lint every .py file under ``root`` (default: the src/repro tree this
    module ships in). Violations are repo-tree-relative and sorted."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out: list[LintViolation] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                out.extend(lint_file(os.path.join(dirpath, fn), root))
    return sorted(out, key=lambda v: (v.path, v.line, v.rule))
