"""Shared AST machinery for graftlint rules.

Three project-specific analyses several rules need:

- **dotted names** — resolve `jax.lax.psum` / `watchdog.deadline` style
  call targets to a dotted string, honoring per-module import aliases
  (``import jax.random as jr`` / ``from jax import random``).
- **traced contexts** — which functions' bodies execute under a jax
  trace. Seeds: functions decorated with ``jax.jit`` (directly or via
  ``functools.partial``) or ``shard_map``; functions passed by name to
  ``jax.jit(...)`` / ``shard_map(...)``;
  plus rule-configured known-traced name patterns (for getattr-style
  wrapping the AST cannot see, e.g. ops/predict.py's forest kernels
  jitted through ``gbdt._forest_jit``). Tracedness propagates through
  the module-local call graph and lexical nesting: a helper called from
  a traced function runs at trace time and receives tracers.
- **guard coverage** — which statements run under a given ``with``
  guard (``watchdog.deadline(...)`` for collectives, ``self._lock`` for
  serving counters), including one-hop interprocedural coverage: a
  function counts as covered when it has in-module call sites and EVERY
  one of them is inside the guard (fixed point), which is exactly the
  ``__call__``-arms-the-deadline-then-calls-``_dispatch`` idiom in
  parallel/learners.py.

All of it is per-module and syntactic: this is a lint, not a verifier —
the rules document their scope and the fixture corpus pins it.
"""
from __future__ import annotations

import ast
import re
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Set, Tuple

FuncNode = (ast.FunctionDef, ast.AsyncFunctionDef)


def walk_shallow(fn: ast.AST) -> Iterable[ast.AST]:
    """Nodes lexically belonging to `fn`'s own body: descends through
    everything EXCEPT nested function defs (their bodies run in their
    own scope and are visited as their own functions). Lambda bodies
    stay included — they are not tracked as separate functions."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, FuncNode):
            # still yield the nested def's decorators/defaults (they
            # evaluate in the enclosing scope), but not its body
            stack.extend(node.decorator_list)
            stack.extend(d for d in node.args.defaults if d is not None)
            stack.extend(d for d in (node.args.kw_defaults or [])
                         if d is not None)
            continue
        stack.extend(ast.iter_child_nodes(node))


def parent_map(tree: ast.AST) -> Dict[ast.AST, ast.AST]:
    out: Dict[ast.AST, ast.AST] = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            out[child] = node
    return out


def enclosing_functions(node: ast.AST,
                        parents: Dict[ast.AST, ast.AST]) -> List[ast.AST]:
    """Innermost-first chain of enclosing function defs."""
    out: List[ast.AST] = []
    cur = parents.get(node)
    while cur is not None:
        if isinstance(cur, FuncNode):
            out.append(cur)
        cur = parents.get(cur)
    return out


# ---------------------------------------------------------------------------
# imports and dotted names
# ---------------------------------------------------------------------------
class ImportTable:
    """local name -> dotted module/object path, from this module's
    imports. `import jax.random as jr` maps jr -> jax.random;
    `from jax import random` maps random -> jax.random;
    `from jax.random import uniform` maps uniform -> jax.random.uniform."""

    def __init__(self, tree: ast.AST):
        self.names: Dict[str, str] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    local = alias.asname or alias.name.split(".")[0]
                    target = alias.name if alias.asname else \
                        alias.name.split(".")[0]
                    self.names[local] = target
            elif isinstance(node, ast.ImportFrom) and node.module \
                    and node.level == 0:
                for alias in node.names:
                    local = alias.asname or alias.name
                    self.names[local] = f"{node.module}.{alias.name}"

    def resolve(self, dotted: str) -> str:
        """Rewrite the first component through the import table:
        jr.uniform -> jax.random.uniform."""
        head, _, rest = dotted.partition(".")
        base = self.names.get(head)
        if base is None:
            return dotted
        return f"{base}.{rest}" if rest else base


def dotted_name(node: ast.AST) -> Optional[str]:
    """'a.b.c' for Name/Attribute chains, else None."""
    parts: List[str] = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        parts.append(cur.id)
        return ".".join(reversed(parts))
    return None


def call_target(call: ast.Call,
                imports: Optional[ImportTable] = None) -> Optional[str]:
    name = dotted_name(call.func)
    if name is None:
        return None
    return imports.resolve(name) if imports is not None else name


def identifiers_in(node: ast.AST) -> Set[str]:
    """Every Name id and Attribute attr appearing inside `node`."""
    out: Set[str] = set()
    for n in ast.walk(node):
        if isinstance(n, ast.Name):
            out.add(n.id)
        elif isinstance(n, ast.Attribute):
            out.add(n.attr)
    return out


# ---------------------------------------------------------------------------
# traced-context classification
# ---------------------------------------------------------------------------
_JIT_NAMES = {"jax.jit", "jit", "jax.pjit", "pjit",
              "jax.experimental.pjit.pjit"}
_SHARD_MAP_NAMES = {"jax.shard_map", "shard_map",
                    "jax.experimental.shard_map.shard_map"}


def _is_jit_expr(expr: ast.AST, imports: ImportTable) -> bool:
    """Does `expr` denote jit/shard_map — directly, or as
    functools.partial(jax.jit, ...)?"""
    name = dotted_name(expr)
    if name is not None:
        resolved = imports.resolve(name)
        if resolved in _JIT_NAMES or resolved in _SHARD_MAP_NAMES:
            return True
        # unresolved tail match: jax.jit spelled through an odd alias
        if resolved.endswith(".jit") or resolved.endswith("shard_map"):
            return True
        return False
    if isinstance(expr, ast.Call):
        fn = dotted_name(expr.func)
        if fn is not None and imports.resolve(fn).endswith("partial"):
            return any(_is_jit_expr(a, imports) for a in expr.args)
        return _is_jit_expr(expr.func, imports)
    return False


def static_argnames_of(call_or_deco: ast.AST) -> Set[str]:
    """static_argnames=(...) strings from a jit decorator/wrap call."""
    out: Set[str] = set()
    calls = [n for n in ast.walk(call_or_deco) if isinstance(n, ast.Call)]
    for call in calls:
        for kw in call.keywords:
            if kw.arg != "static_argnames":
                continue
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, str):
                    out.add(n.value)
    return out


class ModuleIndex:
    """Per-module function index: tracedness, guard coverage, call
    graph. Built once per (file, configuration) by rules that need it."""

    def __init__(self, src_tree: ast.AST, display_path: str,
                 known_traced: Sequence[Tuple[str, str]] = ()):
        self.tree = src_tree
        self.path = display_path
        self.imports = ImportTable(src_tree)
        self.parents = parent_map(src_tree)
        self.functions: List[ast.AST] = [
            n for n in ast.walk(src_tree) if isinstance(n, FuncNode)]
        self._known_traced = known_traced
        self._traced: Optional[Set[ast.AST]] = None
        self._static_args: Dict[ast.AST, Set[str]] = {}

    # -- tracedness --------------------------------------------------------
    def directly_traced(self, fn: ast.AST) -> bool:
        """Decorated with jit/shard_map, wrapped by name in a jit/
        shard_map call in this module, or matching a known-traced
        pattern for this file."""
        for deco in fn.decorator_list:
            if _is_jit_expr(deco, self.imports):
                self._static_args.setdefault(fn, set()).update(
                    static_argnames_of(deco))
                return True
        name = fn.name
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            if not _is_jit_expr(node.func, self.imports):
                continue
            for arg in node.args:
                if isinstance(arg, ast.Name) and arg.id == name:
                    self._static_args.setdefault(fn, set()).update(
                        static_argnames_of(node))
                    return True
        for path_pat, name_pat in self._known_traced:
            if re.search(path_pat, self.path) and re.match(name_pat, name):
                return True
        return False

    def traced_functions(self) -> Set[ast.AST]:
        """Fixed point over direct seeds + lexical nesting + the
        module-local call graph (any traced caller taints the callee:
        its body runs at trace time and may receive tracers)."""
        if self._traced is not None:
            return self._traced
        traced: Set[ast.AST] = {f for f in self.functions
                                if self.directly_traced(f)}
        by_name: Dict[str, List[ast.AST]] = {}
        for f in self.functions:
            by_name.setdefault(f.name, []).append(f)
        changed = True
        while changed:
            changed = False
            for f in self.functions:
                if f in traced:
                    continue
                # nested inside a traced function
                if any(enc in traced
                       for enc in enclosing_functions(f, self.parents)):
                    traced.add(f)
                    changed = True
                    continue
            # call-graph propagation: look at every call inside traced fns
            for f in list(traced):
                for node in ast.walk(f):
                    if not isinstance(node, ast.Call):
                        continue
                    callee = None
                    if isinstance(node.func, ast.Name):
                        callee = node.func.id
                    elif isinstance(node.func, ast.Attribute) and \
                            isinstance(node.func.value, ast.Name) and \
                            node.func.value.id == "self":
                        callee = node.func.attr
                    if callee is None:
                        continue
                    for target in by_name.get(callee, ()):
                        if target not in traced:
                            traced.add(target)
                            changed = True
        self._traced = traced
        return traced

    def static_params(self, fn: ast.AST) -> Set[str]:
        """static_argnames recorded while classifying `fn` as directly
        traced (empty for propagated helpers)."""
        self.directly_traced(fn)
        return set(self._static_args.get(fn, ()))

    def traced_params(self, fn: ast.AST) -> Set[str]:
        """Parameter names of a directly-traced function that carry
        traced values (everything not named in static_argnames)."""
        names = [a.arg for a in (fn.args.posonlyargs + fn.args.args
                                 + fn.args.kwonlyargs)]
        return set(names) - self.static_params(fn) - {"self", "cls"}

    # -- guard coverage ----------------------------------------------------
    def in_guard_with(self, node: ast.AST,
                      is_guard: Callable[[ast.AST], bool]) -> bool:
        """Is `node` lexically inside a `with` whose context expression
        satisfies `is_guard`? Stops at function boundaries (a nested
        def's body does not inherit the enclosing with — it runs
        later)."""
        cur = self.parents.get(node)
        while cur is not None and not isinstance(cur, FuncNode):
            if isinstance(cur, (ast.With, ast.AsyncWith)):
                for item in cur.items:
                    if is_guard(item.context_expr):
                        return True
            cur = self.parents.get(cur)
        return False

    def covered_functions(
            self, is_guard: Callable[[ast.AST], bool]) -> Set[ast.AST]:
        """Functions whose EVERY in-module call site sits inside the
        guard (lexically, or inside an already-covered function) —
        fixed point. Functions with no visible call sites are NOT
        covered."""
        by_name: Dict[str, List[ast.AST]] = {}
        for f in self.functions:
            by_name.setdefault(f.name, []).append(f)
        # call sites: name -> [(site_node, enclosing_fn)]
        sites: Dict[str, List[Tuple[ast.AST, Optional[ast.AST]]]] = {}
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            callee = None
            if isinstance(node.func, ast.Name):
                callee = node.func.id
            elif isinstance(node.func, ast.Attribute):
                callee = node.func.attr
            if callee is None or callee not in by_name:
                continue
            encs = enclosing_functions(node, self.parents)
            sites.setdefault(callee, []).append(
                (node, encs[0] if encs else None))
        covered: Set[ast.AST] = set()
        changed = True
        while changed:
            changed = False
            for f in self.functions:
                if f in covered:
                    continue
                f_sites = sites.get(f.name, [])
                if not f_sites:
                    continue
                if all(self.in_guard_with(site, is_guard)
                       or (enc is not None and enc in covered)
                       for site, enc in f_sites):
                    covered.add(f)
                    changed = True
        return covered

    def guarded(self, node: ast.AST,
                is_guard: Callable[[ast.AST], bool],
                covered: Optional[Set[ast.AST]] = None) -> bool:
        """Lexical guard, or enclosing function fully covered."""
        if self.in_guard_with(node, is_guard):
            return True
        if covered is None:
            covered = self.covered_functions(is_guard)
        return any(enc in covered
                   for enc in enclosing_functions(node, self.parents))


# ---------------------------------------------------------------------------
# common guard predicates
# ---------------------------------------------------------------------------
def deadline_guard(imports: ImportTable) -> Callable[[ast.AST], bool]:
    """`with watchdog.deadline(...)` / `with deadline(...)` context
    expressions (the PR 11 collective-watchdog contract)."""
    def is_guard(expr: ast.AST) -> bool:
        if not isinstance(expr, ast.Call):
            return False
        name = dotted_name(expr.func)
        return name is not None and \
            name.split(".")[-1] == "deadline"
    return is_guard


_LOCK_WORD = re.compile(r"(?:^|_)(?:lock|cv|cond|mutex|mu)$")


def lock_guard(expr: ast.AST) -> bool:
    """`with self._lock:` / `with self._cv:` style context expressions
    (bare lock attribute/name, or a Condition used as its lock)."""
    name = dotted_name(expr)
    if name is None:
        return False
    return bool(_LOCK_WORD.search(name.split(".")[-1]))
