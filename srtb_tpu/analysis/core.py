"""Shared machinery for the srtb-lint rules.

Pure-AST: the scanned code is parsed, never imported, so the linter can
run on broken or accelerator-only modules from any environment.  The
interesting piece is a lightweight whole-project call graph — enough
name resolution (module aliases, ``self.method``, nested functions,
``jax.jit`` wrapper assignments) to answer the two questions every rule
here needs: *which functions execute inside a jit trace* and *which
functions run on a spawned thread*.
"""

from __future__ import annotations

import ast
import json
import os
import re
from dataclasses import dataclass, field

_PRAGMA_RE = re.compile(
    r"#\s*srtb-lint:\s*disable(?P<file>-file)?\s*=\s*"
    r"(?P<rules>[A-Za-z0-9_,\- ]+)")


# ----------------------------------------------------------- findings


@dataclass
class Finding:
    """One rule hit, pointing at file:line with enough context to build
    a line-number-independent baseline key."""

    rule: str
    path: str          # path as given on the command line (display)
    rel: str           # package-relative path (stable baseline key part)
    line: int
    col: int
    message: str
    context: str       # enclosing function qualname or "<module>"
    line_text: str

    @property
    def key(self) -> str:
        """Baseline identity: survives unrelated edits that only move
        line numbers (file + rule + enclosing function + source text)."""
        return "::".join((self.rel, self.rule, self.context,
                          self.line_text.strip()))

    def render(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: {self.rule} "
                f"{self.message} [in {self.context}]")


# ----------------------------------------------------------- functions


@dataclass
class FunctionInfo:
    """One function/method/nested def, with its resolution context."""

    name: str
    qualname: str
    node: ast.AST                    # FunctionDef / AsyncFunctionDef
    module: "ModuleSource"
    class_name: str | None = None    # nearest enclosing class
    parent: str | None = None        # enclosing function qualname
    calls: set = field(default_factory=set)   # resolved FunctionInfo set

    def __hash__(self):
        return hash((self.module.rel, self.qualname))

    def __eq__(self, other):
        return (isinstance(other, FunctionInfo)
                and self.module is other.module
                and self.qualname == other.qualname)

    def body_nodes(self):
        """All AST nodes of this function's own body, excluding the
        bodies of nested function/class definitions (those are separate
        FunctionInfo / scope units)."""
        todo = list(ast.iter_child_nodes(self.node))
        while todo:
            node = todo.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            yield node
            todo.extend(ast.iter_child_nodes(node))


# ------------------------------------------------------------- module


class ModuleSource:
    """One parsed source file: AST, function index, import aliases and
    suppression pragmas."""

    def __init__(self, path: str, rel: str, text: str, dotted: str):
        self.path = path
        self.rel = rel
        self.dotted = dotted
        self.text = text
        self.lines = text.splitlines()
        self.tree = ast.parse(text, filename=path)
        self.functions: dict[str, FunctionInfo] = {}
        self.classes: dict[str, list[FunctionInfo]] = {}
        # local name -> dotted module, or "dotted.module:symbol"
        self.import_alias: dict[str, str] = {}
        self._collect_functions()
        self._collect_imports()
        self._disable_line: dict[int, set[str]] = {}
        self._disable_file: set[str] = set()
        self._collect_pragmas()

    # -- construction

    def _collect_functions(self) -> None:
        mod = self

        class V(ast.NodeVisitor):
            def __init__(self):
                self.stack: list[tuple[str, str]] = []  # (kind, name)

            def _qual(self, name):
                return ".".join([n for _, n in self.stack] + [name])

            def visit_ClassDef(self, node):
                self.stack.append(("class", node.name))
                self.generic_visit(node)
                self.stack.pop()

            def _func(self, node):
                qual = self._qual(node.name)
                cls = next((n for k, n in reversed(self.stack)
                            if k == "class"), None)
                parent = None
                for k, n in reversed(self.stack):
                    if k == "func":
                        parent = ".".join(
                            [x for _, x in self.stack[
                                :self.stack.index((k, n)) + 1]])
                        break
                info = FunctionInfo(node.name, qual, node, mod,
                                    class_name=cls, parent=parent)
                mod.functions[qual] = info
                if cls is not None:
                    mod.classes.setdefault(cls, []).append(info)
                self.stack.append(("func", node.name))
                self.generic_visit(node)
                self.stack.pop()

            visit_FunctionDef = _func
            visit_AsyncFunctionDef = _func

        V().visit(self.tree)

    def _collect_imports(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    local = a.asname or a.name.split(".")[0]
                    self.import_alias[local] = (a.name if a.asname
                                                else a.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    local = a.asname or a.name
                    self.import_alias[local] = f"{node.module}:{a.name}"

    def _collect_pragmas(self) -> None:
        for i, line in enumerate(self.lines, start=1):
            m = _PRAGMA_RE.search(line)
            if not m:
                continue
            rules = {r.strip() for r in m.group("rules").split(",")
                     if r.strip()}
            if m.group("file"):
                self._disable_file |= rules
            else:
                self._disable_line.setdefault(i, set()).update(rules)

    # -- queries

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1]
        return ""

    def disabled(self, lineno: int, rule: str) -> bool:
        """Suppressed by a pragma on this line, on directly preceding
        comment-only lines, or file-wide."""
        def hit(ln):
            rules = self._disable_line.get(ln, ())
            return rule in rules or "all" in rules

        if rule in self._disable_file or "all" in self._disable_file:
            return True
        if hit(lineno):
            return True
        ln = lineno - 1
        while ln >= 1 and self.line_text(ln).lstrip().startswith("#"):
            if hit(ln):
                return True
            ln -= 1
        return False

    def enclosing_function(self, node: ast.AST) -> FunctionInfo | None:
        """Innermost FunctionInfo whose span contains ``node``."""
        best = None
        for info in self.functions.values():
            f = info.node
            end = getattr(f, "end_lineno", f.lineno)
            if f.lineno <= node.lineno <= end:
                if best is None or f.lineno > best.node.lineno:
                    best = info
        return best

    def resolves_to(self, expr: ast.expr, *candidates: str) -> bool:
        """True when ``expr`` names one of the dotted ``candidates``
        through this module's import aliases.  E.g. with ``import
        jax``, ``jax.jit`` resolves to "jax.jit"; with ``from jax
        import jit as J``, ``J`` resolves to "jax.jit"."""
        dotted = self.dotted_name(expr)
        return dotted is not None and dotted in candidates

    def dotted_name(self, expr: ast.expr) -> str | None:
        """Alias-resolved dotted name of a Name/Attribute chain."""
        parts = []
        while isinstance(expr, ast.Attribute):
            parts.append(expr.attr)
            expr = expr.value
        if not isinstance(expr, ast.Name):
            return None
        base = self.import_alias.get(expr.id, expr.id)
        base = base.replace(":", ".")
        return ".".join([base] + list(reversed(parts)))


# ------------------------------------------------------------ project


def _jit_callee(call: ast.Call, mod: ModuleSource) -> bool:
    return mod.resolves_to(call.func, "jax.jit", "jax.api.jit",
                           "jax.pjit")


def _donated_positions(call: ast.Call):
    """donate_argnums of a jax.jit call: a set of ints, or "dynamic"
    when the value is not a literal (conditionally donating wrappers —
    still rule-relevant, treated as position 0)."""
    for kw in call.keywords:
        if kw.arg in ("donate_argnums", "donate_argnames"):
            v = kw.value
            if isinstance(v, (ast.Tuple, ast.List)):
                out = set()
                for e in v.elts:
                    if isinstance(e, ast.Constant) and isinstance(
                            e.value, int):
                        out.add(e.value)
                    else:
                        return "dynamic"
                return out
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return {v.value}
            return "dynamic"
    return set()


class Project:
    """All scanned modules + the cross-module call graph + jit roots."""

    def __init__(self, modules: list[ModuleSource]):
        self.modules = modules
        self.by_dotted: dict[str, ModuleSource] = {}
        for m in modules:
            self.by_dotted[m.dotted] = m
            # a package's modules are importable both as
            # "srtb_tpu.ops.fft" and (scan-root relative) "ops.fft"
            short = m.dotted.split(".", 1)[-1]
            self.by_dotted.setdefault(short, m)
        # (module, class|None, name) -> (target FunctionInfo, donated)
        self.jit_wrappers: dict[tuple, tuple[FunctionInfo, object]] = {}
        self.jit_roots: set[FunctionInfo] = set()
        self._build_call_graph()
        self._find_jit_roots()
        self.jit_bodies = self.reachable(self.jit_roots)

    # -- resolution

    def _resolve_module_func(self, mod: ModuleSource, dotted: str,
                             name: str) -> FunctionInfo | None:
        target = self.by_dotted.get(dotted)
        if target is None:
            return None
        return target.functions.get(name)

    def resolve_call(self, mod: ModuleSource, caller: FunctionInfo,
                     func: ast.expr) -> FunctionInfo | None:
        """Best-effort callee resolution for the edge kinds this project
        actually contains: bare names (nested/sibling/module scope),
        ``self.method``, and ``alias.func`` across modules."""
        if isinstance(func, ast.Name):
            name = func.id
            # own nested defs, then enclosing-function siblings
            scope = caller
            while scope is not None:
                hit = mod.functions.get(f"{scope.qualname}.{name}")
                if hit is not None:
                    return hit
                scope = (mod.functions.get(scope.parent)
                         if scope.parent else None)
            # same-class method referenced bare (rare), module function
            if caller.class_name:
                hit = mod.functions.get(f"{caller.class_name}.{name}")
                if hit is not None:
                    return hit
            hit = mod.functions.get(name)
            if hit is not None:
                return hit
            # imported symbol
            alias = mod.import_alias.get(name)
            if alias and ":" in alias:
                dotted, sym = alias.split(":", 1)
                return self._resolve_module_func(mod, dotted, sym)
            return None
        if isinstance(func, ast.Attribute):
            if isinstance(func.value, ast.Name) and func.value.id == "self":
                name = func.attr
                if caller.class_name:
                    hit = mod.functions.get(
                        f"{caller.class_name}.{name}")
                    if hit is not None:
                        return hit
                # inherited methods: any class in this module that
                # defines the method (approximation good enough for the
                # Pipeline/ThreadedPipeline pair)
                for infos in mod.classes.values():
                    for info in infos:
                        if info.name == name:
                            return info
                return None
            dotted = mod.dotted_name(func.value)
            if dotted is not None:
                return self._resolve_module_func(mod, dotted, func.attr)
        return None

    # -- graph construction

    def _build_call_graph(self) -> None:
        for mod in self.modules:
            for info in mod.functions.values():
                for node in info.body_nodes():
                    if isinstance(node, ast.Call):
                        callee = self.resolve_call(mod, info, node.func)
                        if callee is not None:
                            info.calls.add(callee)

    def _find_jit_roots(self) -> None:
        for mod in self.modules:
            # decorator spellings: @jax.jit, @jit, and
            # @partial(jax.jit, ...) all make the function a jit body
            for info in mod.functions.values():
                for dec in getattr(info.node, "decorator_list", ()):
                    if mod.resolves_to(dec, "jax.jit") or (
                            isinstance(dec, ast.Call)
                            and (_jit_callee(dec, mod) or any(
                                mod.resolves_to(a, "jax.jit")
                                for a in dec.args))):
                        self.jit_roots.add(info)
            for node in ast.walk(mod.tree):
                if not (isinstance(node, ast.Call)
                        and _jit_callee(node, mod) and node.args):
                    continue
                donated = _donated_positions(node)
                enclosing = mod.enclosing_function(node)
                targets = self._jit_targets(mod, enclosing, node.args[0])
                for t in targets:
                    self.jit_roots.add(t)
                self._record_wrapper(mod, node, targets, donated)

    def _jit_targets(self, mod, enclosing, wrapped) -> list[FunctionInfo]:
        """Function(s) a jax.jit argument refers to.  For a lambda the
        functions *called inside it* become jit bodies."""
        if isinstance(wrapped, ast.Lambda):
            out = []
            for sub in ast.walk(wrapped.body):
                if isinstance(sub, ast.Call):
                    t = self.resolve_call(
                        mod, enclosing or _module_scope(mod), sub.func)
                    if t is not None:
                        out.append(t)
            return out
        if isinstance(wrapped, ast.Call):
            # jax.jit(jax.vmap(f)) and friends: unwrap one level
            if wrapped.args:
                return self._jit_targets(mod, enclosing, wrapped.args[0])
            return []
        t = self.resolve_call(mod, enclosing or _module_scope(mod),
                              wrapped)
        return [t] if t is not None else []

    def _record_wrapper(self, mod, call, targets, donated) -> None:
        """If the jax.jit(...) result is assigned (``self._jit_x = ...``
        or ``wrapper = ...``), remember the wrapper name so call sites
        through it can be linked to the wrapped function + donation."""
        if not targets:
            return
        assign = _assign_parent(mod.tree, call)
        if assign is None:
            return
        for tgt in assign.targets if isinstance(
                assign, ast.Assign) else [assign.target]:
            cls = None
            name = None
            if isinstance(tgt, ast.Attribute) and isinstance(
                    tgt.value, ast.Name) and tgt.value.id == "self":
                enclosing = mod.enclosing_function(call)
                cls = enclosing.class_name if enclosing else None
                name = tgt.attr
            elif isinstance(tgt, ast.Name):
                name = tgt.id
            if name is not None:
                self.jit_wrappers[(mod.dotted, cls, name)] = (
                    targets[0], donated)

    # -- reachability

    def reachable(self, seeds) -> set[FunctionInfo]:
        seen = set(seeds)
        todo = list(seeds)
        while todo:
            f = todo.pop()
            for callee in f.calls:
                if callee not in seen:
                    seen.add(callee)
                    todo.append(callee)
        return seen


def _module_scope(mod: ModuleSource) -> FunctionInfo:
    """Synthetic scope for module-level expressions."""
    return FunctionInfo("<module>", "<module>", mod.tree, mod)


def _assign_parent(tree: ast.AST, call: ast.Call) -> ast.AST | None:
    for node in ast.walk(tree):
        if isinstance(node, (ast.Assign, ast.AnnAssign)) \
                and node.value is call:
            return node
    return None


# ----------------------------------------------------------- baseline


class Baseline:
    """Checked-in accepted findings.  Keys are line-number independent
    (see Finding.key); each entry carries an occurrence count (the same
    source line may legitimately hit a rule twice in one function) and
    a human note explaining why the finding is accepted."""

    def __init__(self, entries: dict[str, dict] | None = None):
        self.entries: dict[str, dict] = entries or {}

    @classmethod
    def load(cls, path: str) -> "Baseline":
        if not path or not os.path.exists(path):
            return cls()
        with open(path) as f:
            data = json.load(f)
        return cls(data.get("entries", {}))

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump({"version": 1, "entries": self.entries}, f,
                      indent=2, sort_keys=True)
            f.write("\n")

    def filter(self, findings: list[Finding]):
        """Split findings into (new, accepted) honoring per-key counts,
        and report stale baseline keys that no longer fire."""
        budget = {k: v.get("count", 1) for k, v in self.entries.items()}
        new, accepted = [], []
        for f in findings:
            if budget.get(f.key, 0) > 0:
                budget[f.key] -= 1
                accepted.append(f)
            else:
                new.append(f)
        stale = sorted(k for k, n in budget.items()
                       if n >= self.entries.get(k, {}).get("count", 1)
                       and n > 0)
        return new, accepted, stale

    @classmethod
    def from_findings(cls, findings: list[Finding],
                      old: "Baseline | None" = None) -> "Baseline":
        entries: dict[str, dict] = {}
        for f in findings:
            e = entries.setdefault(f.key, {"count": 0})
            e["count"] += 1
        if old is not None:  # carry notes forward across rewrites
            for k, e in entries.items():
                note = old.entries.get(k, {}).get("note")
                if note:
                    e["note"] = note
        return cls(entries)
