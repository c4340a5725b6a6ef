"""Interprocedural analysis engine for impala-lint v2.

PR 7's checkers are single-function AST walks; the sharding subsystem's
bugs cross function boundaries (an axis name bound at a call site in
models/transformer.py reaches a collective three frames down in
parallel/ulysses.py; a donated batch leaks into a helper). This module
builds the shared cross-file infrastructure the v2 checkers
(sharding.py, donation.py, dtypes.py) analyze over:

- a **module map** — every scanned file keyed by its dotted module name
  (``torched_impala_tpu/runtime/learner.py`` ->
  ``torched_impala_tpu.runtime.learner``);
- per-module **import alias tables** (``import x.y as z``,
  ``from a.b import c as d``, relative ``from . import mesh``);
- a **function index** of every def — module-level functions and
  methods (``Learner.step_once``) — with parameter lists;
- a **call graph**: each ``ast.Call`` resolved (where statically
  possible) to a function in the index.  Resolution handles plain
  names, dotted module attributes through import aliases, ``self.m()``
  method calls (with one level of base-class lookup), and
  constructor calls (``Cls(...)`` -> ``Cls.__init__``).  Unresolvable
  dynamic calls are simply absent — the checkers are best-effort
  detectors, not verifiers.

Propagation is intentionally shallow (one to two hops): deep transitive
closures over a dynamic codebase breed false positives; the bugs this
suite exists for (ISSUE 11, docs/STATIC_ANALYSIS.md) live one call away
from their facts.  Cycles are harmless — every traversal carries a
visited set or a bounded iteration count.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from tools.lint.core import SourceFile


def dotted(node: ast.expr) -> str:
    """'a.b.c' for a plain dotted expression, '' otherwise."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def module_name(rel: str) -> str:
    """Dotted module name for a repo-relative path.

    'torched_impala_tpu/parallel/mesh.py' -> 'torched_impala_tpu.parallel.mesh'
    'torched_impala_tpu/ops/__init__.py'  -> 'torched_impala_tpu.ops'
    'chip_smoke.py'                       -> 'chip_smoke'
    """
    mod = rel[:-3] if rel.endswith(".py") else rel
    mod = mod.replace("/", ".")
    if mod.endswith(".__init__"):
        mod = mod[: -len(".__init__")]
    return mod


@dataclasses.dataclass
class FunctionInfo:
    """One def in the scanned tree (module function or method)."""

    module: str
    qualname: str  # "fn" or "Cls.fn"
    sf: SourceFile
    node: ast.FunctionDef  # or AsyncFunctionDef
    class_name: Optional[str] = None

    @property
    def fid(self) -> str:
        return f"{self.module}:{self.qualname}"

    @property
    def name(self) -> str:
        return self.node.name

    def params(self) -> List[str]:
        a = self.node.args
        names = [p.arg for p in a.posonlyargs + a.args]
        if names and names[0] in ("self", "cls"):
            names = names[1:]
        return names

    def all_param_names(self) -> Set[str]:
        a = self.node.args
        out = {p.arg for p in a.posonlyargs + a.args + a.kwonlyargs}
        return out - {"self", "cls"}


@dataclasses.dataclass
class CallSite:
    caller: FunctionInfo
    callee: FunctionInfo
    node: ast.Call
    # True when the call goes through a constructor (Cls() -> __init__)
    is_constructor: bool = False


class ClassInfo:
    def __init__(self, module: str, name: str, node: ast.ClassDef) -> None:
        self.module = module
        self.name = name
        self.node = node
        self.methods: Dict[str, FunctionInfo] = {}
        self.base_names: List[str] = [
            dotted(b) for b in node.bases if dotted(b)
        ]


class CallGraph:
    """Function index + resolved call edges over a set of SourceFiles."""

    def __init__(self, files: Sequence[SourceFile]) -> None:
        self.files = list(files)
        self.modules: Dict[str, SourceFile] = {}
        self.imports: Dict[str, Dict[str, str]] = {}  # mod -> alias -> tgt
        self.functions: Dict[str, FunctionInfo] = {}  # fid -> info
        self.classes: Dict[str, ClassInfo] = {}  # "mod:Cls" -> info
        self.calls_out: Dict[str, List[CallSite]] = {}
        self.calls_in: Dict[str, List[CallSite]] = {}
        self._index()
        self._resolve_calls()

    # -- indexing ----------------------------------------------------------

    def _index(self) -> None:
        for sf in self.files:
            if sf.tree is None:
                continue
            mod = module_name(sf.rel)
            self.modules[mod] = sf
            self.imports[mod] = self._imports_of(mod, sf.tree)
            for stmt in sf.tree.body:
                if isinstance(
                    stmt, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    info = FunctionInfo(mod, stmt.name, sf, stmt)
                    self.functions[info.fid] = info
                elif isinstance(stmt, ast.ClassDef):
                    ci = ClassInfo(mod, stmt.name, stmt)
                    self.classes[f"{mod}:{stmt.name}"] = ci
                    for sub in stmt.body:
                        if isinstance(
                            sub, (ast.FunctionDef, ast.AsyncFunctionDef)
                        ):
                            info = FunctionInfo(
                                mod,
                                f"{stmt.name}.{sub.name}",
                                sf,
                                sub,
                                class_name=stmt.name,
                            )
                            self.functions[info.fid] = info
                            ci.methods[sub.name] = info

    def _imports_of(self, mod: str, tree: ast.AST) -> Dict[str, str]:
        table: Dict[str, str] = {}
        pkg_parts = mod.split(".")[:-1]  # containing package
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    name = alias.asname or alias.name.split(".")[0]
                    target = alias.name if alias.asname else (
                        alias.name.split(".")[0]
                    )
                    table[name] = target
                    if alias.asname is None:
                        # `import a.b.c` binds `a`, but the full dotted
                        # path stays resolvable through it.
                        table[alias.name.split(".")[0]] = (
                            alias.name.split(".")[0]
                        )
            elif isinstance(node, ast.ImportFrom):
                if node.level:
                    base_parts = mod.split(".")
                    # level=1 from a module: its package; each extra
                    # level strips one more component.
                    base_parts = base_parts[: len(base_parts) - node.level]
                    base = ".".join(
                        base_parts + ([node.module] if node.module else [])
                    )
                else:
                    base = node.module or ""
                for alias in node.names:
                    if alias.name == "*":
                        continue
                    name = alias.asname or alias.name
                    table[name] = f"{base}.{alias.name}" if base else (
                        alias.name
                    )
        # implicit: a package module can reference sibling modules once
        # imported; handled by the explicit table only.
        del pkg_parts
        return table

    # -- resolution --------------------------------------------------------

    def resolve_name(self, mod: str, expr: ast.expr) -> Optional[str]:
        """Fully-resolve a call-target expression to a dotted path
        through `mod`'s import table ('torched_impala_tpu.parallel.mesh.
        make_mesh'), or None when dynamic."""
        d = dotted(expr)
        if not d:
            return None
        head, _, rest = d.partition(".")
        table = self.imports.get(mod, {})
        if head in table:
            base = table[head]
            return f"{base}.{rest}" if rest else base
        # plain local name / dotted chain rooted at a local name
        return f"{mod}.{d}" if "." not in d else d

    def _function_at(self, path: str) -> Optional[FunctionInfo]:
        """FunctionInfo for a dotted path: module.fn, module.Cls
        (constructor), or module.Cls.fn."""
        parts = path.split(".")
        for split in range(len(parts) - 1, 0, -1):
            mod = ".".join(parts[:split])
            if mod not in self.modules:
                continue
            tail = parts[split:]
            if len(tail) == 1:
                fi = self.functions.get(f"{mod}:{tail[0]}")
                if fi is not None:
                    return fi
                ci = self.classes.get(f"{mod}:{tail[0]}")
                if ci is not None:
                    return ci.methods.get("__init__")
            elif len(tail) == 2:
                return self.functions.get(f"{mod}:{tail[0]}.{tail[1]}")
        return None

    def _method_on(
        self, ci: ClassInfo, name: str, depth: int = 2
    ) -> Optional[FunctionInfo]:
        """`name` on `ci` or (one level of) its in-tree bases."""
        if name in ci.methods:
            return ci.methods[name]
        if depth <= 0:
            return None
        for base in ci.base_names:
            resolved = self.resolve_name(ci.module, ast.parse(
                base, mode="eval"
            ).body) if "." in base else None
            cand_keys = []
            if resolved:
                parts = resolved.rsplit(".", 1)
                if len(parts) == 2:
                    cand_keys.append(f"{parts[0]}:{parts[1]}")
            cand_keys.append(f"{ci.module}:{base}")
            # resolve `Base` imported via `from mod import Base`
            tbl = self.imports.get(ci.module, {})
            if base in tbl:
                parts = tbl[base].rsplit(".", 1)
                if len(parts) == 2:
                    cand_keys.append(f"{parts[0]}:{parts[1]}")
            for key in cand_keys:
                bci = self.classes.get(key)
                if bci is not None:
                    m = self._method_on(bci, name, depth - 1)
                    if m is not None:
                        return m
        return None

    def resolve_call(
        self, caller: FunctionInfo, call: ast.Call
    ) -> Optional[FunctionInfo]:
        """Best-effort static resolution of one call expression."""
        fn = call.func
        # self.method()
        if (
            isinstance(fn, ast.Attribute)
            and isinstance(fn.value, ast.Name)
            and fn.value.id in ("self", "cls")
            and caller.class_name is not None
        ):
            ci = self.classes.get(f"{caller.module}:{caller.class_name}")
            if ci is not None:
                return self._method_on(ci, fn.attr)
            return None
        path = self.resolve_name(caller.module, fn)
        if path is None:
            return None
        return self._function_at(path)

    def _resolve_calls(self) -> None:
        for fi in self.functions.values():
            sites: List[CallSite] = []
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                callee = self.resolve_call(fi, node)
                if callee is None or callee.fid == fi.fid:
                    continue
                site = CallSite(
                    caller=fi,
                    callee=callee,
                    node=node,
                    is_constructor=callee.name == "__init__"
                    and not dotted(node.func).endswith("__init__"),
                )
                sites.append(site)
                self.calls_in.setdefault(callee.fid, []).append(site)
            self.calls_out[fi.fid] = sites

    # -- traversal helpers -------------------------------------------------

    def callees(
        self, fid: str, max_hops: int = 1
    ) -> Iterator[Tuple[FunctionInfo, int]]:
        """(callee, hops) pairs reachable from `fid` within `max_hops`,
        each function yielded once at its minimum distance. Cycle-safe."""
        seen: Set[str] = {fid}
        frontier = [fid]
        for hop in range(1, max_hops + 1):
            nxt: List[str] = []
            for f in frontier:
                for site in self.calls_out.get(f, []):
                    cid = site.callee.fid
                    if cid in seen:
                        continue
                    seen.add(cid)
                    yield site.callee, hop
                    nxt.append(cid)
            frontier = nxt


def bound_arguments(
    fn: FunctionInfo, call: ast.Call
) -> Dict[str, ast.expr]:
    """Map `call`'s arguments onto `fn`'s parameter names (positional +
    keyword; *args/**kwargs ignored). The workhorse for 1-hop fact
    propagation: a checker looks up which expression feeds a parameter
    it cares about."""
    out: Dict[str, ast.expr] = {}
    params = fn.params()
    for i, arg in enumerate(call.args):
        if isinstance(arg, ast.Starred):
            break
        if i < len(params):
            out[params[i]] = arg
    names = fn.all_param_names()
    for kw in call.keywords:
        if kw.arg is not None and kw.arg in names:
            out[kw.arg] = kw.value
    return out


def param_defaults(fn: FunctionInfo) -> Dict[str, ast.expr]:
    """Parameter-name -> default-value expression (positional and
    keyword-only)."""
    a = fn.node.args
    out: Dict[str, ast.expr] = {}
    pos = a.posonlyargs + a.args
    for p, d in zip(pos[len(pos) - len(a.defaults):], a.defaults):
        out[p.arg] = d
    for p, d in zip(a.kwonlyargs, a.kw_defaults):
        if d is not None:
            out[p.arg] = d
    return out


def build(files: Sequence[SourceFile]) -> CallGraph:
    return CallGraph(files)
