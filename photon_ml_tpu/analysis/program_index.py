"""Whole-program index: cross-module jit resolution for photonlint.

The per-module ``JitIndex`` (analysis/jit_index.py) deliberately stops at
module boundaries — a function defined in ``core/objective.py`` and jitted
in ``parallel/fixed.py`` is invisible to the trace-scoped rules (PL001
host-sync, PL003 tracer-safety, PL004 dtype-discipline).  This module adds
the whole-program layer:

  1. parse every module of the package ONCE;
  2. build a module/symbol table — ``import a.b as c``, ``from a import b``
     (absolute and relative), module-level function defs, module-level
     string/tuple constants;
  3. seed a call graph at every jit entry point: the per-module JitIndex
     roots plus ``jax.jit(target)`` call sites whose target resolves through
     the import table to a function in ANOTHER module;
  4. propagate "traced" reachability over the call graph: a call inside
     traced code to a resolvable function (local ``Name``, imported symbol,
     ``alias.fn`` through a module alias, or ``self.method`` by name within
     the module) marks the callee traced, to a fixpoint.

``extra_roots(relpath, base_index)`` then returns, per module, the traced
functions the per-module index did NOT already cover; ``ModuleContext``
splices them into its ``JitIndex`` so every existing trace-scoped rule sees
cross-module flows with no rule changes.

The index also collects the program's **mesh-axis universe** — the axis
names of every ``jax.sharding.Mesh(...)`` constructed anywhere in the
package, with name constants (``DATA_AXIS`` et al.) resolved through the
import table — which PL007 (mesh-axis) and PL008 (sharding-annotation)
validate collective axis names and ``PartitionSpec`` strings against.

Resolution is best-effort and conservative: anything unresolvable simply
contributes nothing (no finding), so whole-program mode can only ADD
findings relative to per-module mode, never invent phantom context.
"""

from __future__ import annotations

import ast
import hashlib
import os
import time
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from photon_ml_tpu.analysis.jit_index import (FunctionNode, JitIndex,
                                              _static_names_from_call,
                                              _static_nums_from_call,
                                              _unwrap_transform, _walk_scope,
                                              dotted_name, is_jit_call,
                                              param_names)

_MESH_TERMINALS = {"Mesh"}


def module_name_for(relpath: str) -> str:
    """``photon_ml_tpu/parallel/fixed.py`` -> ``photon_ml_tpu.parallel.fixed``."""
    name = relpath.replace(os.sep, "/")
    if name.endswith(".py"):
        name = name[:-3]
    name = name.strip("/").replace("/", ".")
    if name.endswith(".__init__"):
        name = name[: -len(".__init__")]
    return name


def _source_digest(source: str) -> str:
    return hashlib.sha1(source.encode("utf-8", "surrogatepass")).hexdigest()


# relpath -> (source digest, parsed tree).  Cross-run parse reuse: the
# summary cache (analysis/dataflow) is keyed by AST-node identity
# (``id(fn)``), so reusing a cached ModuleSummaries REQUIRES the index to
# adopt the very tree object those summaries were built over — this cache
# is what makes the two identities coincide across ProgramIndex builds in
# one process (e.g. photonlint --diff linting several changed files).
# Unbounded but tiny: one tree per module
# file actually linted.
_PARSE_CACHE: Dict[str, Tuple[str, ast.Module]] = {}


class ModuleInfo:
    """Symbol table of one parsed module."""

    def __init__(self, relpath: str, source: str):
        self.relpath = relpath.replace(os.sep, "/")
        self.name = module_name_for(self.relpath)
        self.source = source
        self.digest = _source_digest(source)
        self.tree: Optional[ast.Module] = None
        cached = _PARSE_CACHE.get(self.relpath)
        if cached is not None and cached[0] == self.digest:
            self.tree = cached[1]
        else:
            try:
                self.tree = ast.parse(source)
                _PARSE_CACHE[self.relpath] = (self.digest, self.tree)
            except SyntaxError:
                # the framework re-parses and surfaces this as a PL000
                # finding; an unparseable module just contributes nothing
                # to the index
                pass
        # local alias -> (module dotted path, symbol-in-module or None)
        self.imports: Dict[str, Tuple[str, Optional[str]]] = {}
        # module-level function defs (jit targets / call-graph callees)
        self.defs: Dict[str, FunctionNode] = {}
        # ALL function defs by name, any nesting (self.method resolution)
        self.defs_by_name: Dict[str, List[FunctionNode]] = {}
        # module-level simple constants: NAME = <expr>
        self.constants: Dict[str, ast.expr] = {}
        self.jit_index = JitIndex(self.tree)
        if self.tree is None:
            return
        self._collect()

    def _collect(self) -> None:
        pkg = self.name.rpartition(".")[0]  # enclosing package for relatives
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.defs_by_name.setdefault(node.name, []).append(node)
        for stmt in self.tree.body:
            if isinstance(stmt, ast.Import):
                for alias in stmt.names:
                    bound = alias.asname or alias.name.split(".")[0]
                    target = alias.name if alias.asname else alias.name.split(".")[0]
                    self.imports[bound] = (target, None)
                    if alias.asname is None and "." in alias.name:
                        # `import a.b.c` binds `a`, but the dotted chain
                        # a.b.c.fn resolves through the FULL path; remember
                        # it keyed by the head with the chain retained
                        self.imports.setdefault(
                            alias.name.split(".")[0],
                            (alias.name.split(".")[0], None))
            elif isinstance(stmt, ast.ImportFrom):
                base = stmt.module or ""
                if stmt.level:  # relative import
                    parts = pkg.split(".") if pkg else []
                    cut = stmt.level - 1
                    parts = parts[: len(parts) - cut] if cut else parts
                    base = ".".join(p for p in (".".join(parts), base) if p)
                for alias in stmt.names:
                    if alias.name == "*":
                        continue
                    bound = alias.asname or alias.name
                    self.imports[bound] = (base, alias.name)
            elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.defs[stmt.name] = stmt
            elif isinstance(stmt, ast.Assign) and len(stmt.targets) == 1:
                tgt = stmt.targets[0]
                if isinstance(tgt, ast.Name):
                    self.constants[tgt.id] = stmt.value
            elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
                if isinstance(stmt.target, ast.Name):
                    self.constants[stmt.target.id] = stmt.value


class ProgramIndex:
    """Cross-module symbol table + traced-reachability index (see module
    docstring).  Build once per lint run; O(total AST nodes)."""

    def __init__(self, sources: Dict[str, str]):
        t0 = time.perf_counter()
        self.modules: Dict[str, ModuleInfo] = {}      # by relpath
        self.by_name: Dict[str, ModuleInfo] = {}      # by dotted module name
        for relpath in sorted(sources):
            info = ModuleInfo(relpath, sources[relpath])
            self.modules[info.relpath] = info
            self.by_name[info.name] = info
        # id(fn) -> (ModuleInfo, fn, tracer-param names)
        self._traced: Dict[int, Tuple[ModuleInfo, FunctionNode, Set[str]]] = {}
        self._propagate()
        self.axis_universe: Set[str] = self._collect_mesh_axes()
        # lazy caches for the dataflow-backed cross-module queries
        self._on_loop: Optional[Dict[int, Tuple[ModuleInfo,
                                                FunctionNode]]] = None
        self._mesh_scoped: Optional[Dict[int, Tuple[ModuleInfo,
                                                    FunctionNode]]] = None
        self._donor_exports: Optional[Dict[str, Dict[str, Tuple[Tuple[int, ...],
                                                                Tuple[str, ...]]]]] = None
        self._summaries: Optional["ProgramSummaries"] = None
        self.build_seconds = time.perf_counter() - t0

    @classmethod
    def from_paths(cls, paths: Sequence[str], root: str) -> "ProgramIndex":
        from photon_ml_tpu.analysis.framework import _iter_py_files

        root = os.path.abspath(root)
        sources: Dict[str, str] = {}
        for path in paths:
            for fpath in _iter_py_files(path):
                rel = os.path.relpath(os.path.abspath(fpath), root)
                with open(fpath, "r", encoding="utf-8") as f:
                    sources[rel.replace(os.sep, "/")] = f.read()
        return cls(sources)

    # -- lookups -------------------------------------------------------------
    def tree_for(self, relpath: str) -> Optional[ast.Module]:
        info = self.modules.get(relpath.replace(os.sep, "/"))
        return info.tree if info else None

    def _split_target(self, full: str) -> Optional[Tuple[ModuleInfo, str]]:
        """Longest-prefix match of a dotted path against known modules;
        the remainder must be a single symbol."""
        parts = full.split(".")
        for i in range(len(parts) - 1, 0, -1):
            mod = self.by_name.get(".".join(parts[:i]))
            if mod is not None:
                rest = parts[i:]
                if len(rest) == 1:
                    return mod, rest[0]
                return None
        return None

    def resolve_symbol(self, info: ModuleInfo,
                       dotted: str) -> Optional[Tuple[ModuleInfo, str]]:
        """A dotted name as WRITTEN in ``info`` -> (defining module, symbol),
        resolved through the import table.  None when it doesn't lead to a
        module in this program."""
        head, _, rest = dotted.partition(".")
        imp = info.imports.get(head)
        if imp is None:
            return None
        target_mod, target_sym = imp
        if target_sym is None:
            full = target_mod + ("." + rest if rest else "")
        else:
            full = target_mod + "." + target_sym + ("." + rest if rest else "")
        # `from a import b` where b is a MODULE (subpackage import)
        mod = self.by_name.get(full)
        if mod is not None:
            return None  # a bare module reference, not a symbol
        return self._split_target(full)

    def resolve_function(self, info: ModuleInfo,
                         dotted: str) -> Optional[Tuple[ModuleInfo,
                                                        FunctionNode]]:
        got = self.resolve_symbol(info, dotted)
        if got is None:
            return None
        mod, sym = got
        fn = mod.defs.get(sym)
        return (mod, fn) if fn is not None else None

    def const_value(self, info: ModuleInfo, expr: ast.AST, depth: int = 0):
        """Best-effort literal value of a module-level expression: constants,
        name references (local or imported), tuples/lists.  None = unknown."""
        if depth > 8 or expr is None:
            return None
        if isinstance(expr, ast.Constant):
            return expr.value
        if isinstance(expr, (ast.Tuple, ast.List)):
            vals = []
            for e in expr.elts:
                v = self.const_value(info, e, depth + 1)
                if v is None:
                    return None
                vals.append(v)
            return tuple(vals)
        name = dotted_name(expr)
        if name is None:
            return None
        if "." not in name and name in info.constants:
            return self.const_value(info, info.constants[name], depth + 1)
        got = self.resolve_symbol(info, name)
        if got is not None:
            mod, sym = got
            if sym in mod.constants:
                return self.const_value(mod, mod.constants[sym], depth + 1)
        return None

    # -- mesh axes -----------------------------------------------------------
    def _collect_mesh_axes(self) -> Set[str]:
        axes: Set[str] = set()
        for info in self.modules.values():
            if info.tree is None:
                continue
            for node in ast.walk(info.tree):
                if not isinstance(node, ast.Call):
                    continue
                fname = dotted_name(node.func)
                if fname is None or fname.rpartition(".")[2] not in _MESH_TERMINALS:
                    continue
                axes_expr = None
                for kw in node.keywords:
                    if kw.arg == "axis_names":
                        axes_expr = kw.value
                if axes_expr is None and len(node.args) >= 2:
                    axes_expr = node.args[1]
                val = self.const_value(info, axes_expr)
                if isinstance(val, str):
                    axes.add(val)
                elif isinstance(val, tuple):
                    axes.update(v for v in val if isinstance(v, str))
        return axes

    # -- traced propagation --------------------------------------------------
    def _seed(self, info: ModuleInfo) -> Iterable[Tuple[ModuleInfo,
                                                        FunctionNode,
                                                        Set[str]]]:
        # per-module roots (decorators, local jit call sites)
        for fn, params in info.jit_index.roots:
            yield info, fn, params
        if info.tree is None:
            return
        # cross-module jit call sites: jax.jit(target) where target is an
        # imported symbol or a module-alias attribute
        for node in ast.walk(info.tree):
            if not (isinstance(node, ast.Call) and is_jit_call(node)
                    and node.args):
                continue
            target = _unwrap_transform(node.args[0])
            dn = dotted_name(target) if target is not None else None
            if dn is None:
                continue
            if "." not in dn and dn in info.defs_by_name:
                continue  # local — per-module index already covers it
            got = self.resolve_function(info, dn)
            if got is None:
                continue
            mod, fn = got
            statics = _static_names_from_call(node)
            nums = _static_nums_from_call(node)
            yield mod, fn, param_names(fn, statics, nums)

    def _propagate(self) -> None:
        stack: List[Tuple[ModuleInfo, FunctionNode, Set[str]]] = []
        for info in self.modules.values():
            for mod, fn, params in self._seed(info):
                if id(fn) not in self._traced:
                    self._traced[id(fn)] = (mod, fn, params)
                    stack.append((mod, fn, params))
        while stack:
            info, fn, params = stack.pop()
            for node, _ in _walk_scope(fn, params):
                if not isinstance(node, ast.Call):
                    continue
                callee = self._resolve_callee(info, node.func)
                if callee is None:
                    continue
                mod, target = callee
                if id(target) in self._traced:
                    continue
                # conservatively every parameter of a call-graph-reached
                # function is a tracer (mirrors nested-def handling in
                # jit_index._walk_scope)
                tparams = param_names(target, set(), set())
                self._traced[id(target)] = (mod, target, tparams)
                stack.append((mod, target, tparams))

    def _resolve_callee(self, info: ModuleInfo, func: ast.AST
                        ) -> Optional[Tuple[ModuleInfo, FunctionNode]]:
        if isinstance(func, ast.Name):
            local = info.defs.get(func.id)
            if local is not None:
                return info, local
            return self.resolve_function(info, func.id)
        if isinstance(func, ast.Attribute):
            # self.method: by-name within the module (the same terminal-attr
            # convention the per-module JitIndex uses for jit targets)
            if isinstance(func.value, ast.Name) and func.value.id == "self":
                cands = info.defs_by_name.get(func.attr)
                if cands and len(cands) == 1:
                    return info, cands[0]
                return None
            dn = dotted_name(func)
            if dn is not None:
                return self.resolve_function(info, dn)
        return None

    # -- rule-facing queries ---------------------------------------------------
    def traced_in(self, relpath: str) -> List[Tuple[FunctionNode, Set[str]]]:
        relpath = relpath.replace(os.sep, "/")
        out = [(fn, params) for (mod, fn, params) in self._traced.values()
               if mod.relpath == relpath]
        out.sort(key=lambda t: t[0].lineno)
        return out

    # -- event-loop reachability (PL013) --------------------------------------
    def _compute_on_loop(self) -> Dict[int, Tuple[ModuleInfo, FunctionNode]]:
        """Cross-module fixpoint of "runs on the asyncio event loop": seeds
        are every ``async def`` plus loop-scheduled callbacks anywhere in
        the program; propagation follows resolvable CALLS only (function
        references handed to executors are exempt by construction)."""
        from photon_ml_tpu.analysis.dataflow import (_timed, lexical_calls,
                                                     loop_callback_exprs)

        on: Dict[int, Tuple[ModuleInfo, FunctionNode]] = {}
        stack: List[Tuple[ModuleInfo, FunctionNode]] = []

        def seed(info: ModuleInfo, fn: FunctionNode) -> None:
            if id(fn) not in on:
                on[id(fn)] = (info, fn)
                stack.append((info, fn))

        with _timed():
            for info in self.modules.values():
                if info.tree is None:
                    continue
                for fns in info.defs_by_name.values():
                    for fn in fns:
                        if isinstance(fn, ast.AsyncFunctionDef):
                            seed(info, fn)
                for cb in loop_callback_exprs(info.tree):
                    if isinstance(cb, ast.Lambda):
                        seed(info, cb)
                        continue
                    got = self._resolve_callee(info, cb)
                    if got is not None:
                        seed(got[0], got[1])
            while stack:
                info, fn = stack.pop()
                for call in lexical_calls(fn):
                    got = self._resolve_callee(info, call.func)
                    if got is not None:
                        seed(got[0], got[1])
        return on

    def async_reachable_in(self, relpath: str) -> List[FunctionNode]:
        """Functions of ``relpath`` that run on (or are call-graph-reachable
        from) the asyncio event loop anywhere in the program."""
        if self._on_loop is None:
            self._on_loop = self._compute_on_loop()
        relpath = relpath.replace(os.sep, "/")
        return [fn for (mod, fn) in self._on_loop.values()
                if mod.relpath == relpath]

    # -- mesh-scoped functions (PL012) ----------------------------------------
    _MESH_BINDERS = {"shard_map", "pmap", "xmap"}

    def _compute_mesh_scoped(self) -> Dict[int, Tuple[ModuleInfo,
                                                      FunctionNode]]:
        """Functions executing under a collective-binding transform anywhere
        in the program: shard_map/pmap/xmap targets (plus vmap targets that
        bind an ``axis_name``) and everything they transitively call."""
        from photon_ml_tpu.analysis.dataflow import _timed, lexical_calls

        scoped: Dict[int, Tuple[ModuleInfo, FunctionNode]] = {}
        stack: List[Tuple[ModuleInfo, FunctionNode]] = []

        def seed(info: ModuleInfo, fn: FunctionNode) -> None:
            if id(fn) not in scoped:
                scoped[id(fn)] = (info, fn)
                stack.append((info, fn))

        with _timed():
            for info in self.modules.values():
                if info.tree is None:
                    continue
                for node in ast.walk(info.tree):
                    if not (isinstance(node, ast.Call) and node.args):
                        continue
                    fname = dotted_name(node.func)
                    term = (fname or "").rpartition(".")[2]
                    binds = term in self._MESH_BINDERS or (
                        term == "vmap"
                        and any(kw.arg == "axis_name"
                                for kw in node.keywords))
                    if not binds:
                        continue
                    target = _unwrap_transform(node.args[0])
                    if isinstance(target, (ast.FunctionDef,
                                           ast.AsyncFunctionDef, ast.Lambda)):
                        seed(info, target)
                        continue
                    got = (self._resolve_callee(info, target)
                           if target is not None else None)
                    if got is not None:
                        seed(got[0], got[1])
            while stack:
                info, fn = stack.pop()
                for call in lexical_calls(fn):
                    got = self._resolve_callee(info, call.func)
                    if got is not None:
                        seed(got[0], got[1])
        return scoped

    def mesh_scoped_in(self, relpath: str) -> List[FunctionNode]:
        if self._mesh_scoped is None:
            self._mesh_scoped = self._compute_mesh_scoped()
        relpath = relpath.replace(os.sep, "/")
        return [fn for (mod, fn) in self._mesh_scoped.values()
                if mod.relpath == relpath]

    # -- cross-module donor table (PL014) -------------------------------------
    def donor_exports(self) -> Dict[str, Dict[str, Tuple[Tuple[int, ...],
                                                         Tuple[str, ...]]]]:
        """Per module relpath: symbol -> (donate_argnums, donate_argnames)
        for every module-level name whose value donates buffers — direct
        ``jax.jit(..., donate_argnums=...)`` bindings, AOT ``.lower().
        compile()`` chains over one, and (to a cross-module fixpoint)
        module-level functions that forward their own parameters into a
        donated position of another donor."""
        if self._donor_exports is not None:
            return self._donor_exports
        from photon_ml_tpu.analysis.dataflow import _timed

        exports: Dict[str, Dict[str, Tuple[Tuple[int, ...],
                                           Tuple[str, ...]]]] = {
            relpath: {} for relpath in self.modules}

        def as_ints(val) -> Tuple[int, ...]:
            if isinstance(val, bool):
                return ()
            if isinstance(val, int):
                return (val,)
            if isinstance(val, tuple):
                return tuple(v for v in val if isinstance(v, int)
                             and not isinstance(v, bool))
            return ()

        def as_strs(val) -> Tuple[str, ...]:
            if isinstance(val, str):
                return (val,)
            if isinstance(val, tuple):
                return tuple(v for v in val if isinstance(v, str))
            return ()

        def spec_of(info: ModuleInfo, expr: ast.AST, depth: int = 0
                    ) -> Optional[Tuple[Tuple[int, ...], Tuple[str, ...]]]:
            if depth > 6 or expr is None:
                return None
            if isinstance(expr, ast.Name):
                return exports[info.relpath].get(expr.id)
            if isinstance(expr, ast.Call):
                if is_jit_call(expr):
                    nums: Tuple[int, ...] = ()
                    names: Tuple[str, ...] = ()
                    for kw in expr.keywords:
                        if kw.arg == "donate_argnums":
                            nums = as_ints(self.const_value(info, kw.value))
                        elif kw.arg == "donate_argnames":
                            names = as_strs(self.const_value(info, kw.value))
                    return (nums, names) if (nums or names) else None
                f = expr.func
                if isinstance(f, ast.Attribute) and f.attr in ("lower",
                                                               "compile"):
                    return spec_of(info, f.value, depth + 1)
                return None
            if isinstance(expr, ast.Attribute):
                if expr.attr in ("lower", "compile"):
                    return spec_of(info, expr.value, depth + 1)
                dn = dotted_name(expr)
                if dn is not None and "." in dn:
                    got = self.resolve_symbol(info, dn)
                    if got is not None:
                        mod, sym = got
                        return exports[mod.relpath].get(sym)
            return None

        with _timed():
            # pass 1: direct module-level donor bindings
            for info in self.modules.values():
                if info.tree is None:
                    continue
                for name, expr in info.constants.items():
                    spec = spec_of(info, expr)
                    if spec is not None:
                        exports[info.relpath][name] = spec
            # pass 2 (fixpoint): imported donors + derived donor functions —
            # a module-level fn forwarding its own params into a donated
            # position exports those positions, across module boundaries
            changed = True
            guard = 0
            while changed and guard < 10:
                changed = False
                guard += 1
                for info in self.modules.values():
                    if info.tree is None:
                        continue
                    for name, expr in info.constants.items():
                        if name in exports[info.relpath]:
                            continue
                        spec = spec_of(info, expr)
                        if spec is not None:
                            exports[info.relpath][name] = spec
                            changed = True
                    for fname, fn in info.defs.items():
                        a = fn.args
                        ordered = [p.arg for p in
                                   list(a.posonlyargs) + list(a.args)]
                        nums: Set[int] = set()
                        old = exports[info.relpath].get(fname)
                        if old:
                            nums.update(old[0])
                        for node in ast.walk(fn):
                            if not isinstance(node, ast.Call):
                                continue
                            spec = spec_of(info, node.func)
                            if spec is None:
                                continue
                            for i, arg in enumerate(node.args):
                                if i in spec[0] and isinstance(arg, ast.Name) \
                                        and arg.id in ordered:
                                    nums.add(ordered.index(arg.id))
                            for kw in node.keywords:
                                if kw.arg in spec[1] \
                                        and isinstance(kw.value, ast.Name) \
                                        and kw.value.id in ordered:
                                    nums.add(ordered.index(kw.value.id))
                        if nums:
                            new = (tuple(sorted(nums)),
                                   old[1] if old else ())
                            if new != old:
                                exports[info.relpath][fname] = new
                                changed = True
        self._donor_exports = exports
        return exports

    # -- interprocedural summaries (v4, PL015–PL018) --------------------------
    def summaries(self) -> "ProgramSummaries":
        """Program-wide join of the per-module function summaries (built
        lazily on first use, cached for the run)."""
        if self._summaries is None:
            self._summaries = ProgramSummaries(self)
        return self._summaries

    def extra_roots(self, relpath: str, base: JitIndex
                    ) -> List[Tuple[FunctionNode, Set[str]]]:
        """Traced functions of ``relpath`` the per-module ``base`` index does
        not already walk (not jitted there, not nested under a base root or
        an earlier extra root)."""
        covered: Set[int] = set()
        for root, _ in base.roots:
            covered.update(id(n) for n in ast.walk(root))
        extras: List[Tuple[FunctionNode, Set[str]]] = []
        for fn, params in self.traced_in(relpath):
            if base.is_jitted(fn) or id(fn) in covered:
                continue
            extras.append((fn, params))
            covered.update(id(n) for n in ast.walk(fn))
        return extras


# -- program-wide summary fixpoints (v4) --------------------------------------

# an escape fact: (class key "relpath::Class", protected attr, lock attr)
EscapeFact = Tuple[str, str, str]
# a lock-order edge witness: (relpath, function name, AST site)
LockWitness = Tuple[str, str, ast.AST]


# method names of the builtin containers/strings: a call spelled with one
# of these is near-certainly a dict/list/set/str operation, not a program
# def, whatever unique name the program happens to hold
_BUILTIN_METHOD_NAMES = frozenset(
    m for t in (dict, list, set, tuple, str, bytes)
    for m in dir(t) if not m.startswith("__"))


class ProgramSummaries:
    """Join of the per-module ``dataflow.ModuleSummaries`` across the
    program call graph.  Three fixpoints:

      * **escapes** — which lock-protected ``self.<attr>`` objects a
        function's return value may alias, closed over ``return f(...)``
        chains so an accessor-of-an-accessor still leaks (PL016);
      * **return ranks** — definite array rank of return values, closed
        over single-call return chains (PL017);
      * **lock-order graph** — directed edges ``outer -> inner`` from
        direct lexical nesting AND from calls made while holding a lock
        into the callee's transitive acquisitions; strongly-connected
        components of size >= 2 are deadlock cycles (PL018).  Reentrant
        RLock self-nesting never forms an edge (self-edges are dropped),
        and lock identity is class-level, so a cycle here means two code
        paths take the same two locks in opposite orders somewhere.
    """

    def __init__(self, index: ProgramIndex):
        from photon_ml_tpu.analysis.dataflow import (_timed_summary,
                                                     cached_module_summaries)

        self.index = index
        self.mod: Dict[str, "ModuleSummaries"] = {}
        # id(fn) -> (owning ModuleInfo, its FunctionSummary)
        self._owner: Dict[int, Tuple[ModuleInfo, object]] = {}
        for relpath, info in index.modules.items():
            # digest-keyed summary reuse: a module whose source (and
            # therefore, via the index's parse cache, whose TREE object)
            # is unchanged since the last run in this process skips the
            # whole per-function summary pass — the id(fn) keys stay
            # valid because the tree is the same object
            ms = cached_module_summaries(info.tree, relpath, info.digest)
            self.mod[relpath] = ms
            for fid, summ in ms.by_id.items():
                self._owner[fid] = (info, summ)
        with _timed_summary():
            # program-wide def-name census (for the cautious unique-by-name
            # fallback PL016 uses on non-self attribute calls)
            self._name_count: Dict[str, int] = {}
            for info in index.modules.values():
                for name, fns in info.defs_by_name.items():
                    self._name_count[name] = (self._name_count.get(name, 0)
                                              + len(fns))
            self.escapes: Dict[int, frozenset] = self._fix_escapes()
            self._ranks: Dict[int, Optional[int]] = self._fix_ranks()
            self.lock_edges: Dict[Tuple[str, str], LockWitness] = {}
            self.lock_cycles: List[Tuple[Tuple[str, ...],
                                         Dict[Tuple[str, str],
                                              LockWitness]]] = []
            self._build_lock_graph()

    # -- shared resolution ----------------------------------------------------
    def _resolve_call(self, info: ModuleInfo,
                      func: ast.AST) -> Optional[int]:
        got = self.index._resolve_callee(info, func)
        if got is None:
            return None
        fid = id(got[1])
        return fid if fid in self._owner else None

    # -- escape fixpoint ------------------------------------------------------
    def _fix_escapes(self) -> Dict[int, frozenset]:
        esc: Dict[int, frozenset] = {}
        for fid, (info, s) in self._owner.items():
            if s.cls is None or not s.return_attrs:
                continue
            ms = self.mod[info.relpath]
            hits = s.return_attrs & ms.locked_attrs_of(s.cls)
            if hits:
                # immutable-valued attrs cannot be mutated through the
                # alias — classified lazily, only when a hit exists
                hits -= ms.immutable_attrs_of(s.cls)
            if hits:
                lock = ms.lock_display.get(s.cls, "_lock")
                key = f"{info.relpath}::{s.cls}"
                esc[fid] = frozenset((key, a, lock) for a in hits)
        changed, guard = True, 0
        while changed and guard < 12:
            changed, guard = False, guard + 1
            for fid, (info, s) in self._owner.items():
                if not s.return_calls:
                    continue
                cur = esc.get(fid, frozenset())
                new = cur
                for call in s.return_calls:
                    callee = self._resolve_call(info, call.func)
                    if callee is not None:
                        new = new | esc.get(callee, frozenset())
                if new != cur:
                    esc[fid] = new
                    changed = True
        return esc

    def escape_facts(self, fn: ast.AST) -> frozenset:
        """Escape facts of a function node (empty when it leaks nothing)."""
        return self.escapes.get(id(fn), frozenset())

    def resolve_escape_source(self, relpath: str, expr: ast.AST
                              ) -> Optional[Tuple[frozenset, str]]:
        """Escape facts of the function a VALUE expression was produced by:
        ``store.table()`` / ``self.hot()`` calls, or a bare attribute access
        hitting a @property.  Unresolvable receivers fall back to a
        program-wide unique-name match — only when exactly ONE def in the
        whole program carries that name, so the match cannot be wrong.
        Returns (facts, display name of the source) or None."""
        info = self.index.modules.get(relpath)
        if info is None:
            return None
        if isinstance(expr, ast.Call):
            fid = self._resolve_call(info, expr.func)
            if fid is None and isinstance(expr.func, ast.Attribute):
                fid = self._unique_by_name(expr.func.attr)
            if fid is not None and self.escapes.get(fid):
                _, s = self._owner[fid]
                return self.escapes[fid], self._display(fid)
            return None
        if isinstance(expr, ast.Attribute) \
                and not (isinstance(expr.value, ast.Name)
                         and expr.value.id == "self"):
            fid = self._unique_by_name(expr.attr)
            if fid is not None:
                _, s = self._owner[fid]
                if s.is_property and self.escapes.get(fid):
                    return self.escapes[fid], self._display(fid)
        return None

    def _unique_by_name(self, name: str) -> Optional[int]:
        if self._name_count.get(name) != 1:
            return None
        for fid, (info, s) in self._owner.items():
            if s.name == name:
                return fid
        return None

    def _display(self, fid: int) -> str:
        info, s = self._owner[fid]
        qual = f"{s.cls}.{s.name}" if s.cls else s.name
        return f"{qual} ({info.relpath})"

    # -- return-rank fixpoint -------------------------------------------------
    def _fix_ranks(self) -> Dict[int, Optional[int]]:
        ranks: Dict[int, Optional[int]] = {
            fid: s.return_rank for fid, (_, s) in self._owner.items()}
        changed, guard = True, 0
        while changed and guard < 12:
            changed, guard = False, guard + 1
            for fid, (info, s) in self._owner.items():
                if ranks.get(fid) is not None or s.return_rank_call is None:
                    continue
                callee = self._resolve_call(info, s.return_rank_call.func)
                if callee is not None and ranks.get(callee) is not None:
                    ranks[fid] = ranks[callee]
                    changed = True
        return ranks

    def call_rank(self, relpath: str, call: ast.Call) -> Optional[int]:
        """Definite return rank of a call expression, through the summary
        fixpoint (None when the callee or its rank is unknown)."""
        info = self.index.modules.get(relpath)
        if info is None:
            return None
        fid = self._resolve_call(info, call.func)
        return self._ranks.get(fid) if fid is not None else None

    # -- lock-order graph -----------------------------------------------------
    def _resolve_lock_call(self, info: ModuleInfo,
                           func: ast.AST) -> Optional[int]:
        """``_resolve_call`` plus a cautious unique-by-name fallback for
        method calls through an object attribute (``self.beta.grab()``) —
        the shape cross-object lock nesting actually takes in the serving
        plane.  Two guards keep the fallback honest: builtin-container/str
        method names never match (``dropped.append`` must not resolve to a
        class's own ``append``), and a chain rooted at an imported module
        alias never matches (``os.remove`` is not a method call).  A unique
        program-wide def name past both guards cannot mis-resolve; anything
        ambiguous stays unresolved and forms no edge."""
        fid = self._resolve_call(info, func)
        if fid is not None or not isinstance(func, ast.Attribute):
            return fid
        if func.attr in _BUILTIN_METHOD_NAMES:
            return None
        node: ast.AST = func.value
        while isinstance(node, (ast.Attribute, ast.Subscript)):
            node = node.value
        if isinstance(node, ast.Name) and node.id in info.imports:
            return None
        return self._unique_by_name(func.attr)

    def _transitive_acquires(self, fid: int, memo: Dict[int, Set[str]],
                             seen: Set[int]) -> Set[str]:
        got = memo.get(fid)
        if got is not None:
            return got
        if fid in seen:  # call cycle — contribute what is known so far
            return set()
        seen.add(fid)
        info, s = self._owner[fid]
        acc: Set[str] = set(s.lock_acquires)
        for call in s.calls:
            callee = self._resolve_lock_call(info, call.func)
            if callee is not None:
                acc |= self._transitive_acquires(callee, memo, seen)
        memo[fid] = acc
        return acc

    def _build_lock_graph(self) -> None:
        edges = self.lock_edges
        memo: Dict[int, Set[str]] = {}
        for fid, (info, s) in self._owner.items():
            for outer, inner, site in s.lock_pairs:
                if outer != inner:
                    edges.setdefault((outer, inner),
                                     (info.relpath, s.name, site))
            for outer, call in s.held_calls:
                callee = self._resolve_lock_call(info, call.func)
                if callee is None:
                    continue
                for inner in self._transitive_acquires(callee, memo, set()):
                    if inner != outer:
                        edges.setdefault((outer, inner),
                                         (info.relpath, s.name, call))
        # Tarjan SCC over the key graph; every SCC with >= 2 nodes is a
        # deadlock cycle (self-edges were never added)
        adj: Dict[str, List[str]] = {}
        for (a, b) in edges:
            adj.setdefault(a, []).append(b)
            adj.setdefault(b, [])
        index_of: Dict[str, int] = {}
        low: Dict[str, int] = {}
        on_stack: Set[str] = set()
        stack: List[str] = []
        counter = [0]
        sccs: List[List[str]] = []

        def strongconnect(v: str) -> None:
            work = [(v, iter(adj[v]))]
            index_of[v] = low[v] = counter[0]
            counter[0] += 1
            stack.append(v)
            on_stack.add(v)
            while work:
                node, it = work[-1]
                advanced = False
                for w in it:
                    if w not in index_of:
                        index_of[w] = low[w] = counter[0]
                        counter[0] += 1
                        stack.append(w)
                        on_stack.add(w)
                        work.append((w, iter(adj[w])))
                        advanced = True
                        break
                    if w in on_stack:
                        low[node] = min(low[node], index_of[w])
                if advanced:
                    continue
                work.pop()
                if work:
                    parent = work[-1][0]
                    low[parent] = min(low[parent], low[node])
                if low[node] == index_of[node]:
                    comp: List[str] = []
                    while True:
                        w = stack.pop()
                        on_stack.discard(w)
                        comp.append(w)
                        if w == node:
                            break
                    if len(comp) >= 2:
                        sccs.append(comp)

        for v in sorted(adj):
            if v not in index_of:
                strongconnect(v)
        for comp in sccs:
            keys = tuple(sorted(comp))
            members = set(comp)
            cyc_edges = {e: w for e, w in edges.items()
                         if e[0] in members and e[1] in members}
            self.lock_cycles.append((keys, cyc_edges))
        self.lock_cycles.sort(key=lambda c: c[0])
