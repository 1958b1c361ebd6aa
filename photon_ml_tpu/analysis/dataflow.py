"""Per-function forward dataflow + call-graph reachability for photonlint.

The v1/v2 rules are lexical: PL005 only sees mutations spelled ``self.X``,
PL007 only sees collectives lexically inside a shard_map target, and nothing
at all connects an ``async def`` body to the synchronous helpers it calls.
The concurrency and distributed-protocol rules (PL011–PL014, PL005v2) need
two things the lexical passes cannot answer:

  1. **"what does this name alias here?"** — a per-function forward dataflow
     over a CFG lowered from the AST (branches, loops run to convergence,
     try/except/finally with per-statement exception edges).  The abstract
     state maps each local name to (a) the set of ``self.<attr>`` objects it
     may alias and (b) the line numbers of the reaching definitions.
     ``a = self._store; b = a`` makes both ``a`` and ``b`` aliases of
     ``_store``; ``self._x = buf`` makes ``buf`` an alias of ``_x``; any
     other assignment kills.  Joins are set unions, so the analysis is
     monotone and the loop fixpoint terminates.

  2. **"is this call reachable from an async body / a jit root / a
     lock-held region?"** — a module-local call graph (``Name`` → module
     def, unique by-name fallback; ``self.method`` → unique method, the
     same convention ``ProgramIndex._resolve_callee`` uses) with seeded
     reachability: event-loop seeds are every ``async def`` plus the
     callback targets of ``loop.call_soon[_threadsafe]/call_later/call_at``;
     lock seeds are the callees invoked inside ``with self.<lock>:`` blocks;
     jit reachability reuses the (program-augmented) ``JitIndex`` walk.
     Propagation follows only real ``Call`` nodes — a function REFERENCE
     handed to ``run_in_executor``/``to_thread``/``Thread(target=...)`` is
     not a call, so executor hand-offs are exempt by construction.

Everything here is best-effort and conservative in the same direction as
the rest of the analysis stack: unresolvable facts contribute nothing, so
dataflow can only ADD precision, never invent phantom findings.  The time
spent in this module is accounted separately (``reset_cost``/
``cost_seconds``) so ``tools/photonlint.py`` can report the dataflow pass
cost next to the ProgramIndex build.
"""

from __future__ import annotations

import ast
import dataclasses
import time
from typing import (Dict, FrozenSet, Iterable, Iterator, List, Optional,
                    Sequence, Set, Tuple)

from photon_ml_tpu.analysis.jit_index import FunctionNode, dotted_name

# -- cost accounting ---------------------------------------------------------

_COST = {"s": 0.0, "summary_s": 0.0, "summary_cached": 0}


def reset_cost() -> None:
    _COST["s"] = 0.0
    _COST["summary_s"] = 0.0
    _COST["summary_cached"] = 0


def cost_seconds() -> float:
    return _COST["s"]


def summary_seconds() -> float:
    """Time spent computing interprocedural function summaries (v4),
    reported as ``summaries_s`` next to ``dataflow_s``."""
    return _COST["summary_s"]


def summaries_cached_count() -> int:
    """Modules whose summary pass was skipped this run because the
    digest-keyed cache held them (``summaries_cached`` in BENCH_LINT)."""
    return int(_COST["summary_cached"])


class _timed:
    """Context manager accumulating wall time into the dataflow cost."""

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        _COST["s"] += time.perf_counter() - self._t0
        return False


class _timed_summary:
    """Accumulate wall time into the SUMMARY cost.  FunctionFlow fixpoints
    built while summarising self-report into the dataflow cost; their share
    is subtracted here so ``dataflow_s`` and ``summaries_s`` never double-
    count the same second."""

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._d0 = _COST["s"]
        return self

    def __exit__(self, *exc):
        spent = time.perf_counter() - self._t0
        nested = _COST["s"] - self._d0
        _COST["summary_s"] += max(spent - nested, 0.0)
        return False


# -- abstract state ----------------------------------------------------------
# name -> (frozenset of aliased self-attrs, frozenset of reaching-def lines)
VarFact = Tuple[FrozenSet[str], FrozenSet[int]]
AliasState = Dict[str, VarFact]

_EMPTY: FrozenSet = frozenset()


def _self_attr(node: ast.AST) -> Optional[str]:
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name) and node.value.id == "self"):
        return node.attr
    return None


def _value_aliases(state: AliasState, expr: ast.AST,
                   depth: int = 0) -> FrozenSet[str]:
    """Self-attrs the VALUE expression may alias under ``state``."""
    if depth > 6 or expr is None:
        return _EMPTY
    if isinstance(expr, ast.Name):
        return state.get(expr.id, (_EMPTY, _EMPTY))[0]
    attr = _self_attr(expr)
    if attr is not None:
        return frozenset((attr,))
    if isinstance(expr, ast.IfExp):
        return (_value_aliases(state, expr.body, depth + 1)
                | _value_aliases(state, expr.orelse, depth + 1))
    if isinstance(expr, ast.NamedExpr):
        return _value_aliases(state, expr.value, depth + 1)
    return _EMPTY


def _kill_target(new: AliasState, tgt: ast.AST, line: int) -> None:
    for sub in ast.walk(tgt):
        if isinstance(sub, ast.Name):
            new[sub.id] = (_EMPTY, frozenset((line,)))


def _apply_assign(new: AliasState, old: AliasState, tgt: ast.AST,
                  value: Optional[ast.AST], line: int) -> None:
    if isinstance(tgt, ast.Name):
        aliases = _value_aliases(old, value) if value is not None else _EMPTY
        new[tgt.id] = (aliases, frozenset((line,)))
    elif isinstance(tgt, (ast.Tuple, ast.List)):
        elts = tgt.elts
        if (isinstance(value, (ast.Tuple, ast.List))
                and len(value.elts) == len(elts)
                and not any(isinstance(e, ast.Starred) for e in elts)):
            for t, v in zip(elts, value.elts):
                _apply_assign(new, old, t, v, line)
        else:
            _kill_target(new, tgt, line)
    elif isinstance(tgt, ast.Starred):
        _kill_target(new, tgt.value, line)
    else:
        # attribute/subscript target: binds no local — but `self.X = name`
        # makes `name` an alias of X from here on (the object is shared)
        attr = _self_attr(tgt)
        if attr is not None and isinstance(value, ast.Name):
            aliases, defs = new.get(value.id, (_EMPTY, _EMPTY))
            new[value.id] = (aliases | {attr}, defs)


def _header_exprs(stmt: ast.AST) -> List[ast.AST]:
    """Expressions evaluated AT a CFG node for a compound statement (its
    body statements are separate CFG nodes)."""
    if isinstance(stmt, (ast.If, ast.While)):
        return [stmt.test]
    if isinstance(stmt, (ast.For, ast.AsyncFor)):
        return [stmt.iter]
    if isinstance(stmt, (ast.With, ast.AsyncWith)):
        return [item.context_expr for item in stmt.items]
    if isinstance(stmt, ast.Try) or stmt.__class__.__name__ == "TryStar":
        return []
    if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef,
                         ast.ExceptHandler)):
        return []
    return [stmt]  # simple statement: whole subtree


def _transfer(state: AliasState, stmt: ast.AST) -> AliasState:
    new = dict(state)
    line = getattr(stmt, "lineno", 0)
    if isinstance(stmt, ast.Assign):
        for tgt in stmt.targets:
            _apply_assign(new, state, tgt, stmt.value, line)
    elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
        _apply_assign(new, state, stmt.target, stmt.value, line)
    elif isinstance(stmt, ast.AugAssign):
        if isinstance(stmt.target, ast.Name):
            # x += v rebinds x for immutables; conservatively drop aliases
            _kill_target(new, stmt.target, line)
    elif isinstance(stmt, (ast.For, ast.AsyncFor)):
        _kill_target(new, stmt.target, line)
    elif isinstance(stmt, (ast.With, ast.AsyncWith)):
        for item in stmt.items:
            if item.optional_vars is not None:
                # `with self._lock as l:` — l aliases the context object
                _apply_assign(new, state, item.optional_vars,
                              item.context_expr, line)
    elif isinstance(stmt, ast.ExceptHandler):
        if stmt.name:
            new[stmt.name] = (_EMPTY, frozenset((line,)))
    elif isinstance(stmt, (ast.Import, ast.ImportFrom)):
        for alias in stmt.names:
            bound = alias.asname or alias.name.split(".")[0]
            new[bound] = (_EMPTY, frozenset((line,)))
    elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.ClassDef)):
        new[stmt.name] = (_EMPTY, frozenset((line,)))
    elif isinstance(stmt, ast.Delete):
        for tgt in stmt.targets:
            if isinstance(tgt, ast.Name):
                new[tgt.id] = (_EMPTY, frozenset((line,)))
    # walrus bindings in the expressions this node evaluates
    for expr in _header_exprs(stmt):
        for sub in ast.walk(expr):
            if isinstance(sub, ast.NamedExpr) \
                    and isinstance(sub.target, ast.Name):
                new[sub.target.id] = (_value_aliases(state, sub.value),
                                      frozenset((getattr(sub, "lineno",
                                                         line),)))
    return new


def _join(states: Iterable[AliasState]) -> AliasState:
    out: AliasState = {}
    for st in states:
        for name, (aliases, defs) in st.items():
            if name in out:
                a0, d0 = out[name]
                out[name] = (a0 | aliases, d0 | defs)
            else:
                out[name] = (aliases, defs)
    return out


# -- CFG ---------------------------------------------------------------------

class _Loop:
    __slots__ = ("header", "breaks")

    def __init__(self, header: int):
        self.header = header
        self.breaks: Set[int] = set()


class _CFG:
    """Statement-level control-flow graph of one function body.  Each
    statement (and each ``except`` handler head) is one node; edges follow
    branch/loop/try structure, with per-statement exception edges from try
    bodies to their handlers."""

    def __init__(self, body: Sequence[ast.stmt]):
        self.stmts: List[ast.AST] = []
        self.succ: List[Set[int]] = []
        self._seq(body, frontier=set(), loops=[], handlers=[])

    def _add(self, stmt: ast.AST) -> int:
        self.stmts.append(stmt)
        self.succ.append(set())
        return len(self.stmts) - 1

    def _seq(self, body: Sequence[ast.stmt], frontier: Set[int],
             loops: List[_Loop], handlers: List[int]) -> Set[int]:
        for stmt in body:
            idx = self._add(stmt)
            for f in frontier:
                self.succ[f].add(idx)
            for h in handlers:
                self.succ[idx].add(h)  # an exception may fire mid-statement
            frontier = self._stmt(stmt, idx, loops, handlers)
        return frontier

    def _stmt(self, stmt: ast.AST, idx: int, loops: List[_Loop],
              handlers: List[int]) -> Set[int]:
        if isinstance(stmt, (ast.Return, ast.Raise)):
            return set()
        if isinstance(stmt, ast.Break):
            if loops:
                loops[-1].breaks.add(idx)
            return set()
        if isinstance(stmt, ast.Continue):
            if loops:
                self.succ[idx].add(loops[-1].header)
            return set()
        if isinstance(stmt, ast.If):
            f_then = self._seq(stmt.body, {idx}, loops, handlers)
            f_else = (self._seq(stmt.orelse, {idx}, loops, handlers)
                      if stmt.orelse else {idx})
            return f_then | f_else
        if isinstance(stmt, (ast.While, ast.For, ast.AsyncFor)):
            loop = _Loop(header=idx)
            f_body = self._seq(stmt.body, {idx}, loops + [loop], handlers)
            for f in f_body:
                self.succ[f].add(idx)  # back edge — fixpoint converges it
            f_exit = (self._seq(stmt.orelse, {idx}, loops, handlers)
                      if stmt.orelse else {idx})
            return f_exit | loop.breaks
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            return self._seq(stmt.body, {idx}, loops, handlers)
        if isinstance(stmt, ast.Try) \
                or stmt.__class__.__name__ == "TryStar":
            region_lo = len(self.stmts)
            h_idx = [self._add(h) for h in stmt.handlers]
            for h in h_idx:
                self.succ[idx].add(h)
            f_body = self._seq(stmt.body, {idx}, loops, handlers + h_idx)
            f_handlers: Set[int] = set()
            for h, hi in zip(stmt.handlers, h_idx):
                f_handlers |= self._seq(h.body, {hi}, loops, handlers)
            f_else = (self._seq(stmt.orelse, f_body, loops, handlers)
                      if stmt.orelse else f_body)
            after = f_else | f_handlers
            if stmt.finalbody:
                # the finally runs whether or not the protected region
                # completed: feed it the Try head (pre-body state, for an
                # exception before the first assignment lands) and every
                # statement lowered in the region (mid-region exceptions),
                # not just the normal-completion frontier
                region = set(range(region_lo, len(self.stmts)))
                after = self._seq(stmt.finalbody, after | {idx} | region,
                                  loops, handlers)
            return after
        return {idx}


# -- per-function flow -------------------------------------------------------

class FunctionFlow:
    """Alias-set + reaching-definition facts for one function, queryable at
    any AST node inside it."""

    def __init__(self, fn: FunctionNode):
        with _timed():
            self.fn = fn
            if isinstance(fn, ast.Lambda):
                body: List[ast.stmt] = [ast.Expr(value=fn.body)]
            else:
                body = list(fn.body)
            self._cfg = _CFG(body)
            self._in: List[AliasState] = []
            self._fixpoint()
            # any node -> index of its (innermost) CFG statement.  Nodes are
            # visited in CFG order; inner statements were added after their
            # enclosing compound, so later writes win = innermost wins.
            self._stmt_of: Dict[int, int] = {}
            for i, s in enumerate(self._cfg.stmts):
                for sub in ast.walk(s):
                    self._stmt_of[id(sub)] = i

    def _entry_state(self) -> AliasState:
        a = getattr(self.fn, "args", None)
        state: AliasState = {}
        if a is None:
            return state
        line = getattr(self.fn, "lineno", 0)
        params = list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)
        if a.vararg:
            params.append(a.vararg)
        if a.kwarg:
            params.append(a.kwarg)
        for p in params:
            state[p.arg] = (_EMPTY, frozenset((line,)))
        return state

    def _fixpoint(self) -> None:
        cfg = self._cfg
        n = len(cfg.stmts)
        preds: List[List[int]] = [[] for _ in range(n)]
        for i, succs in enumerate(cfg.succ):
            for j in succs:
                preds[j].append(i)
        entry = self._entry_state()
        self._in = [{} for _ in range(n)]
        out: List[Optional[AliasState]] = [None] * n
        work: List[int] = list(range(n))
        guard = 0
        while work:
            guard += 1
            if guard > 50 * (n + 1):  # safety valve; cannot trip for
                break                 # monotone transfer, kept for hygiene
            i = work.pop(0)
            incoming = [out[p] for p in preds[i] if out[p] is not None]
            state = _join(incoming) if incoming else {}
            if not preds[i]:
                state = dict(entry)
            self._in[i] = state
            new_out = _transfer(state, cfg.stmts[i])
            if new_out != out[i]:
                out[i] = new_out
                for j in sorted(cfg.succ[i]):
                    if j not in work:
                        work.append(j)

    # -- queries -------------------------------------------------------------
    def state_at(self, node: ast.AST) -> AliasState:
        """Abstract state just BEFORE the statement enclosing ``node``
        ({} when the node is not inside this function)."""
        idx = self._stmt_of.get(id(node))
        return self._in[idx] if idx is not None else {}

    def attr_aliases(self, name: str, at: ast.AST) -> FrozenSet[str]:
        """``self.<attr>`` objects the local ``name`` may alias at ``at``."""
        return self.state_at(at).get(name, (_EMPTY, _EMPTY))[0]

    def reaching_defs(self, name: str, at: ast.AST) -> FrozenSet[int]:
        """Line numbers of the definitions of ``name`` reaching ``at``."""
        return self.state_at(at).get(name, (_EMPTY, _EMPTY))[1]


# -- module call graph -------------------------------------------------------

# loop.<scheduler>(callback, ...) — positional index of the callback
_LOOP_SCHEDULERS: Dict[str, int] = {
    "call_soon": 0, "call_soon_threadsafe": 0, "call_later": 1, "call_at": 1,
}
_LOCKISH = ("lock", "cond", "mutex")


def lexical_calls(fn: FunctionNode) -> Iterator[ast.Call]:
    """Call nodes in ``fn``'s own body, excluding nested function/lambda
    bodies (their execution context is their own)."""
    body = fn.body if isinstance(fn.body, list) else [fn.body]
    stack: List[ast.AST] = list(body)
    while stack:
        node = stack.pop()
        if isinstance(node, ast.Call):
            yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def loop_callback_exprs(tree: ast.AST) -> Iterator[ast.expr]:
    """Callback argument expressions of every event-loop scheduling call
    (``call_soon``/``call_soon_threadsafe``/``call_later``/``call_at``) —
    these callbacks RUN ON the loop, so they seed event-loop reachability."""
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)):
            continue
        pos = _LOOP_SCHEDULERS.get(node.func.attr)
        if pos is not None and len(node.args) > pos:
            yield node.args[pos]


def resolve_local_callee(func: ast.AST, defs: Dict[str, FunctionNode],
                         defs_by_name: Dict[str, List[FunctionNode]]
                         ) -> Optional[FunctionNode]:
    """Module-local callee resolution: ``Name`` -> module-level def (unique
    by-name fallback for nested/method helpers), ``self.attr`` -> unique
    method by name.  Mirrors ``ProgramIndex._resolve_callee``."""
    if isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
        return func
    if isinstance(func, ast.Name):
        fn = defs.get(func.id)
        if fn is not None:
            return fn
        cands = defs_by_name.get(func.id)
        if cands is not None and len(cands) == 1:
            return cands[0]
        return None
    if isinstance(func, ast.Attribute) and isinstance(func.value, ast.Name) \
            and func.value.id == "self":
        cands = defs_by_name.get(func.attr)
        if cands is not None and len(cands) == 1:
            return cands[0]
    return None


def _lockish_context(item: ast.withitem) -> bool:
    """Does a ``with`` item look like taking a lock (``self._lock`` /
    ``self.cv`` / a name bound to one — name-based heuristic)?"""
    expr = item.context_expr
    name = dotted_name(expr) or ""
    leaf = name.rpartition(".")[2].lower()
    return any(k in leaf for k in _LOCKISH)


class ModuleCallGraph:
    """Module-local call graph with seeded reachability queries."""

    def __init__(self, tree: Optional[ast.Module]):
        with _timed():
            self.tree = tree
            self.defs: Dict[str, FunctionNode] = {}
            self.defs_by_name: Dict[str, List[FunctionNode]] = {}
            self.fns: List[FunctionNode] = []
            self._edges: Dict[int, List[FunctionNode]] = {}
            if tree is None:
                return
            for node in ast.walk(tree):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    self.fns.append(node)
                    self.defs_by_name.setdefault(node.name, []).append(node)
            for stmt in tree.body:
                if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    self.defs[stmt.name] = stmt

    def resolve(self, func: ast.AST) -> Optional[FunctionNode]:
        return resolve_local_callee(func, self.defs, self.defs_by_name)

    def callees(self, fn: FunctionNode) -> List[FunctionNode]:
        got = self._edges.get(id(fn))
        if got is None:
            got = []
            for call in lexical_calls(fn):
                target = self.resolve(call.func)
                if target is not None:
                    got.append(target)
            self._edges[id(fn)] = got
        return got

    def reachable(self, seeds: Iterable[FunctionNode]) -> Set[int]:
        """ids of every function reachable from ``seeds`` through module-
        local calls (seeds included)."""
        with _timed():
            out: Set[int] = set()
            stack: List[FunctionNode] = []
            for fn in seeds:
                if id(fn) not in out:
                    out.add(id(fn))
                    stack.append(fn)
            while stack:
                fn = stack.pop()
                for callee in self.callees(fn):
                    if id(callee) not in out:
                        out.add(id(callee))
                        stack.append(callee)
            return out

    def event_loop_fns(self) -> Set[int]:
        """ids of functions that run on the asyncio event loop: every
        ``async def``, every scheduled loop callback, and everything they
        transitively CALL.  Hand-offs (``run_in_executor``/``to_thread``/
        ``Thread(target=...)``) pass function references, not calls, so
        they do not propagate — the exemption the rules rely on."""
        if self.tree is None:
            return set()
        seeds: List[FunctionNode] = [fn for fn in self.fns
                                     if isinstance(fn, ast.AsyncFunctionDef)]
        for cb in loop_callback_exprs(self.tree):
            if isinstance(cb, ast.Lambda):
                seeds.append(cb)
                continue
            target = self.resolve(cb)
            if target is not None:
                seeds.append(target)
        return self.reachable(seeds)

    def lock_held_fns(self) -> Set[int]:
        """ids of functions invoked (transitively) from inside a
        ``with self.<lock>:`` region."""
        if self.tree is None:
            return set()
        seeds: List[FunctionNode] = []
        for node in ast.walk(self.tree):
            if not isinstance(node, (ast.With, ast.AsyncWith)):
                continue
            if not any(_lockish_context(i) for i in node.items):
                continue
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call):
                    target = self.resolve(sub.func)
                    if target is not None:
                        seeds.append(target)
        return self.reachable(seeds)


# -- module facade -----------------------------------------------------------

class ModuleDataflow:
    """Lazy per-module dataflow facade exposed as ``ctx.dataflow``: cached
    per-function flows, the module call graph, and reachability sets."""

    def __init__(self, ctx):
        self.ctx = ctx
        self._flows: Dict[int, FunctionFlow] = {}
        self._graph: Optional[ModuleCallGraph] = None
        self._traced_ids: Optional[Set[int]] = None
        self._loop_fns: Optional[Set[int]] = None
        self._lock_fns: Optional[Set[int]] = None

    def function_flow(self, fn: FunctionNode) -> FunctionFlow:
        flow = self._flows.get(id(fn))
        if flow is None:
            flow = FunctionFlow(fn)
            self._flows[id(fn)] = flow
        return flow

    @property
    def call_graph(self) -> ModuleCallGraph:
        if self._graph is None:
            self._graph = ModuleCallGraph(self.ctx.tree)
        return self._graph

    def traced_node_ids(self) -> Set[int]:
        """ids of every AST node that executes under a jit trace (per the
        program-augmented JitIndex) — "reachable from a jit root"."""
        if self._traced_ids is None:
            from photon_ml_tpu.analysis.jit_index import walk_jit_code
            with _timed():
                ids = {id(node) for node, _
                       in walk_jit_code(self.ctx.jit_index)}
                # a helper CALLED from traced code executes under the same
                # trace even though the JitIndex only walks root bodies —
                # close over the module call graph from the jit roots
                graph = self.call_graph
                reach = graph.reachable(
                    fn for fn, _ in self.ctx.jit_index.roots)
                for fn in graph.fns:
                    if id(fn) in reach:
                        for sub in ast.walk(fn):
                            ids.add(id(sub))
                self._traced_ids = ids
        return self._traced_ids

    def event_loop_fns(self) -> Set[int]:
        """ids of functions on the event loop — module-local seeds plus, in
        whole-program mode, functions proven reachable from another
        module's async code by the ProgramIndex."""
        if self._loop_fns is None:
            fns = set(self.call_graph.event_loop_fns())
            program = getattr(self.ctx, "program", None)
            if program is not None:
                fns |= {id(fn) for fn
                        in program.async_reachable_in(self.ctx.relpath)}
            self._loop_fns = fns
        return self._loop_fns

    def lock_held_fns(self) -> Set[int]:
        if self._lock_fns is None:
            self._lock_fns = self.call_graph.lock_held_fns()
        return self._lock_fns


# -- interprocedural summaries (v4) ------------------------------------------
#
# Per-function facts cheap enough to compute once per module and join to a
# program-wide fixpoint through ProgramIndex's call graph (see
# program_index.ProgramSummaries):
#
#   * which lock-protected ``self.<attr>`` objects a return value may alias
#     (``t = self._table; return t`` — through the FunctionFlow alias state),
#   * the definite array rank of the return value where it can be inferred
#     syntactically (shape literals, full reductions, reshape, ...),
#   * which locks the function acquires, in what nesting order, and which
#     calls it makes while holding one.
#
# Lock identity is CLASS-level (``relpath::Class.attr``) — the classic
# static approximation that conflates instances; conservative for the
# deadlock rule because a real per-instance order inversion is a subset of
# the class-level one, and self-edges are excluded to avoid the reentrant /
# multi-instance false positives the approximation would otherwise invent.

LOCK_FACTORIES = {"Lock", "RLock", "Condition", "Semaphore",
                  "BoundedSemaphore"}
MUTATOR_METHODS = {
    "append", "extend", "insert", "add", "update", "setdefault", "pop",
    "popitem", "remove", "discard", "clear", "move_to_end", "appendleft",
    "popleft", "sort", "reverse",
}


def chain_root_attr(expr: ast.AST) -> Optional[str]:
    """Innermost self-attr of an attribute/subscript chain:
    ``self._hot.table[k]`` -> ``"_hot"`` (None when not rooted at self)."""
    node: ast.AST = expr
    first: Optional[str] = None
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        if isinstance(node, ast.Attribute):
            first = node.attr
        node = node.value
    if isinstance(node, ast.Name) and node.id == "self" and first is not None:
        return first
    return None


def attr_chain_root(expr: ast.AST) -> Optional[str]:
    """Like :func:`chain_root_attr` but ATTRIBUTE links only: a subscript
    (``self._base[0]``) reads an *element*, a different object from the
    protected container, so it does not alias the root for escape
    purposes (mutation targets keep the subscript-including walk)."""
    node: ast.AST = expr
    first: Optional[str] = None
    while isinstance(node, ast.Attribute):
        first = node.attr
        node = node.value
    if isinstance(node, ast.Name) and node.id == "self" and first is not None:
        return first
    return None


def class_lock_info(cls: ast.ClassDef
                    ) -> Tuple[Set[str], Dict[str, str], Dict[str, str]]:
    """(lock attr names, canonical map wrapper->base lock for
    ``self._cond = threading.Condition(self._lock)``, factory name by
    canonical attr).  Superset of rules.locks._lock_names: also records
    WHICH factory built each lock so reentrant RLocks can be told apart.
    Memoized on the node — the summary layer and the lock rule both ask
    for the same class."""
    cached = getattr(cls, "_pl_lock_info", None)
    if cached is not None:
        return cached
    names: Set[str] = set()
    canon: Dict[str, str] = {}
    factory_of: Dict[str, str] = {}
    for node in ast.walk(cls):
        if isinstance(node, ast.Assign):
            value_fn = (dotted_name(node.value.func)
                        if isinstance(node.value, ast.Call) else None)
            factory = (value_fn or "").rpartition(".")[2]
            if factory in LOCK_FACTORIES:
                for tgt in node.targets:
                    attr = _self_attr(tgt)
                    if attr is None:
                        continue
                    names.add(attr)
                    factory_of.setdefault(attr, factory)
                    if factory == "Condition" and node.value.args:
                        base = _self_attr(node.value.args[0])
                        if base is not None:
                            canon[attr] = base
        elif isinstance(node, ast.With):
            for item in node.items:
                attr = _self_attr(item.context_expr)
                if attr is not None and _lockish_context(item):
                    names.add(attr)
    # resolve wrapper chains (Condition(wraps) of Condition(wraps) ...)
    def resolve(a: str, depth: int = 0) -> str:
        nxt = canon.get(a)
        return a if nxt is None or depth > 4 else resolve(nxt, depth + 1)
    canon = {a: resolve(a) for a in names}
    cls._pl_lock_info = (names, canon, factory_of)
    return cls._pl_lock_info


def class_locked_attrs(cls: ast.ClassDef, lock_attrs: Set[str]
                       ) -> FrozenSet[str]:
    """self-attrs mutated anywhere in ``cls`` under a ``with self.<lock>:``
    region (syntactic chain roots — the conservative base the alias-escape
    fixpoint grows from)."""
    out: Set[str] = set()
    for node in ast.walk(cls):
        if not isinstance(node, (ast.With, ast.AsyncWith)):
            continue
        if not any((_self_attr(i.context_expr) or "") in lock_attrs
                   for i in node.items):
            continue
        for sub in ast.walk(node):
            roots: List[Optional[str]] = []
            if isinstance(sub, (ast.Assign, ast.AnnAssign)):
                tgts = sub.targets if isinstance(sub, ast.Assign) \
                    else [sub.target]
                roots = [chain_root_attr(t) for t in tgts]
            elif isinstance(sub, ast.AugAssign):
                roots = [chain_root_attr(sub.target)]
            elif isinstance(sub, ast.Delete):
                roots = [chain_root_attr(t) for t in sub.targets]
            elif (isinstance(sub, ast.Call)
                  and isinstance(sub.func, ast.Attribute)
                  and sub.func.attr in MUTATOR_METHODS):
                roots = [chain_root_attr(sub.func)]
            for r in roots:
                if r is not None and r not in lock_attrs:
                    out.add(r)
    return frozenset(out)


_IMMUTABLE_TYPES = {"int", "float", "str", "bool", "bytes", "complex",
                    "frozenset"}
# builtins whose RESULT is immutable regardless of argument types
_IMMUTABLE_CALLS = {"int", "float", "str", "bool", "bytes", "len", "round",
                    "abs", "hash", "ord", "chr", "repr", "format", "id"}
# builtins that return ONE OF their arguments — immutable iff all args are
_ARG_SELECT_CALLS = {"min", "max"}


def immutable_valued_attrs(cls: ast.ClassDef) -> FrozenSet[str]:
    """self-attrs of ``cls`` whose EVERY write assigns a definitely
    immutable value (literal scalars/tuples of immutables, arithmetic over
    them, parameters annotated with immutable types, calls to
    value-constructing builtins).  An alias to such an attr cannot be
    mutated through — so accessor returns of these are not escapes,
    whatever the caller does with them.  Conservative: one unclassifiable
    write (or zero writes) disqualifies the attr."""
    writes: Dict[str, List[bool]] = {}

    def ann_name(a: Optional[ast.AST]) -> Optional[str]:
        # ``int`` / ``typing.Optional[int]`` -> "int" (Optional wrapping
        # keeps immutability — None is immutable too)
        if isinstance(a, ast.Subscript) \
                and (dotted_name(a.value) or "").rpartition(".")[2] \
                == "Optional":
            a = a.slice
        name = dotted_name(a)
        return name.rpartition(".")[2] if name else None

    def immut(expr: ast.AST, ann: Dict[str, Optional[str]],
              attr: str, depth: int = 0) -> bool:
        if depth > 6:
            return False
        if isinstance(expr, ast.Constant):
            return True
        if isinstance(expr, ast.JoinedStr):
            return True
        if isinstance(expr, ast.Compare):
            return True  # result is a bool
        if isinstance(expr, ast.Tuple):
            return all(immut(e, ann, attr, depth + 1) for e in expr.elts)
        if isinstance(expr, ast.Name):
            return ann.get(expr.id) in _IMMUTABLE_TYPES
        if isinstance(expr, ast.Attribute) and _self_attr(expr) == attr:
            return True  # coinductive: self-reference holds if the rest does
        if isinstance(expr, ast.BinOp):
            return immut(expr.left, ann, attr, depth + 1) \
                and immut(expr.right, ann, attr, depth + 1)
        if isinstance(expr, ast.UnaryOp):
            return immut(expr.operand, ann, attr, depth + 1)
        if isinstance(expr, ast.BoolOp):
            return all(immut(v, ann, attr, depth + 1) for v in expr.values)
        if isinstance(expr, ast.IfExp):
            return immut(expr.body, ann, attr, depth + 1) \
                and immut(expr.orelse, ann, attr, depth + 1)
        if isinstance(expr, ast.Call) and isinstance(expr.func, ast.Name):
            if expr.func.id in _IMMUTABLE_CALLS:
                return True
            if expr.func.id in _ARG_SELECT_CALLS:
                return bool(expr.args) and all(
                    immut(a, ann, attr, depth + 1) for a in expr.args)
        return False

    def scan(node: ast.AST, ann: Dict[str, Optional[str]]) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            a = node.args
            ann = {p.arg: ann_name(p.annotation)
                   for p in (a.posonlyargs + a.args + a.kwonlyargs)}
        if isinstance(node, (ast.Assign, ast.AnnAssign)):
            tgts = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for tgt in tgts:
                attr = _self_attr(tgt)
                if attr is None:
                    # ``self._x[k] = v`` / ``self._x.y = v`` prove the
                    # held object mutable
                    root = chain_root_attr(tgt)
                    if root is not None:
                        writes.setdefault(root, []).append(False)
                    continue
                if isinstance(node, ast.AnnAssign) \
                        and ann_name(node.annotation) in _IMMUTABLE_TYPES:
                    writes.setdefault(attr, []).append(True)
                elif node.value is not None:
                    writes.setdefault(attr, []).append(
                        immut(node.value, ann, attr))
        elif isinstance(node, ast.AugAssign):
            attr = _self_attr(node.target)
            if attr is not None:
                # sound only together with the all-writes rule: an
                # immutable RHS augments in place when the attr holds a
                # mutable, but then some plain write already disqualified
                writes.setdefault(attr, []).append(
                    immut(node.value, ann, attr))
        elif isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr in MUTATOR_METHODS:
            root = chain_root_attr(node.func)
            if root is not None:
                writes.setdefault(root, []).append(False)
        for child in ast.iter_child_nodes(node):
            scan(child, ann)

    for stmt in cls.body:
        scan(stmt, {})
    return frozenset(a for a, ws in writes.items() if all(ws))


# -- definite rank inference --------------------------------------------------

_FULL_REDUCERS = {"sum", "mean", "prod", "max", "min", "all", "any",
                  "std", "var"}
_SHAPE_BUILDERS = {"zeros", "ones", "empty", "full"}
_RANK_OF_FIRST_ARG = {"psum", "pmean", "pmax", "pmin", "abs", "exp", "log",
                      "negative", "tanh", "sqrt", "square", "where"}


def _literal_shape_rank(expr: ast.AST) -> Optional[int]:
    if isinstance(expr, (ast.Tuple, ast.List)):
        if any(isinstance(e, ast.Starred) for e in expr.elts):
            return None
        return len(expr.elts)
    if isinstance(expr, ast.Constant) and isinstance(expr.value, int):
        return 1  # zeros(8) -> rank 1
    return None


def infer_rank(expr: Optional[ast.AST],
               env: Optional[Dict[str, Optional[int]]] = None,
               rank_of_call=None, depth: int = 0) -> Optional[int]:
    """Definite array rank of ``expr``, or None when unknown.  Only facts
    that hold regardless of input shapes are reported: literal scalars,
    shape-literal constructors, full (axis-free) reductions, reshape with a
    literal shape, ravel/flatten, rank-preserving elementwise ops, and —
    via the ``rank_of_call`` hook — callee return ranks from the
    interprocedural summary fixpoint."""
    if expr is None or depth > 8:
        return None
    env = env or {}
    if isinstance(expr, ast.Constant):
        return 0 if isinstance(expr.value, (int, float, bool, complex)) \
            else None
    if isinstance(expr, ast.Name):
        return env.get(expr.id)
    if isinstance(expr, ast.NamedExpr):
        return infer_rank(expr.value, env, rank_of_call, depth + 1)
    if isinstance(expr, ast.UnaryOp):
        return infer_rank(expr.operand, env, rank_of_call, depth + 1)
    if isinstance(expr, ast.BinOp):
        l = infer_rank(expr.left, env, rank_of_call, depth + 1)
        r = infer_rank(expr.right, env, rank_of_call, depth + 1)
        if l is not None and r is not None:
            return max(l, r)  # broadcasting
        return None
    if isinstance(expr, ast.IfExp):
        l = infer_rank(expr.body, env, rank_of_call, depth + 1)
        r = infer_rank(expr.orelse, env, rank_of_call, depth + 1)
        return l if l == r else None
    if isinstance(expr, ast.Call):
        terminal = (dotted_name(expr.func) or "").rpartition(".")[2]
        kwnames = {k.arg for k in expr.keywords}
        if terminal == "reshape":
            # x.reshape(a, b) / x.reshape((a, b)) / jnp.reshape(x, shape)
            shape_args = list(expr.args)
            if (len(shape_args) >= 2 and isinstance(expr.func, ast.Attribute)
                    and (dotted_name(expr.func.value) or "")
                    in ("jnp", "np", "numpy", "jax.numpy")):
                shape_args = shape_args[1:]
            if len(shape_args) == 1:
                return _literal_shape_rank(shape_args[0]) \
                    if isinstance(shape_args[0], (ast.Tuple, ast.List)) \
                    else (1 if isinstance(shape_args[0], ast.Constant)
                          and isinstance(shape_args[0].value, int) else None)
            if shape_args and not any(isinstance(a, ast.Starred)
                                      for a in shape_args):
                return len(shape_args)
            return None
        if terminal in _SHAPE_BUILDERS and expr.args:
            return _literal_shape_rank(expr.args[0])
        if terminal in ("ravel", "flatten"):
            return 1
        if terminal in _FULL_REDUCERS and "axis" not in kwnames \
                and "keepdims" not in kwnames:
            if isinstance(expr.func, ast.Attribute) and not expr.args \
                    and isinstance(expr.func.value,
                                   (ast.Name, ast.Attribute, ast.Subscript)):
                return 0  # x.sum() with no axis — full reduction to scalar
            if len(expr.args) == 1 and isinstance(expr.func, ast.Attribute) \
                    and isinstance(expr.func.value, ast.Name):
                return 0  # jnp.sum(x); bare builtin max(x) is a Name func
            return None
        if terminal in _RANK_OF_FIRST_ARG and expr.args:
            return infer_rank(expr.args[0], env, rank_of_call, depth + 1)
        if terminal == "expand_dims" and expr.args:
            base = infer_rank(expr.args[0], env, rank_of_call, depth + 1)
            return None if base is None else base + 1
        if rank_of_call is not None:
            return rank_of_call(expr)
        return None
    return None


def local_rank_env(fn: FunctionNode, rank_of_call=None
                   ) -> Dict[str, Optional[int]]:
    """Name -> definite rank for single-assignment locals of ``fn``,
    computed in source order so chained definitions resolve."""
    counts: Dict[str, int] = {}
    assigns: List[ast.Assign] = []
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            counts[node.targets[0].id] = counts.get(node.targets[0].id,
                                                    0) + 1
            assigns.append(node)
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            tgt = node.target
            if isinstance(tgt, ast.Name):
                counts[tgt.id] = counts.get(tgt.id, 0) + 2
        elif isinstance(node, (ast.For, ast.AsyncFor)):
            for sub in ast.walk(node.target):
                if isinstance(sub, ast.Name):
                    counts[sub.id] = counts.get(sub.id, 0) + 2
    env: Dict[str, Optional[int]] = {}
    for node in sorted(assigns, key=lambda a: a.lineno):
        name = node.targets[0].id
        if counts.get(name, 0) == 1:
            env[name] = infer_rank(node.value, env, rank_of_call)
    return env


# -- per-function summary -----------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FunctionSummary:
    """Interprocedural facts for one function, consumed by the program-wide
    fixpoints in ``program_index.ProgramSummaries``."""
    name: str
    cls: Optional[str]                       # enclosing class name
    is_property: bool
    return_attrs: FrozenSet[str]             # self-attrs the return may alias
    return_attr_sites: Tuple[Tuple[ast.Return, Tuple[str, ...]], ...]
    return_calls: Tuple[ast.Call, ...]       # `return f(...)` forms
    return_rank: Optional[int]               # definite rank of all returns
    return_rank_call: Optional[ast.Call]     # rank == rank of this callee
    lock_acquires: Tuple[str, ...]           # lock keys taken anywhere
    lock_pairs: Tuple[Tuple[str, str, ast.AST], ...]  # (outer, inner, site)
    held_calls: Tuple[Tuple[str, ast.Call], ...]      # calls under a lock
    calls: Tuple[ast.Call, ...]              # all lexical calls


def _is_property(fn: FunctionNode) -> bool:
    for dec in getattr(fn, "decorator_list", []):
        name = (dotted_name(dec) or "").rpartition(".")[2]
        if name in ("property", "cached_property"):
            return True
    return False


# relpath -> (source digest, tree object, ModuleSummaries).  Cross-run
# reuse: summaries key functions by ``id(fn)`` (AST node identity), so a
# hit additionally REQUIRES the caller's tree to be the SAME object the
# cached summaries were computed over — program_index's parse cache
# guarantees that for unchanged sources, and the identity check below
# makes a violated assumption a miss instead of silent corruption.
_SUMMARY_CACHE: Dict[str, Tuple[str, object, "ModuleSummaries"]] = {}


def cached_module_summaries(tree: Optional[ast.Module], relpath: str,
                            digest: Optional[str] = None
                            ) -> "ModuleSummaries":
    """``ModuleSummaries`` with a digest-keyed per-process cache.

    With a ``digest`` (the module source's content hash), an unchanged
    module's whole summary pass is skipped on every build after the first
    — the ``--diff`` fast path, where re-linting a handful of changed
    files no longer re-summarises the rest of the package.  Counted in
    ``summaries_cached_count()``; ``digest=None`` bypasses the cache."""
    if digest is not None:
        hit = _SUMMARY_CACHE.get(relpath)
        if hit is not None and hit[0] == digest and hit[1] is tree:
            _COST["summary_cached"] += 1
            return hit[2]
    ms = ModuleSummaries(tree, relpath)
    if digest is not None and tree is not None:
        _SUMMARY_CACHE[relpath] = (digest, tree, ms)
    return ms


class ModuleSummaries:
    """Per-module summary computation: one ``FunctionSummary`` per def, plus
    the class-level lock/locked-attr tables the summaries key against."""

    def __init__(self, tree: Optional[ast.Module], relpath: str):
        self.relpath = relpath
        self.by_id: Dict[int, FunctionSummary] = {}
        self.fn_of_id: Dict[int, FunctionNode] = {}
        self.lock_attrs: Dict[str, Set[str]] = {}       # class -> lock attrs
        self.lock_canon: Dict[str, Dict[str, str]] = {}
        self.lock_factory: Dict[str, str] = {}          # key -> factory
        self.locked_attrs: Dict[str, FrozenSet[str]] = {}     # lazy cache
        self.immutable_attrs: Dict[str, FrozenSet[str]] = {}  # lazy cache
        self._class_nodes: Dict[str, ast.ClassDef] = {}
        self.lock_display: Dict[str, str] = {}          # class -> main lock
        self.module_locks: Dict[str, str] = {}          # name -> factory
        self._flows: Dict[int, FunctionFlow] = {}
        if tree is None:
            return
        with _timed_summary():
            owned: List[Tuple[FunctionNode, Optional[str]]] = []
            self._enumerate(tree, None, owned)
            for stmt in tree.body:
                if isinstance(stmt, ast.Assign) \
                        and isinstance(stmt.value, ast.Call):
                    factory = (dotted_name(stmt.value.func) or "") \
                        .rpartition(".")[2]
                    if factory in LOCK_FACTORIES:
                        for tgt in stmt.targets:
                            if isinstance(tgt, ast.Name):
                                self.module_locks[tgt.id] = factory
                                self.lock_factory[
                                    f"{relpath}::{tgt.id}"] = factory
            for fn, cls_name in owned:
                self.by_id[id(fn)] = self._summarize(fn, cls_name)
                self.fn_of_id[id(fn)] = fn

    def _enumerate(self, root: ast.AST, cls: Optional[str],
                   out: List[Tuple[FunctionNode, Optional[str]]]) -> None:
        # defs and classes are statements: walking statement lists only
        # (never expression subtrees) finds every one at a fraction of a
        # full-node traversal
        stack: List[Tuple[ast.AST, Optional[str]]] = [(root, cls)]
        while stack:
            node, cls = stack.pop()
            if isinstance(node, ast.ClassDef):
                names, canon, factory_of = class_lock_info(node)
                self.lock_attrs[node.name] = names
                self.lock_canon[node.name] = canon
                canonical = sorted({canon.get(a, a) for a in names})
                if canonical:
                    self.lock_display[node.name] = canonical[0]
                for attr, fac in factory_of.items():
                    key = f"{self.relpath}::{node.name}.{canon.get(attr, attr)}"
                    # a Condition wrapping an RLock is reentrant with it
                    self.lock_factory.setdefault(key, fac)
                self._class_nodes[node.name] = node
                cls = node.name
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.append((node, cls))
            for field in ("handlers", "finalbody", "orelse", "body"):
                for child in reversed(getattr(node, field, ())):
                    stack.append((child, cls))

    def locked_attrs_of(self, cls_name: str) -> FrozenSet[str]:
        """Lazy :func:`class_locked_attrs` — only classes with an
        attr-returning method ever pay for the mutation scan."""
        got = self.locked_attrs.get(cls_name)
        if got is None:
            node = self._class_nodes.get(cls_name)
            got = (class_locked_attrs(node,
                                      self.lock_attrs.get(cls_name, set()))
                   if node is not None else frozenset())
            self.locked_attrs[cls_name] = got
        return got

    def immutable_attrs_of(self, cls_name: str) -> FrozenSet[str]:
        """Lazy :func:`immutable_valued_attrs` — only classes that produce
        an escape hit ever pay for the write classification."""
        got = self.immutable_attrs.get(cls_name)
        if got is None:
            node = self._class_nodes.get(cls_name)
            got = (immutable_valued_attrs(node) if node is not None
                   else frozenset())
            self.immutable_attrs[cls_name] = got
        return got

    def _flow(self, fn: FunctionNode) -> FunctionFlow:
        flow = self._flows.get(id(fn))
        if flow is None:
            flow = FunctionFlow(fn)
            self._flows[id(fn)] = flow
        return flow

    def _lock_key(self, cls_name: Optional[str], attr: str) -> str:
        if cls_name is None:
            return f"{self.relpath}::{attr}"
        canon = self.lock_canon.get(cls_name, {})
        return f"{self.relpath}::{cls_name}.{canon.get(attr, attr)}"

    def _resolve_lock_item(self, item: ast.withitem, fn: FunctionNode,
                           cls_name: Optional[str],
                           may_flow: bool) -> Optional[str]:
        expr = item.context_expr
        attr = _self_attr(expr)
        if attr is not None:
            if cls_name is not None \
                    and attr in self.lock_attrs.get(cls_name, set()):
                return self._lock_key(cls_name, attr)
            return None
        if isinstance(expr, ast.Name):
            if expr.id in self.module_locks:
                return f"{self.relpath}::{expr.id}"
            if may_flow and cls_name is not None and _lockish_context(item):
                aliases = self._flow(fn).attr_aliases(expr.id, expr)
                hits = sorted(aliases & self.lock_attrs.get(cls_name, set()))
                if hits:
                    return self._lock_key(cls_name, hits[0])
        return None

    def _resolve_lock_expr(self, expr: ast.AST,
                           cls_name: Optional[str]) -> Optional[str]:
        """Lock key for the receiver of a bare ``.acquire()``/``.release()``
        — ``self.<lock attr>`` (Condition wrappers canonicalise onto their
        base lock via ``_lock_key``) or a module-level lock name.  No alias
        flow: bare lock calls on a local alias are rare enough that the
        self-attr/module-name forms carry the rule."""
        attr = _self_attr(expr)
        if attr is not None:
            if cls_name is not None \
                    and attr in self.lock_attrs.get(cls_name, set()):
                return self._lock_key(cls_name, attr)
            return None
        if isinstance(expr, ast.Name) and expr.id in self.module_locks:
            return f"{self.relpath}::{expr.id}"
        return None

    def _return_roots(self, expr: ast.AST, fn: FunctionNode,
                      use_flow: bool, depth: int = 0) -> FrozenSet[str]:
        if expr is None or depth > 4:
            return _EMPTY
        root = attr_chain_root(expr)
        if root is not None:
            return frozenset((root,))
        if isinstance(expr, ast.Name) and use_flow:
            return self._flow(fn).attr_aliases(expr.id, expr)
        if isinstance(expr, ast.IfExp):
            return (self._return_roots(expr.body, fn, use_flow, depth + 1)
                    | self._return_roots(expr.orelse, fn, use_flow,
                                         depth + 1))
        if isinstance(expr, ast.NamedExpr):
            return self._return_roots(expr.value, fn, use_flow, depth + 1)
        return _EMPTY

    @staticmethod
    def _mentions_local(expr: ast.AST) -> bool:
        return any(isinstance(n, ast.Name) and n.id != "self"
                   for n in ast.walk(expr))

    def _summarize(self, fn: FunctionNode,
                   cls_name: Optional[str]) -> FunctionSummary:
        # one fused pass over the body collecting everything the summary
        # needs: returns, lexical calls, whether any with-block exists (the
        # expensive held-lock walk only runs when one does), whether any
        # local is bound FROM a self-attr (without one, a returned name
        # cannot alias self state, so no flow is needed), and the
        # single-assignment census the rank env is built from
        returns: List[ast.Return] = []
        fast_calls: List[ast.Call] = []
        rank_assigns: List[ast.Assign] = []
        name_counts: Dict[str, int] = {}
        has_with = False
        has_lock_calls = False
        has_self_src = False

        def _selfish(v: Optional[ast.AST]) -> bool:
            if isinstance(v, ast.IfExp):
                return (attr_chain_root(v.body) is not None
                        or attr_chain_root(v.orelse) is not None)
            if isinstance(v, (ast.Tuple, ast.List)):
                return any(attr_chain_root(e) is not None for e in v.elts)
            return v is not None and attr_chain_root(v) is not None

        stack: List[ast.AST] = list(fn.body)
        while stack:
            node = stack.pop()
            if isinstance(node, ast.Return):
                returns.append(node)
            elif isinstance(node, ast.Call):
                fast_calls.append(node)
                if not has_lock_calls \
                        and isinstance(node.func, ast.Attribute) \
                        and node.func.attr in ("acquire", "release"):
                    has_lock_calls = True
            elif isinstance(node, (ast.With, ast.AsyncWith)):
                has_with = True
            elif isinstance(node, ast.Assign):
                if len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name):
                    nm = node.targets[0].id
                    name_counts[nm] = name_counts.get(nm, 0) + 1
                    rank_assigns.append(node)
                if not has_self_src:
                    has_self_src = _selfish(node.value)
            elif isinstance(node, (ast.AnnAssign, ast.NamedExpr)):
                if isinstance(node.target, ast.Name):
                    name_counts[node.target.id] = \
                        name_counts.get(node.target.id, 0) + 2
                if not has_self_src:
                    has_self_src = _selfish(node.value)
            elif isinstance(node, ast.AugAssign):
                if isinstance(node.target, ast.Name):
                    name_counts[node.target.id] = \
                        name_counts.get(node.target.id, 0) + 2
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                for sub in ast.walk(node.target):
                    if isinstance(sub, ast.Name):
                        name_counts[sub.id] = name_counts.get(sub.id, 0) + 2
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.Lambda)):
                continue
            stack.extend(ast.iter_child_nodes(node))
        returns.sort(key=lambda r: r.lineno)

        # may-alias self-attrs of the return value.  A flow fixpoint is only
        # built when a bare-Name return makes it necessary.
        need_flow = cls_name is not None and has_self_src and any(
            isinstance(r.value, (ast.Name, ast.IfExp)) for r in returns)
        attr_sites: List[Tuple[ast.Return, Tuple[str, ...]]] = []
        all_attrs: Set[str] = set()
        for r in returns:
            if r.value is None:
                continue
            roots = self._return_roots(r.value, fn, need_flow)
            if roots:
                attr_sites.append((r, tuple(sorted(roots))))
                all_attrs |= roots

        return_calls = tuple(r.value for r in returns
                             if isinstance(r.value, ast.Call))

        # definite return rank.  The single-assignment env costs a full
        # fn walk, so it is only built when a return actually mentions a
        # local name — infer_rank consults env for nothing else.
        return_rank: Optional[int] = None
        return_rank_call: Optional[ast.Call] = None
        value_returns = [r for r in returns if r.value is not None]
        if value_returns:
            if len(value_returns) == 1 \
                    and isinstance(value_returns[0].value, ast.Call):
                return_rank_call = value_returns[0].value
            env: Optional[Dict[str, Optional[int]]] = None
            if any(self._mentions_local(r.value) for r in value_returns):
                # the env local_rank_env() would build, from the census the
                # fused pass already collected — no second body walk
                env = {}
                for a in sorted(rank_assigns, key=lambda a: a.lineno):
                    nm = a.targets[0].id
                    if name_counts.get(nm, 0) == 1:
                        env[nm] = infer_rank(a.value, env)
            ranks = [infer_rank(r.value, env) for r in value_returns]
            if all(k is not None for k in ranks) and len(set(ranks)) == 1:
                return_rank = ranks[0]

        # lock walk — only functions with a with-block or a bare
        # acquire()/release() call pay for it.  `bare` is the function-wide
        # document-order stack of locks taken by bare ``.acquire()`` and not
        # yet ``.release()``d: unlike with-blocks the hold outlives the
        # statement, so it participates in every pair/held-call formed after
        # it (branch-insensitive, like the rest of the walk).
        pairs: List[Tuple[str, str, ast.AST]] = []
        held_calls: List[Tuple[str, ast.Call]] = []
        acquires: List[str] = []
        calls: List[ast.Call] = fast_calls
        if has_with or has_lock_calls:
            calls = []
            bare: List[str] = []
            lockish_names = has_with and cls_name is not None and any(
                isinstance(i.context_expr, ast.Name) and _lockish_context(i)
                for n in ast.walk(fn)
                if isinstance(n, (ast.With, ast.AsyncWith))
                for i in n.items)

            def visit(node: ast.AST, held: List[str]) -> None:
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.Lambda)):
                    return
                if isinstance(node, (ast.With, ast.AsyncWith)):
                    got: List[str] = []
                    for item in node.items:
                        visit(item.context_expr, held + got)
                        key = self._resolve_lock_item(item, fn, cls_name,
                                                      lockish_names)
                        if key is not None:
                            if key not in acquires:
                                acquires.append(key)
                            for h in held + bare + got:
                                if h != key:
                                    pairs.append((h, key,
                                                  item.context_expr))
                            got.append(key)
                    for sub in node.body:
                        visit(sub, held + got)
                    return
                if isinstance(node, ast.Call):
                    calls.append(node)
                    for h in held + bare:
                        held_calls.append((h, node))
                    if isinstance(node.func, ast.Attribute) \
                            and node.func.attr in ("acquire", "release"):
                        key = self._resolve_lock_expr(node.func.value,
                                                      cls_name)
                        if key is not None:
                            if node.func.attr == "acquire":
                                if key not in acquires:
                                    acquires.append(key)
                                for h in held + bare:
                                    if h != key:
                                        pairs.append((h, key, node))
                                bare.append(key)
                            else:
                                # release the innermost matching hold
                                for i in range(len(bare) - 1, -1, -1):
                                    if bare[i] == key:
                                        del bare[i]
                                        break
                for child in ast.iter_child_nodes(node):
                    visit(child, held)

            for stmt in fn.body:
                visit(stmt, [])

        return FunctionSummary(
            name=fn.name, cls=cls_name, is_property=_is_property(fn),
            return_attrs=frozenset(all_attrs),
            return_attr_sites=tuple(attr_sites),
            return_calls=return_calls,
            return_rank=return_rank, return_rank_call=return_rank_call,
            lock_acquires=tuple(acquires), lock_pairs=tuple(pairs),
            held_calls=tuple(held_calls), calls=tuple(calls))
