"""Structured tracer: nestable spans into a fixed-size ring buffer.

Photon ML reference counterpart: util/Timed.scala wraps pipeline phases and
logs wall-clock durations — a flat, text-only timeline.  Production serving
needs the question Timed cannot answer: *where inside this request's 2ms did
the time go*, across threads (the async batcher worker, the hot-swap thread,
the scoring caller) and across layers (submit -> flush -> resolve -> AOT
execute).  This tracer records **complete spans** (name, start, duration,
thread, parent span) plus **instant events** (the ``utils/events`` lifecycle
bridge) into a preallocated ring buffer and exports the Chrome
``trace_event`` JSON format, so one Perfetto load shows training sweeps and
serving requests on the same nested timeline.

Concurrency model ("lock-free-ish"): every record claims a slot by bumping
a cursor under a single lock — on the record path the lock protects ONLY
the increment — and then fills the preallocated slot outside the lock.  Two
writers can never share a slot; a reader (the exporter) skips slots whose
sequence stamp says they are mid-write.  Slots are preallocated fixed-arity lists, so steady-
state tracing allocates nothing but the per-span attrs dict.

Disabled cost: call sites go through the module-level ``span()`` /
``instant()`` helpers, which check one boolean and return a shared no-op
context manager.  Tracing is OFF by default; ``enable()`` / ``cli`` flags turn it
on.

Device-accurate timings: wall-clocking a host block around async device
work measures dispatch, not execution (the gap ``utils/logging.py``
documents).  ``span(..., device_sync=True)`` runs a device fence at entry
and exit when tracing is enabled — enqueue a trivial op and block on it, so
on an in-order accelerator stream the span brackets the actual device work.
The fence costs a device round-trip, which is why it is per-span opt-in and
completely absent when tracing is disabled.

Device layers: inside ONE compiled program no host span can exist, so the
program names its layers itself.  ``device_scope(layer, *ids)`` is a
``jax.named_scope("photon.<layer>[.<id>...]")`` — trace-time metadata, free
on the device, always on.  ``hlo_op_table(compiled.as_text())`` reads the
other half of the join off the program's own executable: instruction name
(what a ``jax.profiler`` trace prints on its ``XLA Ops`` line) -> ``op_name``
path with the scopes in it.  The tracer keeps such tables by program name
(``record_device_table``) and exports them under ``otherData`` next to the
spans, so a reader of a device trace can say which layer ``fusion.71`` is.
Enabled spans also open a ``jax.profiler.TraceAnnotation`` of the same name:
they sit in the profiler's own host plane, on its clock.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import re
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional

# slot layout (preallocated lists; indices, not attributes, for write speed)
_SEQ = 0      # claim sequence; -1 while the writer is mid-fill
_NAME = 1
_PHASE = 2    # "X" complete span | "i" instant
_TS = 3       # perf_counter_ns at start
_DUR = 4      # ns
_TID = 5
_SPAN = 6     # span id
_PARENT = 7   # parent span id (0 = root)
_ATTRS = 8
_WIDTH = 9


# -- cross-process trace context (photonpulse) ------------------------------
# One thread-local cell shared by every Tracer instance: the binding is a
# property of the THREAD doing the work (this request, this publish), not of
# whichever ring it records into, so tracer swaps in tests never strand a
# binding.  The cell holds an opaque ``(trace_id, origin_span)`` pair minted
# by ``obs.pulse`` — trace.py only copies it into record attrs, keeping this
# module free of any pulse import.  Cost: one getattr on the ENABLED record
# path; the disabled ``span()`` guard is untouched.
_ctx_local = threading.local()


def current_context():
    """The thread's bound ``(trace_id, origin_span)`` pair, or None."""
    return getattr(_ctx_local, "ctx", None)


def set_context(ctx) -> object:
    """Bind ``ctx`` (or None to unbind) on this thread; returns the previous
    binding so callers can restore it (``obs.pulse.bind`` does)."""
    prev = getattr(_ctx_local, "ctx", None)
    _ctx_local.ctx = ctx
    return prev


# Export metadata: a stable human label for this process ("frontend",
# "owner", "replica") plus a provider hook pulse uses to attach its clock
# offsets without trace.py importing pulse.
_process_label: Optional[str] = None
_export_meta_provider: Optional[Callable[[], dict]] = None


def set_process_label(label: Optional[str]) -> None:
    """Name this process in Chrome exports (``process_name`` metadata)."""
    global _process_label
    _process_label = label


def get_process_label() -> Optional[str]:
    return _process_label


def set_export_meta_provider(provider: Optional[Callable[[], dict]]) -> None:
    """Extra ``otherData`` fields for ``chrome_trace()`` (pulse installs its
    clock-offset table here)."""
    global _export_meta_provider
    _export_meta_provider = provider


def _default_device_fence() -> None:
    """Enqueue a trivial device op and block on it: on an in-order
    accelerator stream this drains previously enqueued work, giving span
    boundaries that bracket device execution instead of dispatch.  Never
    raises — a host without jax initialized just gets wall clock."""
    try:
        import jax
        import jax.numpy as jnp

        (jnp.zeros(()) + 0).block_until_ready()
    except Exception:
        pass


def _profiler_annotation(name: str):
    """An entered ``jax.profiler.TraceAnnotation(name)``, or None in a
    process that never imported jax (nothing could be profiling it; the
    tracer must not be what imports jax there).  Never raises."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        return ann
    except Exception:
        return None


# -- device layers: scopes in the program, op-to-layer tables from it -------
SCOPE_PREFIX = "photon."
_SCOPE_ID = re.compile(r"[^A-Za-z0-9_]")


def _scope_id(i) -> str:
    return _SCOPE_ID.sub("_", str(i))


def device_scope(layer: str, *ids):
    """``with device_scope("update", cid):`` inside traced code: every op
    traced within carries ``photon.update.<cid>`` in its HLO ``op_name``.
    Metadata only — the device code is the same with or without it — so it
    is always on.  The layer vocabulary is PERF.md section 3's; ids are
    sanitised to ``[A-Za-z0-9_]`` ("/" and "." structure the path)."""
    import jax

    return jax.named_scope(".".join(
        (SCOPE_PREFIX + layer, *map(_scope_id, ids))))


_HLO_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = .*?\s([a-z][a-z0-9\-]*)\(")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_HLO_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
# computations that run as part of ONE instruction (a fusion, a reducer)
_HLO_INSIDE = re.compile(r"\b(?:calls|to_apply)=%?([\w.\-]+)")
# computations whose instructions run as ops of their own, and what their
# place in the caller adds to its path
_HLO_CALLED = re.compile(
    r"\b(body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)|\bbranch_computations=\{([^}]*)\}")
_HLO_CALLED_AS = {"body": "/body", "condition": "/cond"}
# never an event of their own on the device's op line
_HLO_NO_EVENT = frozenset(("parameter", "constant", "get-tuple-element",
                           "tuple", "bitcast"))


def hlo_op_table(hlo_text: str) -> Dict[str, str]:
    """Compiled HLO text (``jit(f).lower(...).compile().as_text()``) ->
    ``{instruction name: op_name path}``, names as a device trace prints
    them (no ``%``, no ``ROOT``).  Every instruction that can be an event
    on the trace's op line is in it (fusions, ``while``, ``conditional``,
    ``custom-call``, copies, ...); the insides of fusions and of reducers
    are left out (a fusion runs as ONE op, charged to the scope of the
    instruction that gave it its metadata).  An instruction the compiler
    made itself carries no metadata: it takes the path of the ``while``,
    ``conditional`` or ``call`` whose body it runs in (+ ``/body`` or
    ``/cond``), and ``""`` where there is none (the entry computation)."""
    ops: Dict[str, Dict[str, Optional[str]]] = {}  # computation -> its ops
    inside = set()
    callers: Dict[str, tuple] = {}  # computation -> (caller's, path, as)
    computation = ""
    for line in hlo_text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m is None:
            c = _HLO_COMPUTATION.match(line)
            if c is not None:
                computation = c.group(1)
                ops.setdefault(computation, {})
            continue
        name, opcode = m.groups()
        found = _HLO_OP_NAME.search(line)
        path = found.group(1) if found is not None else None
        if opcode in ("while", "conditional", "call"):
            for role, one, several in _HLO_CALLED.findall(line):
                for called in ([one] if one else
                               re.findall(r"[\w.\-]+", several)):
                    callers[called] = (computation, path,
                                       _HLO_CALLED_AS.get(role, ""))
        else:
            inside.update(_HLO_INSIDE.findall(line))
        if opcode not in _HLO_NO_EVENT:
            ops.setdefault(computation, {})[name] = path

    def inherited(computation: str) -> str:
        caller = callers.get(computation)
        if caller is None:
            return ""
        callers_computation, path, called_as = caller
        base = path or inherited(callers_computation)
        return base + called_as if base else ""

    table: Dict[str, str] = {}
    for computation, its_ops in ops.items():
        if computation in inside:
            continue
        scope = None
        for name, path in its_ops.items():
            if path is None:
                if scope is None:
                    scope = inherited(computation)
                path = scope
            table[name] = path
    return table


_HLO_COLLECTIVES = frozenset(
    kind + half for kind in ("all-reduce", "all-gather", "all-to-all",
                             "reduce-scatter", "collective-permute")
    for half in ("", "-start", "-done"))


def hlo_collectives(hlo_text: str) -> Dict[str, str]:
    """Compiled HLO text -> ``{instruction name: opcode}`` of its collective
    operations, the ``-start`` and ``-done`` halves of asynchronous ones
    among them.  By OPCODE: a name does not tell (the TPU compiler calls a
    ``psum``'s all-reduce ``psum.80``, and gives an all-gather it rewrote
    as an all-reduce no metadata at all)."""
    found: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m is not None and m.group(2) in _HLO_COLLECTIVES:
            found[m.group(1)] = m.group(2)
    return found


@contextlib.contextmanager
def metadata_keyed_compile_cache():
    """Compile inside with JAX's persistent cache keyed on HLO metadata.

    By default the key leaves metadata out, so an executable loaded from a
    cache another tree filled carries THAT tree's ``op_name``s: a table
    read from it would name stale scopes, or none.  Keyed on metadata, the
    executable is this tree's (compiled once more the first time, loaded
    afterwards).  Scoped to the table's own compile: every other program
    of the process keeps the default key and its cached executable."""
    import jax

    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        yield
    finally:
        jax.config.update(flag, before)


class _NoopSpan:
    """Shared do-nothing context manager for the disabled path."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> None:
        pass


_NOOP = _NoopSpan()


class _Span:
    """Active span handle; records the slot on exit."""

    __slots__ = ("_tracer", "_name", "_attrs", "_sync", "_t0", "_id",
                 "_parent", "_ann")

    def __init__(self, tracer: "Tracer", name: str,
                 attrs: Optional[Dict[str, Any]], sync: bool):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs
        self._sync = sync

    def __enter__(self) -> "_Span":
        t = self._tracer
        stack = t._stack()
        self._parent = stack[-1] if stack else 0
        self._id = next(t._ids)
        stack.append(self._id)
        if self._sync:
            t.device_fence()
        # the same span in the profiler's own host plane, on its clock
        self._ann = _profiler_annotation(self._name)
        self._t0 = time.perf_counter_ns()
        return self

    def set(self, **attrs) -> None:
        """Attach attrs learned inside the span (bytes moved, rows)."""
        if self._attrs is None:
            self._attrs = {}
        self._attrs.update(attrs)

    def __exit__(self, *exc) -> bool:
        t = self._tracer
        if self._sync:
            t.device_fence()
        dur = time.perf_counter_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        stack = t._stack()
        if stack and stack[-1] == self._id:
            stack.pop()
        t._record("X", self._name, self._t0, dur, self._id, self._parent,
                  self._attrs)
        return False


class Tracer:
    """Fixed-capacity span recorder (see module docstring).

    ``capacity``: ring slots — the newest ``capacity`` records win; older
    ones are silently overwritten (bounded memory is the contract, not
    completeness).  ``enabled`` gates every record; flipping it never
    invalidates outstanding ``_Span`` handles (they record into the ring,
    which is harmless either way).
    """

    def __init__(self, capacity: int = 8192, enabled: bool = False):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.enabled = bool(enabled)
        self._slots: List[list] = [[0] * _WIDTH for _ in range(self.capacity)]
        self._cursor = 0
        self._cursor_lock = threading.Lock()
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._fence: Callable[[], None] = _default_device_fence
        # tid -> thread name, filled the first time a thread records; export
        # emits these as Chrome "thread_name" metadata so merged timelines
        # show "batcher-worker" instead of a bare ident
        self._thread_names: Dict[int, str] = {}
        # program name ("jit_program") -> hlo_op_table of its executable
        self._device_tables: Dict[str, Dict[str, str]] = {}

    # -- per-thread span stack ---------------------------------------------
    def _stack(self) -> List[int]:
        s = getattr(self._local, "stack", None)
        if s is None:
            s = self._local.stack = []
            self._thread_names[threading.get_ident()] = \
                threading.current_thread().name
        return s

    # -- recording ---------------------------------------------------------
    def span(self, name: str, device_sync: bool = False, **attrs):
        """Nestable timed span; a context manager.  ``device_sync=True``
        fences the device at both edges (see module docstring)."""
        if not self.enabled:
            return _NOOP
        return _Span(self, name, attrs or None, device_sync)

    def instant(self, name: str, **attrs) -> None:
        """One point-in-time event (Chrome phase "i") at the current
        nesting level — the ``utils/events`` lifecycle bridge."""
        if not self.enabled:
            return
        stack = self._stack()
        parent = stack[-1] if stack else 0
        self._record("i", name, time.perf_counter_ns(), 0, next(self._ids),
                     parent, attrs or None)

    def complete(self, name: str, start_ns: int, dur_ns: int,
                 **attrs) -> None:
        """Record a complete span with explicit timing — for work whose
        start and end live in different callbacks (a frontend request
        admitted on one event-loop tick and settled on another), where a
        ``with`` block cannot bracket it."""
        if not self.enabled:
            return
        stack = self._stack()
        parent = stack[-1] if stack else 0
        self._record("X", name, start_ns, dur_ns, next(self._ids), parent,
                     attrs or None)

    def _record(self, phase: str, name: str, ts: int, dur: int,
                span_id: int, parent: int,
                attrs: Optional[Dict[str, Any]]) -> None:
        if not self.enabled:
            return
        ctx = getattr(_ctx_local, "ctx", None)
        if ctx is not None:
            # propagation: stamp the bound trace id (and the origin span on
            # the far side of a wire hop) into this record's attrs
            attrs = dict(attrs) if attrs else {}
            attrs["trace"] = ctx[0]
            if ctx[1]:
                attrs["origin"] = ctx[1]
        with self._cursor_lock:  # held ONLY to claim the slot
            seq = self._cursor
            self._cursor = seq + 1
        slot = self._slots[seq % self.capacity]
        slot[_SEQ] = -1  # mid-write marker: exporter skips torn slots
        slot[_NAME] = name
        slot[_PHASE] = phase
        slot[_TS] = ts
        slot[_DUR] = dur
        slot[_TID] = threading.get_ident()
        slot[_SPAN] = span_id
        slot[_PARENT] = parent
        slot[_ATTRS] = attrs
        slot[_SEQ] = seq + 1  # valid: seq stamps are 1-based, 0 = empty

    def record_device_table(self, program: str,
                            table: Dict[str, str]) -> None:
        """Keep ``program``'s op-to-layer table (``hlo_op_table``) with the
        spans: exported under ``otherData`` and read by the benchmark's
        layer readers.  A re-recorded program replaces its table."""
        if self.enabled:
            with self._cursor_lock:
                self._device_tables[program] = table

    def device_tables(self) -> Dict[str, Dict[str, str]]:
        with self._cursor_lock:
            return dict(self._device_tables)

    def device_fence(self) -> None:
        self._fence()

    def set_device_fence(self, fence: Callable[[], None]) -> None:
        """Override the ``device_sync=True`` fence (tests, exotic
        backends)."""
        self._fence = fence

    # -- control -----------------------------------------------------------
    def enable(self) -> "Tracer":
        self.enabled = True
        return self

    def disable(self) -> "Tracer":
        self.enabled = False
        return self

    def clear(self) -> None:
        with self._cursor_lock:
            self._cursor = 0
            self._device_tables = {}
        for slot in self._slots:
            slot[_SEQ] = 0

    # -- export ------------------------------------------------------------
    def records(self) -> List[dict]:
        """Valid ring records, oldest first.  Skips empty and mid-write
        slots; the window is the last ``capacity`` claims."""
        with self._cursor_lock:
            cursor = self._cursor
        lo = max(0, cursor - self.capacity)
        out = []
        for seq in range(lo, cursor):
            slot = self._slots[seq % self.capacity]
            snap = list(slot)  # one read; a racing overwrite changes _SEQ
            if snap[_SEQ] != seq + 1:
                continue  # empty, torn, or already lapped
            out.append({
                "name": snap[_NAME], "ph": snap[_PHASE],
                "ts_ns": snap[_TS], "dur_ns": snap[_DUR],
                "tid": snap[_TID], "id": snap[_SPAN],
                "parent": snap[_PARENT], "attrs": snap[_ATTRS] or {},
            })
        return out

    def chrome_trace(self) -> dict:
        """Chrome ``trace_event`` JSON (load in Perfetto / chrome://tracing).

        Complete spans use phase "X" with microsecond ``ts``/``dur``;
        instants use phase "i" with thread scope.  Span/parent ids ride in
        ``args`` so nesting survives tools that re-sort events.

        The export carries the identity ``tools/tracemerge.py`` needs:
        "M"-phase ``process_name``/``thread_name`` metadata events (the
        label set via ``set_process_label``; thread names captured at first
        record) and an ``otherData`` block with the label, pid, and
        whatever the export-meta provider adds (pulse's clock-offset
        table)."""
        pid = os.getpid()
        label = _process_label or f"py-{pid}"
        events = [{"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                   "ts": 0, "args": {"name": label}}]
        for tid, tname in sorted(self._thread_names.items()):
            events.append({"name": "thread_name", "ph": "M", "pid": pid,
                           "tid": tid, "ts": 0, "args": {"name": tname}})
        for r in sorted(self.records(), key=lambda r: (r["ts_ns"], r["id"])):
            ev = {
                "name": r["name"], "ph": r["ph"], "pid": pid,
                "tid": r["tid"], "ts": r["ts_ns"] / 1e3,
                "args": dict(r["attrs"], span_id=r["id"],
                             parent_id=r["parent"]),
            }
            if r["ph"] == "X":
                ev["dur"] = r["dur_ns"] / 1e3
            else:
                ev["s"] = "t"  # thread-scoped instant
            events.append(ev)
        other = {"process_label": label, "pid": pid}
        if self._device_tables:
            other["device_op_tables"] = self.device_tables()
        if _export_meta_provider is not None:
            try:
                other.update(_export_meta_provider())
            except Exception:
                pass  # export must never fail because a meta hook did
        return {"traceEvents": events, "displayTimeUnit": "ns",
                "otherData": other}

    def export_chrome_trace(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)


# ---------------------------------------------------------------------------
# module-level default tracer: the hot-path entry points
# ---------------------------------------------------------------------------
_default = Tracer()


def get_tracer() -> Tracer:
    return _default


def set_tracer(tracer: Tracer) -> Tracer:
    """Swap the process-default tracer; returns the previous one (tests
    restore it)."""
    global _default
    prev, _default = _default, tracer
    return prev


def span(name: str, device_sync: bool = False, **attrs):
    """``with span("solve", coordinate=cid):`` against the default tracer.
    Disabled: one boolean check + a shared no-op context manager."""
    t = _default
    if not t.enabled:
        return _NOOP
    return _Span(t, name, attrs or None, device_sync)


def instant(name: str, **attrs) -> None:
    t = _default
    if t.enabled:
        t.instant(name, **attrs)


def enabled() -> bool:
    return _default.enabled
