"""photonpulse merge: join per-process Chrome traces into one timeline.

Each process exports its photonscope ring with (a) ``process_name``
metadata and a stable pid, (b) ``otherData.clock`` — the NTP-style offsets
this process estimated against its named peers (``pulse.clock``), and
(c) ``trace=`` attrs stamped on every span recorded under a bound context
(``pulse.context``).  Those three are exactly what a merge needs:

  1. **align** — pick a reference process (the one every other process
     measured an offset against, e.g. the owner), chain offsets across at
     most a few hops, and shift every event onto the reference clock;
  2. **join** — bucket events by the trace ids in their args (``trace``
     for single-request spans, ``traces`` for batched spans like the
     engine flush that serve many requests at once);
  3. **emit** — one Perfetto-loadable Chrome trace with per-process rows
     (re-numbered pids so two processes that shared an OS pid across
     restarts cannot collide) and a ``trace_ids`` summary in
     ``otherData``.

Pure host-side JSON transforms — no jax, no sockets — so the same code
backs ``tools/tracemerge.py`` and the e2e tests.
"""

from __future__ import annotations

import json
from typing import Dict, List, Optional, Sequence


def load_trace(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _labels(traces: Sequence[dict]) -> List[str]:
    """One stable, unique label per input trace."""
    out: List[str] = []
    for i, t in enumerate(traces):
        label = (t.get("otherData") or {}).get("process_label") or f"p{i}"
        if label in out:
            label = f"{label}#{i}"
        out.append(label)
    return out


def _clock_shifts(traces: Sequence[dict], labels: List[str],
                  reference: Optional[str]) -> Dict[str, int]:
    """ns shift per label mapping its clock onto the reference's.

    Offsets are directed (``clock[peer] = peer_clock - my_clock``); the
    graph walks them in both directions so a replica that measured the
    owner aligns even though the owner measured nobody.

    A DISCONNECTED offset graph (two islands of processes that never
    exchanged clock pings — e.g. traces from two separate deployments
    merged after the fact) cannot be aligned onto one clock; pretending
    otherwise by zero-shifting the unreachable island would silently
    interleave unrelated timelines.  Instead the merge degrades: each
    extra component gets its OWN local reference (BFS from its first
    label), and a warning per component is smuggled out under
    ``__warnings__`` for ``otherData.clock_warnings`` /
    ``tools/tracemerge.py`` stderr.  Within a component, relative timing
    is still exact."""
    # adjacency: edge (a -> b, w) means t_b = t_a + w
    edges: Dict[str, List[tuple]] = {lb: [] for lb in labels}
    for lb, t in zip(labels, traces):
        clock = (t.get("otherData") or {}).get("clock") or {}
        for peer, est in clock.items():
            if peer not in edges or not isinstance(est, dict):
                continue
            try:
                off = int(est["offset_ns"])
            except (KeyError, TypeError, ValueError):
                continue
            edges[lb].append((peer, off))
            edges[peer].append((lb, -off))
    if reference is None or reference not in edges:
        # prefer the label others measured against but which measured no
        # one itself — the natural root (owner/frontend) of the exchange
        measured = {peer for t in traces
                    for peer in ((t.get("otherData") or {}).get("clock")
                                 or {})}
        roots = [lb for lb, t in zip(labels, traces)
                 if lb in measured
                 and not ((t.get("otherData") or {}).get("clock") or {})]
        reference = roots[0] if roots else labels[0]
    def _bfs(root: str) -> None:
        shifts[root] = shifts.get(root, 0)
        frontier = [root]
        while frontier:
            nxt = []
            for a in frontier:
                for b, w in edges[a]:
                    if b in shifts:
                        continue
                    # t_ref = t_a + shifts[a] and t_b = t_a + w
                    shifts[b] = shifts[a] - w
                    nxt.append(b)
            frontier = nxt

    shifts: Dict[str, int] = {reference: 0}
    _bfs(reference)
    warnings: List[str] = []
    component_refs = {reference: reference}
    for lb in labels:
        if lb in shifts:
            component_refs.setdefault(lb, reference)
            continue
        # disconnected component: align it to its own local reference
        before = set(shifts)
        _bfs(lb)
        members = sorted((set(shifts) - before) & set(labels))
        for m in members:
            component_refs[m] = lb
        warnings.append(
            f"clock-offset graph disconnected: {members} share no "
            f"measured peer with reference {reference!r}; aligned to "
            f"local reference {lb!r} instead (cross-component timing "
            "is NOT comparable)")
    shifts["__reference__"] = reference  # smuggled out; popped by caller
    shifts["__warnings__"] = warnings
    shifts["__component_refs__"] = component_refs
    return shifts


def _event_trace_ids(ev: dict) -> List[str]:
    args = ev.get("args") or {}
    ids = []
    t = args.get("trace")
    if isinstance(t, str):
        ids.append(t)
    for t in (args.get("traces") or ()):
        if isinstance(t, str) and t not in ids:
            ids.append(t)
    return ids


def merge_traces(traces: Sequence[dict],
                 reference: Optional[str] = None) -> dict:
    """Merge per-process Chrome traces into one aligned timeline."""
    labels = _labels(traces)
    shifts = _clock_shifts(traces, labels, reference)
    reference = shifts.pop("__reference__")
    clock_warnings = shifts.pop("__warnings__")
    component_refs = shifts.pop("__component_refs__")
    events: List[dict] = []
    processes: Dict[str, str] = {}
    trace_counts: Dict[str, int] = {}
    for i, (label, t) in enumerate(zip(labels, traces)):
        pid = i + 1
        processes[str(pid)] = label
        shift_us = shifts[label] / 1e3
        saw_process_name = False
        for ev in t.get("traceEvents", ()):
            ev = dict(ev)
            ev["pid"] = pid
            if ev.get("ph") == "M":
                if ev.get("name") == "process_name":
                    saw_process_name = True
                    ev = dict(ev, args={"name": label})
            else:
                ev["ts"] = ev.get("ts", 0) + shift_us
                for tid in _event_trace_ids(ev):
                    trace_counts[tid] = trace_counts.get(tid, 0) + 1
            events.append(ev)
        if not saw_process_name:
            events.append({"name": "process_name", "ph": "M", "pid": pid,
                           "tid": 0, "ts": 0, "args": {"name": label}})
    events.sort(key=lambda e: (e.get("ph") != "M", e.get("ts", 0)))
    return {
        "traceEvents": events,
        "displayTimeUnit": "ns",
        "otherData": {
            "merged_from": labels,
            "reference": reference,
            "offsets_ns": {lb: shifts[lb] for lb in labels},
            "processes": processes,
            "trace_ids": dict(sorted(trace_counts.items())),
            # degradation record: per-label local reference (== the global
            # reference when the offset graph was connected) and one
            # warning per disconnected component
            "component_references": component_refs,
            "clock_warnings": clock_warnings,
        },
    }


def spans_by_trace(merged: dict) -> Dict[str, List[dict]]:
    """Events of a merged trace bucketed by trace id (batched spans that
    serve several requests appear under each), each sorted by aligned
    start time."""
    out: Dict[str, List[dict]] = {}
    for ev in merged.get("traceEvents", ()):
        if ev.get("ph") == "M":
            continue
        for tid in _event_trace_ids(ev):
            out.setdefault(tid, []).append(ev)
    for evs in out.values():
        evs.sort(key=lambda e: e.get("ts", 0))
    return out
