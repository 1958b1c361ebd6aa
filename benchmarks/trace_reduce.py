"""From a ``jax.profiler`` trace (``.xplane.pb``) to numbers.

The reduction every PR's per-layer metrics rest on, kept with the benchmark
so that no PR that claims a gain can change it.  Checked against the small
recorded trace in ``tests/data`` (tests/test_rehearsal.py).

What a TPU trace holds: one plane per chip (``/device:TPU:<n>``) whose line
``XLA Ops`` carries one event per HLO operation that ran, under the
operation's name (``fusion.12``, ``while.3``, a Mosaic kernel under the
``name=`` of its ``pallas_call``).  Control-flow operations (``while``,
``conditional``, ``call``) span the operations of their bodies on the same
line, so time per operation is SELF time: an event's duration less that of
the events nested in it.  Host threads are lines of the plane
``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans land there.

    busy     the union of the op intervals on a chip, inside the slice
    idle gap an interval of the slice in which no op ran on that chip
    window   the slice, on the profiler's clock

All times in the result are seconds; events keep nanoseconds.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

SYNC_NAME = "bench.clock_sync"
OPS_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
TOP = 10


def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str) -> dict:
    """{plane name: {line name: [(event name, start_ns, dur_ns), ...]}}.
    Lines of one name (host threads can share one) are merged."""
    planes = {}
    for plane in _profile_data(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events)
    return planes


def _profile_data(path: str):
    """``jax.profiler.ProfileData`` of an ``.xplane.pb``, gzipped or not."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def op_name(raw: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    name = raw.split(" = ", 1)[0].strip()
    return name[1:] if name.startswith("%") else name


def device_ops(planes: dict) -> dict:
    """{chip index: [(name, start, dur), ...]} sorted by start."""
    out = {}
    for pname, lines in planes.items():
        m = _DEVICE_PLANE.match(pname)
        if m and lines.get(OPS_LINE):
            out[int(m.group(1))] = sorted(
                ((op_name(n), s, d) for n, s, d in lines[OPS_LINE]),
                key=lambda e: (e[1], -e[2]))
    return out


def host_events(planes: dict) -> list:
    """Every event of the host plane: TraceAnnotations among them."""
    out = []
    for pname, lines in planes.items():
        if pname.startswith("/host:CPU"):
            for events in lines.values():
                out.extend(events)
    return out


def busy_and_gaps(events: list, t0: float, t1: float) -> tuple:
    """(busy_ns, [(gap start, gap end), ...]) of one chip inside [t0, t1]:
    the union of its op intervals, and what the union leaves."""
    busy, gaps, cursor = 0.0, [], t0
    for _, start, dur in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b <= a:
            continue
        if a > cursor:
            gaps.append((cursor, a))
            cursor = a
        if b > cursor:
            busy += b - cursor
            cursor = b
    if t1 > cursor:
        gaps.append((cursor, t1))
    return busy, gaps


def self_times(events: list, t0: float, t1: float) -> dict:
    """{name: [self_ns, calls]} over the events that START inside
    [t0, t1]: an enclosing event (``while``) is charged only the time its
    nested events leave."""
    out = defaultdict(lambda: [0.0, 0])
    stack = []  # [name, end, self]

    def close(until):
        while stack and stack[-1][1] <= until:
            name, _, own = stack.pop()
            out[name][0] += max(own, 0.0)
            out[name][1] += 1

    for name, start, dur in events:
        if start < t0 or start >= t1:
            continue
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


def time_of(ops_self: dict, *needles: str) -> tuple:
    """(seconds, calls) of the operations whose name holds a needle."""
    ns = calls = 0
    for name, (own, n) in ops_self.items():
        if any(k in name for k in needles):
            ns += own
            calls += n
    return ns * 1e-9, calls


def label_gap(gap: tuple, host_spans: list) -> str:
    """The host span that covers most of the gap (innermost on a tie)."""
    a, b = gap
    best, best_cover, best_len = "no span", 0.0, float("inf")
    for name, s, e in host_spans:
        cover = min(b, e) - max(a, s)
        if cover <= 0:
            continue
        if cover > best_cover * 1.001 or (cover >= best_cover * 0.999
                                          and e - s < best_len):
            best, best_cover, best_len = name, cover, e - s
    return best


def reduce(planes: dict, sync_perf_ns: int | None = None,
           slice_perf_ns: tuple | None = None, host_spans: list = (),
           n_devices: int = 1) -> dict | None:
    """The numbers of one traced slice, or None where the trace holds no
    device operation (a CPU run).  ``host_spans``: (name, start, end) on
    ``perf_counter_ns``; ``sync_perf_ns``: that clock's reading inside the
    SYNC_NAME annotation, which ties it to the profiler's."""
    chips = device_ops(planes)
    if not chips:
        return None
    hosts = host_events(planes)
    offset = None
    if sync_perf_ns is not None:
        sync = [s for n, s, _ in hosts if n == SYNC_NAME]
        if sync:
            offset = sync[0] - sync_perf_ns
    if offset is not None and slice_perf_ns is not None:
        t0, t1 = slice_perf_ns[0] + offset, slice_perf_ns[1] + offset
    else:
        t0 = min(ev[0][1] for ev in chips.values())
        t1 = max(max(s + d for _, s, d in ev) for ev in chips.values())
    used = sorted(chips)[:n_devices]
    spans = [(n, s, s + d) for n, s, d in hosts
             if d > 0 and not n.startswith("$")]
    if offset is not None:
        spans += [(n, s + offset, e + offset) for n, s, e in host_spans]

    busy_ns, gaps, ops = [], [], defaultdict(lambda: [0.0, 0])
    for chip in used:
        b, g = busy_and_gaps(chips[chip], t0, t1)
        busy_ns.append(b)
        gaps += g
        for name, (own, calls) in self_times(chips[chip], t0, t1).items():
            ops[name][0] += own
            ops[name][1] += calls
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1][0])[:TOP]
    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:5]
    by_label = defaultdict(float)
    for g in gaps:
        by_label[label_gap(g, spans)] += (g[1] - g[0]) * 1e-9
    return {
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": sum(busy_ns) * 1e-9 / len(used),
        "chips": len(used),
        "clock_tied": offset is not None,
        "ops_self": {k: [v[0], v[1]] for k, v in ops.items()},
        "idle_by_host_span": dict(by_label),
        "breakdown": {
            "device_ops": [[f"{name} x{calls}", own * 1e-9 / len(used)]
                           for name, (own, calls) in top_ops],
            "idle_gaps": [[label_gap(g, spans), (g[1] - g[0]) * 1e-9]
                          for g in top_gaps],
        },
    }


def reduce_dir(trace_dir: str, **kw) -> dict | None:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    return reduce(load(path), **kw)


def _dump(path: str) -> None:
    """What a trace holds, for a reader who has not seen one."""
    for plane in _profile_data(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            total = defaultdict(lambda: [0.0, 0])
            for ev in events:
                total[ev.name][0] += ev.duration_ns
                total[ev.name][1] += 1
            print("  LINE", line.name, len(events))
            for name, (ns, n) in sorted(total.items(),
                                        key=lambda kv: -kv[1][0])[:25]:
                print(f"     {ns * 1e-6:12.3f} ms x{n:<6} {name[:140]}")
            for ev in events[:2]:
                print("     stats:", [(k, str(v)[:80]) for k, v in ev.stats])


if __name__ == "__main__":
    import sys

    _dump(sys.argv[1])
