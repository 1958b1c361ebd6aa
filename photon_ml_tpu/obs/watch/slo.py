"""Declarative SLOs evaluated as multi-window burn rates.

An SLO declares an error budget (``objective=0.999`` leaves 0.1% of events
allowed to be bad).  The **burn rate** over a window is how fast that
budget is being spent relative to plan::

    burn(W) = (bad events in W / total events in W) / (1 - objective)

``burn == 1`` spends exactly the budget over the SLO period; ``burn == 14``
exhausts a 30-day budget in ~2 days.  Following the multi-window pattern,
an alert condition pairs a short and a long window at the same burn
threshold — the long window proves the problem is sustained, the short
window makes the alert RESOLVE quickly once the bleeding stops.  Two pairs
run in parallel: a *fast* pair (page-grade, high threshold) and a *slow*
pair (ticket-grade, low threshold).  Production windows are 5m/1h and
30m/6h; the dataclass takes them as plain seconds so tests
scale the same logic down to sub-second episodes.

Event sources are cumulative registry series, read from whatever registry
the caller hands ``evaluate()`` — a process's own registry for local mode,
a :class:`~photon_ml_tpu.obs.watch.federation.FleetView`'s merged registry
for fleet mode:

* ``kind="availability"``: total from one counter family, bad from one or
  more counter families (shed/error counters),
* ``kind="latency"``: both from one histogram family's fixed-bin ladder —
  total is the observation count, bad is observations above
  ``threshold_s`` (counted from the first bin bound >= the threshold, so
  pick a threshold on a bin edge for exactness).

Alert latches publish ``fleet_slo_burn_rate{slo=}`` / ``fleet_slo_alert``
gauges every evaluation and fire ``flight_dump("slo_burn", ...)`` on each
rising edge — the fleet-wide ring dump that answers "what was everyone
doing when the budget started burning".
"""

from __future__ import annotations

import dataclasses
import json
import threading
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Sequence, Tuple

from photon_ml_tpu.obs.pulse.flight import flight_dump
from photon_ml_tpu.obs.registry import MetricsRegistry


@dataclasses.dataclass(frozen=True)
class SLO:
    """One declarative objective.  ``fast``/``slow`` are (short, long)
    window pairs in seconds; ``*_burn`` their shared burn thresholds."""

    name: str
    objective: float = 0.999
    kind: str = "availability"              # "availability" | "latency"
    # availability sources
    total: str = "front_requests_total"
    bad: Tuple[str, ...] = ("requests_shed_total",)
    # latency sources
    histogram: str = "serving_latency_s"
    threshold_s: float = 0.050
    # multi-window burn-rate alert policy
    fast: Tuple[float, float] = (300.0, 3600.0)
    slow: Tuple[float, float] = (1800.0, 21600.0)
    fast_burn: float = 14.4
    slow_burn: float = 6.0

    def __post_init__(self) -> None:
        if not 0.0 < self.objective < 1.0:
            raise ValueError(f"objective must be in (0, 1): {self.objective}")
        if self.kind not in ("availability", "latency"):
            raise ValueError(f"unknown SLO kind: {self.kind!r}")
        if self.fast[0] >= self.fast[1] or self.slow[0] >= self.slow[1]:
            raise ValueError("window pairs must be (short, long)")

    @classmethod
    def from_dict(cls, d: dict) -> "SLO":
        kw = dict(d)
        for field in ("bad", "fast", "slow"):
            if field in kw:
                kw[field] = tuple(kw[field])
        return cls(**kw)


def load_slos(path: str) -> List[SLO]:
    """Load a JSON spec file: either a list of SLO dicts or
    ``{"slos": [...]}`` (room for future top-level config)."""
    with open(path) as f:
        doc = json.load(f)
    items = doc["slos"] if isinstance(doc, dict) else doc
    return [SLO.from_dict(d) for d in items]


def _read_counter_family(registry: MetricsRegistry, name: str) -> float:
    return sum(registry.counter_series(name).values())


def _read_latency(registry: MetricsRegistry, name: str,
                  threshold_s: float) -> Tuple[float, float]:
    """(total observations, observations above threshold) summed across the
    family's label sets, from the cumulative fixed-bin ladders."""
    total = 0.0
    bad = 0.0
    for state in registry.histogram_state_series(name).values():
        total += state["count"]
        good = 0
        for bound, c in zip(state["bounds"], state["counts"]):
            if bound <= threshold_s:
                good += c
            else:
                break
        bad += state["count"] - good
    return total, bad


class _Track:
    """Per-SLO evaluation state: cumulative samples + alert latch."""

    def __init__(self, slo: SLO) -> None:
        self.slo = slo
        horizon = max(slo.fast[1], slo.slow[1])
        # samples are (t, total, bad); keep a little past the longest
        # window so the boundary lookup always has an anchor
        self.horizon = horizon * 1.25
        self.samples: Deque[Tuple[float, float, float]] = deque()
        self.firing = False

    def window_burn(self, now: float, window: float) -> float:
        """Burn rate over the trailing ``window`` seconds.  Uses the oldest
        sample inside the window as the anchor; with no in-window history
        (cold start) there is nothing to burn yet — 0.0, never a guess."""
        if not self.samples:
            return 0.0
        t_now, total_now, bad_now = self.samples[-1]
        anchor = None
        for t, total, bad in self.samples:
            if t >= now - window:
                anchor = (t, total, bad)
                break
        if anchor is None or anchor[0] >= t_now:
            return 0.0
        d_total = total_now - anchor[1]
        d_bad = bad_now - anchor[2]
        if d_total <= 0:
            return 0.0
        return (d_bad / d_total) / (1.0 - self.slo.objective)


class SLOEngine:
    """Evaluates a set of SLOs against a registry on each ``evaluate()``.

    Stateless about WHERE the registry comes from — the caller passes it
    every tick (the FleetView merge target, or a local process registry).
    Burn gauges are published into ``publish`` (defaults to the evaluated
    registry, which for fleet mode puts ``fleet_slo_burn_rate`` right next
    to the merged series the admission controller already reads).
    """

    def __init__(self, slos: Sequence[SLO],
                 publish: Optional[MetricsRegistry] = None,
                 on_alert: Optional[Callable[[dict], None]] = None) -> None:
        names = [s.name for s in slos]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate SLO names: {names}")
        self._tracks = [_Track(s) for s in slos]
        self._publish = publish
        self._on_alert = on_alert
        self._events: List[dict] = []

    @property
    def slos(self) -> List[SLO]:
        return [t.slo for t in self._tracks]

    def events(self) -> List[dict]:
        """Every alert edge (firing/resolved) seen so far, oldest first."""
        return list(self._events)

    def firing(self) -> List[str]:
        return [t.slo.name for t in self._tracks if t.firing]

    def evaluate(self, registry: MetricsRegistry,
                 now: Optional[float] = None) -> List[dict]:
        """One tick: sample sources, compute window burns, publish gauges,
        latch alerts.  Returns the edges produced by THIS tick."""
        now = time.time() if now is None else now
        publish = self._publish if self._publish is not None else registry
        edges: List[dict] = []
        for track in self._tracks:
            slo = track.slo
            if slo.kind == "availability":
                total = _read_counter_family(registry, slo.total)
                bad = sum(_read_counter_family(registry, b)
                          for b in slo.bad)
            else:
                total, bad = _read_latency(registry, slo.histogram,
                                           slo.threshold_s)
            track.samples.append((now, total, bad))
            while track.samples and \
                    track.samples[0][0] < now - track.horizon:
                track.samples.popleft()

            fast_short = track.window_burn(now, slo.fast[0])
            fast_long = track.window_burn(now, slo.fast[1])
            slow_short = track.window_burn(now, slo.slow[0])
            slow_long = track.window_burn(now, slo.slow[1])
            fast_hit = (fast_short > slo.fast_burn
                        and fast_long > slo.fast_burn)
            slow_hit = (slow_short > slo.slow_burn
                        and slow_long > slo.slow_burn)
            alerting = fast_hit or slow_hit

            # the short fast window is the most reactive view of current
            # pressure — that is what admission consults
            publish.set_gauge("fleet_slo_burn_rate", fast_short,
                              slo=slo.name)
            publish.set_gauge("fleet_slo_alert", 1 if alerting else 0,
                              slo=slo.name)
            if alerting != track.firing:
                track.firing = alerting
                edge = {
                    "slo": slo.name,
                    "state": "firing" if alerting else "resolved",
                    "at_unix": now,
                    "burn_fast": (fast_short, fast_long),
                    "burn_slow": (slow_short, slow_long),
                    "pair": ("fast" if fast_hit else
                             "slow" if slow_hit else None),
                }
                edges.append(edge)
                self._events.append(edge)
                if alerting:
                    # fleet-wide ring dump: freeze what every process was
                    # doing the moment the budget started burning
                    flight_dump("slo_burn", slo=slo.name,
                                burn_rate=fast_short)
                if self._on_alert is not None:
                    self._on_alert(edge)
        return edges


class SLOEvalThread:
    """Sidecar thread ticking an :class:`SLOEngine` against a registry
    provider — how ``--slo`` runs inside serve/learn/fleetwatch without
    touching their event loops."""

    def __init__(self, engine: SLOEngine,
                 source: Callable[[], MetricsRegistry],
                 interval_s: float = 1.0) -> None:
        self._engine = engine
        self._source = source
        self._interval_s = float(interval_s)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.eval_errors = 0
        self.last_error: Optional[BaseException] = None

    @property
    def engine(self) -> SLOEngine:
        return self._engine

    def start(self) -> "SLOEvalThread":
        t = threading.Thread(target=self._run, name="slo-eval", daemon=True)
        self._thread = t
        t.start()
        return self

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                self._engine.evaluate(self._source())
            except Exception as e:
                # keep the sidecar alive (obs must not kill the process
                # it observes), but leave evidence for the operator
                self.eval_errors += 1
                self.last_error = e
            self._stop.wait(self._interval_s)

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
