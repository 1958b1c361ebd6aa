"""Deterministic fault injection (photonchaos).

The availability story of the reference GLMix system is delegated to
Spark's driver/executor supervision; this repo runs owner, replica, and
frontend as cooperating processes and has to prove the topology heals on
its own.  The delta log already learned that lesson at byte granularity
(the every-offset truncation property test) — this module generalizes it
to the process level: every failure seam carries a NAMED fault point, and
a test arms a deterministic schedule against it.

Discipline (photonscope's ``obs.span`` rule applies unchanged):

  - **Disabled is free.**  A fault point costs ONE boolean check when no
    injector is armed — ``fault(point)`` reads ``_injector.enabled`` and
    returns ``None`` before touching any lock, dict, or RNG.
  - **Deterministic.**  Every schedule is a pure function of its
    configuration: fire-on-Nth-hit counts calls, seeded probability draws
    from a per-point ``random.Random(seed)``, timed windows measure from
    the moment the point was armed.  Same arms + same call sequence →
    same fires.
  - **Sites interpret, the injector schedules.**  ``check`` returns a
    ``FaultAction`` (kind + data) or None; the seam decides what "drop"
    or "torn" means locally (raise, sleep, write garbage, close).  Sites
    that just want an exception use ``FaultAction.to_error()``.

Fault-point names are dotted, seam-local constants — the catalog lives in
the README ("Robustness & chaos").  Armed points that a run never hits
are visible via ``FaultInjector.hits`` — a chaos schedule asserting on a
misspelled point fails loudly instead of testing nothing.
"""

from __future__ import annotations

import errno
import math
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

__all__ = [
    "FaultAction", "FaultInjector", "InjectedCrash", "InjectedFault",
    "fault", "get_injector", "set_injector",
]


class InjectedFault(Exception):
    """An exception raised on purpose by an armed fault point."""


class InjectedCrash(InjectedFault):
    """Process-death stand-in: seams NEVER catch this (tests do)."""


@dataclass(frozen=True)
class FaultAction:
    """What an armed fault point should do on this hit.

    ``kind`` is interpreted by the seam (``"enospc"``, ``"torn"``,
    ``"drop"``, ``"stall"``, ``"stall_dist"``, ``"garbage"``,
    ``"disconnect"``, ``"crash"``, ``"corrupt"``, ``"slow"``,
    ``"error"``); ``data`` carries kind-specific knobs (e.g.
    ``stall_s``).  ``stall_dist`` is ``stall`` with the hold sampled per
    fire from the rule's seeded lognormal (see ``FaultInjector.check``) —
    stall-interpreting seams treat the two identically."""

    point: str
    kind: str
    data: dict = field(default_factory=dict)

    def to_error(self) -> BaseException:
        """The canonical exception for this action — seams that only
        need "make this operation fail" raise it verbatim."""
        if self.kind == "enospc":
            return OSError(errno.ENOSPC,
                           f"injected ENOSPC at {self.point}")
        if self.kind == "torn":
            # a torn write IS an I/O error after a partial write
            return OSError(errno.EIO,
                           f"injected torn write at {self.point}")
        if self.kind == "crash":
            return InjectedCrash(f"injected crash at {self.point}")
        if self.kind in ("drop", "disconnect"):
            return ConnectionResetError(
                f"injected {self.kind} at {self.point}")
        return InjectedFault(f"injected {self.kind} at {self.point}")


# stall_dist defaults: median 30ms holds, heavy-tailed (sigma 0.6 puts the
# p99 near 4x the median), capped so a pathological draw cannot wedge a
# run; all three overridable via the rule's data
_STALL_DIST_MU = math.log(0.03)
_STALL_DIST_SIGMA = 0.6
_STALL_DIST_CAP_S = 0.25


class _Rule:
    """One armed schedule on one point.  ``decide(hit_no, now)`` is
    called under the injector lock with the 1-based hit number."""

    def __init__(self, kind: str, data: dict, nth: Optional[int],
                 repeat: bool, probability: Optional[float],
                 seed: int, window: Optional[Tuple[float, float]],
                 max_fires: Optional[int]):
        self.kind = kind
        self.data = dict(data or {})
        self.nth = nth
        self.repeat = repeat
        self.probability = probability
        self.window = window
        self.max_fires = max_fires
        self.fires = 0
        self.armed_at = time.monotonic()
        # per-rule RNG: probability schedules replay identically for the
        # same seed regardless of what other points draw
        self._rng = random.Random(seed)

    def decide(self, hit_no: int, now: float) -> bool:
        if self.max_fires is not None and self.fires >= self.max_fires:
            return False
        if self.window is not None:
            after, duration = self.window
            dt = now - self.armed_at
            if dt < after or dt >= after + duration:
                return False
        if self.nth is not None:
            if self.repeat:
                if hit_no % self.nth != 0:
                    return False
            elif hit_no != self.nth:
                return False
        if self.probability is not None:
            if self._rng.random() >= self.probability:
                return False
        self.fires += 1
        return True


class FaultInjector:
    """Named fault points with deterministic, seeded schedules.

    Thread-safe: seams call ``check`` from asyncio loops, daemon
    threads, and the request path concurrently.  ``enabled`` is a plain
    attribute read outside the lock — the disabled fast path never
    synchronizes (stale reads only extend the no-op window by one call,
    exactly like ``obs.trace``'s tracer swap)."""

    def __init__(self, registry=None):
        self.enabled = False
        self.registry = registry
        self._lock = threading.Lock()
        self._rules: Dict[str, _Rule] = {}
        self._hits: Dict[str, int] = {}

    def arm(self, point: str, kind: str = "error", *,
            nth: Optional[int] = None, repeat: bool = False,
            probability: Optional[float] = None, seed: int = 0,
            window: Optional[Tuple[float, float]] = None,
            max_fires: Optional[int] = None,
            data: Optional[dict] = None) -> None:
        """Arm ``point`` with one schedule (re-arming replaces it).

        ``nth``: fire on the Nth hit (every Nth with ``repeat=True``).
        ``probability``: fire when ``Random(seed).random() < p`` —
        deterministic per arm.  ``window``: ``(after_s, duration_s)``
        measured from this call.  Omitting all three fires on EVERY hit.
        ``max_fires`` caps total fires for any schedule."""
        if nth is not None and nth < 1:
            raise ValueError(f"nth must be >= 1, got {nth}")
        with self._lock:
            self._rules[point] = _Rule(kind, data or {}, nth, repeat,
                                       probability, seed, window, max_fires)
            self.enabled = True

    def disarm(self, point: Optional[str] = None) -> None:
        """Disarm one point, or everything when ``point`` is None (hit
        counters survive — a schedule can assert coverage after)."""
        with self._lock:
            if point is None:
                self._rules.clear()
            else:
                self._rules.pop(point, None)
            self.enabled = bool(self._rules)

    def check(self, point: str) -> Optional[FaultAction]:
        """One hit on ``point``: returns the action to take, or None."""
        with self._lock:
            hit_no = self._hits.get(point, 0) + 1
            self._hits[point] = hit_no
            rule = self._rules.get(point)
            if rule is None or not rule.decide(hit_no, time.monotonic()):
                return None
            data = rule.data
            if rule.kind == "stall_dist":
                # latency-distribution stall: every fire samples its OWN
                # hold from the rule's seeded lognormal — one armed rule
                # yields a realistic heavy-tailed degradation instead of a
                # square pulse.  Sampled under the lock from the per-rule
                # RNG, so same seed + same call sequence -> same holds.
                mu = float(data.get("mu", _STALL_DIST_MU))
                sigma = float(data.get("sigma", _STALL_DIST_SIGMA))
                cap = float(data.get("cap_s", _STALL_DIST_CAP_S))
                data = dict(data, stall_s=min(
                    rule._rng.lognormvariate(mu, sigma), cap))
            action = FaultAction(point=point, kind=rule.kind, data=data)
        if self.registry is not None:
            self.registry.inc("chaos_faults_fired_total", point=point,
                              kind=action.kind)
        return action

    def hits(self, point: str) -> int:
        """Times ``point`` was reached (armed or not, fired or not)."""
        with self._lock:
            return self._hits.get(point, 0)

    def fired(self, point: str) -> int:
        """Times the CURRENTLY armed schedule on ``point`` fired."""
        with self._lock:
            rule = self._rules.get(point)
            return rule.fires if rule is not None else 0

    def reset(self) -> None:
        """Disarm everything and zero the hit counters."""
        with self._lock:
            self._rules.clear()
            self._hits.clear()
            self.enabled = False


# ---------------------------------------------------------------------------
# process-wide injector (obs.trace's tracer-swap idiom)
# ---------------------------------------------------------------------------
_injector = FaultInjector()


def get_injector() -> FaultInjector:
    """The process-wide injector (disabled until something arms it)."""
    return _injector


def set_injector(injector: FaultInjector) -> FaultInjector:
    """Swap the process-wide injector; returns the previous one (tests
    restore it in a finally)."""
    global _injector
    prev = _injector
    _injector = injector
    return prev


def fault(point: str) -> Optional[FaultAction]:
    """The seam-side entry point.  Disabled cost: one boolean check."""
    inj = _injector
    if not inj.enabled:
        return None
    return inj.check(point)
