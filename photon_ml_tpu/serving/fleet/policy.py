"""Canary rollout: safe-deployment POLICY on top of the hot-swap mechanism.

Photon ML reference counterpart: none — model rotation in the reference's
world is an offline artifact push; whether the new artifact is SAFE is
left to the serving infrastructure.  This module is that judgment, made
deterministic and automatic:

  **Deterministic traffic split.**  ``stable_bucket`` hashes the request
  key (``Request.uid``, falling back to the entity-id map) with BLAKE2b —
  not an RNG — so the canary slice is a pure function of the request
  stream: a replayed log splits identically, a test predicts exactly which
  uids ride the candidate, and two frontends splitting the same stream
  agree without coordination.

  **Both legs scored, drift observed.**  A canary-leg request is scored on
  the CANDIDATE (that score is served) and on the ACTIVE generation (that
  score is the reference); ``|new - old|`` feeds the drift gate.  Control
  traffic never touches the candidate.  Executables come from the shared
  ``KernelCache`` — the candidate was warmed at ``start``, so the whole
  episode performs zero compiles.

  **Auto-promote / auto-rollback.**  After every scored batch the
  controller settles: a clean observation window (``min_observations``
  canary scores with mean drift <= ``max_drift`` and the PR-14 health
  plane ready) promotes — the pointer flip runs through
  ``HotSwapper.activate_store``, i.e. under the swap lock and through the
  SAME ``swap.activate`` chaos seam as a deployment swap.  A drift breach
  or a not-ready health plane rolls back.  Either way the losing store is
  simply dropped: the active generation object was never touched, so
  rollback leaves it serving bitwise-identically, and every admitted
  request was scored by SOME generation — zero loss by construction.
  An injected fault at promotion becomes a rollback (``InjectedCrash``
  propagates — a crash is never handled, exactly like swap).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

from photon_ml_tpu.chaos.health import HealthState
from photon_ml_tpu.chaos.injector import InjectedCrash, InjectedFault
from photon_ml_tpu.obs.trace import span as obs_span
from photon_ml_tpu.serving.batcher import Request
from photon_ml_tpu.serving.coefficient_store import CoefficientStore
from photon_ml_tpu.serving.fleet.registry import ModelHandle

# canary episode states
IDLE = "idle"
CANARY = "canary"
PROMOTED = "promoted"
ROLLED_BACK = "rolled_back"

_BUCKETS = 10_000  # split granularity: 0.01% steps


def stable_bucket(key: str, buckets: int = _BUCKETS) -> int:
    """Request key -> bucket in ``[0, buckets)`` via BLAKE2b — stable
    across processes, Python hash seeds, and replays (``hash()`` is none
    of those).  The canary slice is ``bucket < fraction * buckets``."""
    h = hashlib.blake2b(str(key).encode("utf-8"), digest_size=8)
    return int.from_bytes(h.digest(), "big") % buckets


def request_key(req: Request) -> str:
    """The deterministic key a request is split on: its uid when the
    client set one, else its entity-id map (the same entities always land
    on the same leg, which is what an A/B read needs)."""
    if req.uid is not None:
        return str(req.uid)
    return json.dumps(req.ids, sort_keys=True)


@dataclasses.dataclass(frozen=True)
class CanaryPolicy:
    """Knobs for one rollout episode.

    ``fraction``: slice of traffic (by stable key hash) riding the
    candidate.  ``min_observations``: canary scores needed for a clean
    window.  ``max_drift``: mean ``|candidate - active|`` score drift the
    window may carry and still promote; above it the episode rolls back.
    ``health_poll_s``: how often the health plane is re-polled (readyz
    walks every check; the throttle keeps it off the per-batch path).
    """

    fraction: float = 0.25
    min_observations: int = 100
    max_drift: float = 1e-6
    health_poll_s: float = 0.25

    def __post_init__(self):
        if not 0.0 < self.fraction <= 1.0:
            raise ValueError(f"fraction must be in (0, 1], got "
                             f"{self.fraction}")
        if self.min_observations < 1:
            raise ValueError("min_observations must be >= 1, got "
                             f"{self.min_observations}")
        if self.max_drift < 0:
            raise ValueError(f"max_drift must be >= 0, got {self.max_drift}")


class CanaryController:
    """One model handle's rollout state machine (module docstring).

    Single-owner state: score/settle run on the handle's dispatch path
    (one thread), like the frontend's admission latch — documented rather
    than defended.  The pointer flip itself goes through the swapper's
    lock.
    """

    def __init__(self, handle: ModelHandle,
                 policy: Optional[CanaryPolicy] = None,
                 health: Optional[HealthState] = None,
                 clock=time.monotonic):
        self.handle = handle
        self.policy = policy or CanaryPolicy()
        self.health = health
        self._clock = clock
        self.state = IDLE
        self.candidate: Optional[CoefficientStore] = None
        self.candidate_dir: Optional[str] = None
        self.observations = 0
        self.drift_sum = 0.0
        self.drift_max = 0.0
        self.started_at: Optional[float] = None
        self.settled_at: Optional[float] = None
        self.rollback_reason: Optional[str] = None
        self._health_checked_at: Optional[float] = None
        self._health_ok = True
        self._registry = handle.engine.metrics.registry

    # -- episode lifecycle -------------------------------------------------
    def start(self, candidate: CoefficientStore,
              model_dir: Optional[str] = None) -> None:
        """Begin an episode: warm the candidate on the shared cache (free
        for a same-shape generation) and start splitting traffic."""
        if self.state == CANARY:
            raise RuntimeError("canary episode already running")
        self.handle.engine.warm(store=candidate)
        self.candidate = candidate
        self.candidate_dir = model_dir
        self.state = CANARY
        self.observations = 0
        self.drift_sum = 0.0
        self.drift_max = 0.0
        self.started_at = self._clock()
        self.settled_at = None
        self.rollback_reason = None
        self._health_checked_at = None
        self._transition_metric(CANARY)

    def _transition_metric(self, state: str) -> None:
        self._registry.inc("fleet_canary_transitions_total",
                           model=self.handle.model_id, state=state)

    def is_canary(self, req: Request) -> bool:
        """Deterministic membership of the canary slice."""
        return (stable_bucket(request_key(req))
                < self.policy.fraction * _BUCKETS)

    @property
    def mean_drift(self) -> float:
        return self.drift_sum / self.observations if self.observations \
            else 0.0

    @property
    def settle_s(self) -> Optional[float]:
        """Episode wall time, start -> promote/rollback."""
        if self.started_at is None or self.settled_at is None:
            return None
        return self.settled_at - self.started_at

    # -- scoring -----------------------------------------------------------
    def score(self, requests: Sequence[Request],
              predict_mean: bool = False) -> np.ndarray:
        """Score a batch under the split: control rows on the active
        generation, canary rows on BOTH (candidate served, active as the
        drift reference), then settle.  With no episode running this is
        exactly ``engine.score_requests``."""
        engine = self.handle.engine
        if self.state != CANARY or not requests:
            return engine.score_requests(requests,
                                         predict_mean=predict_mean)
        canary_ix = [i for i, r in enumerate(requests) if self.is_canary(r)]
        control_ix = [i for i in range(len(requests))
                      if i not in set(canary_ix)]
        out: Optional[np.ndarray] = None
        if control_ix:
            control = engine.score_requests(
                [requests[i] for i in control_ix],
                predict_mean=predict_mean)
            out = np.empty(len(requests), control.dtype)
            out[control_ix] = control
        if canary_ix:
            leg = [requests[i] for i in canary_ix]
            with obs_span("fleet.canary", model=self.handle.model_id,
                          rows=len(leg)):
                new = engine.score_requests(leg, predict_mean=predict_mean,
                                            store=self.candidate)
                old = engine.score_requests(leg, predict_mean=predict_mean)
            drift = np.abs(np.asarray(new) - np.asarray(old))
            self.observations += len(leg)
            self.drift_sum += float(drift.sum())
            self.drift_max = max(self.drift_max, float(drift.max()))
            if out is None:
                out = np.empty(len(requests), new.dtype)
            out[canary_ix] = new
        self.maybe_settle()
        return out

    # -- settling ----------------------------------------------------------
    def _healthy(self) -> bool:
        if self.health is None:
            return True
        now = self._clock()
        if (self._health_checked_at is None
                or now - self._health_checked_at >= self.policy.health_poll_s):
            self._health_ok = bool(self.health.readyz()[0])
            self._health_checked_at = now
        return self._health_ok

    def maybe_settle(self) -> str:
        """One settle decision; returns the (possibly new) state.  Health
        is checked FIRST so a degraded plane rolls back even before the
        window fills — the rollback edge chaos tests lean on this."""
        if self.state != CANARY:
            return self.state
        if not self._healthy():
            self.rollback("health_not_ready")
        elif self.observations >= self.policy.min_observations:
            if self.mean_drift > self.policy.max_drift:
                self.rollback("score_drift")
            else:
                self.promote()
        return self.state

    def promote(self) -> None:
        """Flip the handle to the candidate through the swapper (swap
        lock + ``swap.activate`` chaos seam).  An injected FAULT becomes a
        rollback — the old generation never stopped serving; an injected
        CRASH propagates, as everywhere."""
        assert self.candidate is not None
        try:
            self.handle.swapper.activate_store(self.candidate,
                                              model_dir=self.candidate_dir)
        except InjectedCrash:
            raise
        except InjectedFault:
            self.rollback("promotion_fault")
            return
        self.state = PROMOTED
        self.settled_at = self._clock()
        self.candidate = None
        self._transition_metric(PROMOTED)

    def rollback(self, reason: str) -> None:
        """Drop the candidate; the active generation (never touched) keeps
        serving.  Recorded under ``fleet_canary_transitions_total`` with
        the triggering gate as a label."""
        self.state = ROLLED_BACK
        self.settled_at = self._clock()
        self.rollback_reason = reason
        self.candidate = None
        self._registry.inc("fleet_canary_rollbacks_total",
                           model=self.handle.model_id, reason=reason)
        self._transition_metric(ROLLED_BACK)

    def status(self) -> dict:
        return {
            "state": self.state,
            "fraction": self.policy.fraction,
            "observations": self.observations,
            "mean_drift": self.mean_drift,
            "max_drift": self.drift_max,
            "rollback_reason": self.rollback_reason,
            "settle_s": self.settle_s,
        }


def split_preview(uids: Sequence[object],
                  fraction: float) -> Tuple[List[object], List[object]]:
    """Which of ``uids`` would ride the canary at ``fraction`` — the
    deterministic-split oracle tests and operators use."""
    canary, control = [], []
    for uid in uids:
        (canary if stable_bucket(str(uid)) < fraction * _BUCKETS
         else control).append(uid)
    return canary, control
