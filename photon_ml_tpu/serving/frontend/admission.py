"""Admission control: deadline-budget load shedding with hysteresis.

Photon ML reference counterpart: none — overload behavior is the part of
LinkedIn's serving stack the paper leaves to infrastructure.  The policy
here is the classic one for a batching accelerator backend:

  **Shed when the work already admitted cannot resolve a new request
  within its deadline budget.**  The predictor is
  ``AsyncBatcher.queue_wait_estimate`` — an EWMA of observed flush
  latencies (the registry's ``serve.flush`` service times, observed where
  they happen) times the number of flush waves queued ahead, plus the
  residual deadline wait for a non-full tail bucket.  Under overload that
  estimate grows linearly with queue depth, so the controller starts
  refusing work while the queue is still ~one deadline deep — bounding
  p99 at roughly the budget instead of letting the queue (and every
  client's latency) grow without bound, which is exactly the cliff an
  open-loop arrival process exposes.

  **Hysteresis makes shedding stable.**  A single threshold oscillates: one
  shed reply drains the queue below the limit, the next request is
  admitted, the queue refills, repeat — the shed/admit decision would
  flap at the arrival rate.  Instead the controller latches into a
  shedding state at the HIGH watermark (estimate > budget) and only
  unlatches at the LOW watermark (estimate <= ``resume_fraction`` *
  budget), so each transition requires the backlog to genuinely drain.

Shed replies carry ``retry_after_ms`` — the predicted time until the
backlog is back under the resume watermark, clamped to at least one
deadline budget — so a well-behaved client backs off instead of hammering.

  **Per-client budgets** (``client_budget_s``, off by default) add a
  second, narrower deadline checked FIRST against the wait attributable
  to the requesting client's OWN backlog (its fair-queue depth plus the
  shared batcher residue).  A single connection firehosing the edge trips
  its own latch (shed reason ``client_overload``) and gets refused while
  every other client keeps being admitted — without this, the burning
  client drives the GLOBAL estimate over budget and the edge latches shut
  for everyone.  Each client's latch carries the same two-watermark
  hysteresis; ``forget_client`` drops the latch when a connection closes.

  **Per-tenant budgets** (``tenant_budget_s``, off by default) generalize
  the per-client machinery one level up: a tenant is a *set* of
  connections serving one model family (serving/fleet), and its latch is
  checked against the wait attributable to that tenant's aggregate
  backlog.  Shed reason ``tenant_overload``; same hysteresis.  Unlike
  clients, tenant latches persist across connection churn — tenants are
  configured, not discovered — so there is no ``forget_tenant`` on close.

  **Per-shard budgets** (``shard_budget_s``, off by default) point the
  same machinery DOWN the stack: a shard is one slice of the pod-slice
  mesh, and the wait attributable to it is the frontend's estimate of the
  backlog headed for that shard (its share of recent traffic times the
  global estimate, scaled by the shard count — a hot shard's queue is the
  fleet p99 long before the average trips the global budget).  Shed
  reason ``shard_overload``; same two-watermark hysteresis, keyed by
  shard id.  The traffic-aware rebalance (serving/coefficient_store) is
  the slow corrective loop; this latch is the fast one that protects p99
  while placement catches up.

  **Readiness shedding** is the one check that is not a deadline: when the
  frontend's HealthState reports not-ready (``/readyz`` false), requests
  are refused up front with reason ``not_ready``.  The check lives in the
  frontend (it owns the HealthState); admission just names the reason so
  the shed metric and wire replies stay one vocabulary.

  **Fleet-pressure shedding** (``fleet_burn_budget``, off by default) is
  the photonwatch hook: the SLO engine publishes
  ``fleet_slo_burn_rate{slo=}`` gauges (into this process's registry in
  local mode, or pushed down from the fleet aggregator), and when the max
  across objectives exceeds the configured burn budget the edge sheds with
  reason ``fleet_pressure`` — skew visible only ACROSS frontends (every
  per-process estimate healthy, the fleet p99 burning) still gets load off
  the floor.  The gauge read is throttled (``fleet_burn_poll_s``) so the
  per-request cost is a float compare; the latch carries the same
  two-watermark hysteresis as every other shed reason.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional

from photon_ml_tpu.obs.pulse.flight import flight_dump
from photon_ml_tpu.obs.registry import MetricsRegistry

# requests_shed_total{reason=...} reasons
SHED_OVERLOAD = "overload"
SHED_DRAINING = "draining"
SHED_SHUTDOWN = "shutdown"
SHED_CLIENT = "client_overload"
SHED_TENANT = "tenant_overload"
SHED_SHARD = "shard_overload"
SHED_NOT_READY = "not_ready"
SHED_FLEET = "fleet_pressure"


@dataclasses.dataclass(frozen=True)
class AdmissionConfig:
    """Knobs for the deadline-budget controller.

    ``budget_s``: per-request deadline — the latency the edge promises; a
    request predicted to resolve later than this is refused up front.
    ``resume_fraction``: the low watermark as a fraction of the budget
    (must sit strictly below 1.0 for the hysteresis to exist).
    ``retry_after_ms``: floor for the advisory backoff in shed replies.
    ``client_budget_s``: per-connection deadline checked against the
    client's OWN backlog wait (None = per-client budgets off; module
    docstring).  Usually set below ``budget_s`` so a burning client sheds
    before the whole edge latches.
    ``tenant_budget_s``: per-tenant deadline checked against the tenant's
    aggregate backlog wait (None = per-tenant budgets off) — one tenant's
    burst sheds under ``tenant_overload`` while other tenants' models keep
    admitting.
    ``shard_budget_s``: per-mesh-shard deadline checked against the wait
    attributable to the shard a request's hot-path work routes to (None =
    per-shard budgets off) — one overloaded slice sheds its own traffic
    under ``shard_overload`` instead of dragging the fleet p99.
    ``fleet_burn_budget``: max ``fleet_slo_burn_rate`` gauge value (across
    objectives) tolerated before shedding with reason ``fleet_pressure``
    (None = fleet-pressure shedding off; module docstring) — burn 1.0
    spends the error budget exactly on plan, so a sensible setting sits
    well above 1 (e.g. the SLO's page threshold).
    ``fleet_burn_poll_s``: how often the burn gauges are re-read; between
    polls ``decide`` compares against the cached value.
    """

    budget_s: float = 0.050
    resume_fraction: float = 0.5
    retry_after_ms: float = 0.0  # 0 -> derive from the budget
    client_budget_s: Optional[float] = None
    tenant_budget_s: Optional[float] = None
    shard_budget_s: Optional[float] = None
    fleet_burn_budget: Optional[float] = None
    fleet_burn_poll_s: float = 0.25

    def __post_init__(self):
        if self.budget_s <= 0:
            raise ValueError(f"budget_s must be > 0, got {self.budget_s}")
        if not 0.0 < self.resume_fraction < 1.0:
            raise ValueError("resume_fraction must be in (0, 1), got "
                             f"{self.resume_fraction}")
        if self.client_budget_s is not None and self.client_budget_s <= 0:
            raise ValueError("client_budget_s must be > 0, got "
                             f"{self.client_budget_s}")
        if self.tenant_budget_s is not None and self.tenant_budget_s <= 0:
            raise ValueError("tenant_budget_s must be > 0, got "
                             f"{self.tenant_budget_s}")
        if self.shard_budget_s is not None and self.shard_budget_s <= 0:
            raise ValueError("shard_budget_s must be > 0, got "
                             f"{self.shard_budget_s}")
        if self.fleet_burn_budget is not None and self.fleet_burn_budget <= 0:
            raise ValueError("fleet_burn_budget must be > 0, got "
                             f"{self.fleet_burn_budget}")
        if self.fleet_burn_poll_s <= 0:
            raise ValueError("fleet_burn_poll_s must be > 0, got "
                             f"{self.fleet_burn_poll_s}")


@dataclasses.dataclass
class Verdict:
    """One admission decision: ``admitted`` or shed with advice."""

    admitted: bool
    predicted_wait_s: float
    reason: Optional[str] = None  # SHED_* when not admitted
    retry_after_ms: float = 0.0


class AdmissionController:
    """Two-watermark (hysteresis) deadline-budget admission (module doc).

    Single-owner state: the front end calls ``decide`` from its event loop
    only, so the latch needs no lock — documented rather than defended,
    like the rest of the asyncio-side front-end state.
    """

    def __init__(self, config: Optional[AdmissionConfig] = None,
                 registry: Optional[MetricsRegistry] = None):
        self.config = config or AdmissionConfig()
        self._registry = registry
        self._shedding = False
        self._client_shedding: Dict[str, bool] = {}  # latched clients only
        self._tenant_shedding: Dict[str, bool] = {}  # latched tenants only
        self._shard_shedding: Dict[int, bool] = {}   # latched shards only
        self._fleet_shedding = False
        self._fleet_burn = 0.0                 # cached gauge read
        self._fleet_burn_checked: Optional[float] = None

    @property
    def shedding(self) -> bool:
        return self._shedding

    def client_shedding(self, client: str) -> bool:
        return self._client_shedding.get(client, False)

    def tenant_shedding(self, tenant: str) -> bool:
        return self._tenant_shedding.get(tenant, False)

    def shard_shedding(self, shard: int) -> bool:
        return self._shard_shedding.get(shard, False)

    @property
    def fleet_shedding(self) -> bool:
        return self._fleet_shedding

    def _fleet_burn_now(self) -> float:
        """Max ``fleet_slo_burn_rate`` across objectives, re-read from the
        registry at most every ``fleet_burn_poll_s`` (the ``_health_ready``
        throttled-cache pattern) so per-request cost is a float compare."""
        now = time.monotonic()
        if (self._fleet_burn_checked is None
                or now - self._fleet_burn_checked
                >= self.config.fleet_burn_poll_s):
            series = self._registry.gauge_series("fleet_slo_burn_rate") \
                if self._registry is not None else {}
            self._fleet_burn = max(series.values(), default=0.0)
            self._fleet_burn_checked = now
        return self._fleet_burn

    def _set_fleet_shedding(self, value: bool) -> None:
        if value != self._fleet_shedding:
            self._fleet_shedding = value
            if self._registry is not None:
                self._registry.set_gauge("front_fleet_shedding", int(value))
            if value:
                # fleet latch ENGAGED: the burn the aggregator saw started
                # before this process shed — spool what this process has
                flight_dump("fleet_pressure", burn_rate=self._fleet_burn)

    def _set_shedding(self, value: bool) -> None:
        if value != self._shedding:
            self._shedding = value
            if self._registry is not None:
                self._registry.set_gauge("front_shedding", int(value))
            if value:
                # latch ENGAGED: the spans leading into overload are in
                # the ring right now — spool them before they get lapped
                # (one None check when no flight recorder is configured)
                flight_dump("admission_shed")

    def _set_client_shedding(self, client: str, value: bool) -> None:
        newly_latched = value and not self._client_shedding.get(client, False)
        if value:
            self._client_shedding[client] = True
        else:
            self._client_shedding.pop(client, None)
        if self._registry is not None:
            self._registry.set_gauge("front_client_shedding", int(value),
                                     client=client)
        if newly_latched:
            # per-client latch ENGAGED (edge-triggered, not per shed
            # reply): spool the flight ring so the burning client's spans
            # are retrievable from /flightz after the fact
            flight_dump("client_overload", client=client)

    def _set_tenant_shedding(self, tenant: str, value: bool) -> None:
        if value:
            self._tenant_shedding[tenant] = True
        else:
            self._tenant_shedding.pop(tenant, None)
        if self._registry is not None:
            self._registry.set_gauge("front_tenant_shedding", int(value),
                                     tenant=tenant)

    def _set_shard_shedding(self, shard: int, value: bool) -> None:
        if value:
            self._shard_shedding[shard] = True
        else:
            self._shard_shedding.pop(shard, None)
        if self._registry is not None:
            self._registry.set_gauge("front_shard_shedding", int(value),
                                     shard=str(shard))

    def forget_client(self, client: str) -> None:
        """Drop a closed connection's latch (and its gauge series)."""
        if client in self._client_shedding:
            self._set_client_shedding(client, False)

    def _retry_ms(self, predicted_wait_s: float, budget_s: float) -> float:
        c = self.config
        drain_s = max(predicted_wait_s - c.resume_fraction * budget_s, 0.0)
        return round(max(drain_s, budget_s, c.retry_after_ms * 1e-3) * 1e3,
                     3)

    def retry_after_ms(self, predicted_wait_s: float) -> float:
        """Advisory backoff: predicted time until the backlog is under the
        resume watermark, floored at one budget (a client that retries
        sooner than the backlog can possibly drain just re-queues itself
        for another shed reply)."""
        return self._retry_ms(predicted_wait_s, self.config.budget_s)

    def decide(self, predicted_wait_s: float,
               client: Optional[str] = None,
               client_wait_s: float = 0.0,
               tenant: Optional[str] = None,
               tenant_wait_s: float = 0.0,
               shard: Optional[int] = None,
               shard_wait_s: float = 0.0) -> Verdict:
        """One admission decision for a request arriving now, given the
        backlog predictor's estimate of its time-to-resolution and (with
        per-client/per-tenant/per-shard budgets on) the wait attributable
        to the requesting client's, tenant's, and target shard's own
        backlogs.  ``shard`` < 0 means the request has no shard affinity
        (unsharded store, cold entity) and skips the shard check."""
        c = self.config
        if c.client_budget_s is not None and client is not None:
            # the narrow check first: a client burning its own budget is
            # shed alone, BEFORE its backlog can trip the global latch
            budget = c.client_budget_s
            if self._client_shedding.get(client, False):
                if client_wait_s <= budget * c.resume_fraction:
                    self._set_client_shedding(client, False)
                else:
                    return Verdict(False, client_wait_s, SHED_CLIENT,
                                   self._retry_ms(client_wait_s, budget))
            elif client_wait_s > budget:
                self._set_client_shedding(client, True)
                return Verdict(False, client_wait_s, SHED_CLIENT,
                               self._retry_ms(client_wait_s, budget))
        if c.tenant_budget_s is not None and tenant is not None:
            # one level wider than a client, still narrower than global: a
            # tenant burst sheds under its own latch while other tenants'
            # models keep admitting
            budget = c.tenant_budget_s
            if self._tenant_shedding.get(tenant, False):
                if tenant_wait_s <= budget * c.resume_fraction:
                    self._set_tenant_shedding(tenant, False)
                else:
                    return Verdict(False, tenant_wait_s, SHED_TENANT,
                                   self._retry_ms(tenant_wait_s, budget))
            elif tenant_wait_s > budget:
                self._set_tenant_shedding(tenant, True)
                return Verdict(False, tenant_wait_s, SHED_TENANT,
                               self._retry_ms(tenant_wait_s, budget))
        if c.shard_budget_s is not None and shard is not None and shard >= 0:
            # narrower than global, orthogonal to client/tenant: one hot
            # mesh slice sheds ITS requests while the cool shards (and
            # shard-less traffic) keep admitting
            budget = c.shard_budget_s
            if self._shard_shedding.get(shard, False):
                if shard_wait_s <= budget * c.resume_fraction:
                    self._set_shard_shedding(shard, False)
                else:
                    return Verdict(False, shard_wait_s, SHED_SHARD,
                                   self._retry_ms(shard_wait_s, budget))
            elif shard_wait_s > budget:
                self._set_shard_shedding(shard, True)
                return Verdict(False, shard_wait_s, SHED_SHARD,
                               self._retry_ms(shard_wait_s, budget))
        if c.fleet_burn_budget is not None:
            # the widest check: the fleet aggregator's burn-rate gauges say
            # the WHOLE constellation is spending its error budget too fast
            # — shed here even though this process's own backlog is healthy
            burn = self._fleet_burn_now()
            if self._fleet_shedding:
                if burn <= c.fleet_burn_budget * c.resume_fraction:
                    self._set_fleet_shedding(False)
                else:
                    return Verdict(False, predicted_wait_s, SHED_FLEET,
                                   self.retry_after_ms(predicted_wait_s))
            elif burn > c.fleet_burn_budget:
                self._set_fleet_shedding(True)
                return Verdict(False, predicted_wait_s, SHED_FLEET,
                               self.retry_after_ms(predicted_wait_s))
        if self._shedding:
            if predicted_wait_s <= c.budget_s * c.resume_fraction:
                self._set_shedding(False)  # backlog drained: unlatch
            else:
                return Verdict(False, predicted_wait_s, SHED_OVERLOAD,
                               self.retry_after_ms(predicted_wait_s))
        elif predicted_wait_s > c.budget_s:
            self._set_shedding(True)  # latch: stays shedding until the
            # estimate is back under the LOW watermark, not just under the
            # budget — that gap is what keeps the decision from flapping
            return Verdict(False, predicted_wait_s, SHED_OVERLOAD,
                           self.retry_after_ms(predicted_wait_s))
        if self._registry is not None:
            self._registry.observe("front_predicted_wait_s",
                                   predicted_wait_s)
        return Verdict(True, predicted_wait_s)
