"""photonfront: asyncio socket front end over the AsyncBatcher.

Photon ML reference counterpart: none — the reference publishes models and
LinkedIn's serving infrastructure owns the edge.  This module IS that edge
for the TPU-native stack: a stdlib-only asyncio TCP server that multiplexes
many concurrent client connections into the existing
``serving.batcher.AsyncBatcher`` / AOT ``ScoringEngine``, speaking the SAME
newline-delimited JSON wire protocol as the stdio ``cli/serve.py`` loop
(requests, blank-line flush, ``{"cmd": ...}`` control lines), so existing
drivers work unchanged pointed at a socket.

What makes it an edge rather than a socket wrapper:

  - **Admission control / load shedding** (``admission.py``): every score
    request is checked against a deadline budget BEFORE it joins the
    queue, using the batcher's flush-latency EWMA times the queued flush
    waves (``AsyncBatcher.queue_wait_estimate``).  Refusals are explicit —
    ``{"error": "overloaded", "retry_after_ms": ...}`` — and hysteresis
    (two watermarks) keeps the shed decision latched until the backlog
    genuinely drains, so shedding is stable, not flappy.
  - **Per-client fairness** (``fairness.py``): admitted requests queue per
    connection and a round-robin dispatcher fills a bounded batcher window
    (default 2 flush waves), so one firehose connection cannot park a
    trickle client behind its backlog; any client's added wait is bounded
    by (clients x window), not by another client's queue depth.
  - **Graceful drain**: ``{"cmd": "swap"}``, ``{"cmd": "delta"}``,
    ``{"cmd": "shutdown"}`` and SIGTERM (wired in cli/serve.py) stop
    admitting (shed reason ``draining``), submit everything queued, flush
    the batcher, and wait for every in-flight future to resolve before
    flipping the generation / applying the delta / exiting — zero admitted
    requests are ever dropped or errored by a rotation.
  - **Bounded reads** (``protocol.py``): a malformed line gets an
    ``{"error": ...}`` reply and the connection survives; an oversized
    line is discarded through its newline under a hard byte bound, so one
    client cannot OOM the server.

  - **Per-client admission budgets** (``AdmissionConfig.client_budget_s``,
    off by default): before the global deadline check, the wait a client's
    OWN backlog explains is tested against a per-connection budget with
    its own hysteresis latch — a single firehose connection sheds with
    reason ``client_overload`` while everyone else keeps being admitted.
  - **Connection cap** (``FrontendConfig.max_connections``): accepts past
    the cap get one ``{"error": "too_many_connections"}`` line and a clean
    close before any per-connection state is allocated.
  - **Shared-secret auth** (``FrontendConfig.auth_token``, off by
    default): the FIRST line of every connection must then be
    ``{"cmd": "auth", "token": "..."}``; the compare is constant-time
    (``hmac.compare_digest``) and anything else — wrong token, missing
    line, timeout — gets exactly one ``{"error": "unauthorized"}`` frame
    and a close (``front_auth_failures_total``).  A good token is answered
    with ``{"auth": "ok"}`` and the normal wire protocol follows.

Observability: photonscope spans/instants ``front.accept`` /
``front.admit`` / ``front.shed`` / ``front.refuse`` / ``front.drain`` and
registry series ``front_connections`` (gauge),
``front_connections_total``, ``front_connections_refused_total``,
``front_requests_total``, ``front_queue_depth{client=...}``,
``requests_shed_total{reason=...}``, ``front_protocol_errors_total{kind=
...}``, ``front_shedding``, ``front_client_shedding{client=...}``,
``front_predicted_wait_s`` (histogram) — all in the engine's registry,
scrapeable via ``metrics_http.py``.

Concurrency model: ALL front-end state (fair queue, admission latch,
in-flight accounting) is owned by the event loop; the only cross-thread
edges are ``AsyncBatcher.submit`` (thread-safe by contract) and future
completion callbacks, which re-enter the loop via
``call_soon_threadsafe``.  Per-connection reply ORDER is the submission
order: each connection has a reply queue of futures its writer task awaits
in sequence, so fairness reorders work ACROSS clients, never within one.

Wire protocol extension over stdio: ``{"cmd": "shutdown"}`` drains and
stops the whole server (the socket analog of stdin EOF).

Fleet mode (``fleet=ModelFleet(...)``): requests grow an optional
``"model"`` field (absent -> the default model, so pre-fleet clients work
unchanged) routed to per-model ``AsyncBatcher``s that score through a
``FleetRouter`` — the seam canary episodes and shadow scorers interpose
on.  Tenancy rides the same edge: tenant tokens
(``FrontendConfig.tenant_tokens``) scope a connection to one tenant's
models, per-tenant admission budgets (``AdmissionConfig.tenant_budget_s``)
latch shed reason ``tenant_overload`` against the tenant's own
admitted-unsettled backlog, and every admit is attributed to its
``(model, tenant)`` pair in the labeled ``fleet_*`` metric families.
Control commands gain ``fleet`` / ``canary`` / ``promote`` / ``rollback``
/ ``shadow`` plus an optional ``"model"`` field on ``swap`` / ``delta`` /
``rebalance``; all policy transitions run behind the same quiesce barrier
as hot swap, so zero admitted requests are lost across a rollback.  A
wired ``HealthState`` adds /readyz-driven shedding (reason ``not_ready``),
and ``trace_sample_n`` turns always-on tracing into deterministic 1-in-N
sampling at the admission edge.
"""

from __future__ import annotations

import asyncio
import dataclasses
import hmac
import json
import logging
import threading
import time
from typing import Dict, Optional, Tuple

from photon_ml_tpu.chaos.injector import fault as _chaos_fault
from photon_ml_tpu.obs.pulse import clock as pulse_clock
from photon_ml_tpu.obs.pulse.context import bind as ctx_bind
from photon_ml_tpu.obs.pulse.context import maybe_mint as ctx_maybe_mint
from photon_ml_tpu.obs.pulse.context import mint as ctx_mint
from photon_ml_tpu.obs.pulse.flight import get_flight
from photon_ml_tpu.obs.trace import enabled as obs_enabled
from photon_ml_tpu.obs.trace import get_process_label, get_tracer
from photon_ml_tpu.obs.trace import instant as obs_instant
from photon_ml_tpu.obs.trace import span as obs_span
from photon_ml_tpu.serving.batcher import request_from_json
from photon_ml_tpu.serving.engine import ScoringEngine
from photon_ml_tpu.serving.frontend.admission import (SHED_DRAINING,
                                                      SHED_NOT_READY,
                                                      SHED_SHUTDOWN,
                                                      AdmissionConfig,
                                                      AdmissionController)
from photon_ml_tpu.serving.frontend.fairness import FairQueue
from photon_ml_tpu.serving.frontend.protocol import (DEFAULT_MAX_LINE_BYTES,
                                                     BoundedLineReader,
                                                     LineTooLong, encode,
                                                     error_reply)
from photon_ml_tpu.serving.swap import HotSwapper

logger = logging.getLogger("photon_ml_tpu.serving.frontend")

_CLOSE = object()  # writer-task sentinel: flush backlog, then close


@dataclasses.dataclass(frozen=True)
class FrontendConfig:
    """Front-end policy knobs (wire format itself is not configurable)."""

    host: str = "127.0.0.1"
    port: int = 0  # 0 -> ephemeral; FrontendServer.port holds the binding
    max_line_bytes: int = DEFAULT_MAX_LINE_BYTES
    admission: AdmissionConfig = dataclasses.field(
        default_factory=AdmissionConfig)
    batcher_deadline_s: float = 500e-6
    flush_threshold: Optional[int] = None  # None -> engine's top bucket
    # max requests resident in the batcher at once; the rest wait in the
    # per-client fair queue where round-robin applies.  None -> 2 flush
    # waves: one scoring, one forming — enough to never starve the engine,
    # small enough that the backlog lives where fairness can see it.
    dispatch_window: Optional[int] = None
    drain_grace_s: float = 30.0
    predict_mean: bool = False
    # hard connection-count cap: excess accepts get ONE
    # {"error": "too_many_connections"} reply and a clean close, so a
    # connection storm cannot exhaust fds or per-conn task memory.
    # None = unlimited.
    max_connections: Optional[int] = None
    # shared secret: when set, the first line of every connection must be
    # {"cmd": "auth", "token": ...} (constant-time compare; one error
    # frame, then close).  None = open listener.
    auth_token: Optional[str] = None
    auth_timeout_s: float = 10.0
    # fleet tenancy: token -> tenant name.  A connection authenticating
    # with a tenant token is SCOPED to that tenant's models (requests for
    # another tenant's model get {"error": "forbidden"}); the global
    # auth_token (when also set) stays tenant-unscoped.  Setting this
    # turns the auth handshake on even without auth_token.
    tenant_tokens: Optional[Dict[str, str]] = None
    # sampled always-on tracing: when > 0 and the client sent no "tp",
    # mint a context for every Nth admitted request (deterministic
    # counter, photonpulse.maybe_mint) instead of every request — bounded
    # trace volume, but production flight dumps still carry request
    # context.  0 = mint for every request (the pre-fleet behavior).
    trace_sample_n: int = 0
    # /readyz-driven admission shedding: how often the HealthState (when
    # one is wired) is re-polled on the request path.  readyz walks every
    # check, so the throttle keeps it off the per-request cost.
    health_poll_s: float = 0.25
    # default CanaryPolicy knobs for {"cmd": "canary"} episodes (fields
    # the command itself carries win): fraction / min_observations /
    # max_drift
    canary_defaults: Optional[Dict[str, float]] = None


class _Conn:
    """Per-connection state: identity, streams, and the ordered reply
    queue its writer task drains.  ``tenant`` is set by a tenant-token
    auth handshake (None = unscoped)."""

    __slots__ = ("cid", "reader", "writer", "replies", "alive", "tenant")

    def __init__(self, cid: str, reader: asyncio.StreamReader,
                 writer: asyncio.StreamWriter):
        self.cid = cid
        self.reader = reader
        self.writer = writer
        self.replies: asyncio.Queue = asyncio.Queue()
        self.alive = True
        self.tenant: Optional[str] = None


class _Pending:
    """One admitted score request: reply future + settle-once accounting.
    ``t0_ns`` is the admission timestamp when tracing is on (None when
    off): settle records the enclosing ``front.request`` span from it.
    ``batcher``/``tenant`` are the fleet routing resolved at admission
    (None = the default single-engine batcher, untenanted)."""

    __slots__ = ("conn", "req", "reply", "settled", "t0_ns", "batcher",
                 "tenant")

    def __init__(self, conn: _Conn, req, reply: asyncio.Future,
                 t0_ns: Optional[int] = None, batcher=None,
                 tenant: Optional[str] = None):
        self.conn = conn
        self.req = req
        self.reply = reply
        self.settled = False
        self.t0_ns = t0_ns
        self.batcher = batcher
        self.tenant = tenant


class FrontendServer:
    """Asyncio TCP front end for one ScoringEngine (module docstring)."""

    def __init__(self, engine: ScoringEngine,
                 swapper: Optional[HotSwapper] = None,
                 config: Optional[FrontendConfig] = None,
                 registry=None, fleet=None, health=None):
        self.engine = engine
        self.swapper = swapper or HotSwapper(engine)
        self.config = config or FrontendConfig()
        self._registry = registry if registry is not None \
            else engine.metrics.registry
        # fleet mode: requests carry an optional "model" field routed to
        # per-model batchers; scoring goes through a FleetRouter so canary
        # episodes and shadow scorers can interpose per model.  None keeps
        # the single-engine edge byte-identical.
        self.fleet = fleet
        self.router = None
        self.health = health
        self._health_ok = True
        self._health_checked: Optional[float] = None
        if fleet is not None:
            from photon_ml_tpu.serving.fleet.router import FleetRouter
            self.router = FleetRouter(fleet, health=health)
        self._batchers: Dict[str, object] = {}  # model_id -> AsyncBatcher
        if self.router is not None and fleet.default_model is not None:
            self._batcher = self._model_batcher(fleet.default_model)
        else:
            self._batcher = engine.async_batcher(
                deadline_s=self.config.batcher_deadline_s,
                predict_mean=self.config.predict_mean,
                flush_threshold=self.config.flush_threshold)
        self._window = self.config.dispatch_window or \
            2 * self._batcher.flush_threshold
        self._tenant_inflight: Dict[str, int] = {}
        self._queue = FairQueue()
        self._admission = AdmissionController(self.config.admission,
                                              registry=self._registry)
        self._conns: Dict[str, _Conn] = {}
        # photonwatch subscriptions: delta-compression state is per
        # SUBSCRIBER, keyed by connection id (dropped with the connection)
        self._watch_exporters: Dict[str, object] = {}
        self._conn_seq = 0
        self._outstanding = 0  # resident in the batcher (dispatch window)
        self._inflight = 0     # admitted, not yet settled (drain barrier)
        self._draining = False
        # per-model drain barriers: models currently quiescing (their
        # requests shed; siblings keep serving), plus per-batcher
        # admitted-unsettled counts + idle events so a scoped drain can
        # wait on ONE model's batcher instead of the whole edge
        self._draining_models: set = set()
        self._batcher_inflight: Dict[int, int] = {}   # id(batcher) -> n
        self._batcher_idle: Dict[int, asyncio.Event] = {}
        # per-shard admission pressure: EWMA-ish share of recent admits
        # headed to each mesh shard (periodic halving keeps it recent)
        self._shard_counts: Dict[int, float] = {}
        self._shard_seen = 0.0
        self._closing = False
        self._server: Optional[asyncio.base_events.Server] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._state_lock: Optional[asyncio.Lock] = None  # swap/delta serial
        self._idle: Optional[asyncio.Event] = None
        self._closed: Optional[asyncio.Event] = None
        self.port: Optional[int] = None

    @property
    def batcher(self):
        """The edge's (default-model) AsyncBatcher — chaos.health wires a
        watchdog to its worker thread."""
        return self._batcher

    def _model_batcher(self, model_id: str):
        """Fleet mode: one AsyncBatcher per model, scoring through the
        router so canary/shadow interpose.  Built on first use; every
        batcher shares the fleet's one metrics registry."""
        b = self._batchers.get(model_id)
        if b is None:
            from photon_ml_tpu.serving.batcher import AsyncBatcher
            handle = self.fleet.handle(model_id)

            def score(reqs, _mid=model_id):
                return self.router.score(_mid, reqs,
                                         predict_mean=self.config.predict_mean)

            b = AsyncBatcher(
                score,
                flush_threshold=(self.config.flush_threshold
                                 or handle.engine.batcher.max_batch),
                deadline_s=self.config.batcher_deadline_s,
                metrics=handle.engine.metrics)
            self._batchers[model_id] = b
        return b

    def _all_batchers(self):
        seen = {id(self._batcher): self._batcher}
        for b in self._batchers.values():
            seen[id(b)] = b
        return list(seen.values())

    def _health_ready(self) -> bool:
        """Cached /readyz poll (throttled; config.health_poll_s).  No
        HealthState wired -> always ready (the pre-chaos edge)."""
        if self.health is None:
            return True
        now = time.monotonic()
        if (self._health_checked is None
                or now - self._health_checked >= self.config.health_poll_s):
            self._health_ok = bool(self.health.readyz()[0])
            self._health_checked = now
        return self._health_ok

    # -- lifecycle ---------------------------------------------------------
    async def start(self) -> "FrontendServer":
        self._loop = asyncio.get_running_loop()
        self._state_lock = asyncio.Lock()
        self._idle = asyncio.Event()
        self._idle.set()
        self._closed = asyncio.Event()
        self._server = await asyncio.start_server(
            self._on_connect, self.config.host, self.config.port)
        self.port = self._server.sockets[0].getsockname()[1]
        logger.info("photonfront listening on %s:%d (window %d, budget "
                    "%.1fms)", self.config.host, self.port, self._window,
                    self.config.admission.budget_s * 1e3)
        return self

    async def wait_closed(self) -> None:
        await self._closed.wait()

    async def aclose(self) -> None:
        """Graceful shutdown: stop accepting, drain in-flight work to
        completion, stop the batcher, close connections.  Idempotent."""
        if self._closing:
            await self._closed.wait()
            return
        self._closing = True
        if self._server is not None:
            self._server.close()
        async with self._state_lock:
            self._draining = True
            await self._drain()
        # batcher.shutdown joins its worker thread — off the loop
        for b in self._all_batchers():
            await self._loop.run_in_executor(
                None, lambda _b=b: _b.shutdown(drain=True))
        for conn in list(self._conns.values()):
            conn.replies.put_nowait(_CLOSE)
        if self._server is not None:
            await self._server.wait_closed()
        self._closed.set()

    # -- connection handling -----------------------------------------------
    async def _on_connect(self, reader: asyncio.StreamReader,
                          writer: asyncio.StreamWriter) -> None:
        act = _chaos_fault("front.conn")
        if act is not None:
            # chaos: the edge kills the connection before reading a byte —
            # nothing was admitted, so nothing can be lost; the client
            # retries against a fresh connection
            try:
                writer.close()
            except Exception:
                pass
            return
        cap = self.config.max_connections
        if cap is not None and len(self._conns) >= cap:
            self._registry.inc("front_connections_refused_total")
            obs_instant("front.refuse", connections=len(self._conns))
            try:
                writer.write(encode(
                    error_reply("too_many_connections", max_connections=cap)))
                await writer.drain()
            except (ConnectionError, OSError):
                pass
            finally:
                try:
                    writer.close()
                except Exception:
                    pass
            return
        with obs_span("front.accept"):
            peer = writer.get_extra_info("peername") or ("?", 0)
            self._conn_seq += 1
            cid = f"{peer[0]}:{peer[1]}#{self._conn_seq}"
            conn = _Conn(cid, reader, writer)
            self._conns[cid] = conn
            self._registry.inc("front_connections_total")
            self._registry.set_gauge("front_connections", len(self._conns))
        writer_task = asyncio.ensure_future(self._conn_writer(conn))
        try:
            await self._conn_reader(conn)
        except (ConnectionError, asyncio.IncompleteReadError):
            pass  # abrupt disconnect: same cleanup as EOF
        finally:
            conn.alive = False
            self._abort_queued(conn)
            conn.replies.put_nowait(_CLOSE)
            try:
                await writer_task
            except asyncio.CancelledError:
                pass
            self._conns.pop(cid, None)
            self._watch_exporters.pop(cid, None)
            self._admission.forget_client(cid)
            self._registry.set_gauge("front_connections", len(self._conns))
            self._registry.set_gauge("front_queue_depth", 0, client=cid)

    def _match_token(self, token: str) -> Tuple[bool, Optional[str]]:
        """(accepted, tenant): the global token admits unscoped; a tenant
        token admits scoped to its tenant.  EVERY candidate is compared
        (constant-time each) so which one matched is not timeable."""
        ok, tenant = False, None
        tok = token.encode("utf-8")
        if self.config.auth_token is not None and hmac.compare_digest(
                tok, self.config.auth_token.encode("utf-8")):
            ok = True
        for cand, t in (self.config.tenant_tokens or {}).items():
            if hmac.compare_digest(tok, cand.encode("utf-8")) and not ok:
                ok, tenant = True, t
        return ok, tenant

    async def _authenticate(self, conn: _Conn,
                            lines: BoundedLineReader) -> bool:
        """First-line shared-secret handshake.  Anything but a good token
        — wrong secret, malformed line, oversize, timeout — costs exactly
        one ``{"error": "unauthorized"}`` frame and the connection.  A
        tenant token scopes the connection to that tenant's models."""
        try:
            raw = await asyncio.wait_for(lines.readline(),
                                         self.config.auth_timeout_s)
        except (asyncio.TimeoutError, LineTooLong):
            raw = None
        token = ""
        if raw is not None:
            try:
                obj = json.loads(raw)
            except ValueError:
                obj = None
            if isinstance(obj, dict) and obj.get("cmd") == "auth" and \
                    isinstance(obj.get("token"), str):
                token = obj["token"]
        ok, tenant = self._match_token(token)
        if not ok:
            self._registry.inc("front_auth_failures_total")
            obs_instant("front.auth_fail", client=conn.cid)
            logger.warning("photonfront: rejected unauthenticated "
                           "connection %s", conn.cid)
            self._reply_now(conn, error_reply("unauthorized"))
            return False
        conn.tenant = tenant
        reply = {"auth": "ok"}
        if tenant is not None:
            reply["tenant"] = tenant
        self._reply_now(conn, reply)
        return True

    async def _conn_reader(self, conn: _Conn) -> None:
        lines = BoundedLineReader(conn.reader.read,
                                  self.config.max_line_bytes)
        if self.config.auth_token is not None or self.config.tenant_tokens:
            if not await self._authenticate(conn, lines):
                return
        while True:
            try:
                raw = await lines.readline()
            except LineTooLong as e:
                self._registry.inc("front_protocol_errors_total",
                                   kind="oversize")
                self._reply_now(conn, error_reply(str(e)))
                continue
            if raw is None:
                return  # EOF
            line = raw.strip()
            if not line:
                self._flush_conn(conn)  # blank line: force-flush (stdio
                continue                # parity, scoped to this client)
            try:
                obj = json.loads(line)
            except ValueError as e:
                self._registry.inc("front_protocol_errors_total",
                                   kind="json")
                self._reply_now(conn, error_reply(str(e)))
                continue
            cmd = obj.get("cmd") if isinstance(obj, dict) else None
            if cmd is not None:
                await self._handle_cmd(conn, cmd, obj)
            elif isinstance(obj, dict):
                self._handle_request(conn, obj)
            else:
                self._registry.inc("front_protocol_errors_total",
                                   kind="json")
                self._reply_now(conn, error_reply(
                    f"expected a JSON object, got {type(obj).__name__}"))

    async def _conn_writer(self, conn: _Conn) -> None:
        """Drain the reply queue in order; replies may be dicts, futures of
        dicts, or zero-arg callables evaluated at WRITE time (metrics/trace
        snapshots must reflect everything already replied to)."""
        try:
            while True:
                entry = await conn.replies.get()
                if entry is _CLOSE:
                    return
                if asyncio.isfuture(entry):
                    try:
                        entry = await entry
                    except asyncio.CancelledError:
                        continue
                if callable(entry):
                    entry = entry()
                if entry is None:
                    continue
                conn.writer.write(encode(entry))
                await conn.writer.drain()
        except (ConnectionError, asyncio.CancelledError):
            pass  # peer gone: stop writing, reader cleanup owns state
        finally:
            try:
                conn.writer.close()
            except Exception:
                pass

    # -- reply plumbing ----------------------------------------------------
    def _reply_now(self, conn: _Conn, obj: dict) -> None:
        conn.replies.put_nowait(obj)

    def _reply_future(self, conn: _Conn) -> asyncio.Future:
        fut = self._loop.create_future()
        conn.replies.put_nowait(fut)
        return fut

    # -- score-request path ------------------------------------------------
    def _resolve_fleet(self, conn: _Conn, req):
        """Fleet routing at admission: (handle, batcher, error_reply).
        ``None`` model -> the default handle, so pre-fleet clients work
        unchanged; an unknown model or a tenant-scope violation is an
        explicit error reply, never a shed (it would never succeed on
        retry)."""
        from photon_ml_tpu.serving.fleet.registry import UnknownModelError
        try:
            handle = self.fleet.resolve(req.model)
        except UnknownModelError:
            self._registry.inc("fleet_unknown_model_total")
            return None, None, error_reply("unknown_model", uid=req.uid,
                                           model=req.model)
        if conn.tenant is not None and handle.tenant != conn.tenant:
            self._registry.inc("fleet_forbidden_total", tenant=conn.tenant)
            return None, None, error_reply("forbidden", uid=req.uid,
                                           model=handle.model_id)
        return handle, self._model_batcher(handle.model_id), None

    def _handle_request(self, conn: _Conn, obj: dict) -> None:
        try:
            req = request_from_json(obj)
        except (ValueError, TypeError) as e:
            self._registry.inc("front_protocol_errors_total", kind="request")
            self._reply_now(conn, error_reply(str(e), uid=obj.get("uid")))
            return
        self._registry.inc("front_requests_total")
        handle, batcher, tenant = None, self._batcher, None
        if self.fleet is not None:
            handle, batcher, err = self._resolve_fleet(conn, req)
            if err is not None:
                self._reply_now(conn, err)
                return
            tenant = handle.tenant
        if self._draining or self._closing:
            self._shed(conn, req,
                       SHED_SHUTDOWN if self._closing else SHED_DRAINING,
                       self.config.admission.budget_s)
            return
        if handle is not None and handle.model_id in self._draining_models:
            # scoped barrier: only THIS model is quiescing (sibling
            # models keep admitting through their own batchers)
            self._shed(conn, req, SHED_DRAINING,
                       self.config.admission.budget_s)
            return
        if not self._health_ready():
            # /readyz-driven shedding: a not-ready plane (stalled worker,
            # stale catch-up, failed check) refuses work up front — the
            # client retries against a sibling instead of queueing here
            self._shed(conn, req, SHED_NOT_READY,
                       self.config.admission.budget_s)
            return
        estimate = batcher.queue_wait_estimate(extra=self._queue.depth())
        if self.config.admission.client_budget_s is not None:
            # the wait THIS client's own backlog explains: its fair-queue
            # depth over the shared batcher residue (other clients' queued
            # work is excluded — round-robin keeps it from billing here)
            client_wait = batcher.queue_wait_estimate(
                extra=self._queue.depth_of(conn.cid))
        else:
            client_wait = 0.0
        if (self.config.admission.tenant_budget_s is not None
                and tenant is not None):
            # the tenant's own backlog: its admitted-unsettled requests
            # over the model batcher's residue
            tenant_wait = batcher.queue_wait_estimate(
                extra=self._tenant_inflight.get(tenant, 0))
        else:
            tenant_wait = 0.0
        if self.config.admission.shard_budget_s is not None:
            shard, shard_wait = self._shard_pressure(handle, req, estimate)
        else:
            shard, shard_wait = None, 0.0
        verdict = self._admission.decide(
            estimate,
            client=conn.cid if self.config.admission.client_budget_s
            is not None else None,
            client_wait_s=client_wait,
            tenant=tenant, tenant_wait_s=tenant_wait,
            shard=shard, shard_wait_s=shard_wait)
        if not verdict.admitted:
            self._shed(conn, req, verdict.reason, verdict.predicted_wait_s,
                       verdict.retry_after_ms)
            return
        t0_ns = None
        if obs_enabled():
            # the propagation edge: adopt the context the request carried
            # on the wire ("tp", already parsed — garbage degraded to
            # None), or mint here at admission — every request, or every
            # Nth with sampled tracing (trace_sample_n); an unsampled
            # request proceeds untraced
            if req.ctx is None:
                req.ctx = ctx_maybe_mint(self.config.trace_sample_n) \
                    if self.config.trace_sample_n > 0 else ctx_mint()
            t0_ns = time.perf_counter_ns()
            with ctx_bind(req.ctx):
                obs_instant("front.admit", uid=req.uid, client=conn.cid,
                            predicted_wait_us=int(estimate * 1e6))
        if handle is not None:
            # per-tenant metric labels end to end: the admit is attributed
            # to its (model, tenant) pair
            self.engine.metrics.observe_fleet_request(handle.model_id,
                                                      tenant)
            self._tenant_inflight[tenant] = \
                self._tenant_inflight.get(tenant, 0) + 1
        self._inflight += 1
        self._idle.clear()
        self._track_admit(batcher)
        pending = _Pending(conn, req, self._reply_future(conn), t0_ns,
                           batcher=batcher, tenant=tenant)
        self._queue.enqueue(conn.cid, pending)
        self._registry.set_gauge("front_queue_depth",
                                 self._queue.depth_of(conn.cid),
                                 client=conn.cid)
        self._pump()

    def _shard_pressure(self, handle, req, estimate: float):
        """(shard, predicted wait) attributable to the mesh shard this
        request's hot-path work routes to — the admission signal for
        ``shard_budget_s``.  The wait model is the global backlog estimate
        scaled by the shard's share of recent admits times the shard
        count: uniform traffic gives every shard exactly the global
        estimate, a shard drawing k× its fair share shows k× the
        pressure.  Returns (None, 0.0) when the request has no shard
        affinity (unsharded store, unknown entity)."""
        engine = handle.engine if handle is not None else self.engine
        store = engine.store
        n = store.config.mesh_shards
        if n <= 1:
            return None, 0.0
        shard = store.shard_of_request(req.ids)
        if shard < 0:
            return None, 0.0
        self._shard_counts[shard] = self._shard_counts.get(shard, 0.0) + 1.0
        self._shard_seen += 1.0
        if self._shard_seen >= 512.0:  # halve: keep the share RECENT
            self._shard_counts = {s: c * 0.5
                                  for s, c in self._shard_counts.items()}
            self._shard_seen *= 0.5
        share = self._shard_counts[shard] / self._shard_seen
        wait = estimate * share * n
        engine.metrics.set_shard_pressure(shard, wait)
        return shard, wait

    def _track_admit(self, batcher) -> None:
        """Per-batcher admitted-unsettled count (scoped drain barrier)."""
        bid = id(batcher)
        self._batcher_inflight[bid] = self._batcher_inflight.get(bid, 0) + 1
        ev = self._batcher_idle.get(bid)
        if ev is None:
            ev = self._batcher_idle[bid] = asyncio.Event()
        ev.clear()

    def _track_settle(self, batcher) -> None:
        bid = id(batcher)
        left = self._batcher_inflight.get(bid, 1) - 1
        if left > 0:
            self._batcher_inflight[bid] = left
        else:
            self._batcher_inflight.pop(bid, None)
            ev = self._batcher_idle.get(bid)
            if ev is not None:
                ev.set()

    def _shed(self, conn: _Conn, req, reason: str, predicted_wait_s: float,
              retry_after_ms: Optional[float] = None) -> None:
        obs_instant("front.shed", uid=req.uid, client=conn.cid,
                    reason=reason)
        self._registry.inc("requests_shed_total", reason=reason)
        if retry_after_ms is None:
            retry_after_ms = self._admission.retry_after_ms(predicted_wait_s)
        self._reply_now(conn, {
            "uid": req.uid, "error": "overloaded", "reason": reason,
            "retry_after_ms": retry_after_ms,
            "predicted_wait_ms": round(predicted_wait_s * 1e3, 3)})

    def _pump(self) -> None:
        """Fill the dispatch window round-robin from the fair queue."""
        while self._outstanding < self._window:
            nxt = self._queue.next_item()
            if nxt is None:
                return
            cid, pending = nxt
            self._registry.set_gauge("front_queue_depth",
                                     self._queue.depth_of(cid), client=cid)
            self._dispatch(pending)

    def _dispatch(self, pending: _Pending) -> None:
        if pending.settled:
            return  # aborted while queued (connection died)
        try:
            fut = (pending.batcher or self._batcher).submit(pending.req)
        except RuntimeError as e:  # batcher already shut down
            self._settle(pending, error_reply(str(e), uid=pending.req.uid))
            return
        self._outstanding += 1
        fut.add_done_callback(
            lambda f: self._loop.call_soon_threadsafe(self._scored,
                                                      pending, f))

    def _scored(self, pending: _Pending, fut) -> None:
        self._outstanding -= 1
        if fut.cancelled():
            reply = error_reply("request cancelled at shutdown",
                                uid=pending.req.uid)
        else:
            exc = fut.exception()
            if exc is not None:
                reply = error_reply(str(exc), uid=pending.req.uid)
            else:
                # photonlint: disable=blocking-in-async -- `_scored` is
                # scheduled from the future's OWN done-callback, so the
                # future is already settled here and result() returns
                # without blocking
                reply = {"uid": pending.req.uid, "score": fut.result()}
        self._settle(pending, reply)
        self._pump()

    def _settle(self, pending: _Pending, reply: Optional[dict]) -> None:
        if pending.settled:
            return
        pending.settled = True
        if pending.t0_ns is not None:
            # explicit-timing span: admission and settle happen on
            # different event-loop ticks, so no `with` block can bracket
            # the request — this is the span that ENCLOSES the engine
            # flush on the merged timeline
            tracer = get_tracer()
            if tracer.enabled:
                with ctx_bind(pending.req.ctx):
                    tracer.complete(
                        "front.request", pending.t0_ns,
                        time.perf_counter_ns() - pending.t0_ns,
                        uid=pending.req.uid, client=pending.conn.cid)
        self._inflight -= 1
        self._track_settle(pending.batcher or self._batcher)
        if pending.tenant is not None:
            left = self._tenant_inflight.get(pending.tenant, 1) - 1
            if left > 0:
                self._tenant_inflight[pending.tenant] = left
            else:
                self._tenant_inflight.pop(pending.tenant, None)
        if self._inflight == 0:
            self._idle.set()
        if not pending.reply.done():
            pending.reply.set_result(reply)

    def _abort_queued(self, conn: _Conn) -> None:
        """Connection died: settle its queued-but-undispatched requests
        (dispatched ones resolve through the batcher as usual)."""
        for pending in self._queue.drop_client(conn.cid):
            self._settle(pending, None)

    def _flush_conn(self, conn: _Conn) -> None:
        """Blank-line semantics, scoped: THIS connection's queued requests
        go to the batcher now (ignoring the window) and the batcher
        flushes.  Other clients' backlogs stay in the fair queue — one
        client's flush must not launder another's firehose past the
        round-robin dispatcher."""
        for pending in self._queue.drop_client(conn.cid):
            self._dispatch(pending)
        self._registry.set_gauge("front_queue_depth", 0, client=conn.cid)
        for b in self._all_batchers():
            b.flush()

    def _flush_all(self) -> None:
        """Drain semantics: everything queued, every client, goes to its
        batcher now (ignoring the window) and every batcher flushes."""
        while True:
            nxt = self._queue.next_item()
            if nxt is None:
                break
            self._dispatch(nxt[1])
        for b in self._all_batchers():
            b.flush()

    # -- drain / control commands ------------------------------------------
    async def _drain(self) -> None:
        """Submit everything queued, flush, and wait until every admitted
        request has settled.  Callers hold ``_state_lock`` and have set
        ``_draining`` (so admission refuses new work meanwhile)."""
        with obs_span("front.drain", inflight=self._inflight,
                      queued=self._queue.depth()):
            self._registry.inc("front_drains_total")
            self._flush_all()
            if self._inflight:
                try:
                    await asyncio.wait_for(self._idle.wait(),
                                           self.config.drain_grace_s)
                except asyncio.TimeoutError:
                    logger.warning(
                        "drain grace (%.1fs) expired with %d in flight",
                        self.config.drain_grace_s, self._inflight)

    async def _drain_model(self, model_id: str) -> None:
        """Scoped drain: submit only ``model_id``'s queued requests (the
        rest go back to the fair queue), flush ITS batcher, and wait until
        its admitted requests settle.  Callers hold ``_state_lock`` and
        have added the model to ``_draining_models``."""
        batcher = self._model_batcher(model_id)
        bid = id(batcher)
        with obs_span("front.drain_model", model=model_id,
                      inflight=self._batcher_inflight.get(bid, 0)):
            self._registry.inc("front_drains_total")
            requeue = []
            while True:
                nxt = self._queue.next_item()
                if nxt is None:
                    break
                cid, pending = nxt
                if pending.batcher is batcher:
                    self._dispatch(pending)
                else:
                    requeue.append((cid, pending))
            for cid, pending in requeue:  # per-client FIFO order preserved
                self._queue.enqueue(cid, pending)
            batcher.flush()
            if self._batcher_inflight.get(bid, 0):
                try:
                    await asyncio.wait_for(self._batcher_idle[bid].wait(),
                                           self.config.drain_grace_s)
                except asyncio.TimeoutError:
                    logger.warning(
                        "model %s drain grace (%.1fs) expired with %d in "
                        "flight", model_id, self.config.drain_grace_s,
                        self._batcher_inflight.get(bid, 0))

    async def _quiesced(self, fn, model_id: Optional[str] = None):
        """Run ``fn`` (blocking, in the executor) with admission stopped
        and zero requests in flight — the swap/delta barrier.  With a
        ``model_id`` (fleet mode), the barrier is SCOPED: only that
        model's admission pauses and only its batcher drains, so an
        untouched sibling model keeps serving straight through a
        neighbor's swap/canary/promote."""
        async with self._state_lock:
            if model_id is None or self.fleet is None:
                self._draining = True
                try:
                    await self._drain()
                    return await self._loop.run_in_executor(None, fn)
                finally:
                    self._draining = False
            self._draining_models.add(model_id)
            try:
                await self._drain_model(model_id)
                return await self._loop.run_in_executor(None, fn)
            finally:
                self._draining_models.discard(model_id)

    def _cmd_target(self, obj: dict):
        """(swapper, store, model_id) a control command acts on: in fleet
        mode the optional ``"model"`` field routes to that handle
        (``UnknownModelError`` propagates to the caller's error reply);
        without a fleet, the single engine — byte-identical pre-fleet."""
        if self.fleet is not None:
            h = self.fleet.resolve(obj.get("model"))
            return h.swapper, h.engine.store, h.model_id
        return self.swapper, self.engine.store, None

    def _canary_policy(self, obj: dict):
        from photon_ml_tpu.serving.fleet.policy import CanaryPolicy
        kw = dict(self.config.canary_defaults or {})
        if obj.get("fraction") is not None:
            kw["fraction"] = float(obj["fraction"])
        if obj.get("min_observations") is not None:
            kw["min_observations"] = int(obj["min_observations"])
        if obj.get("max_drift") is not None:
            kw["max_drift"] = float(obj["max_drift"])
        return CanaryPolicy(**kw)

    def _load_store(self, model_dir: str, config):
        """Blocking (executor-side) bundle load for canary/shadow legs —
        built on the handle's own StoreConfig so the signature (and
        therefore the warmed executables) is shared with the active
        generation."""
        from photon_ml_tpu.serving.coefficient_store import CoefficientStore
        from photon_ml_tpu.storage.model_io import load_model_bundle
        bundle = load_model_bundle(model_dir)
        return CoefficientStore.from_bundle(bundle, config=config,
                                            version=model_dir,
                                            metrics=self.engine.metrics)

    async def _handle_cmd(self, conn: _Conn, cmd: str, obj: dict) -> None:
        if cmd == "swap":
            model_dir = obj.get("model_dir")
            if not model_dir:
                self._reply_now(conn, error_reply("swap needs model_dir"))
                return
            try:
                swapper, store, _mid = self._cmd_target(obj)
            except ValueError as e:
                self._reply_now(conn, error_reply(str(e)))
                return
            fut = self._reply_future(conn)
            ok = await self._quiesced(lambda: swapper.swap(model_dir),
                                      model_id=_mid)
            fut.set_result({
                "swap": "ok" if ok else "rejected",
                "generation": swapper.engine.store.generation,
                "version": swapper.engine.store.version,
                "delta_version": swapper.delta_version})
        elif cmd == "delta":
            try:
                swapper, store, _mid = self._cmd_target(obj)
            except ValueError as e:
                self._reply_now(conn, error_reply(str(e)))
                return
            fut = self._reply_future(conn)
            ok = await self._quiesced(
                lambda: swapper.apply_delta(obj.get("coordinate"),
                                            obj.get("entity"),
                                            obj.get("row") or ()),
                model_id=_mid)
            fut.set_result({"delta": "ok" if ok else "rejected",
                            "delta_version": swapper.delta_version})
        elif cmd == "rebalance":
            fut = self._reply_future(conn)
            if self.fleet is not None and obj.get("model") is None:
                # fleet-wide pass: every model, then the tenant-quota
                # invariant re-check + gauge export
                moves = await self._loop.run_in_executor(
                    None, self.fleet.rebalance)
                fut.set_result({"rebalance": {
                    mid: {cid: list(m) for cid, m in mm.items()}
                    for mid, mm in moves.items()}})
                return
            try:
                _swapper, store, _mid = self._cmd_target(obj)
            except ValueError as e:
                fut.set_result(error_reply(str(e)))
                return
            moves = await self._loop.run_in_executor(None, store.rebalance)
            fut.set_result({"rebalance": {cid: list(m)
                                          for cid, m in moves.items()}})
        elif cmd == "fleet":
            if self.router is None:
                self._reply_now(conn, error_reply(
                    "no fleet configured; run with --add-model"))
            else:
                self._reply_now(conn,
                                lambda: {"fleet": self.router.status()})
        elif cmd == "canary":
            if self.router is None:
                self._reply_now(conn, error_reply(
                    "no fleet configured; run with --add-model"))
                return
            model_dir = obj.get("model_dir")
            if not model_dir:
                self._reply_now(conn, error_reply("canary needs model_dir"))
                return
            try:
                handle = self.fleet.resolve(obj.get("model"))
                policy = self._canary_policy(obj)
            except ValueError as e:
                self._reply_now(conn, error_reply(str(e)))
                return
            fut = self._reply_future(conn)

            def _start():
                candidate = self._load_store(model_dir,
                                             handle.store.config)
                ctl = self.router.start_canary(handle.model_id, candidate,
                                               policy=policy,
                                               model_dir=model_dir)
                return ctl.status()

            try:
                status = await self._quiesced(_start,
                                              model_id=handle.model_id)
            except Exception as e:
                fut.set_result(error_reply(str(e)))
                return
            fut.set_result({"canary": status})
        elif cmd in ("promote", "rollback"):
            if self.router is None:
                self._reply_now(conn, error_reply(
                    "no fleet configured; run with --add-model"))
                return
            try:
                handle = self.fleet.resolve(obj.get("model"))
            except ValueError as e:
                self._reply_now(conn, error_reply(str(e)))
                return
            fut = self._reply_future(conn)

            def _ctl(_cmd=cmd, _mid=handle.model_id):
                if _cmd == "promote":
                    return self.router.promote(_mid).status()
                return self.router.rollback(
                    _mid, reason=obj.get("reason", "operator")).status()

            try:
                status = await self._quiesced(_ctl,
                                              model_id=handle.model_id)
            except ValueError as e:
                fut.set_result(error_reply(str(e)))
                return
            fut.set_result({cmd: status})
        elif cmd == "shadow":
            if self.router is None:
                self._reply_now(conn, error_reply(
                    "no fleet configured; run with --add-model"))
                return
            try:
                handle = self.fleet.resolve(obj.get("model"))
            except ValueError as e:
                self._reply_now(conn, error_reply(str(e)))
                return
            if obj.get("off"):
                fut = self._reply_future(conn)
                ok = await self._quiesced(
                    lambda: self.router.detach_shadow(handle.model_id),
                    model_id=handle.model_id)
                fut.set_result({"shadow": "off" if ok else "none",
                                "model": handle.model_id})
                return
            model_dir = obj.get("model_dir")
            if not model_dir:
                self._reply_now(conn, error_reply("shadow needs model_dir"))
                return
            fut = self._reply_future(conn)

            def _attach():
                store = self._load_store(model_dir, handle.store.config)
                self.router.attach_shadow(handle.model_id, store)
                return {"shadow": "on", "model": handle.model_id,
                        "version": store.version}

            try:
                reply = await self._quiesced(_attach,
                                             model_id=handle.model_id)
            except Exception as e:
                fut.set_result(error_reply(str(e)))
                return
            fut.set_result(reply)
        elif cmd == "metrics":
            # lazy: the snapshot is taken when the reply is WRITTEN, i.e.
            # after every earlier reply on this connection has resolved —
            # the stdio loop's flush-then-snapshot semantics
            for b in self._all_batchers():
                b.flush()
            if obj.get("format") == "prometheus":
                self._reply_now(conn, lambda: {
                    "prometheus": self.engine.metrics.to_prometheus()})
            else:
                self._reply_now(
                    conn, lambda: self.engine.metrics.snapshot())
        elif cmd == "trace":
            for b in self._all_batchers():
                b.flush()

            def _trace_reply():
                from photon_ml_tpu import obs

                tracer = obs.get_tracer()
                if not tracer.enabled:
                    return error_reply(
                        "tracing disabled; rerun with --trace")
                return tracer.chrome_trace()

            self._reply_now(conn, _trace_reply)
        elif cmd == "clock":
            # photonpulse ping-pong leg: t1 = receipt on our clock, t2 =
            # send time (lazy: stamped when the reply is actually written).
            # The caller combines them with its own t0/t3 to estimate the
            # offset between our perf_counter epochs (pulse.clock).
            t0 = obj.get("t0")
            t1 = pulse_clock.now_ns()
            who = get_process_label() or "frontend"
            self._reply_now(conn, lambda: {
                "clock": {"t0": t0, "t1": t1, "t2": pulse_clock.now_ns(),
                          "who": who}})
        elif cmd == "flight":
            recorder = get_flight()
            if recorder is None:
                self._reply_now(conn, error_reply(
                    "flight recorder not configured; rerun with "
                    "--flight-dir"))
            else:
                self._reply_now(conn,
                                lambda: {"flight": recorder.snapshot()})
        elif cmd == "watch":
            # photonwatch federation subscription: the first frame on a
            # connection is the full registry; every later ``watch`` gets
            # only the series that changed since (frames are lazy like
            # ``metrics``, snapshotted when the reply is written)
            for b in self._all_batchers():
                b.flush()
            exporter = self._watch_exporters.get(conn.cid)
            if exporter is None:
                from photon_ml_tpu.obs.watch import DeltaExporter
                exporter = self._watch_exporters[conn.cid] = DeltaExporter(
                    self._registry, label=get_process_label() or "frontend")
            self._reply_now(conn, lambda: {"watch": exporter.frame()})
        elif cmd == "shutdown":
            fut = self._reply_future(conn)
            fut.set_result({"shutdown": "ok",
                            "generation": self.engine.store.generation})
            asyncio.ensure_future(self.aclose())
        else:
            self._reply_now(conn, error_reply(f"unknown cmd {cmd!r}"))


class ThreadedFrontend:
    """Run a FrontendServer on a dedicated event-loop thread.

    The harness tests use: ``start()`` blocks until
    the socket is bound (``.port`` is then live), ``stop()`` runs the
    graceful drain and joins.  The CLI's asyncio main does NOT use this —
    it owns its loop; this exists for callers living in blocking code.
    """

    def __init__(self, engine: ScoringEngine,
                 swapper: Optional[HotSwapper] = None,
                 config: Optional[FrontendConfig] = None,
                 registry=None, fleet=None, health=None):
        self.server = FrontendServer(engine, swapper, config, registry,
                                     fleet=fleet, health=health)
        self._ready = threading.Event()
        self._error: Optional[BaseException] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="photonfront")

    @property
    def port(self) -> int:
        return self.server.port

    def _run(self) -> None:
        try:
            asyncio.run(self._main())
        except BaseException as e:  # startup failures surface in start()
            self._error = e
            self._ready.set()

    async def _main(self) -> None:
        try:
            await self.server.start()
        except BaseException as e:
            self._error = e
            self._ready.set()
            raise
        self._loop = asyncio.get_running_loop()
        self._ready.set()
        await self.server.wait_closed()

    def start(self, timeout: float = 30.0) -> "ThreadedFrontend":
        self._thread.start()
        if not self._ready.wait(timeout):
            raise RuntimeError("frontend did not start within "
                               f"{timeout}s")
        if self._error is not None:
            raise RuntimeError("frontend failed to start") from self._error
        return self

    def stop(self, timeout: float = 30.0) -> None:
        if self._loop is not None and self._thread.is_alive():
            asyncio.run_coroutine_threadsafe(self.server.aclose(),
                                             self._loop)
        self._thread.join(timeout)
