"""GAME online scoring driver — JSON-lines in, JSON-lines out.

Photon ML reference counterpart: there is none in the batch repo — the
reference's GameScoringDriver scores offline datasets; online traffic is
served by LinkedIn infrastructure reading the published PalDB stores.  This
driver IS that online layer for the TPU-native stack: it loads a training
output directory into a device-resident ``serving.CoefficientStore``,
AOT-warms the ``serving.ScoringEngine`` bucket ladder, then scores a
stream of JSON-lines requests through the ASYNC deadline batcher
(``serving.batcher.AsyncBatcher``: each request is submitted individually
and coalesces with its neighbors until a bucket fills or ``--deadline-us``
expires) and supports atomic hot model swap and streaming coefficient
deltas mid-stream.

Wire protocol (one JSON object per line on stdin / ``--requests`` file):

  request   {"uid": 7, "features": [{"name": "g0", "term": "", "value": 0.3},
             ...], "ids": {"userId": "user3"}, "offset": 0.0}
            (features also accept compact [name, value] / [name, term,
             value] lists)
  flush     a blank line — force-flush the batcher and drain every pending
            score (otherwise the batcher flushes whenever a top bucket
            fills or the deadline expires, and at EOF)
  swap      {"cmd": "swap", "model_dir": "/path/to/new/output"}
            -> {"swap": "ok"|"rejected", ...}; a rejected swap (corrupt or
            incomplete model dir) leaves the current version serving
  delta     {"cmd": "delta", "coordinate": "user", "entity": "user3",
             "row": [0.1, ...]}
            -> {"delta": "ok"|"rejected", "delta_version": n}; scatters one
            online-learned coefficient row into the live generation (device
            table when hot, host archive + LRU invalidation always) — no
            generation flip, no recompile
  rebalance {"cmd": "rebalance"} -> {"rebalance": {cid: [promoted,
            demoted]}}; one synchronous frequency-ranked hot-set pass (the
            background cadence is ``--hot-set-interval``)
  metrics   {"cmd": "metrics"} -> one metrics JSON line;
            {"cmd": "metrics", "format": "prometheus"} ->
            {"prometheus": "<text exposition>"} (the full labeled registry)
  trace     {"cmd": "trace"} -> one Chrome ``trace_event`` JSON line
            (load in Perfetto) covering the tracer ring buffer: submit ->
            batch flush -> resolve -> AOT execute spans; needs ``--trace``
            (otherwise -> {"error": ...})
  flight    {"cmd": "flight"} -> {"flight": {"spool_dir", "dumps",
            "latest"}} — the flight recorder's spool index plus the most
            recent degradation dump; needs ``--flight-dir`` (otherwise
            -> {"error": ...})
  watch     {"cmd": "watch"} -> {"watch": <snapshot frame>} — photonwatch
            federation: the first reply per stream is a full structured
            registry snapshot, every later one a delta of the series that
            moved since (obs/watch/federation.py); feed the frames to a
            ``FleetView`` (or ``tools/fleetwatch.py``) to aggregate many
            processes into one fleet registry

Responses are ``{"uid": ..., "score": ...}`` lines on stdout, in request
order.  Every command drains pending requests first, so everything
submitted before a swap/delta line scores on the pre-swap/pre-delta
coefficients.  Programmatic use: ``build_server`` returns the (engine,
swapper) pair without touching stdio.

``--listen host:port`` serves the SAME wire protocol over TCP instead of
stdio, through the ``serving.frontend`` edge: many concurrent clients,
deadline-budget admission control (``{"error": "overloaded",
"retry_after_ms": ...}`` when the predicted queue wait exceeds
``--admission-budget-ms``), per-client round-robin fairness, and graceful
drain on swap / ``{"cmd": "shutdown"}`` / SIGTERM.  ``--metrics-port``
additionally exposes ``GET /metrics`` (Prometheus text exposition) on
localhost in either mode.  Input lines in both modes are byte-bounded
(``--max-line-bytes``): an oversized line gets an ``{"error": ...}`` reply
and the stream keeps going.

``--delta-log DIR`` makes this process a photonlearn REPLICA: the
delta log a ``cli/learn.py`` trainer writes is replayed into the store
before serving, tailed on a background thread (``--delta-log-poll``), and
replayed onto every hot-swapped-in generation before it activates — so a
second serving process converges to the trainer's live coefficients with
no coordination beyond the shared log directory (see online/catchup.py).

``--add-model NAME=DIR[,tenant=T]`` (repeatable) turns the process into a
photonfleet node: the primary ``--model-dir`` registers under
``--model-name`` and every added directory becomes another model handle on
the SAME AOT kernel cache and device hot-row budget (``--fleet-budget``,
``--tenant-quota T=ROWS``).  Requests grow an optional ``"model"`` field
(absent -> the default model, so existing clients keep working), control
commands grow ``fleet`` / ``canary`` / ``promote`` / ``rollback`` /
``shadow`` plus ``"model"`` routing on swap/delta/rebalance, and in
``--listen`` mode ``--tenant-token T=TOK`` scopes connections to one
tenant's models while ``--tenant-budget-ms`` sheds a bursting tenant alone
(reason ``tenant_overload``).

``--subscribe host:port`` removes even that shared directory: the process
connects to a photonrepl owner (``learn.py --repl-listen``, or any
``online.replication.ReplicationServer``), bootstraps its base model from
a checksummed snapshot tarstream into ``--spool``, mirrors the owner's
live record stream into a local delta log there, and serves from the
mirror exactly as ``--delta-log`` would — including
replay-before-activate when the owner hot-swaps mid-stream (the new base
ships inline and this process swaps to it).  A restarted replica with a
warm spool resumes from its last applied identity (log replay when the
owner still retains it, fresh snapshot otherwise).  ``--auth-token``
(default ``$PHOTON_AUTH_TOKEN``) is presented to the owner AND required
of clients on ``--listen``.
"""

from __future__ import annotations

import argparse
import asyncio
import collections
import json
import logging
import os
import signal
import sys
from typing import IO, List, Optional, Sequence, Tuple

from photon_ml_tpu.obs.trace import span as obs_span
from photon_ml_tpu.serving.batcher import BucketedBatcher, request_from_json
from photon_ml_tpu.serving.frontend.protocol import (DEFAULT_MAX_LINE_BYTES,
                                                     LineTooLong,
                                                     iter_bounded_lines)
from photon_ml_tpu.serving.coefficient_store import (CoefficientStore,
                                                     HotSetManager,
                                                     StoreConfig)
from photon_ml_tpu.serving.engine import ScoringEngine
from photon_ml_tpu.serving.metrics import ServingMetrics
from photon_ml_tpu.serving.swap import HotSwapper
from photon_ml_tpu.storage.model_io import ModelLoadError, load_model_bundle

logger = logging.getLogger("photon_ml_tpu.serve")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="photon-tpu-serve",
                                description="Online scoring with a trained "
                                            "GAME model (JSON-lines)")
    p.add_argument("--model-dir", default="",
                   help="training output dir (best/, *.idx, *.entities.json) "
                        "or a model dir with metadata.json.  Required "
                        "unless --subscribe bootstraps the base instead")
    p.add_argument("--max-batch", type=int, default=64,
                   help="micro-batch flush threshold and top bucket size")
    p.add_argument("--buckets", default="",
                   help="comma list of bucket sizes (default: powers of two "
                        "up to --max-batch)")
    p.add_argument("--deadline-us", type=float, default=500.0,
                   help="async batcher deadline: a pending request waits at "
                        "most this long for a bucket to fill before its "
                        "batch flushes anyway")
    p.add_argument("--sync-batcher", action="store_true",
                   help="legacy synchronous batching: buffer requests and "
                        "flush at --max-batch / blank line / EOF instead of "
                        "the async deadline accumulator")
    p.add_argument("--device-entity-capacity", type=int, default=0,
                   help="max entity rows device-resident per coordinate "
                        "(0 = all; colder entities serve from the host LRU "
                        "fallback and rebalancing promotes the hottest)")
    p.add_argument("--mesh-shards", type=int, default=0,
                   help="partition every random-effect coefficient table "
                        "over this many devices (parallel/mesh.serving_mesh "
                        "axis 'shard'); 0 = unsharded.  When set, "
                        "--device-entity-capacity is the PER-SHARD hot-row "
                        "budget, so aggregate hot capacity scales with the "
                        "shard count")
    p.add_argument("--no-load-aware-routing", action="store_true",
                   help="freeze sharded entity->shard routing at the "
                        "round-robin (archive slot %% N) layout instead of "
                        "re-fitting it to observed traffic at each "
                        "rebalance — the pre-traffic-aware router, kept "
                        "for A/B comparison (scores are bitwise identical "
                        "either way; only placement and hit rate differ)")
    p.add_argument("--replicate-top-k", type=int, default=0,
                   help="give the K hottest entities hot residency on "
                        "EVERY mesh shard (reads stay shard-local, "
                        "streaming deltas fan out to all replicas under "
                        "one generation/delta_version) — flattens a zipf "
                        "head that one shard's hot budget cannot hold "
                        "(0 = off; needs --mesh-shards)")
    p.add_argument("--lru-capacity", type=int, default=4096,
                   help="host LRU entries per coordinate for cold entities")
    p.add_argument("--hot-set-interval", type=float, default=0.0,
                   help="seconds between background frequency-ranked "
                        "promotion/demotion passes (0 = only on "
                        "{\"cmd\": \"rebalance\"})")
    p.add_argument("--hot-decay", type=float, default=0.5,
                   help="EWMA decay applied to entity hit counters at each "
                        "rebalance pass")
    p.add_argument("--predict-mean", action="store_true",
                   help="emit inverse-link means instead of raw margins")
    p.add_argument("--no-warm", action="store_true",
                   help="skip AOT pre-compilation of the bucket ladder "
                        "(first request per bucket then pays the compile)")
    p.add_argument("--requests", default="-",
                   help="JSON-lines request file ('-' = stdin)")
    p.add_argument("--listen", default="",
                   help="host:port — serve the wire protocol over TCP "
                        "through the serving.frontend edge (admission "
                        "control, per-client fairness, graceful drain) "
                        "instead of stdio; port 0 picks an ephemeral port "
                        "(logged at startup)")
    p.add_argument("--metrics-port", type=int, default=0,
                   help="expose GET /metrics (Prometheus text exposition) "
                        "and /metrics.json on this localhost port "
                        "(0 = off; in --listen mode it shares the event "
                        "loop, in stdio mode it runs on a sidecar thread)")
    p.add_argument("--max-line-bytes", type=int,
                   default=DEFAULT_MAX_LINE_BYTES,
                   help="hard per-line byte bound on every input stream; "
                        "an oversized line is discarded with an "
                        "{\"error\": ...} reply and the stream survives")
    p.add_argument("--admission-budget-ms", type=float, default=50.0,
                   help="--listen mode: per-request deadline budget; "
                        "requests predicted to wait longer are shed with "
                        "{\"error\": \"overloaded\", \"retry_after_ms\"...}")
    p.add_argument("--resume-fraction", type=float, default=0.5,
                   help="--listen mode: hysteresis low watermark as a "
                        "fraction of the budget — shedding latches until "
                        "the predicted wait drops below this")
    p.add_argument("--dispatch-window", type=int, default=0,
                   help="--listen mode: max requests resident in the "
                        "batcher at once; the rest queue per-client where "
                        "round-robin fairness applies (0 = 2 flush waves)")
    p.add_argument("--client-budget-ms", type=float, default=0.0,
                   help="--listen mode: per-CONNECTION deadline budget — a "
                        "client whose own backlog is predicted to wait "
                        "longer is shed alone ({\"error\": \"overloaded\", "
                        "\"reason\": \"client_overload\"}) before the "
                        "global latch trips for everyone (0 = off)")
    p.add_argument("--max-connections", type=int, default=0,
                   help="--listen mode: hard connection-count cap; excess "
                        "accepts get one {\"error\": "
                        "\"too_many_connections\"} reply and a clean close "
                        "(0 = unlimited)")
    p.add_argument("--add-model", action="append", default=[],
                   metavar="NAME=DIR[,tenant=T]",
                   help="register an additional model directory as a fleet "
                        "handle (repeatable): shares the primary engine's "
                        "AOT kernel cache (same-shape models compile "
                        "nothing) and the --fleet-budget hot-row budget; "
                        "tenant defaults to 'default'")
    p.add_argument("--model-name", default="default",
                   help="fleet model id the primary --model-dir registers "
                        "under (only meaningful with --add-model)")
    p.add_argument("--fleet-budget", type=int, default=0,
                   help="fleet-wide device hot-row cap across every "
                        "model's hot tables (0 = unbudgeted); registration "
                        "that would exceed it is refused")
    p.add_argument("--tenant-quota", action="append", default=[],
                   metavar="TENANT=ROWS",
                   help="per-tenant carve-out of --fleet-budget "
                        "(repeatable); a tenant over quota cannot register "
                        "more models and rebalance re-verifies the "
                        "invariant")
    p.add_argument("--tenant-token", action="append", default=[],
                   metavar="TENANT=TOKEN",
                   help="--listen mode: auth token scoping a connection to "
                        "one tenant's models (repeatable; requests for "
                        "another tenant's model get {\"error\": "
                        "\"forbidden\"}).  Turns the auth handshake on "
                        "even without --auth-token")
    p.add_argument("--tenant-budget-ms", type=float, default=0.0,
                   help="--listen mode: per-TENANT deadline budget — a "
                        "tenant whose aggregate backlog is predicted to "
                        "wait longer is shed alone (reason "
                        "\"tenant_overload\") before the global latch "
                        "trips (0 = off)")
    p.add_argument("--shard-budget-ms", type=float, default=0.0,
                   help="--listen mode: per-MESH-SHARD deadline budget — "
                        "requests routed to a shard whose attributable "
                        "backlog is predicted to wait longer are shed "
                        "alone (reason \"shard_overload\") while the cool "
                        "shards keep admitting (0 = off; needs "
                        "--mesh-shards)")
    p.add_argument("--canary-fraction", type=float, default=0.25,
                   help="default traffic fraction a {\"cmd\": \"canary\"} "
                        "episode routes to the candidate (deterministic "
                        "request-key hash split, not RNG)")
    p.add_argument("--canary-min-observations", type=int, default=100,
                   help="default clean-observation window before a canary "
                        "auto-promotes")
    p.add_argument("--canary-max-drift", type=float, default=1e-6,
                   help="default mean |canary - control| score drift above "
                        "which a canary auto-rolls-back")
    p.add_argument("--trace-sample", type=int, default=0,
                   help="sampled always-on tracing: mint a photonpulse "
                        "trace context for every Nth request arriving "
                        "without one (0 = --listen mints for every "
                        "request; stdio mints only when sampling)")
    p.add_argument("--delta-log", default="",
                   help="FOLLOW a photonlearn delta log directory "
                        "(online/delta_log.py): replay it into the store "
                        "before serving, then tail it so rows a trainer "
                        "process publishes become visible here within "
                        "--delta-log-poll seconds; the log is read-only to "
                        "this process and hot swaps replay it onto the "
                        "incoming generation before activation")
    p.add_argument("--delta-log-poll", type=float, default=0.05,
                   help="seconds between delta-log tail polls")
    p.add_argument("--staleness-bound", type=float, default=5.0,
                   help="readiness (/readyz on --metrics-port): maximum "
                        "age of the last successful delta-log catch-up "
                        "pass before this replica reports not-ready; also "
                        "the watchdog's per-worker stall bound")
    p.add_argument("--subscribe", default="",
                   help="host:port of a photonrepl owner (learn.py "
                        "--repl-listen): bootstrap the base model from a "
                        "snapshot over the socket, then live-tail its "
                        "delta stream into a local mirror under --spool — "
                        "no shared directory.  Mutually exclusive with "
                        "--model-dir / --delta-log")
    p.add_argument("--spool", default="",
                   help="replica spool directory for --subscribe "
                        "(mirror log, extracted snapshot bases, resume "
                        "state); reusing it across restarts enables "
                        "identity-based resume")
    p.add_argument("--bootstrap-timeout", type=float, default=60.0,
                   help="--subscribe: seconds to wait for the first "
                        "snapshot (or a warm spool) before giving up")
    p.add_argument("--auth-token", default=None,
                   help="shared secret: presented to the --subscribe "
                        "owner AND required of --listen clients (first "
                        "line {\"cmd\": \"auth\", \"token\": ...}; "
                        "constant-time compare).  Default: "
                        "$PHOTON_AUTH_TOKEN")
    p.add_argument("--metrics-json", default="",
                   help="write the final metrics snapshot here at exit")
    p.add_argument("--trace", action="store_true",
                   help="enable the photonscope tracer (spans across "
                        "submit/flush/resolve/execute; {\"cmd\": \"trace\"} "
                        "dumps the ring buffer as Chrome trace JSON)")
    p.add_argument("--trace-buffer", type=int, default=8192,
                   help="tracer ring-buffer capacity (newest spans win)")
    p.add_argument("--trace-out", default="",
                   help="write the Chrome trace JSON here at exit "
                        "(implies --trace)")
    p.add_argument("--trace-label", default="",
                   help="photonpulse process label stamped on trace "
                        "exports and clock replies (default: 'replica' "
                        "with --subscribe, else 'frontend')")
    p.add_argument("--flight-dir", default="",
                   help="photonpulse flight recorder spool: on a "
                        "degradation transition (health check failure, "
                        "watchdog stall, admission shed latch) the tracer "
                        "ring is dumped here as Chrome trace JSON; "
                        "retrieve via {\"cmd\": \"flight\"} or "
                        "GET /flightz on --metrics-port")
    p.add_argument("--flight-max-bytes", type=int, default=16 << 20,
                   help="on-disk byte bound for the flight spool "
                        "(oldest dumps evicted first)")
    p.add_argument("--watch", action="store_true",
                   help="accepted and ignored: the {\"cmd\": \"watch\"} "
                        "federation stream and GET /watchz are always on, "
                        "and --slo runs the burn-rate sidecar")
    p.add_argument("--slo", default="", metavar="FILE",
                   help="photonwatch SLO objectives (JSON list, "
                        "obs/watch/slo.py): evaluate multi-window burn "
                        "rates against this process's registry on a "
                        "background thread, publishing "
                        "fleet_slo_burn_rate{slo=} / fleet_slo_alert{slo=} "
                        "and dumping the flight recorder on alert edges")
    p.add_argument("--slo-interval", type=float, default=1.0,
                   help="seconds between --slo evaluation passes")
    p.add_argument("--fleet-burn-budget", type=float, default=0.0,
                   help="--listen mode: shed new requests (reason "
                        "\"fleet_pressure\") while the largest published "
                        "fleet_slo_burn_rate gauge in this process's "
                        "registry exceeds this burn multiple — the hook a "
                        "fleetwatch aggregator (or a local --slo engine) "
                        "drives (0 = off)")
    p.add_argument("--exemplars", action="store_true",
                   help="attach trace-id exemplars to latency histogram "
                        "buckets; with --metrics-port the /metrics route "
                        "switches to OpenMetrics 1.0.0 exposition, the "
                        "format exemplars are specified in (pairs with "
                        "--trace: samples observed outside any trace "
                        "context carry no exemplar)")
    return p


def build_server(model_dir: str,
                 max_batch: int = 64,
                 bucket_sizes: Optional[Sequence[int]] = None,
                 device_entity_capacity: Optional[int] = None,
                 lru_capacity: int = 4096,
                 hot_decay: float = 0.5,
                 mesh_shards: int = 0,
                 metrics: Optional[ServingMetrics] = None,
                 warm: bool = True,
                 delta_log=None,
                 log_owner: bool = True,
                 load_aware_routing: bool = True,
                 replicate_top_k: int = 0
                 ) -> Tuple[ScoringEngine, HotSwapper]:
    """Programmatic entry point: load -> store -> engine (+ warmed ladder)
    -> swapper.  Raises storage.model_io.ModelLoadError on a broken dir.
    ``delta_log``/``log_owner`` attach an ``online.DeltaLog`` to the
    swapper (serving/swap.py module docstring for the owner/follower
    split)."""
    metrics = metrics or ServingMetrics()
    bundle = load_model_bundle(model_dir)
    config = StoreConfig(device_capacity=device_entity_capacity,
                         lru_capacity=lru_capacity, hot_decay=hot_decay,
                         mesh_shards=mesh_shards,
                         load_aware_routing=load_aware_routing,
                         replicate_top_k=replicate_top_k)
    store = CoefficientStore.from_bundle(bundle, config=config,
                                         version=model_dir, metrics=metrics)
    engine = ScoringEngine(store, BucketedBatcher(max_batch, bucket_sizes),
                           metrics=metrics)
    if warm:
        n = engine.warm()
        logger.info("warmed %d executable(s) over buckets %s", n,
                    engine.batcher.bucket_sizes)
    swapper = HotSwapper(engine, delta_log=delta_log, log_owner=log_owner)
    swapper.set_base(model_dir)  # snapshot source for photonrepl owners
    return engine, swapper


def _serve_stream(engine: ScoringEngine, swapper: HotSwapper, lines: IO,
                  out: IO, predict_mean: bool,
                  deadline_s: float = 500e-6,
                  sync: bool = False,
                  max_line_bytes: int = DEFAULT_MAX_LINE_BYTES,
                  fleet=None, health=None,
                  canary_defaults: Optional[dict] = None,
                  trace_sample_n: int = 0) -> int:
    """Drive the engine from a JSON-lines stream.

    Async (default): each request is submitted to an AsyncBatcher and its
    (uid, future) queued; completed scores are written opportunistically in
    submission order, and every command / blank line / EOF force-flushes
    and drains.  ``sync=True`` keeps the legacy buffer-then-score path.

    Fleet mode (``fleet=ModelFleet``): requests route by their optional
    ``"model"`` field to per-model AsyncBatchers scoring through a
    ``FleetRouter``, so canary episodes and shadow scorers interpose per
    model; the canary/promote/rollback/shadow/fleet commands drive them.
    """
    router = None
    batchers: dict = {}  # model_id -> AsyncBatcher (fleet mode)
    if fleet is not None:
        from photon_ml_tpu.serving.fleet.router import FleetRouter
        router = FleetRouter(fleet, health=health)
        if sync:
            logger.warning("--sync-batcher is ignored in fleet mode "
                           "(per-model async batchers)")
            sync = False
    pending: "collections.deque" = collections.deque()  # (uid, future)
    buffered: List = []  # sync mode only
    watch_exporter: List = []  # per-stream photonwatch DeltaExporter (lazy)
    batcher = None if (sync or fleet is not None) else engine.async_batcher(
        deadline_s=deadline_s, predict_mean=predict_mean)

    def model_batcher(model_id: str):
        b = batchers.get(model_id)
        if b is None:
            from photon_ml_tpu.serving.batcher import AsyncBatcher
            handle = fleet.handle(model_id)

            def score(reqs, _mid=model_id):
                return router.score(_mid, reqs, predict_mean=predict_mean)

            b = AsyncBatcher(score,
                             flush_threshold=handle.engine.batcher.max_batch,
                             deadline_s=deadline_s,
                             metrics=handle.engine.metrics)
            batchers[model_id] = b
        return b

    def all_batchers():
        if fleet is not None:
            return list(batchers.values())
        return [] if batcher is None else [batcher]

    def cmd_target(obj):
        """(swapper, store) a control command acts on: the optional
        ``"model"`` field routes in fleet mode.  None after writing the
        error reply for an unknown model."""
        if fleet is None:
            return swapper, engine.store
        try:
            h = fleet.resolve(obj.get("model"))
        except ValueError as e:
            out.write(json.dumps({"error": str(e)}) + "\n")
            out.flush()
            return None
        return h.swapper, h.engine.store

    def emit(uid, fut) -> None:
        with obs_span("serve.respond", uid=uid):
            try:
                out.write(json.dumps({"uid": uid,
                                      "score": fut.result()}) + "\n")
            except Exception as e:  # scoring error: the request's own line
                out.write(json.dumps({"uid": uid, "error": str(e)}) + "\n")

    def drain(block: bool) -> None:
        wrote = False
        while pending and (block or pending[0][1].done()):
            emit(*pending.popleft())
            wrote = True
        if wrote:
            out.flush()

    def flush() -> None:
        if sync:
            if not buffered:
                return
            scores = engine.score_requests(buffered,
                                           predict_mean=predict_mean)
            for req, s in zip(buffered, scores):
                out.write(json.dumps({"uid": req.uid,
                                      "score": float(s)}) + "\n")
            out.flush()
            buffered.clear()
        else:
            for b in all_batchers():
                b.flush()
            drain(block=True)

    try:
        for line in iter_bounded_lines(lines, max_line_bytes):
            if isinstance(line, LineTooLong):
                # oversized line: already discarded through its newline by
                # the bounded reader — reply and keep serving
                logger.error("dropped oversized line: %s", line)
                out.write(json.dumps({"error": str(line)}) + "\n")
                out.flush()
                continue
            line = line.strip()
            if not line:
                flush()
                continue
            try:
                obj = json.loads(line)
            except ValueError as e:
                logger.error("bad request line: %s", e)
                out.write(json.dumps({"error": str(e)}) + "\n")
                continue
            cmd = obj.get("cmd") if isinstance(obj, dict) else None
            if cmd == "swap":
                flush()  # everything buffered scores on the pre-swap version
                target = cmd_target(obj)
                if target is None:
                    continue
                tsw, _tstore = target
                ok = tsw.swap(obj["model_dir"])
                out.write(json.dumps({
                    "swap": "ok" if ok else "rejected",
                    "generation": tsw.engine.store.generation,
                    "version": tsw.engine.store.version,
                    "delta_version": tsw.delta_version}) + "\n")
                out.flush()
            elif cmd == "delta":
                flush()  # pending requests score pre-delta coefficients
                target = cmd_target(obj)
                if target is None:
                    continue
                tsw, _tstore = target
                ok = tsw.apply_delta(obj.get("coordinate"),
                                     obj.get("entity"),
                                     obj.get("row") or ())
                out.write(json.dumps({
                    "delta": "ok" if ok else "rejected",
                    "delta_version": tsw.delta_version}) + "\n")
                out.flush()
            elif cmd == "rebalance":
                if fleet is not None and obj.get("model") is None:
                    moves = fleet.rebalance()
                    out.write(json.dumps({"rebalance": {
                        mid: {cid: list(m) for cid, m in mm.items()}
                        for mid, mm in moves.items()}}) + "\n")
                    out.flush()
                    continue
                target = cmd_target(obj)
                if target is None:
                    continue
                _tsw, tstore = target
                moves = tstore.rebalance()
                out.write(json.dumps({"rebalance": {
                    cid: list(m) for cid, m in moves.items()}}) + "\n")
                out.flush()
            elif cmd == "fleet":
                flush()
                if router is None:
                    out.write(json.dumps({"error": "no fleet configured; "
                                          "run with --add-model"}) + "\n")
                else:
                    out.write(json.dumps({"fleet": router.status()}) + "\n")
                out.flush()
            elif cmd == "canary":
                flush()  # the episode starts with zero requests in flight
                if router is None:
                    out.write(json.dumps({"error": "no fleet configured; "
                                          "run with --add-model"}) + "\n")
                else:
                    try:
                        handle = fleet.resolve(obj.get("model"))
                        policy = _canary_policy_from(obj, canary_defaults)
                        candidate = _load_fleet_store(
                            engine, obj["model_dir"], handle.store.config)
                        ctl = router.start_canary(
                            handle.model_id, candidate, policy=policy,
                            model_dir=obj["model_dir"])
                        out.write(json.dumps({"canary": ctl.status()})
                                  + "\n")
                    except (KeyError, ValueError, ModelLoadError) as e:
                        out.write(json.dumps({"error": str(e)}) + "\n")
                out.flush()
            elif cmd in ("promote", "rollback"):
                flush()  # settle with zero requests in flight (quiesce)
                if router is None:
                    out.write(json.dumps({"error": "no fleet configured; "
                                          "run with --add-model"}) + "\n")
                else:
                    try:
                        handle = fleet.resolve(obj.get("model"))
                        if cmd == "promote":
                            ctl = router.promote(handle.model_id)
                        else:
                            ctl = router.rollback(
                                handle.model_id,
                                reason=obj.get("reason", "operator"))
                        out.write(json.dumps({cmd: ctl.status()}) + "\n")
                    except ValueError as e:
                        out.write(json.dumps({"error": str(e)}) + "\n")
                out.flush()
            elif cmd == "shadow":
                flush()
                if router is None:
                    out.write(json.dumps({"error": "no fleet configured; "
                                          "run with --add-model"}) + "\n")
                else:
                    try:
                        handle = fleet.resolve(obj.get("model"))
                        if obj.get("off"):
                            ok = router.detach_shadow(handle.model_id)
                            out.write(json.dumps(
                                {"shadow": "off" if ok else "none",
                                 "model": handle.model_id}) + "\n")
                        else:
                            store = _load_fleet_store(
                                engine, obj["model_dir"],
                                handle.store.config)
                            router.attach_shadow(handle.model_id, store)
                            out.write(json.dumps(
                                {"shadow": "on", "model": handle.model_id,
                                 "version": store.version}) + "\n")
                    except (KeyError, ValueError, ModelLoadError) as e:
                        out.write(json.dumps({"error": str(e)}) + "\n")
                out.flush()
            elif cmd == "metrics":
                flush()
                if obj.get("format") == "prometheus":
                    out.write(json.dumps(
                        {"prometheus": engine.metrics.to_prometheus()}) + "\n")
                else:
                    out.write(engine.metrics.to_json() + "\n")
                out.flush()
            elif cmd == "trace":
                flush()  # pending spans (flush/execute) land in the ring
                from photon_ml_tpu import obs

                tracer = obs.get_tracer()
                if not tracer.enabled:
                    out.write(json.dumps(
                        {"error": "tracing disabled; rerun with --trace"})
                        + "\n")
                else:
                    out.write(json.dumps(tracer.chrome_trace()) + "\n")
                out.flush()
            elif cmd == "flight":
                from photon_ml_tpu.obs.pulse import get_flight

                recorder = get_flight()
                if recorder is None:
                    out.write(json.dumps(
                        {"error": "flight recorder not configured; rerun "
                                  "with --flight-dir"}) + "\n")
                else:
                    out.write(json.dumps(
                        {"flight": recorder.snapshot()}) + "\n")
                out.flush()
            elif cmd == "watch":
                flush()  # pending work lands in the counters first
                if not watch_exporter:
                    from photon_ml_tpu.obs.trace import get_process_label
                    from photon_ml_tpu.obs.watch import DeltaExporter

                    watch_exporter.append(DeltaExporter(
                        engine.metrics.registry,
                        label=get_process_label() or "serve"))
                out.write(json.dumps(
                    {"watch": watch_exporter[0].frame()}) + "\n")
                out.flush()
            elif cmd is not None:
                out.write(json.dumps({"error": f"unknown cmd {cmd!r}"}) + "\n")
            else:
                try:
                    req = request_from_json(obj)
                except (ValueError, TypeError) as e:
                    logger.error("bad request: %s", e)
                    out.write(json.dumps({"error": str(e)}) + "\n")
                    continue
                if trace_sample_n > 0 and req.ctx is None:
                    # sampled always-on tracing: deterministic 1-in-N
                    # context minting at the admission edge
                    from photon_ml_tpu.obs.pulse import maybe_mint
                    req.ctx = maybe_mint(trace_sample_n)
                if fleet is not None:
                    try:
                        handle = fleet.resolve(req.model)
                    except ValueError:
                        out.write(json.dumps(
                            {"uid": req.uid, "error": "unknown_model",
                             "model": req.model}) + "\n")
                        out.flush()
                        continue
                    engine.metrics.observe_fleet_request(handle.model_id,
                                                         handle.tenant)
                    pending.append((req.uid,
                                    model_batcher(handle.model_id)
                                    .submit(req)))
                    drain(block=False)
                elif sync:
                    buffered.append(req)
                    if len(buffered) >= engine.batcher.max_batch:
                        flush()
                else:
                    pending.append((req.uid, batcher.submit(req)))
                    drain(block=False)
        flush()
    finally:
        for b in all_batchers():
            b.shutdown(drain=True)
        if not sync:
            drain(block=True)
    return 0


def _parse_listen(listen: str) -> Tuple[str, int]:
    host, sep, port = listen.rpartition(":")
    if not sep:
        raise ValueError(f"wanted host:port, got {listen!r}")
    return host or "127.0.0.1", int(port)


def _auth_token(args: argparse.Namespace) -> Optional[str]:
    """--auth-token, falling back to $PHOTON_AUTH_TOKEN (empty = unset)."""
    if args.auth_token is not None:
        return args.auth_token or None
    return os.environ.get("PHOTON_AUTH_TOKEN") or None


def _parse_add_model(spec: str) -> Tuple[str, str, str]:
    """``NAME=DIR[,tenant=T]`` -> (name, dir, tenant)."""
    name, sep, rest = spec.partition("=")
    if not sep or not name or not rest:
        raise ValueError(
            f"--add-model wants NAME=DIR[,tenant=T], got {spec!r}")
    path, tenant = rest, "default"
    if ",tenant=" in rest:
        path, _, tenant = rest.partition(",tenant=")
    if not path or not tenant:
        raise ValueError(
            f"--add-model wants NAME=DIR[,tenant=T], got {spec!r}")
    return name, path, tenant


def _parse_pairs(specs: Sequence[str], flag: str) -> dict:
    """Repeatable ``KEY=VALUE`` flags -> dict."""
    out = {}
    for spec in specs:
        key, sep, value = spec.partition("=")
        if not sep or not key or not value:
            raise ValueError(f"{flag} wants KEY=VALUE, got {spec!r}")
        out[key] = value
    return out


def _canary_defaults(args: argparse.Namespace) -> dict:
    """CLI-level CanaryPolicy defaults for ``{"cmd": "canary"}`` lines."""
    return {"fraction": args.canary_fraction,
            "min_observations": args.canary_min_observations,
            "max_drift": args.canary_max_drift}


def _load_fleet_store(engine: ScoringEngine, model_dir: str,
                      config: StoreConfig) -> CoefficientStore:
    """Load a canary/shadow leg on the handle's own StoreConfig, so its
    signature — and therefore its warmed executables — is shared with the
    active generation."""
    bundle = load_model_bundle(model_dir)
    return CoefficientStore.from_bundle(bundle, config=config,
                                        version=model_dir,
                                        metrics=engine.metrics)


def _canary_policy_from(obj: dict, defaults: Optional[dict] = None):
    """CanaryPolicy for a ``{"cmd": "canary"}`` line: CLI defaults under
    per-command overrides."""
    from photon_ml_tpu.serving.fleet.policy import CanaryPolicy
    kw = dict(defaults or {})
    for key, cast in (("fraction", float), ("min_observations", int),
                      ("max_drift", float)):
        if obj.get(key) is not None:
            kw[key] = cast(obj[key])
    return CanaryPolicy(**kw)


def _run_network(engine: ScoringEngine, swapper: HotSwapper,
                 args: argparse.Namespace, health=None,
                 watchdog=None, fleet=None) -> int:
    """--listen mode: the serving.frontend edge on an asyncio loop this
    process owns, with an optional same-loop /metrics scrape endpoint and
    SIGTERM/SIGINT wired to the graceful drain."""
    from photon_ml_tpu.serving.frontend.admission import AdmissionConfig
    from photon_ml_tpu.serving.frontend.metrics_http import MetricsEndpoint
    from photon_ml_tpu.serving.frontend.server import (FrontendConfig,
                                                       FrontendServer)

    host, port = _parse_listen(args.listen)
    tenant_tokens = {tok: tenant for tenant, tok in
                     _parse_pairs(args.tenant_token,
                                  "--tenant-token").items()}
    config = FrontendConfig(
        host=host, port=port,
        max_line_bytes=args.max_line_bytes,
        admission=AdmissionConfig(
            budget_s=args.admission_budget_ms * 1e-3,
            resume_fraction=args.resume_fraction,
            client_budget_s=(args.client_budget_ms * 1e-3
                             if args.client_budget_ms else None),
            tenant_budget_s=(args.tenant_budget_ms * 1e-3
                             if args.tenant_budget_ms else None),
            shard_budget_s=(args.shard_budget_ms * 1e-3
                            if args.shard_budget_ms else None),
            fleet_burn_budget=(args.fleet_burn_budget or None)),
        batcher_deadline_s=args.deadline_us * 1e-6,
        dispatch_window=(args.dispatch_window or None),
        predict_mean=args.predict_mean,
        max_connections=(args.max_connections or None),
        auth_token=_auth_token(args),
        tenant_tokens=tenant_tokens or None,
        trace_sample_n=args.trace_sample,
        canary_defaults=_canary_defaults(args))

    async def _main() -> int:
        front = FrontendServer(engine, swapper, config, fleet=fleet,
                               health=health)
        await front.start()
        if watchdog is not None:
            # the edge batcher exists only after start(): watch it too
            front.batcher.watch = watchdog.register(
                "batcher", front.batcher.worker_thread)
        scrape = None
        if args.metrics_port:
            scrape = await MetricsEndpoint(
                engine.metrics, port=args.metrics_port,
                health=health, exemplars=args.exemplars).start()
            logger.info("metrics scrape on http://127.0.0.1:%d/metrics "
                        "(+ /healthz, /readyz)", scrape.port)
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                loop.add_signal_handler(
                    sig, lambda: asyncio.ensure_future(front.aclose()))
            except (NotImplementedError, RuntimeError, ValueError):
                pass  # non-main thread / platform without signal support
        try:
            await front.wait_closed()
        finally:
            if scrape is not None:
                await scrape.aclose()
        return 0

    return asyncio.run(_main())


def run(argv: List[str]) -> int:
    logging.basicConfig(level=logging.INFO, stream=sys.stderr,
                        format="%(asctime)s %(name)s %(message)s")
    args = build_parser().parse_args(argv)

    from photon_ml_tpu.utils.runtime import init_runtime

    init_runtime(logger)

    if args.trace or args.trace_out:
        from photon_ml_tpu import obs

        obs.enable_tracing(capacity=args.trace_buffer)
        logger.info("tracing enabled (ring capacity %d)", args.trace_buffer)

    from photon_ml_tpu.obs import pulse

    pulse.configure(args.trace_label or
                    ("replica" if args.subscribe else "frontend"))
    if args.flight_dir:
        pulse.set_flight(pulse.FlightRecorder(
            args.flight_dir, max_bytes=args.flight_max_bytes))
        logger.info("flight recorder spooling to %s (cap %d bytes)",
                    args.flight_dir, args.flight_max_bytes)
    if args.exemplars:
        from photon_ml_tpu.obs.registry import enable_exemplars

        enable_exemplars(True)

    buckets = None
    if args.buckets:
        buckets = [int(b) for b in args.buckets.split(",") if b.strip()]

    client = None
    metrics = None
    model_dir = args.model_dir
    delta_log = None
    if args.subscribe:
        if args.model_dir or args.delta_log:
            logger.error("--subscribe is mutually exclusive with "
                         "--model-dir / --delta-log (the subscription "
                         "provides both the base and the delta feed)")
            return 1
        if not args.spool:
            logger.error("--subscribe needs --spool DIR (mirror log + "
                         "snapshot bases + resume state live there)")
            return 1
        from photon_ml_tpu.online.delta_log import DeltaLog
        from photon_ml_tpu.online.replication import (
            ReplicationClient, ReplicationClientConfig)

        metrics = ServingMetrics()
        try:
            host, port = _parse_listen(args.subscribe)
        except ValueError as e:
            logger.error("--subscribe: %s", e)
            return 1
        client = ReplicationClient(
            ReplicationClientConfig(host=host, port=port,
                                    spool_dir=args.spool,
                                    auth_token=_auth_token(args)),
            registry=metrics.registry).start()
        logger.info("subscribing to photonrepl owner %s:%d (spool %s)",
                    host, port, args.spool)
        try:
            model_dir = client.bootstrap(timeout=args.bootstrap_timeout)
        except RuntimeError as e:
            logger.error("--subscribe: %s", e)
            client.stop()
            return 1
        logger.info("photonrepl bootstrap: base %s (owner floor gen %s)",
                    model_dir, client.floor)
        # the mirror is OURS but the swapper must treat it as a follower
        # log: identities in it belong to the owner, and the replication
        # client is its only writer/compactor
        delta_log = DeltaLog(client.mirror_path, fsync="never")
    elif not args.model_dir:
        logger.error("--model-dir is required (or --subscribe)")
        return 1
    elif args.delta_log:
        from photon_ml_tpu.online.delta_log import DeltaLog

        # follower role: this process never appends (its process-local
        # generation numbers would corrupt the writer's identity order)
        # and never compacts; fsync is moot for a pure reader
        delta_log = DeltaLog(args.delta_log, fsync="never")
    try:
        engine, swapper = build_server(
            model_dir,
            max_batch=args.max_batch,
            bucket_sizes=buckets,
            device_entity_capacity=(args.device_entity_capacity or None),
            lru_capacity=args.lru_capacity,
            hot_decay=args.hot_decay,
            mesh_shards=args.mesh_shards,
            warm=not args.no_warm,
            metrics=metrics,
            delta_log=delta_log,
            log_owner=False,
            load_aware_routing=not args.no_load_aware_routing,
            replicate_top_k=args.replicate_top_k)
    except (ModelLoadError, ValueError) as e:
        logger.error("--model-dir: %s", e)
        if client is not None:
            client.stop()
        return 1
    logger.info("serving generation %d (version %r), task %s",
                engine.store.generation, engine.store.version,
                engine.store.task.value)

    # photonwatch: every process exports who it is
    from photon_ml_tpu.obs.registry import export_build_info

    export_build_info(engine.metrics.registry,
                      role="replica" if args.subscribe else "frontend")

    if client is not None:
        swapper.set_base(model_dir, client.floor or 0)
        # owner hot swap mid-stream: the client extracts the shipped base
        # and we swap to it; replay_floor is the OWNER's generation for
        # that base, so replay-before-activate off the mirror skips
        # records the snapshot supersedes
        client.on_snapshot = \
            lambda d, g: swapper.swap(d, replay_floor=g)
        if client.model_dir != model_dir:
            # a snapshot landed between bootstrap() and the wiring above —
            # catch up now instead of serving a base the owner replaced
            swapper.swap(client.model_dir, replay_floor=client.floor)

    follower = None
    if delta_log is not None:
        from photon_ml_tpu.online.catchup import LogFollower

        follower = LogFollower(delta_log, lambda: engine.store,
                               poll_interval_s=args.delta_log_poll,
                               registry=engine.metrics.registry)
        stats = follower.run_once()  # initial catch-up BEFORE serving
        logger.info("delta-log catch-up: applied %d, rejected %d "
                    "(position %s); following %s every %.3fs",
                    stats.applied, stats.rejected, stats.position,
                    delta_log.path, args.delta_log_poll)
        follower.start()

    hotset = None
    if args.hot_set_interval > 0:
        hotset = HotSetManager(lambda: engine.store,
                               interval_s=args.hot_set_interval).start()
        logger.info("hot-set rebalancing every %.3fs", args.hot_set_interval)

    # readiness surface (/readyz on --metrics-port): engine warmed AND the
    # delta feed writable/fresh AND no registered worker stalled.  Built
    # unconditionally — cheap, and tests read it in-process.
    from photon_ml_tpu.chaos.health import (HealthState, Watchdog,
                                            delta_log_check,
                                            follower_staleness_check)

    health = HealthState(registry=engine.metrics.registry)
    watchdog = Watchdog(stall_after_s=args.staleness_bound,
                        registry=engine.metrics.registry)
    health.add_check("workers", watchdog.check)
    health.set_condition(
        "engine_warmed", True,
        "warm skipped (--no-warm)" if args.no_warm
        else "bucket ladder compiled at startup")
    if delta_log is not None:
        health.add_check("delta_log", delta_log_check(delta_log))
    if follower is not None:
        health.add_check("catchup", follower_staleness_check(
            follower, args.staleness_bound))
        follower.watch = watchdog.register("follower",
                                           follower.worker_thread)
    if client is not None:
        watchdog.register("subscriber", client.worker_thread)

    fleet = None
    if args.add_model:
        from photon_ml_tpu.serving.fleet import FleetError, ModelFleet

        try:
            quotas = {t: int(v) for t, v in
                      _parse_pairs(args.tenant_quota,
                                   "--tenant-quota").items()}
            fleet = ModelFleet(metrics=engine.metrics,
                               total_rows=(args.fleet_budget or None),
                               quotas=quotas)
            # the primary engine's warmed kernel cache becomes the fleet
            # cache; every added model's engine is built on it
            fleet.adopt(args.model_name, engine, swapper)
            for spec in args.add_model:
                name, path, tenant = _parse_add_model(spec)
                fleet.register_dir(name, path, tenant=tenant,
                                   config=engine.store.config)
                logger.info("fleet: registered model %r from %s "
                            "(tenant %r)", name, path, tenant)
        except (FleetError, ModelLoadError, ValueError) as e:
            logger.error("--add-model: %s", e)
            if follower is not None:
                follower.stop()
            if client is not None:
                client.stop()
            return 1
        logger.info("fleet: %d model(s), %d shared executable(s), "
                    "%d compile(s)", len(fleet), len(fleet.kernels),
                    fleet.kernels.compile_count)

    slo_thread = None
    if args.slo:
        from photon_ml_tpu.obs.watch import SLOEngine, SLOEvalThread, load_slos

        try:
            slos = load_slos(args.slo)
        except (OSError, ValueError) as e:
            logger.error("--slo: %s", e)
            if follower is not None:
                follower.stop()
            if client is not None:
                client.stop()
            return 1
        slo_thread = SLOEvalThread(SLOEngine(slos),
                                   lambda: engine.metrics.registry,
                                   interval_s=args.slo_interval).start()
        logger.info("photonwatch: evaluating %d SLO(s) every %.3fs",
                    len(slos), args.slo_interval)

    metrics_sidecar = None
    try:
        if args.listen:
            rc = _run_network(engine, swapper, args, health=health,
                              watchdog=watchdog, fleet=fleet)
        else:
            if args.metrics_port:
                from photon_ml_tpu.serving.frontend.metrics_http import \
                    ThreadedMetricsEndpoint

                metrics_sidecar = ThreadedMetricsEndpoint(
                    engine.metrics, port=args.metrics_port,
                    health=health, exemplars=args.exemplars).start()
                logger.info("metrics scrape on http://127.0.0.1:%d/metrics"
                            " (+ /healthz, /readyz)", metrics_sidecar.port)
            lines = sys.stdin if args.requests == "-" \
                else open(args.requests)
            try:
                rc = _serve_stream(engine, swapper, lines, sys.stdout,
                                   args.predict_mean,
                                   deadline_s=args.deadline_us * 1e-6,
                                   sync=args.sync_batcher,
                                   max_line_bytes=args.max_line_bytes,
                                   fleet=fleet, health=health,
                                   canary_defaults=_canary_defaults(args),
                                   trace_sample_n=args.trace_sample)
            finally:
                if lines is not sys.stdin:
                    lines.close()
    finally:
        if slo_thread is not None:
            slo_thread.stop()
        if follower is not None:
            follower.stop()
        if client is not None:
            client.stop()
        if metrics_sidecar is not None:
            metrics_sidecar.stop()
        if hotset is not None:
            hotset.stop()
        if args.metrics_json:
            engine.metrics.export(args.metrics_json)
            logger.info("metrics -> %s", args.metrics_json)
        if args.trace_out:
            from photon_ml_tpu import obs

            obs.get_tracer().export_chrome_trace(args.trace_out)
            logger.info("trace -> %s", args.trace_out)
    return rc


def main() -> None:
    sys.exit(run(sys.argv[1:]))


if __name__ == "__main__":
    main()
