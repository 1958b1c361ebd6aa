"""Request micro-batcher with padding to a fixed bucket ladder.

Photon ML reference counterpart: the Spark GameTransformer scores whatever
partition sizes the RDD hands it — shape polymorphism is free on CPU.  On an
accelerator every new batch shape is a fresh XLA compile, so the online path
pads each micro-batch up to a SMALL FIXED LADDER of bucket sizes (the same
power-of-two idiom ``parallel/bucketing.py`` uses for per-entity sample
capacities) and every request shape lands on an already-compiled executable
(serving/engine.py).  Padded rows carry zero features and slot -1, so they
are inert through the scoring contraction and are sliced off before results
leave the engine.

Also home to the request schema: ``Request`` (parsed, array-ready) and
``request_from_json`` — the JSON-lines wire format of ``cli/serve.py``,
whose feature triples flow through the SAME (name, term) -> column mapping
``data/reader.read_game_data_avro`` applies to training records, so online
features land in exactly the training columns.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import Future
from typing import (Callable, Dict, List, Optional, Sequence, Tuple)

import numpy as np

from photon_ml_tpu.data.index_map import IndexMap
from photon_ml_tpu.obs.pulse.context import bind as ctx_bind
from photon_ml_tpu.obs.pulse.context import from_wire as ctx_from_wire
from photon_ml_tpu.obs.trace import enabled as obs_enabled
from photon_ml_tpu.obs.trace import instant as obs_instant
from photon_ml_tpu.obs.trace import span as obs_span


def pow2_bucket_ladder(max_batch: int, min_bucket: int = 1) -> Tuple[int, ...]:
    """1, 2, 4, ... up to (and including) the next power of two >= max_batch
    — the same rounding rule as ``parallel/bucketing._capacity_classes``."""
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    top = 1 << (max_batch - 1).bit_length()
    if min_bucket > top:
        # a ladder whose only rung is below min_bucket can't hold any batch
        # the caller promised to send — fail loudly instead of under-bucketing
        raise ValueError(
            f"min_bucket {min_bucket} exceeds the top bucket {top} implied "
            f"by max_batch {max_batch}")
    ladder = []
    b = max(1, min_bucket)
    while b < top:
        ladder.append(b)
        b <<= 1
    ladder.append(top)
    return tuple(ladder)


@dataclasses.dataclass
class Request:
    """One scoring request, array-ready.

    ``features``: ONE name/term/value triple list shared by every feature
    shard (exactly like a TrainingExampleAvro record — each shard's index
    map picks out the columns it knows).  ``ids``: id-tag -> entity string
    (reference GameDatum idTagToValueMap).  ``offset``: added to the raw
    margin, never part of the model score.  ``ctx``: optional photonpulse
    trace context — minted at the frontend edge or adopted from the wire
    ``"tp"`` field — carried with the request into the batcher so the
    flush that scores it joins the same cross-process trace.  ``model``:
    optional fleet model id (wire ``"model"`` field); ``None`` routes to
    the default model, which is what every pre-fleet client sends.
    """

    uid: object = None
    features: Sequence[dict] = ()
    ids: Dict[str, str] = dataclasses.field(default_factory=dict)
    offset: float = 0.0
    ctx: Optional[Tuple[str, str]] = None
    model: Optional[str] = None


def request_from_json(obj: dict) -> Request:
    """Wire JSON -> Request.  Accepts features as NTV triple dicts
    ([{"name": ..., "term": ..., "value": ...}, ...]) or compact
    [name, value] / [name, term, value] lists."""
    feats = []
    for f in obj.get("features") or ():
        if isinstance(f, dict):
            feats.append(f)
        elif isinstance(f, (list, tuple)) and len(f) == 2:
            feats.append({"name": f[0], "term": "", "value": f[1]})
        elif isinstance(f, (list, tuple)) and len(f) == 3:
            feats.append({"name": f[0], "term": f[1], "value": f[2]})
        else:
            raise ValueError(f"unparseable feature entry {f!r}")
    ids = {str(k): str(v) for k, v in (obj.get("ids") or {}).items()}
    # optional trace context: a malformed/torn "tp" decodes to None (the
    # request proceeds untraced); skipped entirely when tracing is off
    ctx = None
    tp = obj.get("tp")
    if tp is not None and obs_enabled():
        ctx = ctx_from_wire(tp)
    # optional fleet model id; absent -> None -> the default model, so
    # pre-fleet clients keep working unchanged
    model = obj.get("model")
    if model is not None:
        model = str(model)
    return Request(uid=obj.get("uid"), features=feats, ids=ids,
                   offset=float(obj.get("offset") or 0.0), ctx=ctx,
                   model=model)


def densify_features(requests: Sequence[Request], index_maps: Dict[str, IndexMap],
                     n_rows: int, dtype=np.float32) -> Dict[str, np.ndarray]:
    """Requests -> one padded dense [n_rows, d_shard] matrix per shard.

    Mirrors data/reader.read_game_data_avro's record loop exactly: intercept
    column filled with 1, features accumulated through
    ``IndexMap.get_index(name, term)``, unknown features dropped.  Rows
    beyond ``len(requests)`` stay all-zero (padding; inert through every
    scoring contraction).  Shards sharing one IndexMap object share ONE
    matrix (the reader's aliasing trick).
    """
    mats: Dict[str, np.ndarray] = {}
    by_map: Dict[int, np.ndarray] = {}
    for shard, m in index_maps.items():
        x = by_map.get(id(m))
        if x is None:
            x = np.zeros((n_rows, m.size), dtype)
            ii = m.intercept_index
            if ii is not None:
                x[: len(requests), ii] = 1.0
            for i, req in enumerate(requests):
                for feat in req.features:
                    j = m.get_index(feat["name"], feat.get("term") or "")
                    if j >= 0:
                        x[i, j] += feat["value"]
            by_map[id(m)] = x
        mats[shard] = x
    return mats


@dataclasses.dataclass(frozen=True)
class MicroBatch:
    """One planned launch: requests[start:stop] padded to ``bucket`` rows."""

    start: int
    stop: int
    bucket: int

    @property
    def real_rows(self) -> int:
        return self.stop - self.start


class BucketedBatcher:
    """Split a request stream into bucket-padded micro-batches.

    ``bucket_sizes``: the compiled-shape ladder (default: powers of two up
    to ``max_batch``).  A chunk of n requests pads to the smallest bucket
    >= n; streams longer than the top bucket split into top-bucket chunks
    first (full buckets have zero padding waste, so the tail is the only
    waste source — the padding-waste metric tracks it).
    """

    def __init__(self, max_batch: int = 64,
                 bucket_sizes: Optional[Sequence[int]] = None):
        if bucket_sizes is None:
            bucket_sizes = pow2_bucket_ladder(max_batch)
        sizes = sorted(set(int(b) for b in bucket_sizes))
        if not sizes or sizes[0] < 1:
            raise ValueError(f"invalid bucket sizes {bucket_sizes!r}")
        self.bucket_sizes: Tuple[int, ...] = tuple(sizes)
        self.max_batch = self.bucket_sizes[-1]

    def bucket_for(self, n: int) -> int:
        """Smallest bucket >= n (n must not exceed the top bucket)."""
        for b in self.bucket_sizes:
            if b >= n:
                return b
        raise ValueError(f"batch of {n} exceeds top bucket {self.max_batch}")

    def plan(self, n_requests: int) -> List[MicroBatch]:
        """Cut n requests into launches: full top-bucket chunks, then one
        padded tail chunk."""
        plan: List[MicroBatch] = []
        start = 0
        while start < n_requests:
            chunk = min(self.max_batch, n_requests - start)
            plan.append(MicroBatch(start=start, stop=start + chunk,
                                   bucket=self.bucket_for(chunk)))
            start += chunk
        return plan

    def padding_rows(self, plan: Sequence[MicroBatch]) -> int:
        return sum(mb.bucket - mb.real_rows for mb in plan)


class AsyncBatcher:
    """Thread-safe deadline-or-full micro-batch accumulator.

    The synchronous ``BucketedBatcher`` API makes the CALLER responsible for
    batch formation — at low QPS every caller hands over a near-singleton
    list and pays the pow2 ladder's padding tax.  This accumulator inverts
    that: callers ``submit`` ONE request at a time and get a
    ``concurrent.futures.Future`` back; a worker thread flushes the pending
    set whenever it reaches ``flush_threshold`` (the engine's top bucket —
    a zero-padding launch) OR the OLDEST pending request has waited
    ``deadline_s`` (default 500µs), whichever comes first.  Concurrent
    low-QPS streams therefore coalesce into high-occupancy buckets, and no
    request waits longer than one deadline for company.

    ``score_fn`` receives the drained request list and returns one score
    per request (``ScoringEngine.score_requests`` — which still splits
    oversized drains along the bucket ladder); each future resolves to its
    request's float score, or to the scoring exception.

    Flush accounting (per-flush, into ``metrics`` when given):
    ``flushes_full`` (threshold reached), ``flushes_deadline`` (deadline
    expired first), ``flushes_forced`` (explicit ``flush()`` / shutdown
    drain) — the occupancy story of a deployment in one ratio.

    Introspection for admission control (serving/frontend): the worker
    keeps an EWMA of observed flush wall times and marks when a flush is in
    progress, so ``queue_wait_estimate`` can predict how long a request
    arriving NOW would wait — the in-flight flush's remainder, plus one
    EWMA per queued flush wave, plus the residual deadline if the tail wave
    would not fill.  All of it reads/writes under ``self._cond`` like every
    other batcher attribute.
    """

    _EWMA_ALPHA = 0.2  # flush-cost smoothing: ~5-flush memory

    def __init__(self, score_fn: Callable[[Sequence[Request]], np.ndarray],
                 flush_threshold: int,
                 deadline_s: float = 500e-6,
                 metrics=None,
                 name: str = "photon-serving-batcher"):
        if flush_threshold < 1:
            raise ValueError(
                f"flush_threshold must be >= 1, got {flush_threshold}")
        if deadline_s < 0:
            raise ValueError(f"deadline_s must be >= 0, got {deadline_s}")
        self._score = score_fn
        self.flush_threshold = int(flush_threshold)
        self.deadline_s = float(deadline_s)
        self._metrics = metrics
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._pending: List[Tuple[Request, Future]] = []
        self._first_ts: Optional[float] = None  # arrival of oldest pending
        self._force = False
        self._closed = False
        self._flush_ewma_s: Optional[float] = None  # observed flush cost
        self._inflight_since: Optional[float] = None  # flush in progress
        # optional chaos.health.WorkerWatch: wraps each flush so a
        # watchdog can flip readiness on a wedged scorer
        self.watch = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=name)
        self._thread.start()

    @property
    def worker_thread(self) -> threading.Thread:
        """The flush worker — what a chaos.health.Watchdog registers."""
        return self._thread

    # -- producer side -----------------------------------------------------
    def submit(self, request: Request) -> "Future[float]":
        """Enqueue one request; returns the future its score resolves on."""
        if request.ctx is not None:
            with ctx_bind(request.ctx):
                obs_instant("serve.submit", uid=request.uid)
        else:
            obs_instant("serve.submit", uid=request.uid)
        fut: Future = Future()
        with self._cond:
            if self._closed:
                raise RuntimeError("AsyncBatcher is shut down")
            self._pending.append((request, fut))
            if self._first_ts is None:
                self._first_ts = time.perf_counter()
            self._cond.notify()
        return fut

    def flush(self) -> List[Future]:
        """Force an immediate flush of whatever is pending; returns the
        pending futures (callers wait on those, not on this call)."""
        with self._cond:
            futs = [f for _, f in self._pending]
            if self._pending:
                self._force = True
                self._cond.notify()
        return futs

    def pending_count(self) -> int:
        """Requests accumulated and not yet handed to a flush."""
        with self._cond:
            return len(self._pending)

    def flush_cost_estimate(self) -> float:
        """EWMA of observed flush wall times (scoring one wave); the
        deadline is the optimistic floor until the first flush lands."""
        with self._cond:
            return self._flush_cost_locked()

    def _flush_cost_locked(self) -> float:
        # photonlint: disable=alias-escape -- returns a float (EWMA
        # sample); the _locked suffix is the calling convention: every
        # caller already holds self._cond
        return (self._flush_ewma_s if self._flush_ewma_s is not None
                else self.deadline_s)

    def queue_wait_estimate(self, extra: int = 0) -> float:
        """Predicted seconds until a request arriving NOW resolves, given
        ``extra`` requests queued ahead of it outside the batcher (the
        front end's fair queue).  The admission controller's input.

        Components: the in-flight flush's unfinished remainder; one flush
        cost per wave the backlog fills; the residual deadline wait when
        the tail wave would flush non-full.
        """
        with self._cond:
            now = time.perf_counter()
            ewma = self._flush_cost_locked()
            ahead = len(self._pending) + max(0, int(extra))
            est = 0.0
            if self._inflight_since is not None:
                est += max(0.0, ewma - (now - self._inflight_since))
            waves, tail = divmod(ahead + 1, self.flush_threshold)
            if tail:
                waves += 1
                est += self.deadline_s  # non-full tail waits out the clock
            est += waves * ewma
            return est

    def shutdown(self, drain: bool = True,
                 timeout: Optional[float] = None) -> None:
        """Stop the worker.  ``drain=True`` scores everything still pending
        first (every outstanding future resolves); ``drain=False`` cancels
        pending futures.  Idempotent; ``submit`` raises afterwards."""
        with self._cond:
            if not self._closed:
                self._closed = True
                if not drain:
                    for _, f in self._pending:
                        f.cancel()
                    self._pending = []
                    self._first_ts = None
                self._cond.notify_all()
        self._thread.join(timeout)

    def __enter__(self) -> "AsyncBatcher":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown(drain=True)

    # -- worker side -------------------------------------------------------
    def _run(self) -> None:
        while True:
            with self._cond:
                while not self._pending and not self._closed:
                    self._cond.wait()
                if not self._pending:
                    return  # closed and drained
                deadline = self._first_ts + self.deadline_s
                while (not self._force and not self._closed
                       and len(self._pending) < self.flush_threshold):
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        break
                    self._cond.wait(timeout=remaining)
                batch = self._pending
                self._pending = []
                self._first_ts = None
                forced, self._force = self._force, False
                closed = self._closed
                self._inflight_since = time.perf_counter()
            if self.watch is not None:
                with self.watch.busy():
                    self._flush_batch(batch, forced=forced or closed)
            else:
                self._flush_batch(batch, forced=forced or closed)
            with self._cond:
                dt = time.perf_counter() - self._inflight_since
                self._inflight_since = None
                prev = self._flush_ewma_s
                self._flush_ewma_s = dt if prev is None else (
                    (1.0 - self._EWMA_ALPHA) * prev + self._EWMA_ALPHA * dt)

    def _flush_batch(self, batch: List[Tuple[Request, Future]],
                     forced: bool) -> None:
        if not batch:
            return
        full = len(batch) >= self.flush_threshold
        if self._metrics is not None:
            self._metrics.inc("flushes_full" if full else
                              "flushes_forced" if forced else
                              "flushes_deadline")
        live = [(r, f) for r, f in batch if f.set_running_or_notify_cancel()]
        if not live:
            return
        attrs = {"n": len(live), "reason": ("full" if full else
                                            "forced" if forced else
                                            "deadline")}
        if obs_enabled():
            # one flush serves many requests: record EVERY trace id it
            # scores so tracemerge can attach the span to each trace
            tids = sorted({r.ctx[0] for r, _ in live if r.ctx is not None})
            if tids:
                attrs["traces"] = tids
        # waiters wake only after the span closes, so a request span that
        # awaits its score strictly encloses serve.flush in the timeline
        err: Optional[Exception] = None
        with obs_span("serve.flush", **attrs):
            try:
                scores = self._score([r for r, _ in live])
            except Exception as e:  # resolve waiters, never kill the worker
                err = e
        if err is not None:
            for _, f in live:
                f.set_exception(err)
            return
        for (_, f), s in zip(live, scores):
            f.set_result(float(s))
