"""photonrepl log server: the delta-log owner's replication endpoint.

One asyncio TCP server runs next to the log owner (``cli/learn.py
--repl-listen``, or in-process in tests).  Each subscriber gets:

  - **Identity-based resume.**  The subscribe hello carries the client's
    last applied ``(generation, delta_version)`` and the base-generation
    ``floor`` it bootstrapped at.  When the floor matches the owner's and
    the retained log covers the identity, the server replays forward from
    the log (``repl_resume_total{mode="log"}``); otherwise the client gets
    a fresh snapshot bootstrap (``mode="snapshot"``): the owner's model
    directory as a checksummed tarstream, followed by every retained
    record of the current base lineage.
  - **Live tailing.**  A ``DeltaLog`` append listener fans each published
    record into per-follower BOUNDED queues.  A follower that cannot keep
    up overflows its queue and is switched to log catch-up — it re-reads
    the records it missed from the durable log, then rejoins the live
    stream.  Memory per follower is bounded by the queue, not by the
    slowest consumer.
  - **In-stream hot swap.**  When the owner activates a new generation
    (``HotSwapper`` calls :meth:`ReplicationServer.note_generation`), each
    follower's sender finishes draining the pre-swap records its current
    base can still use, then ships the NEW snapshot inline and continues
    with post-swap records — the replica hot-swaps with
    replay-before-activate off its mirror, never missing an update.
  - **Retention floor.**  The server installs a ``retention_pin`` on the
    owner's log: compaction keeps segments at or above the minimum
    generation a connected follower still needs (its last acknowledged
    identity).  Byte and age caps bound the pin — a follower that stops
    acking, or whose pinned segments exceed the byte budget, is EVICTED
    (one ``{"repl": "restart"}`` frame, connection closed) and falls back
    to snapshot bootstrap on reconnect, so one dead follower can never pin
    the log forever.

Auth: with ``ReplicationConfig.auth_token`` set, the subscribe hello must
carry the shared secret; the compare is constant-time and a failed hello
gets exactly one ``{"error": "unauthorized"}`` frame before the close.

Metrics (photonscope registry): ``repl_followers`` gauge,
``repl_follower_lag_records`` / ``repl_follower_lag_bytes`` per-peer
gauges (queued + sent-but-unacknowledged), ``repl_records_sent_total``,
``repl_bytes_sent_total``, ``repl_snapshots_total``,
``repl_snapshot_bytes_total``, ``repl_resume_total{mode=log|snapshot}``,
``repl_evictions_total{reason=...}``, ``repl_auth_failures_total``.
"""

from __future__ import annotations

import asyncio
import collections
import dataclasses
import hmac
import logging
import os
import threading
import time
from typing import Callable, Deque, Dict, Optional, Tuple

from photon_ml_tpu.chaos.injector import fault as _chaos_fault
from photon_ml_tpu.obs.pulse import clock as pulse_clock
from photon_ml_tpu.obs.pulse.context import delta_ctx as pulse_delta_ctx
from photon_ml_tpu.obs.pulse.context import forwarded as ctx_forwarded
from photon_ml_tpu.obs.pulse.context import to_wire as ctx_to_wire
from photon_ml_tpu.obs.trace import enabled as obs_enabled
from photon_ml_tpu.obs.trace import get_process_label
from photon_ml_tpu.obs.trace import instant as obs_instant
from photon_ml_tpu.online.delta_log import DeltaLog, DeltaRecord
from photon_ml_tpu.online.replication.snapshot import (SnapshotError,
                                                       pack_model_dir)
from photon_ml_tpu.online.replication.wire import (WireError,
                                                   encode_record_line,
                                                   parse_identity, parse_line)
from photon_ml_tpu.serving.frontend.protocol import (DEFAULT_MAX_LINE_BYTES,
                                                     BoundedLineReader,
                                                     LineTooLong, encode,
                                                     error_reply)

logger = logging.getLogger("photon_ml_tpu.online.replication")

_WAKE = object()  # queue sentinel: re-check floor/catch-up state


@dataclasses.dataclass(frozen=True)
class ReplicationConfig:
    """Owner-side replication policy knobs."""

    host: str = "127.0.0.1"
    port: int = 0  # 0 -> ephemeral; ReplicationServer.port holds the binding
    auth_token: Optional[str] = None
    max_line_bytes: int = DEFAULT_MAX_LINE_BYTES
    hello_timeout_s: float = 10.0
    # live fan-out queue bound per follower; overflow switches the
    # follower to log catch-up (it misses nothing — the log is durable)
    queue_records: int = 1024
    # retention-pin caps: a follower pinning sub-floor segments is evicted
    # when the pinned bytes pass pin_byte_cap or its last ack is older
    # than pin_age_cap_s
    pin_byte_cap: int = 64 << 20
    pin_age_cap_s: float = 300.0
    snapshot_chunk: int = 1 << 16
    housekeeping_interval_s: float = 15.0


class _Follower:
    """Per-subscriber state, owned by the event loop."""

    __slots__ = ("fid", "peer", "writer", "queue", "sent", "acked",
                 "acked_at", "floor", "need_catchup", "alive",
                 "queued_bytes", "unacked", "unacked_bytes", "evicted")

    def __init__(self, fid: int, peer: str,
                 writer: asyncio.StreamWriter, queue_bound: int):
        self.fid = fid
        self.peer = peer
        self.writer = writer
        self.queue: asyncio.Queue = asyncio.Queue(maxsize=queue_bound)
        self.sent: Optional[Tuple[int, int]] = None
        self.acked: Optional[Tuple[int, int]] = None
        self.acked_at = time.monotonic()
        self.floor: Optional[int] = None  # base generation the client holds
        self.need_catchup = True
        self.alive = True
        self.queued_bytes = 0
        # (identity, frame bytes) sent but not yet acknowledged
        self.unacked: Deque[Tuple[Tuple[int, int], int]] = collections.deque()
        self.unacked_bytes = 0
        self.evicted: Optional[str] = None  # eviction reason, once decided

    def pin_generation(self) -> Optional[int]:
        """Oldest generation this follower still needs from the log."""
        if self.acked is not None:
            return self.acked[0]
        return self.floor


class ReplicationServer:
    """Asyncio replication endpoint for one delta log (module docstring).

    ``snapshot_source`` returns the owner's current
    ``(model_dir, base_generation)`` — the directory the serving store was
    built from and the generation it was activated at.  For a trainer
    owner that never hot-swaps, the base generation is the floor below
    which no log record exists to a subscriber's benefit (usually 0: the
    whole log applies to the base).
    """

    def __init__(self, log: DeltaLog,
                 config: Optional[ReplicationConfig] = None,
                 snapshot_source: Optional[
                     Callable[[], Tuple[str, int]]] = None,
                 base_generation: int = 0,
                 registry=None):
        self.log = log
        self.config = config or ReplicationConfig()
        self._snapshot_source = snapshot_source
        self._registry = registry
        self._base_generation = int(base_generation)
        self._followers: Dict[int, _Follower] = {}
        self._fid_seq = 0
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._server: Optional[asyncio.base_events.Server] = None
        self._closed: Optional[asyncio.Event] = None
        self._closing = False
        self._housekeeper: Optional[asyncio.Task] = None
        self.port: Optional[int] = None
        # cross-thread view for the retention pin (compaction runs on the
        # trainer/swap thread): fid -> (pin generation, last ack monotonic)
        self._pin_lock = threading.Lock()
        self._pin_view: Dict[int, Tuple[Optional[int], float]] = {}

    # -- lifecycle ---------------------------------------------------------
    async def start(self) -> "ReplicationServer":
        self._loop = asyncio.get_running_loop()
        self._closed = asyncio.Event()
        self._server = await asyncio.start_server(
            self._on_connect, self.config.host, self.config.port)
        self.port = self._server.sockets[0].getsockname()[1]
        self.log.add_listener(self._on_append)
        self.log.retention_pin = self.retention_floor
        self._housekeeper = asyncio.ensure_future(self._housekeeping())
        logger.info("photonrepl listening on %s:%d (queue %d records, pin "
                    "caps %d bytes / %.0fs)", self.config.host, self.port,
                    self.config.queue_records, self.config.pin_byte_cap,
                    self.config.pin_age_cap_s)
        return self

    async def wait_closed(self) -> None:
        await self._closed.wait()

    async def aclose(self) -> None:
        if self._closing:
            await self._closed.wait()
            return
        self._closing = True
        self.log.remove_listener(self._on_append)
        if self.log.retention_pin is self.retention_floor:
            self.log.retention_pin = None
        if self._housekeeper is not None:
            self._housekeeper.cancel()
        if self._server is not None:
            self._server.close()
        for f in list(self._followers.values()):
            self._close_follower(f)
        if self._server is not None:
            await self._server.wait_closed()
        self._closed.set()

    # -- owner-side hooks (foreign threads) --------------------------------
    def _on_append(self, record: DeltaRecord) -> None:
        """DeltaLog append listener — runs on the publisher's thread."""
        if self._loop is not None and not self._closing:
            self._loop.call_soon_threadsafe(self._fanout, record)

    def note_generation(self, generation: int) -> None:
        """The owner activated a new base (hot swap).  Raise the base
        floor and wake every sender so laggards drain + re-snapshot
        in-stream.  Thread-safe."""
        if self._loop is None:
            self._base_generation = max(self._base_generation,
                                        int(generation))
            return
        self._loop.call_soon_threadsafe(self._note_generation_locked,
                                        int(generation))

    def _note_generation_locked(self, generation: int) -> None:
        if generation <= self._base_generation:
            return
        self._base_generation = generation
        obs_instant("repl.generation", generation=generation,
                    followers=len(self._followers))
        for f in self._followers.values():
            self._nudge(f)

    def retention_floor(self) -> Optional[int]:
        """Compaction pin: the minimum generation a connected,
        well-behaved follower still needs — or None when nothing pins.
        Called from the owner's swap thread via ``DeltaLog.compact``;
        applies the byte/age caps and schedules evictions for followers
        that fail them."""
        now = time.monotonic()
        with self._pin_lock:
            pins = {fid: pin for fid, (pin, acked_at) in
                    self._pin_view.items()
                    if pin is not None and
                    now - acked_at <= self.config.pin_age_cap_s}
            stale = [fid for fid, (pin, acked_at) in self._pin_view.items()
                     if pin is not None and pin < self._base_generation and
                     now - acked_at > self.config.pin_age_cap_s]
        for fid in stale:
            self._evict(fid, "ack_age")
        while pins:
            floor = min(pins.values())
            if floor >= self._base_generation:
                return floor
            cost = sum(
                os.path.getsize(path)
                for gen, path in self.log.segments()
                if floor <= gen < self._base_generation
                and os.path.exists(path))
            if cost <= self.config.pin_byte_cap:
                return floor
            worst = min(pins, key=lambda fid: pins[fid])
            del pins[worst]
            self._evict(worst, "pin_bytes")
        return None

    def _evict(self, fid: int, reason: str) -> None:
        """Schedule an eviction from a foreign thread (idempotent)."""
        with self._pin_lock:
            self._pin_view.pop(fid, None)
        if self._registry is not None:
            self._registry.inc("repl_evictions_total", reason=reason)
        if self._loop is not None:
            self._loop.call_soon_threadsafe(self._evict_locked, fid, reason)

    def _evict_locked(self, fid: int, reason: str) -> None:
        f = self._followers.get(fid)
        if f is None or not f.alive:
            return
        f.evicted = reason
        logger.warning("photonrepl: evicting follower %s (%s) — it will "
                       "re-bootstrap from a snapshot", f.peer, reason)
        try:
            f.writer.write(encode({"repl": "restart", "reason": reason}))
        except (ConnectionError, OSError):
            pass
        self._close_follower(f)

    # -- loop-side state ---------------------------------------------------
    def _fanout(self, record: DeltaRecord) -> None:
        nbytes = len(record.encode())
        for f in self._followers.values():
            if not f.alive:
                continue
            try:
                f.queue.put_nowait(record)
                f.queued_bytes += nbytes
            except asyncio.QueueFull:
                # bounded backpressure: drop from the LIVE queue only —
                # the record is durable, the sender re-reads it from the
                # log once it catches up
                f.need_catchup = True
                self._nudge(f)
            self._lag_gauges(f)

    def _nudge(self, f: _Follower) -> None:
        try:
            f.queue.put_nowait(_WAKE)
        except asyncio.QueueFull:
            pass  # sender is already behind; it re-checks state anyway

    def _publish_pin(self, f: _Follower) -> None:
        with self._pin_lock:
            if f.alive:
                self._pin_view[f.fid] = (f.pin_generation(), f.acked_at)
            else:
                self._pin_view.pop(f.fid, None)

    def _lag_gauges(self, f: _Follower) -> None:
        if self._registry is None:
            return
        self._registry.set_gauge("repl_follower_lag_records",
                                 f.queue.qsize() + len(f.unacked),
                                 peer=f.peer)
        self._registry.set_gauge("repl_follower_lag_bytes",
                                 f.queued_bytes + f.unacked_bytes,
                                 peer=f.peer)

    def _close_follower(self, f: _Follower) -> None:
        if not f.alive:
            return
        f.alive = False
        self._followers.pop(f.fid, None)
        self._publish_pin(f)
        self._nudge(f)  # unblock a sender parked on queue.get()
        try:
            f.writer.close()
        except Exception:  # noqa: BLE001 — best-effort close
            pass
        if self._registry is not None:
            self._registry.set_gauge("repl_followers", len(self._followers))

    async def _housekeeping(self) -> None:
        """Periodic age-cap sweep so a silent follower is evicted even if
        the owner never swaps/compacts in between."""
        while True:
            await asyncio.sleep(self.config.housekeeping_interval_s)
            now = time.monotonic()
            for f in list(self._followers.values()):
                pin = f.pin_generation()
                if (pin is not None and pin < self._base_generation and
                        now - f.acked_at > self.config.pin_age_cap_s):
                    if self._registry is not None:
                        self._registry.inc("repl_evictions_total",
                                           reason="ack_age")
                    self._evict_locked(f.fid, "ack_age")

    # -- connection handling -----------------------------------------------
    async def _on_connect(self, reader: asyncio.StreamReader,
                          writer: asyncio.StreamWriter) -> None:
        peername = writer.get_extra_info("peername")
        peer = (f"{peername[0]}:{peername[1]}"
                if isinstance(peername, tuple) else str(peername))
        br = BoundedLineReader(reader.read, self.config.max_line_bytes)
        try:
            hello = await asyncio.wait_for(
                br.readline(), self.config.hello_timeout_s)
        except (asyncio.TimeoutError, LineTooLong,
                ConnectionError, OSError):
            writer.close()
            return
        try:
            ok, f = await self._subscribe(peer, hello, writer)
        except (ConnectionError, OSError):
            writer.close()
            return
        if not ok:
            return
        if self._registry is not None:
            self._registry.set_gauge("repl_followers", len(self._followers))
        sender = asyncio.ensure_future(self._sender(f))
        try:
            await self._acks(f, br)
        finally:
            self._close_follower(f)
            sender.cancel()
            try:
                await sender
            except (asyncio.CancelledError, ConnectionError, OSError):
                pass

    async def _subscribe(self, peer: str, hello: Optional[bytes],
                         writer: asyncio.StreamWriter,
                         ) -> Tuple[bool, Optional[_Follower]]:
        async def _refuse(msg: str) -> Tuple[bool, None]:
            writer.write(encode(error_reply(msg)))
            await writer.drain()
            writer.close()
            return False, None

        if hello is None:
            writer.close()
            return False, None
        t1 = pulse_clock.now_ns()  # hello receipt — clock ping-pong leg
        try:
            obj = parse_line(hello)
            last = parse_identity(obj.get("last"))
        except WireError as e:
            return await _refuse(str(e))
        if obj.get("cmd") != "subscribe":
            return await _refuse(f"expected subscribe, got "
                                 f"{obj.get('cmd')!r}")
        if self.config.auth_token is not None:
            token = obj.get("token")
            token = token if isinstance(token, str) else ""
            if not hmac.compare_digest(token.encode("utf-8"),
                                       self.config.auth_token.encode(
                                           "utf-8")):
                if self._registry is not None:
                    self._registry.inc("repl_auth_failures_total")
                logger.warning("photonrepl: rejected unauthenticated "
                               "subscriber %s", peer)
                return await _refuse("unauthorized")
        floor = obj.get("floor")
        floor = int(floor) if isinstance(floor, (int, float)) else None
        mode = self._decide_resume(last, floor)
        if mode == "snapshot" and self._snapshot_source is None:
            return await _refuse("snapshot bootstrap unavailable "
                                 "(owner has no snapshot source)")
        self._fid_seq += 1
        f = _Follower(self._fid_seq, peer, writer,
                      self.config.queue_records)
        if mode == "log":
            f.floor = floor
            f.sent = last
            f.acked = last  # the client TOLD us it applied this much
        # register before replying: the retention pin must see this
        # follower before its first catch-up read races a compaction
        self._followers[f.fid] = f
        self._publish_pin(f)
        if self._registry is not None:
            self._registry.inc("repl_resume_total", mode=mode)
        obs_instant("repl.subscribe", peer=peer, mode=mode)
        logger.info("photonrepl: subscriber %s resume mode=%s last=%s "
                    "floor=%s", peer, mode, last, floor)
        resume = {"repl": "resume", "mode": mode,
                  "generation": self._base_generation,
                  "floor": self._base_generation}
        t0 = obj.get("t0")
        if isinstance(t0, int):
            # complete the photonpulse clock ping-pong piggybacked on the
            # subscribe hello: echo t0, stamp receipt (t1) and send (t2)
            resume["who"] = get_process_label() or "owner"
            resume["t0"] = t0
            resume["t1"] = t1
            resume["t2"] = pulse_clock.now_ns()
        writer.write(encode(resume))
        await writer.drain()
        return True, f

    def _decide_resume(self, last: Optional[Tuple[int, int]],
                       floor: Optional[int]) -> str:
        """Log replay when the client's base lineage matches and the
        retained log covers its identity; snapshot otherwise."""
        if floor is None or floor != self._base_generation:
            return "snapshot"
        log_last = self.log.last_identity()
        if last is None:
            return "log"  # has the base, applied nothing: replay all
        if log_last is None or last > log_last:
            return "snapshot"  # claims records this log never wrote
        if last[0] < floor:
            return "snapshot"  # inconsistent client state
        min_gen = self.log.min_retained_generation()
        if min_gen is not None and last[0] < min_gen:
            return "snapshot"  # compaction passed it
        return "log"

    # -- acks --------------------------------------------------------------
    async def _acks(self, f: _Follower, br: BoundedLineReader) -> None:
        while f.alive:
            try:
                line = await br.readline()
            except LineTooLong:
                continue  # stream realigned; drop the garbage line
            except (ConnectionError, OSError):
                return
            if line is None:
                return
            if not line.strip():
                continue
            try:
                obj = parse_line(line)
                if obj.get("cmd") != "ack":
                    continue
                acked = parse_identity(obj.get("last"))
            except WireError:
                continue
            if acked is None:
                continue
            f.acked = acked
            f.acked_at = time.monotonic()
            while f.unacked and f.unacked[0][0] <= acked:
                _, nbytes = f.unacked.popleft()
                f.unacked_bytes -= nbytes
            self._publish_pin(f)
            self._lag_gauges(f)

    # -- sending -----------------------------------------------------------
    async def _sender(self, f: _Follower) -> None:
        try:
            while f.alive:
                base = self._base_generation
                if f.floor is None or f.floor < base:
                    # the client's base is behind: drain the pre-swap
                    # records it can still use (pinned segments), then
                    # ship the new base inline
                    if f.floor is not None:
                        if not await self._catchup(f, lo=f.floor, hi=base):
                            return
                    if not await self._ship_snapshot(f):
                        return
                    continue
                if f.need_catchup:
                    f.need_catchup = False
                    if not await self._catchup(f, lo=f.floor, hi=None):
                        return
                    continue
                rec = await f.queue.get()
                if rec is _WAKE or not f.alive:
                    continue
                f.queued_bytes -= len(rec.encode())
                if f.sent is not None and rec.identity <= f.sent:
                    continue  # already delivered via log catch-up
                if rec.generation < f.floor:
                    continue  # superseded by the base the client holds
                await self._send_record(f, rec)
        except (ConnectionError, OSError):
            pass
        finally:
            self._close_follower(f)

    async def _send_record(self, f: _Follower, rec: DeltaRecord) -> None:
        act = _chaos_fault("repl.server.send")
        if act is not None:
            # chaos seams, in the follower's terms: "drop" = the TCP
            # session dies mid-stream (client reconnects and resumes);
            # "stall"/"stall_dist" = a slow owner (client ack timer keeps
            # ticking; stall_dist holds are sampled by the injector);
            # "garbage" = a corrupt frame on the wire IN PLACE of the
            # record (client must fail typed, reconnect, and recover the
            # record via log catch-up — f.sent is not advanced)
            if act.kind in ("stall", "stall_dist"):
                await asyncio.sleep(float(act.data.get("stall_s", 0.05)))
            elif act.kind == "garbage":
                f.writer.write(b"\x7f{not json//\n")
                await f.writer.drain()
                return
            else:
                raise act.to_error()
        tp = None
        if obs_enabled():
            # the trace context rides BESIDE the payload ("tp" field), so
            # the record bytes stay bit-identical to the owner's frame
            ctx = pulse_delta_ctx(rec.identity)
            if ctx is not None:
                tp = ctx_to_wire(ctx_forwarded(ctx))
        line = encode_record_line(rec, tp=tp)
        f.writer.write(line)
        await f.writer.drain()
        f.sent = rec.identity
        f.unacked.append((rec.identity, len(line)))
        f.unacked_bytes += len(line)
        if self._registry is not None:
            self._registry.inc("repl_records_sent_total")
            self._registry.inc("repl_bytes_sent_total", len(line))
        self._lag_gauges(f)

    async def _catchup(self, f: _Follower, lo: Optional[int],
                       hi: Optional[int]) -> bool:
        """Send every retained record after ``f.sent`` with generation in
        ``[lo, hi)`` (``hi=None`` -> unbounded).  Returns False when the
        follower can no longer be served from the log (restart sent)."""
        lo = lo or 0
        need_gen = f.sent[0] if f.sent is not None else lo
        min_gen = self.log.min_retained_generation()
        if (need_gen < self._base_generation and min_gen is not None
                and need_gen < min_gen):
            # compaction passed this follower mid-connection (pin caps
            # evicted it, or it subscribed in a lost race): it cannot be
            # caught up from the log any more
            f.evicted = f.evicted or "compacted"
            if self._registry is not None:
                self._registry.inc("repl_evictions_total",
                                   reason="compacted")
            f.writer.write(encode({"repl": "restart",
                                   "reason": "compacted"}))
            await f.writer.drain()
            return False
        sent_from = f.sent

        def _scan():
            out = []
            for rec in self.log.replay(after=sent_from):
                if rec.generation < lo:
                    continue
                if hi is not None and rec.generation >= hi:
                    continue
                out.append(rec)
            return out

        records = await asyncio.get_running_loop().run_in_executor(
            None, _scan)
        for rec in records:
            if not f.alive:
                return False
            await self._send_record(f, rec)
        return True

    async def _ship_snapshot(self, f: _Follower) -> bool:
        """Pack the owner's current base and stream it inline.  After this
        the follower's floor is the shipped base generation and catch-up
        resumes from the log at that floor."""
        assert self._snapshot_source is not None
        loop = asyncio.get_running_loop()
        model_dir, gen = self._snapshot_source()
        for _ in range(3):
            try:
                data, crc = await loop.run_in_executor(
                    None, pack_model_dir, model_dir)
            except SnapshotError as e:
                logger.error("photonrepl: snapshot pack failed: %s", e)
                f.writer.write(encode(error_reply(f"snapshot failed: {e}")))
                await f.writer.drain()
                return False
            again_dir, again_gen = self._snapshot_source()
            if (again_dir, again_gen) == (model_dir, gen):
                break
            model_dir, gen = again_dir, again_gen  # swapped mid-pack: retry
        f.writer.write(encode({
            "repl": "snapshot", "bytes": len(data), "crc32": crc,
            "generation": gen, "version": os.path.basename(
                os.path.normpath(model_dir))}))
        for off in range(0, len(data), self.config.snapshot_chunk):
            act = _chaos_fault("repl.server.snapshot")
            if act is not None:
                # mid-snapshot disconnect: the follower sees a short read
                # against the announced byte count, fails its CRC/length
                # check, and re-bootstraps on reconnect
                raise act.to_error()
            f.writer.write(data[off: off + self.config.snapshot_chunk])
            await f.writer.drain()
        f.floor = gen
        f.need_catchup = True
        if self._registry is not None:
            self._registry.inc("repl_snapshots_total")
            self._registry.inc("repl_snapshot_bytes_total", len(data))
        obs_instant("repl.snapshot", peer=f.peer, generation=gen,
                    nbytes=len(data))
        logger.info("photonrepl: shipped snapshot gen %d (%d bytes) to %s",
                    gen, len(data), f.peer)
        return True


class ThreadedReplicationServer:
    """Run a ReplicationServer on a dedicated event-loop thread (the
    ``ThreadedFrontend`` pattern): ``start()`` blocks until the socket is
    bound, ``stop()`` closes and joins.  This is what blocking callers —
    ``cli/learn.py``, tests — use."""

    def __init__(self, log: DeltaLog,
                 config: Optional[ReplicationConfig] = None,
                 snapshot_source: Optional[
                     Callable[[], Tuple[str, int]]] = None,
                 base_generation: int = 0,
                 registry=None):
        self.server = ReplicationServer(
            log, config, snapshot_source=snapshot_source,
            base_generation=base_generation, registry=registry)
        self._ready = threading.Event()
        self._error: Optional[BaseException] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="photonrepl")

    @property
    def port(self) -> int:
        return self.server.port

    def note_generation(self, generation: int) -> None:
        self.server.note_generation(generation)

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

    def start(self, timeout: float = 30.0) -> "ThreadedReplicationServer":
        self._thread.start()
        if not self._ready.wait(timeout):
            raise RuntimeError(
                f"replication server did not start within {timeout}s")
        if self._error is not None:
            raise RuntimeError(
                "replication server failed to start") from self._error
        return self

    def stop(self, timeout: float = 30.0) -> None:
        if self._loop is not None and self._thread.is_alive():
            asyncio.run_coroutine_threadsafe(self.server.aclose(),
                                             self._loop)
        self._thread.join(timeout)


def attach_replication(swapper, config: Optional[ReplicationConfig] = None,
                       registry=None) -> ThreadedReplicationServer:
    """Start a :class:`ThreadedReplicationServer` wired to a log-owning
    ``serving.HotSwapper``: snapshots come from the swapper's serving base
    (``serving_base()`` — the atomic ``(model_dir, floor)`` pair), and a
    successful hot swap raises the server's base floor in-stream via the
    swapper's ``on_swap`` hook (chained, not replaced).  This is the one
    call sites use — ``cli/learn.py --repl-listen``, tests."""
    if swapper.delta_log is None or not swapper.log_owner:
        raise ValueError("replication needs a swapper that OWNS a delta "
                         "log (delta_log=..., log_owner=True)")
    srv = ThreadedReplicationServer(
        swapper.delta_log, config,
        snapshot_source=swapper.serving_base,
        base_generation=swapper.replay_floor,
        registry=registry)
    # a replicated owner's hot swap must leave its live state derivable as
    # ``snapshot dir + retained records >= floor`` — so the incoming base
    # supersedes pre-swap records instead of having them replayed onto it
    # (serving/swap.py __init__ for the full argument)
    swapper.base_supersedes_log = True
    prev = swapper.on_swap

    def _on_swap(model_dir: str, generation: int) -> None:
        if prev is not None:
            prev(model_dir, generation)
        srv.note_generation(generation)

    swapper.on_swap = _on_swap
    return srv.start()
