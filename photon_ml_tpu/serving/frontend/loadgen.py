"""Open-loop Poisson load generator for the socket front end.

Photon ML reference counterpart: none.  The methodology point comes from
the Spark-perf study in PAPERS.md: a CLOSED-loop benchmark (send, wait for
the reply, send the next) self-throttles — when the server slows down the
offered load drops with it, so queueing cliffs are invisible and p99 looks
flat right through saturation.  An OPEN-loop generator fixes the arrival
process instead: requests fire at exponentially-spaced (Poisson) instants
drawn up front from a seeded RNG, whether or not earlier replies have come
back.  Past saturation the backlog grows at (arrival - service) rate and
latency diverges — unless the server sheds, which is the behavior an
open-loop run shows: below saturation shed≈0, past
it p99 stays bounded near the admission budget while the shed rate (not
the latency) absorbs the excess.

Arrivals are split round-robin across ``n_connections`` persistent
connections so the fairness layer sees multiple clients and no single
kernel socket buffer serializes the offered load.  Each connection has an
asyncio sender (fires at the precomputed schedule) and a reader (matches
``uid`` to its timestamps).

Two latencies are recorded per reply, because a sender that falls behind
schedule silently under-reports otherwise (**coordinated omission**): when
the client loop can't fire at the drawn instant — its own event loop is
busy, or ``drain()`` blocked on a full socket buffer — the send-to-reply
clock starts late and the delay the request REALLY experienced (from its
scheduled Poisson arrival) never shows up in the send-based percentiles.
``latency_ms`` is the raw send-instant→reply number (comparable with
earlier BENCH_NET history); ``latency_corrected_ms`` measures from the
scheduled arrival instant on a schedule clock shared by every sender —
the honest open-loop number.  ``max_send_lag_ms`` reports how far the
generator fell behind its own schedule, so a sweep point where the two
percentile sets diverge is diagnosable as client-side lag rather than
server queueing.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import time
from typing import Callable, Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class OpenLoopResult:
    """One arrival-rate point of the sweep."""

    rate_qps: float          # offered (configured) arrival rate
    duration_s: float        # configured generation window
    offered: int             # arrivals actually fired
    completed: int           # {"score": ...} replies
    shed: int                # {"error": "overloaded"} replies
    errors: int              # any other {"error": ...} reply
    lost: int                # fired but no reply (should be 0)
    achieved_qps: float      # offered / wall time of the send phase
    latency_ms: Dict[str, float]  # RAW send->reply p50 / p99 / p999
    # scheduled-arrival->reply percentiles (coordinated-omission corrected)
    latency_corrected_ms: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    max_send_lag_ms: float = 0.0  # worst sender lag behind the schedule

    @property
    def shed_rate(self) -> float:
        return self.shed / self.offered if self.offered else 0.0

    def to_json(self) -> dict:
        out = dataclasses.asdict(self)
        out["shed_rate"] = round(self.shed_rate, 6)
        return out


def _percentiles(latencies_s: List[float]) -> Dict[str, float]:
    if not latencies_s:
        return {"p50": 0.0, "p99": 0.0, "p999": 0.0}
    arr = np.asarray(latencies_s, dtype=np.float64) * 1e3
    return {"p50": round(float(np.percentile(arr, 50)), 4),
            "p99": round(float(np.percentile(arr, 99)), 4),
            "p999": round(float(np.percentile(arr, 99.9)), 4)}


async def measure_closed_loop_capacity(host: str, port: int,
                                       make_request: Callable[[int], dict],
                                       n: int = 2048,
                                       window: int = 128) -> float:
    """Closed-loop capacity probe: keep ``window`` requests outstanding on
    one connection until ``n`` have round-tripped; returns completed qps.

    This measures the capacity of the WHOLE edge — JSON encode/decode,
    socket, event loop, fairness, batcher, engine — which is what an
    open-loop sweep must be calibrated against (the raw engine's
    full-bucket throughput overstates it several-fold).  Running it also
    warms the batcher's flush-cost EWMA with load-realistic observations,
    so the admission controller enters the sweep calibrated rather than
    at its optimistic floor.
    """
    reader, writer = await asyncio.open_connection(host, port)
    sem = asyncio.Semaphore(window)
    done = 0

    async def read_replies() -> None:
        nonlocal done
        while done < n:
            line = await reader.readline()
            if not line:
                return
            if not line.strip():
                continue
            done += 1
            sem.release()

    rx = asyncio.ensure_future(read_replies())
    t0 = time.perf_counter()
    for uid in range(n):
        await sem.acquire()
        writer.write((json.dumps(make_request(uid)) + "\n").encode("utf-8"))
        if uid % 16 == 0:
            await writer.drain()
    writer.write(b"\n")
    await writer.drain()
    await asyncio.wait_for(rx, timeout=60.0)
    dt = time.perf_counter() - t0
    writer.close()
    return n / dt if dt > 0 else 0.0


async def run_open_loop(host: str, port: int, rate_qps: float,
                        duration_s: float,
                        make_request: Callable[[int], dict],
                        n_connections: int = 4,
                        rng: Optional[np.random.Generator] = None,
                        settle_s: float = 10.0) -> OpenLoopResult:
    """Drive one open-loop point against a listening front end.

    ``make_request(uid) -> dict`` builds each wire request; uids are
    assigned 0..n-1 in arrival order and must round-trip in replies.
    After the send window a blank line flushes each connection and the
    readers get ``settle_s`` to collect stragglers.
    """
    if rate_qps <= 0 or duration_s <= 0:
        raise ValueError("rate_qps and duration_s must be > 0")
    rng = rng or np.random.default_rng(0)
    n = max(1, int(round(rate_qps * duration_s)))
    # Poisson process: exponential inter-arrival gaps, drawn up front so
    # the schedule is independent of server behavior (the open loop)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_qps, size=n))

    conns = []
    for _ in range(n_connections):
        reader, writer = await asyncio.open_connection(host, port)
        conns.append((reader, writer))

    sent_at: Dict[int, float] = {}
    latencies: List[float] = []
    latencies_corrected: List[float] = []
    max_lag = 0.0
    counts = {"completed": 0, "shed": 0, "errors": 0}
    pending = set(range(n))
    all_done = asyncio.Event()
    # ONE schedule clock for every sender: scheduled instant of uid is
    # t_start + arrivals[uid], and corrected latency is measured from it —
    # a per-sender clock would hide exactly the lag being corrected for
    t_start = time.perf_counter()

    async def read_replies(reader: asyncio.StreamReader) -> None:
        while pending:
            line = await reader.readline()
            if not line:
                return
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except ValueError:
                counts["errors"] += 1
                continue
            uid = obj.get("uid")
            now = time.perf_counter()
            if uid in pending:
                pending.discard(uid)
                if "score" in obj:
                    counts["completed"] += 1
                    latencies.append(now - sent_at[uid])
                    latencies_corrected.append(
                        now - (t_start + arrivals[uid]))
                elif obj.get("error") == "overloaded":
                    counts["shed"] += 1
                else:
                    counts["errors"] += 1
            elif "error" in obj:
                counts["errors"] += 1
            if not pending:
                all_done.set()

    async def send_arrivals(conn_idx: int) -> None:
        nonlocal max_lag
        _, writer = conns[conn_idx]
        for uid in range(conn_idx, n, n_connections):
            # fire at the SCHEDULED instant, not request-after-response;
            # yield even when behind schedule so this sender's hot loop
            # cannot starve the reply readers sharing the client loop
            # (that would bill server latency for client-side buffering)
            delay = arrivals[uid] - (time.perf_counter() - t_start)
            await asyncio.sleep(delay if delay > 0 else 0)
            now = time.perf_counter()
            sent_at[uid] = now
            lag = now - (t_start + arrivals[uid])
            if lag > max_lag:
                max_lag = lag
            writer.write((json.dumps(make_request(uid)) + "\n")
                         .encode("utf-8"))
            await writer.drain()
        writer.write(b"\n")  # blank line: flush whatever is batching
        await writer.drain()

    readers = [asyncio.ensure_future(read_replies(r)) for r, _ in conns]
    await asyncio.gather(*(send_arrivals(i)
                           for i in range(n_connections)))
    send_wall = time.perf_counter() - t_start
    try:
        await asyncio.wait_for(all_done.wait(), timeout=settle_s)
    except asyncio.TimeoutError:
        pass  # stragglers counted as lost below
    for task in readers:
        task.cancel()
    for _, writer in conns:
        try:
            writer.close()
        except Exception:
            pass

    return OpenLoopResult(
        rate_qps=rate_qps, duration_s=duration_s, offered=n,
        completed=counts["completed"], shed=counts["shed"],
        errors=counts["errors"], lost=len(pending),
        achieved_qps=round(n / send_wall, 2) if send_wall > 0 else 0.0,
        latency_ms=_percentiles(latencies),
        latency_corrected_ms=_percentiles(latencies_corrected),
        max_send_lag_ms=round(max_lag * 1e3, 4))
