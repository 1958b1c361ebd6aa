"""Open-loop slate generator: a child process that never imports JAX.

A copy of the arrival process of ``serving/frontend/loadgen.run_open_loop``
(open loop, Poisson arrivals drawn up front from a seeded generator,
latency from the instant the arrival was DUE on one schedule clock shared
by every sender, the generator's own lag reported), which this benchmark
does not import; the copy no longer follows the program.  What differs:

- it runs in a process of its own, so the generator, the front end and the
  batcher do not share one interpreter lock;
- one arrival is a SLATE: K lines for one user, written back to back on
  one connection; a slate's latency ends with its last line's reply;
- every line is encoded before the window (feature bodies come from a
  seeded pool; the head with ``uid`` and ids is formatted up front);
- every run offers the same amount of work: rate x seconds slates, their
  sizes in exactly the mix's proportions, instants and order from the seed;
- the rate is a number in the cell's file.  Nothing here calibrates.

Protocol with the parent (run.py's traffic kind ``serve_slates``), lines on
stdin/stdout:

    parent: PORT <n>       child connects, scores the check sample
    child:  READY <json>   the check sample's scores, by line
    parent: GO             the window starts at this instant
    child:  RESULT <json>  counts, latencies, lag; then exits

    python benchmarks/loadgen_child.py <spec.json>
"""

from __future__ import annotations

import asyncio
import json
import sys
import time

import numpy as np

SETTLE_S = 5.0        # after the window: how long stragglers may take
CHECK_CHUNK = 16      # check sample: lines outstanding at once


# -- the traffic, from the seed ----------------------------------------------

def feature_names(dims: list) -> list:
    """[("g", 128), ("u", 16), ...] -> g0..g127, u0..u15, ..."""
    return [f"{shard}{j}" for shard, d in dims for j in range(d)]


def make_pool(seed: int, dims: list, size: int) -> tuple:
    """(values [size, sum d] float64 rounded to 5 places, bodies: the JSON
    ``features`` array of each row, as bytes)."""
    rng = np.random.default_rng([seed, 10])
    names = feature_names(dims)
    values = np.round(rng.standard_normal((size, len(names))), 5)
    bodies = [json.dumps([[n, float(v)] for n, v in zip(names, row)],
                         separators=(",", ":")).encode()
              for row in values]
    return values, bodies


def zipf_sampler(rng, n: int, s: float):
    """Draws from {0..n-1} with P(rank r) ~ (r+1)^-s, ranks mapped to ids
    by a seeded permutation so that the hot entities are not the low ids.
    s = 0 is uniform."""
    cdf = np.cumsum(1.0 / np.arange(1, n + 1, dtype=np.float64) ** s)
    cdf /= cdf[-1]
    ids = rng.permutation(n)
    return lambda size: ids[np.searchsorted(cdf, rng.random(size))]


def slate_sizes(rng, spec: dict, n_slates: int) -> np.ndarray:
    """A FIXED amount of work: the sizes in exactly the mix's proportions
    (largest remainders), in an order drawn from the seed.  Independent
    draws would move the count of 32-line slates by a tenth from run to
    run, and the tail with it."""
    weights = np.asarray(spec["slate_weights"], np.float64)
    exact = weights / weights.sum() * n_slates
    counts = np.floor(exact).astype(np.int64)
    short = n_slates - int(counts.sum())
    counts[np.argsort(exact - counts)[::-1][:short]] += 1
    return rng.permutation(np.repeat(np.asarray(spec["slate_sizes"]), counts))


def draw_lines(spec: dict, stream: int, n_slates: int) -> dict:
    """Slate sizes, and per line its user (-1: a user the model has never
    seen), item and pool body, all from the seed."""
    rng = np.random.default_rng([spec["seed"], stream])
    sizes = slate_sizes(rng, spec, n_slates)
    users = zipf_sampler(rng, spec["users"], spec["user_zipf"])(n_slates)
    users = np.repeat(users, sizes)
    n_lines = int(sizes.sum())
    users = np.where(rng.random(n_lines) < spec["unknown_user_share"],
                     -1, users)
    items = zipf_sampler(rng, spec["items"], spec["item_zipf"])(n_lines)
    bodies = rng.integers(0, spec["pool"], size=n_lines)
    return {"sizes": sizes, "users": users, "items": items,
            "bodies": bodies}


def encode_heads(lines: dict, first_uid: int) -> list:
    """``{"uid":7,"ids":{"userId":"user12","itemId":"item3"},"features":``"""
    heads = []
    for k, (u, i) in enumerate(zip(lines["users"], lines["items"])):
        user = f"user{u}" if u >= 0 else f"ghost{first_uid + k}"
        heads.append(
            f'{{"uid":{first_uid + k},"ids":{{"userId":"{user}",'
            f'"itemId":"item{i}"}},"features":'.encode())
    return heads


def percentile(values: np.ndarray, q: float) -> float:
    return float(np.percentile(values, q)) if len(values) else float("nan")


# -- the check sample: closed loop, before the window ------------------------

async def score_check_sample(host, port, heads, bodies, body_idx) -> list:
    reader, writer = await asyncio.open_connection(host, port)
    replies = []
    try:
        for lo in range(0, len(heads), CHECK_CHUNK):
            hi = min(lo + CHECK_CHUNK, len(heads))
            writer.write(b"".join(heads[k] + bodies[body_idx[k]] + b"}\n"
                                  for k in range(lo, hi)) + b"\n")
            await writer.drain()
            for _ in range(lo, hi):
                line = await asyncio.wait_for(reader.readline(), 60.0)
                replies.append(json.loads(line))
    finally:
        writer.close()
    return replies


# -- the window: open loop ---------------------------------------------------

async def run_window(host, port, spec, lines, heads, bodies, ready) -> dict:
    seconds = float(spec["seconds"])
    sizes = lines["sizes"]
    n_slates = len(sizes)
    first = np.concatenate([[0], np.cumsum(sizes)])  # slate -> first line
    n_lines = int(first[-1])
    slate_of = np.repeat(np.arange(n_slates), sizes)
    uid0 = int(spec["check_lines"])  # window uids follow the check sample's
    n_conn = int(spec["connections"])
    body_idx = lines["bodies"]

    conns = [await asyncio.open_connection(host, port)
             for _ in range(n_conn)]
    print("READY " + json.dumps(ready), flush=True)
    go = await asyncio.get_running_loop().run_in_executor(
        None, sys.stdin.readline)
    if go.strip() != "GO":
        raise SystemExit(f"expected GO, got {go!r}")

    t_start = time.perf_counter()
    due = t_start + lines["arrivals"]
    remaining = sizes.astype(np.int64).copy()
    done_at = np.full(n_slates, np.nan)
    sent_at = np.full(n_slates, np.nan)
    shed = np.zeros(n_slates, bool)
    bad = np.zeros(n_slates, bool)
    counts = {"scored": 0, "scored_in_window": 0, "overloaded": 0,
              "other": 0, "unknown_uid": 0}
    outstanding = [n_lines]
    all_done = asyncio.Event()
    t_window_end = t_start + seconds

    async def read_replies(reader):
        while outstanding[0] > 0:
            raw = await reader.readline()
            if not raw:
                return
            if not raw.strip():
                continue
            now = time.perf_counter()
            try:
                obj = json.loads(raw)
                k = int(obj["uid"]) - uid0
                s = slate_of[k]
            except (ValueError, KeyError, TypeError, IndexError):
                counts["unknown_uid"] += 1
                continue
            if "score" in obj:
                counts["scored"] += 1
                if now <= t_window_end:
                    counts["scored_in_window"] += 1
            elif obj.get("error") == "overloaded":
                counts["overloaded"] += 1
                shed[s] = True
            else:
                counts["other"] += 1
                bad[s] = True
            remaining[s] -= 1
            if remaining[s] == 0:
                done_at[s] = now
            outstanding[0] -= 1
            if outstanding[0] == 0:
                all_done.set()

    async def send_slates(c):
        _, writer = conns[c]
        for s in range(c, n_slates, n_conn):
            # fire at the DUE instant whatever has come back; yield even
            # when behind, so a hot sender cannot starve the readers
            delay = due[s] - time.perf_counter()
            await asyncio.sleep(delay if delay > 0 else 0)
            lo, hi = first[s], first[s + 1]
            payload = b"".join(heads[k] + bodies[body_idx[k]] + b"}\n"
                               for k in range(lo, hi))
            sent_at[s] = time.perf_counter()
            writer.write(payload)
            await writer.drain()
        writer.write(b"\n")  # blank line: flush whatever is batching
        await writer.drain()

    readers = [asyncio.ensure_future(read_replies(r)) for r, _ in conns]
    await asyncio.gather(*(send_slates(c) for c in range(n_conn)))
    t_sent = time.perf_counter()
    try:
        await asyncio.wait_for(all_done.wait(), SETTLE_S)
    except asyncio.TimeoutError:
        pass  # what has not come back is lost, counted below
    t_end = time.perf_counter()
    for task in readers:
        task.cancel()
    for _, writer in conns:
        writer.close()

    lost = remaining > 0
    scored = ~(shed | bad | lost)
    latency_ms = (done_at - due) * 1e3
    ok = latency_ms[scored]
    # a shed, failed or lost slate is slower than every scored one
    floor = max(float(ok.max()) if len(ok) else 0.0,
                float((t_end - due.min()) * 1e3))
    all_ms = np.where(scored, latency_ms, floor)
    lag_ms = (sent_at - due) * 1e3
    half = due < t_start + seconds / 2
    return {
        "slates_due": int(n_slates), "slates_scored": int(scored.sum()),
        "slates_shed": int((shed & ~bad & ~lost).sum()),
        "slates_error": int((bad & ~lost).sum()),
        "slates_lost": int(lost.sum()),
        "lines_sent": n_lines, "lines": counts,
        "p50_ms": percentile(all_ms, 50), "p90_ms": percentile(all_ms, 90),
        "p99_ms": percentile(all_ms, 99),
        "scored_p50_ms": percentile(ok, 50),
        "scored_p90_ms": percentile(ok, 90),
        "scored_p95_ms": percentile(ok, 95),
        "scored_p99_ms": percentile(ok, 99),
        "scored_mean_ms": float(ok.mean()) if len(ok) else float("nan"),
        "first_half_p50_ms": percentile(latency_ms[scored & half], 50),
        "second_half_p50_ms": percentile(latency_ms[scored & ~half], 50),
        "gen_lag_p50_ms": percentile(lag_ms, 50),
        "gen_lag_p99_ms": percentile(lag_ms, 99),
        "gen_lag_max_ms": float(np.nanmax(lag_ms)),
        "send_s": t_sent - t_start, "settle_s": t_end - t_sent,
        "window_s": seconds,
    }


async def main(spec: dict) -> dict:
    host = spec.get("host", "127.0.0.1")
    dims = [tuple(d) for d in spec["dims"]]
    _, bodies = make_pool(spec["seed"], dims, int(spec["pool"]))

    n_check = int(spec["check_lines"])
    check = draw_lines(spec, 11, n_check)
    check = {k: v[:n_check] for k, v in check.items()}
    check_heads = encode_heads(check, 0)

    rng = np.random.default_rng([spec["seed"], 12])
    rate, seconds = float(spec["slates_per_s"]), float(spec["seconds"])
    # Poisson arrivals, drawn up front: the schedule does not depend on
    # what the server does (the open loop).  The process is conditioned on
    # its count, rate x seconds, so that every run offers the same amount
    # of work: given the count, Poisson instants are uniform order
    # statistics.
    arrivals = np.sort(rng.uniform(0.0, seconds,
                                   size=max(1, int(round(rate * seconds)))))
    lines = draw_lines(spec, 13, len(arrivals))
    lines["arrivals"] = arrivals
    heads = encode_heads(lines, n_check)

    port_line = sys.stdin.readline().split()
    if len(port_line) != 2 or port_line[0] != "PORT":
        raise SystemExit(f"expected PORT <n>, got {port_line!r}")
    port = int(port_line[1])
    replies = await score_check_sample(host, port, check_heads, bodies,
                                       check["bodies"])
    ready = {
        "check": [{"user": int(u), "item": int(i), "body": int(b),
                   "reply": r}
                  for u, i, b, r in zip(check["users"], check["items"],
                                        check["bodies"], replies)]}
    return await run_window(host, port, spec, lines, heads, bodies, ready)


if __name__ == "__main__":
    with open(sys.argv[1]) as f:
        _spec = json.load(f)
    print("RESULT " + json.dumps(asyncio.run(main(_spec))), flush=True)
