"""Bounded-parallel ordered chunk decode.

``ChunkPipeline`` runs a source's chunks through a thread pool (decode is
zlib + Avro varint walking — it releases the GIL in zlib and is the hot
host cost the reference stack pays in Spark serialization) while the
consumer receives chunks strictly IN SUBMISSION ORDER.  Ordered delivery
is a correctness property, not a convenience: the consumer assigns dense
entity ids grow-on-first-sight and fills global row ranges, and both must
see records in exactly the eager reader's order for the bitwise-parity
guarantee.

The submission window (``workers + depth``) bounds host memory to ~that
many decoded chunks regardless of dataset size, and doubles as the
prefetch depth that hides decode latency behind the consumer's fill+upload
work.

Error policy (the malformed-input knob): ``raise`` re-raises the first
chunk's error; ``skip`` yields the chunk with ``records=None`` and the
error, counts it (``stream_chunk_errors_total``), and keeps going — the
consumer decides what a lost chunk means (the GameData ingest keeps its
row range, inert).  Either way the pool is shut down with futures
cancelled on exit, so a torn file can never hang the epoch.
"""

from __future__ import annotations

import collections
import concurrent.futures
import logging
import time
from typing import Iterator, Optional, Tuple

from photon_ml_tpu.chaos.injector import fault as _chaos_fault
from photon_ml_tpu.obs import trace as _trace
from photon_ml_tpu.obs.registry import get_registry
from photon_ml_tpu.stream.chunks import Chunk

_LOG = logging.getLogger("photon_ml_tpu.stream")


class ChunkPipeline:
    """Ordered bounded decode over ``source.chunks`` (see module docstring).

    Iterating yields ``(chunk, records, error)``: exactly one of
    ``records`` / ``error`` is None.  ``stall_seconds`` accumulates time
    the consumer spent blocked on not-yet-decoded chunks — the pipeline
    stall (0 means decode fully hidden).
    """

    def __init__(self, source, workers: int = 2, depth: int = 2,
                 on_error: str = "raise"):
        if on_error not in ("raise", "skip"):
            raise ValueError(f"on_error must be 'raise' or 'skip', "
                             f"got {on_error!r}")
        self.source = source
        self.workers = max(1, int(workers))
        self.depth = max(0, int(depth))
        self.on_error = on_error
        self.stall_seconds = 0.0
        self.error_count = 0

    def _decode(self, chunk: Chunk):
        act = _chaos_fault("stream.decode")
        if act is not None:
            # "slow"/"stall_dist" exercise the pipeline-stall accounting
            # (stall_dist holds come pre-sampled by the injector);
            # "corrupt" exercises the on_error raise/skip contract — both
            # flow through the exact paths a real bad chunk would take
            if act.kind in ("slow", "stall_dist"):
                time.sleep(float(act.data.get("stall_s", 0.05)))
            else:
                raise ValueError(
                    f"injected {act.kind} chunk at index {chunk.index}")
        with _trace.span("stream.decode", chunk=chunk.index,
                         rows=chunk.n_rows):
            return self.source.decode_chunk(chunk)

    def __iter__(self) -> Iterator[Tuple[Chunk, Optional[list],
                                         Optional[Exception]]]:
        chunks = list(self.source.chunks)
        if not chunks:
            return
        registry = get_registry()
        window = self.workers + self.depth
        pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=self.workers, thread_name_prefix="photonstream")
        pending: collections.deque = collections.deque()
        nxt = 0
        try:
            while nxt < len(chunks) and len(pending) < window:
                pending.append((chunks[nxt],
                                pool.submit(self._decode, chunks[nxt])))
                nxt += 1
            while pending:
                registry.set_gauge("stream_buffer_depth", len(pending))
                chunk, fut = pending.popleft()
                t0 = time.perf_counter()
                try:
                    records, err = fut.result(), None
                except Exception as e:  # noqa: BLE001 — per-chunk policy unit
                    records, err = None, e
                self.stall_seconds += time.perf_counter() - t0
                registry.inc("stream_chunks_total")
                if err is not None:
                    self.error_count += 1
                    registry.inc("stream_chunk_errors_total")
                    if self.on_error == "raise":
                        raise err
                    _LOG.warning("stream: skipping chunk %d (%s): %s",
                                 chunk.index, chunk.path, err)
                # refill BEFORE yielding: the consumer's fill+upload work
                # overlaps the next decode
                if nxt < len(chunks):
                    pending.append((chunks[nxt],
                                    pool.submit(self._decode, chunks[nxt])))
                    nxt += 1
                yield chunk, records, err
        finally:
            registry.set_gauge("stream_buffer_depth", 0)
            # cumulative consumer-blocked time, visible to metrics exports
            # even when the pipeline object is internal
            registry.add_gauge("stream_stall_seconds", self.stall_seconds)
            pool.shutdown(wait=False, cancel_futures=True)
