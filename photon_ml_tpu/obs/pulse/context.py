"""photonpulse trace context: mint, bind, and carry trace ids across wires.

Photon ML reference counterpart: none — the reference's Timed{} blocks are
process-local.  The distributed serving stack needs what Dapper-style
tracers call *context propagation*: a compact id minted once at the edge
(frontend admission, or the owner's publish) and carried on every hop the
request or delta takes, so the per-process photonscope rings can be joined
into one causal timeline by ``tools/tracemerge.py``.

A context is an opaque ``(trace_id, origin)`` pair of short hex tokens:

  - ``trace_id`` (16 hex chars): names the whole causal trace — one served
    request, or one publish -> store-visible path;
  - ``origin`` (8 hex chars): names the hop that forwarded the context, so
    a downstream process can record which remote span handed it work.

Wire form is the single string ``"<trace_id>/<origin>"`` carried in a
``"tp"`` field on existing JSON lines (frontend requests, replication
delta frames).  Decoding is *strictly tolerant*: anything that is not
exactly a well-formed pair — wrong type, wrong length, non-hex, torn by a
crashed peer — decodes to ``None`` and the work proceeds untraced.  A
malformed trace header must never fail a request.

Binding uses the thread-local cell in ``obs.trace``: while bound, every
``span()``/``instant()`` the thread records carries ``trace=`` (and
``origin=``) attrs automatically, so existing call sites join the trace
without signature changes.  All entry points are gated by the caller on
``obs.enabled()`` — when tracing is off nothing mints, binds, or looks up,
preserving the one-boolean disabled cost.

The module also keeps a small bounded map from delta-log identity
``(generation, delta_version)`` to the context that published it: the owner
fills it at ``publish_delta`` time so the replication sender can stamp
outgoing frames, and the replica fills it from incoming frames so the
catch-up follower can mark the store-visible point under the same trace.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Optional, Tuple

from photon_ml_tpu.obs import trace as _trace

TraceContext = Tuple[str, str]

_TRACE_LEN = 16
_ORIGIN_LEN = 8
_HEX = set("0123456789abcdef")


def mint() -> TraceContext:
    """A fresh context: random 64-bit trace id, random 32-bit origin."""
    return (os.urandom(8).hex(), os.urandom(4).hex())


_sample_lock = threading.Lock()
_sample_counter = 0


def maybe_mint(sample_n: int) -> Optional[TraceContext]:
    """Sampled always-on minting: every ``sample_n``-th call mints, the rest
    return None.  The 1-in-N gate is a deterministic shared counter — not
    RNG — so a steady request stream yields an evenly spaced trace sample
    and tests can predict exactly which requests carry context.  The edge
    (frontend admission) calls this when tracing is enabled but the client
    sent no ``"tp"``, so production flight dumps always hold *some* traced
    requests without the cost of tracing every one.  ``sample_n <= 0``
    disables sampling; ``sample_n == 1`` mints for every request."""
    if sample_n <= 0:
        return None
    global _sample_counter
    with _sample_lock:
        _sample_counter += 1
        hit = _sample_counter % sample_n == 0
    return mint() if hit else None


def reset_sampling() -> None:
    """Tests: restart the 1-in-N counter so sampling is phase-deterministic."""
    global _sample_counter
    with _sample_lock:
        _sample_counter = 0


def current() -> Optional[TraceContext]:
    """This thread's bound context, or None."""
    ctx = _trace.current_context()
    return ctx if ctx is not None else None


class _Bound:
    """Context manager restoring the previous binding on exit.  Re-entrant
    and cheap: one thread-local store each way."""

    __slots__ = ("_ctx", "_prev")

    def __init__(self, ctx: Optional[TraceContext]):
        self._ctx = ctx

    def __enter__(self) -> Optional[TraceContext]:
        self._prev = _trace.set_context(self._ctx)
        return self._ctx

    def __exit__(self, *exc) -> bool:
        _trace.set_context(self._prev)
        return False


def bind(ctx: Optional[TraceContext]) -> _Bound:
    """``with bind(ctx):`` — spans/instants recorded by this thread inside
    the block carry the context.  ``bind(None)`` explicitly unbinds (a
    worker thread picking up unrelated work)."""
    return _Bound(ctx)


def to_wire(ctx: TraceContext) -> str:
    """Compact wire form: ``"<16-hex>/<8-hex>"``."""
    return f"{ctx[0]}/{ctx[1]}"


def from_wire(value: object) -> Optional[TraceContext]:
    """Decode a wire field back to a context; anything malformed (wrong
    type, torn, garbage) degrades to None — never raises."""
    if not isinstance(value, str) or len(value) != _TRACE_LEN + _ORIGIN_LEN + 1:
        return None
    tid, sep, origin = value.partition("/")
    if (not sep or len(tid) != _TRACE_LEN or len(origin) != _ORIGIN_LEN
            or not _HEX.issuperset(tid) or not _HEX.issuperset(origin)):
        return None
    return (tid, origin)


def forwarded(ctx: TraceContext) -> TraceContext:
    """The context to put on the wire for the next hop: same trace id, a
    fresh origin naming THIS hop as the forwarder."""
    return (ctx[0], os.urandom(4).hex())


# ---------------------------------------------------------------------------
# delta identity -> context map (bounded; owner and replica both use it)
# ---------------------------------------------------------------------------
_DELTA_MAP_CAP = 1024

_delta_lock = threading.Lock()
_delta_ctx: Dict[Tuple[int, int], TraceContext] = {}


def note_delta(identity: Tuple[int, int], ctx: Optional[TraceContext]) -> None:
    """Remember which context published/shipped delta ``identity``.  Bounded:
    oldest insertions are evicted (dict preserves insertion order)."""
    if ctx is None:
        return
    with _delta_lock:
        _delta_ctx[identity] = ctx
        while len(_delta_ctx) > _DELTA_MAP_CAP:
            _delta_ctx.pop(next(iter(_delta_ctx)))


def delta_ctx(identity: Tuple[int, int]) -> Optional[TraceContext]:
    with _delta_lock:
        return _delta_ctx.get(identity)


def clear_delta_ctx() -> None:
    """Tests: drop all remembered delta contexts."""
    with _delta_lock:
        _delta_ctx.clear()
