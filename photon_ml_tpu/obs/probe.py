"""JAX runtime accounting: compiles, host<->device transfer bytes, fences.

The two runtime costs a wall-clock phase log cannot attribute are XLA
compilation (tens of seconds on a TPU first-compile; the serving stack's
zero-recompile guarantee exists because of it) and host<->device transfer
(the chunked upload path in ``utils/transfer`` exists because one transport
degraded under a monolithic 512MB put).  ``JaxRuntimeProbe`` counts both
into the unified ``MetricsRegistry``, so "which program was rebuilt
mid-sweep, and was it a cache load or a compile" and "how many bytes
crossed the wire during warm" become registry queries instead of log
archaeology.

Compiles, two ways:
  - BY NAME, while tracing is on.  ``listen()`` (called by
    ``obs.enable_tracing``, so by ``cli/train.py --trace-out``,
    ``cli/serve.py --trace`` and the benchmark's ``--trace 1``; never
    registered in an untraced process) listens to ``jax.monitoring``:
    every program JAX builds becomes the spans ``jax.trace`` (Python
    tracing to a jaxpr; an inner ``jit`` traced INSIDE that trace, or a
    helper traced inside a lowering rule, is part of that phase and no
    span of its own: a span a program), ``jax.lower`` (jaxpr
    to StableHLO) and ``jax.compile`` (backend compile OR persistent-cache
    load, as JAX brackets it), each with ``program`` = JAX's own name for
    it, the last with ``cache`` = ``hit`` / ``miss`` / ``off`` and on a
    hit ``retrieval_s`` and ``saved_s``; and one ``jax_compiles_total{
    site="jit", program, cache}`` + ``jax_compile_seconds{site="jit"}``.
    The spans lie on the tracer's clock and nest under whatever ``obs``
    span the thread had open (``descent.dispatch``,
    ``descent.device_table``, ``coord.bucket``, ``tune.trial``, ...);
  - BY SITE, always: ``serving/engine.ScoringEngine._executable`` wraps its
    AOT ``jit().lower().compile()`` in ``compile_span("serving.engine")``.
    A compile inside an open ``compile_span`` is counted by that site ALONE
    (the listener's spans nest inside the site's, its counter stays out),
    so ``compile_count(site=...)`` reads the same with tracing on and off.

Transfers and cache residency:
  - ``utils/compile_cache.enable_compilation_cache`` — reports cache
    residency as a gauge (a disabled cache means every process pays full
    first-compiles; that should be visible, not inferred);
  - ``utils/transfer.device_put_counted`` — design-array transfer bytes;
  - ``utils/transfer.stream_device_put`` — streaming-ingest batch uploads
    (``site="stream_feed"``): ingest bytes.

Per-span device fences (``span(..., device_sync=True)``) live on the
tracer; this module only provides the default fence wiring.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Iterator, Optional

from photon_ml_tpu.obs import registry as _registry_mod
from photon_ml_tpu.obs import trace as _trace_mod
from photon_ml_tpu.obs.registry import MetricsRegistry

# jax.monitoring's names (jax/_src/dispatch.py, compiler.py,
# compilation_cache.py): the three phases of building a program ...
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_PHASE_SPANS = {
    _TRACE_EVENT: "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}
# ... and what the persistent cache says INSIDE the last one's bracket
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
}
_CACHE_STATE = {
    "/jax/compilation_cache/compile_requests_use_cache": "miss",  # so far
    "/jax/compilation_cache/cache_hits": "hit",
}

# per thread: ``cache`` = what the cache events said since the last
# ``jax.compile`` closed; ``sites`` = open ``compile_span``s; ``phases`` =
# the phases JAX has open (it says so at a phase's start, as a scalar)
_thread = threading.local()
_listen_lock = threading.Lock()
_listening = False


def _cache_said() -> dict:
    said = getattr(_thread, "cache", None)
    if said is None:
        said = _thread.cache = {}
    return said


class JaxRuntimeProbe:
    """Counts XLA compiles and transfer bytes into a MetricsRegistry.

    ``registry=None`` binds LAZILY to the process-default registry at each
    record, so a test that swaps the default registry sees probe traffic
    without re-wiring the probe.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self._registry = registry

    # -- listening to JAX ----------------------------------------------------
    def listen(self) -> bool:
        """Register this probe's ``jax.monitoring`` listeners, once a
        process (the first probe asked keeps them; True if this call did):
        durations (a phase's end), events (the cache's verdicts) and
        scalars (a phase's start, to tell an inner trace from a program's).
        While the tracer is off they record nothing."""
        global _listening
        with _listen_lock:
            if _listening:
                return False
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(self._on_duration)
            monitoring.register_event_listener(self._on_event)
            monitoring.register_scalar_listener(self._on_scalar)
            _listening = True
            return True

    def _on_scalar(self, event: str, _value, **_kw) -> None:
        # JAX says so at a phase's START.  Counted whether the tracer is on
        # or not (it may come on, or go off, while a thread is inside a
        # phase): a lookup and two attribute accesses
        if event in _PHASE_SPANS:
            _thread.phases = getattr(_thread, "phases", 0) + 1

    def _on_event(self, event: str, **_kw) -> None:
        state = _CACHE_STATE.get(event)
        if state is not None and _trace_mod.enabled():
            _cache_said()["cache"] = state

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        name = _PHASE_SPANS.get(event)
        if name is None:
            key = _CACHE_SECONDS.get(event)
            if key is not None and _trace_mod.enabled():
                _cache_said()[key] = seconds
            return
        # listen() may have been called inside a phase: never under 0
        _thread.phases = inside = max(getattr(_thread, "phases", 0) - 1, 0)
        if inside and event == _TRACE_EVENT:
            return  # an inner jit's, or a lowering rule's helper's
        if not _trace_mod.enabled():
            return
        # JAX fires at the phase's exit: the span ends now, on the tracer's
        # own clock, and began ``seconds`` earlier
        end = time.perf_counter_ns()
        dur = int(seconds * 1e9)
        attrs = {"program": str(kw.get("fun_name", ""))}
        if name == "jax.compile":
            attrs.update(_cache_said() or {"cache": "off"})
            _thread.cache = None
            if not getattr(_thread, "sites", 0):  # else the site counts it
                self.record_compile("jit", seconds, program=attrs["program"],
                                    cache=attrs["cache"])
        _trace_mod.get_tracer().complete(name, end - dur, dur, **attrs)

    @property
    def registry(self) -> MetricsRegistry:
        return self._registry or _registry_mod.get_registry()

    # -- compiles ----------------------------------------------------------
    def record_compile(self, site: str, seconds: Optional[float] = None,
                       **labels) -> None:
        self.registry.inc("jax_compiles_total", site=site, **labels)
        if seconds is not None:
            self.registry.observe("jax_compile_seconds", seconds, site=site)

    @contextlib.contextmanager
    def compile_span(self, site: str, **attrs) -> Iterator[None]:
        """Wrap one jit/AOT compile call site: counts it, times it, and
        emits a tracer span — the whole accounting in one ``with``."""
        t0 = time.perf_counter()
        _thread.sites = getattr(_thread, "sites", 0) + 1
        try:
            with _trace_mod.span("jax.compile", site=site, **attrs):
                yield
        finally:
            _thread.sites -= 1
        self.record_compile(site, time.perf_counter() - t0, **attrs)

    def compile_count(self, site: Optional[str] = None) -> int:
        """Compiles recorded (at one site, or in total).  Sums across any
        extra labels a site attached (e.g. ``bucket=...``)."""
        total = 0
        for lk, v in self.registry.counter_series(
                "jax_compiles_total").items():
            if site is None or ("site", site) in lk:
                total += v
        return int(total)

    # -- transfers ---------------------------------------------------------
    def record_transfer(self, nbytes: int, direction: str = "h2d",
                        site: str = "") -> None:
        self.registry.inc("jax_transfer_bytes_total", int(nbytes),
                          direction=direction, site=site)
        self.registry.inc("jax_transfers_total", direction=direction,
                          site=site)

    def transfer_bytes(self, direction: Optional[str] = None,
                       site: Optional[str] = None) -> int:
        """Transfer bytes recorded, optionally filtered by direction and/or
        call site (e.g. ``site="stream_feed"`` isolates streaming-ingest
        uploads from design-matrix puts)."""
        total = 0
        for lk, v in self.registry.counter_series(
                "jax_transfer_bytes_total").items():
            if direction is not None and ("direction", direction) not in lk:
                continue
            if site is not None and ("site", site) not in lk:
                continue
            total += v
        return int(total)

    # -- cache residency ---------------------------------------------------
    def record_compile_cache(self, enabled: bool, cache_dir: str = "") -> None:
        self.registry.set_gauge("xla_compile_cache_enabled", int(enabled))
        _trace_mod.instant("compile_cache.enabled" if enabled else
                           "compile_cache.disabled", dir=cache_dir)


# ---------------------------------------------------------------------------
# process-default probe
# ---------------------------------------------------------------------------
_default = JaxRuntimeProbe()


def get_probe() -> JaxRuntimeProbe:
    return _default
