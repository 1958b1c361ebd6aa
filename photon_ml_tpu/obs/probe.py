"""JAX runtime accounting: compiles, host<->device transfer bytes, fences.

The two runtime costs a wall-clock phase log cannot attribute are XLA
compilation (tens of seconds on a TPU first-compile; the serving stack's
zero-recompile guarantee exists because of it) and host<->device transfer
(the chunked upload path in ``utils/transfer`` exists because one transport
degraded under a monolithic 512MB put).  ``JaxRuntimeProbe`` counts both
into the unified ``MetricsRegistry`` with per-site labels, so "which
coordinate's solver recompiled mid-sweep" and "how many bytes crossed the
wire during warm" become registry queries instead of log archaeology.

Instrumented sites:
  - ``serving/engine.ScoringEngine._executable`` — every AOT
    ``jit().lower().compile()`` goes through ``compile_span``;
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
import time
from typing import Iterator, Optional

from photon_ml_tpu.obs import registry as _registry_mod
from photon_ml_tpu.obs import trace as _trace_mod
from photon_ml_tpu.obs.registry import MetricsRegistry


class JaxRuntimeProbe:
    """Counts XLA compiles and transfer bytes into a MetricsRegistry.

    ``registry=None`` binds LAZILY to the process-default registry at each
    record, so a test that swaps the default registry sees probe traffic
    without re-wiring the probe.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self._registry = registry

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
        with _trace_mod.span("jax.compile", site=site, **attrs):
            yield
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
