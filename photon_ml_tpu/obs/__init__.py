"""photonscope: unified tracing, metrics, and XLA runtime accounting.

Photon ML reference counterpart: the util/{PhotonLogger,Timed}.scala +
event/Event.scala trio — text logs, wall-clock phase blocks, and lifecycle
events, each its own silo.  Here the three become one observability layer
shared by training AND serving:

  - ``trace``: nestable spans in a fixed-size ring buffer with a Chrome
    ``trace_event`` exporter (Perfetto-loadable), instant events bridged
    from ``utils/events``, opt-in per-span device fences
    (``device_sync=True``) for device-accurate timings, and the device
    half: ``trace.device_scope`` names the layers inside a compiled
    program, ``trace.hlo_op_table`` reads instruction -> layer off its
    executable;
  - ``registry``: one thread-safe ``MetricsRegistry`` — counters, gauges,
    fixed-bin latency histograms, label support — with Prometheus text
    exposition and JSON snapshots (``serving.ServingMetrics`` is a facade
    over it);
  - ``probe``: ``JaxRuntimeProbe`` counting host<->device transfer bytes at
    the chunked-upload path and XLA compiles: the serving engine's per call
    site, always; while tracing is on every program JAX builds, by name,
    as ``jax.trace`` / ``jax.lower`` / ``jax.compile`` spans (cache hit or
    miss) off ``jax.monitoring``;
  - ``watch``: the fleet-global plane (photonwatch) — metrics federation
    (``DeltaExporter``/``FleetView``) and multi-window SLO burn-rate
    alerting.

Tracing is disabled by default; the module-level ``span()``/``instant()``
fast paths cost one boolean check when off.  Enable with ``photon_ml_tpu.obs.enable_tracing()``,
``cli/serve.py --trace``, or ``cli/train.py --trace-out``.
"""

from photon_ml_tpu.obs.probe import JaxRuntimeProbe, get_probe  # noqa: F401
from photon_ml_tpu.obs.registry import (LatencyHistogram,  # noqa: F401
                                        MetricsRegistry, export_build_info,
                                        family_bounds, get_registry,
                                        process_start_time, series_name,
                                        set_family_bounds, set_registry)
from photon_ml_tpu.obs.trace import (Tracer, enabled, get_tracer,  # noqa: F401
                                     instant, set_tracer, span)


def enable_tracing(capacity: int = None) -> Tracer:
    """Turn the default tracer on (optionally resized); returns it.  From
    here on the probe listens to JAX (``JaxRuntimeProbe.listen``)."""
    get_probe().listen()
    t = get_tracer()
    if capacity is not None and capacity != t.capacity:
        t = Tracer(capacity=capacity, enabled=True)
        set_tracer(t)
    return t.enable()


def disable_tracing() -> Tracer:
    return get_tracer().disable()
