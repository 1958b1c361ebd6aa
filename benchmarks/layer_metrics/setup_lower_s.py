"""Seconds of set-up under the program's ``jax.lower`` spans (jaxpr to
StableHLO module), all programs: ``setup_trace_s``'s rule."""


def read(readings):
    shared = readings["catalog"].module("layer_metrics", "setup_trace_s")
    return shared.setup_seconds(readings, "jax.lower")
