"""Seconds of set-up under the program's ``jax.compile`` spans (backend
compile OR persistent-cache load, as JAX itself brackets it), all programs:
``setup_trace_s``'s rule."""


def read(readings):
    shared = readings["catalog"].module("layer_metrics", "setup_trace_s")
    return shared.setup_seconds(readings, "jax.compile")
