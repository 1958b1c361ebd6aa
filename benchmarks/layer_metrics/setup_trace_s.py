"""Seconds of set-up under the program's ``jax.trace`` spans (Python
tracing to a jaxpr; ``obs/probe.py`` records one for every program JAX
builds while tracing is on, the inner ``jit``s traced inside it included
in it), all programs.

Set-up is what ends before the window: before the first of the benchmark's
own ``fit`` or ``trial`` spans starts.  ``setup_lower_s`` and
``setup_compile_s`` read their spans through ``setup_seconds`` here;
``setup_cache_misses`` through ``setup_spans``.
"""

import layer_join


def setup_spans(readings, name):
    """The listener's spans of that name (they carry ``program``; a serving
    site's own ``jax.compile`` span does not) that end before the window."""
    window = min((t0 for n, t0, _ in readings["spans"]
                  if n in ("fit", "trial")), default=None)
    return [r for r in layer_join.program_spans(name)
            if "program" in r["attrs"]
            and (window is None or r["ts_ns"] + r["dur_ns"] <= window)]


def setup_seconds(readings, name):
    spans = setup_spans(readings, name)
    if not spans:
        return None
    return sum(r["dur_ns"] for r in spans) * 1e-9


def read(readings):
    return setup_seconds(readings, "jax.trace")
